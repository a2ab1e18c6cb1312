"""Share of the actor runtime's stage-thread time spent blocked with no task
ready, %: the threads' summed ``stats.wait`` (inside
``Mailbox.wait_for_work``) over the stages times each step's makespan,
summed over the window's steps.  None where the program counts no ``wait``."""


def read(rec: dict):
    results = rec.get("actor") or []
    waits = [getattr(st, "wait", None) for r in results for st in r.stage_stats]
    span = sum(len(r.stage_stats) * r.makespan for r in results)
    if not waits or None in waits or span <= 0:
        return None
    return 100.0 * sum(waits) / span
