"""Microseconds of the actor runtime's own code per task: the stage threads'
summed ``stats.runtime`` (the mailbox lock and sync, arbitration, ``begin``,
``complete`` and the sends) over the tasks run in the window.  None where
the program counts no ``runtime``."""


def read(rec: dict):
    results = rec.get("actor") or []
    own = [getattr(st, "runtime", None) for r in results for st in r.stage_stats]
    tasks = sum(r.spec.total_tasks() for r in results)
    if not own or None in own or not tasks:
        return None
    return 1e6 * sum(own) / tasks
