"""Milliseconds a stage thread of the actor runtime spends in its callable
per task: the threads' summed ``stats.compute`` over the tasks run.  A call
returns once its work is enqueued, so this is host time, including the wait
for room in the device's queue, and never device busy time."""


def read(rec: dict):
    results = rec.get("actor") or []
    tasks = sum(r.spec.total_tasks() for r in results)
    if not tasks:
        return None
    host = sum(st.compute for r in results for st in r.stage_stats)
    return 1e3 * host / tasks
