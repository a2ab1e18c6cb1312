"""Share of the actor runtime's step spent outside ``run_threaded``, %: the
batch, the stage programs' set-up, the gradient stack, the host AdamW and the
loss read, from the harness's host spans over the window."""


def read(rec: dict):
    spans = rec.get("spans") or {}
    step, run = sum(spans.get("step", [])), sum(spans.get("run_threaded", []))
    if not step or not run:
        return None
    return 100.0 * (step - run) / step
