"""Model FLOP/s utilization of the whole step, %: model FLOPs of a step
(``model_flops.py``, recomputation not counted) times the window's steps,
over the window, over the chips' bf16 peak."""


def read(rec: dict):
    if not rec["steps"]:
        return None
    done = rec["flops_per_step"] * rec["steps"]
    return 100.0 * done / rec["window_s"] / (
        rec["chips"] * rec["peak"]["bf16_flops"])
