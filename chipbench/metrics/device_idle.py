"""Share of the traced window in which no operation ran on the device, %,
averaged over the cell's devices."""


def read(rec: dict):
    tr = rec["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
