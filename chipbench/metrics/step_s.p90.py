"""90th percentile of the window's step times (host clock, each step ending
in ``block_until_ready``), seconds."""
import numpy as np


def read(rec: dict):
    steps = rec["step_s"]
    return float(np.percentile(steps, 90)) if len(steps) >= 2 else None
