"""Roofline share of the Pallas SSD forward, %: the least time its calls
could take on the chip (the larger of FLOPs over peak and bytes over HBM
bandwidth, from ``kernels/ssd_fwd.py``) over their summed device time in the
trace."""
from chipbench.harness import roofline


def read(rec: dict):
    return roofline(rec, "ssd_fwd")
