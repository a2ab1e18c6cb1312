"""Highest ``peak_bytes_in_use`` among the cell's devices after the window,
GiB."""


def read(rec: dict):
    b = rec.get("memory_peak_bytes")
    return b / 2 ** 30 if b else None
