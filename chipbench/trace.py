"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time and
idle gaps laid to the harness's host spans.

The traced window runs from the start of the first ``step`` span on the host
to the end of the last.  On each device, busy time is the union of the
intervals of the operations on its ``XLA Ops`` line, clipped to the window.
A gap in that union is laid to the innermost harness span open on the host
at its middle (``other`` where none is).
"""
from __future__ import annotations

import bisect
import collections
import glob

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def op_name(event_name: str) -> str:
    """An XLA op event is named by its HLO text, ``%name = type op(...)``:
    the op's own name, without the ``%``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def load(path_or_dir: str):
    from jax.profiler import ProfileData

    if not path_or_dir.endswith(".xplane.pb"):
        found = sorted(glob.glob(f"{path_or_dir}/**/*.xplane.pb",
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path_or_dir}")
        path_or_dir = found[-1]
    return ProfileData.from_file(path_or_dir)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(prof, spans: tuple, kernels: dict, *, top: int = 10) -> dict:
    """``kernels``: name -> ``match(op_name) -> bool``.  Returns seconds:
    ``window_s``, ``busy_s`` (mean over devices), per-device ``busy``,
    ``kernels`` {name: [seconds, calls]} (all devices), ``device_ops``
    (``module/op`` summed over devices) and ``idle_gaps`` (by host span), each
    a list of [name, seconds], longest first."""
    host = []  # (start, end, name) of the harness's spans
    devices = []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith(
                "/device:GPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append((lines[OPS_LINE], lines.get(MODULES_LINE)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in spans:
                        host.append((ev.start_ns, ev.end_ns, ev.name))
    steps = [(s, e) for s, e, n in host if n == "step"]
    if not steps or not devices:
        return {}
    w0, w1 = min(s for s, _ in steps), max(e for _, e in steps)
    window = (w1 - w0) * 1e-9
    op_time = collections.Counter()
    kern = {k: [0.0, 0] for k in kernels}
    gaps = collections.Counter()
    busy = []
    for line, modules in devices:
        mods = sorted((ev.start_ns, ev.end_ns, ev.name.split("(", 1)[0])
                      for ev in (modules.events if modules else []))
        starts = [m[0] for m in mods]
        iv = []
        for ev in line.events:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            iv.append((s, e))
            op = op_name(ev.name)
            j = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = mods[j][2] if j >= 0 and ev.start_ns < mods[j][1] else "?"
            op_time[f"{mod}/{op}"] += (e - s) * 1e-9
            for k, match in kernels.items():
                if match(op):
                    kern[k][0] += (e - s) * 1e-9
                    kern[k][1] += 1
        u = _union(iv)
        busy.append(sum(e - s for s, e in u) * 1e-9)
        edges = [w0] + [x for se in u for x in se] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_open_span(host, (a + b) / 2)] += (b - a) * 1e-9
    return {"window_s": window, "busy_s": float(np.mean(busy)),
            "busy": busy, "kernels": kern,
            "device_ops": [[n, t] for n, t in op_time.most_common(top)],
            "idle_gaps": [[n, t] for n, t in gaps.most_common(top)]}


def _open_span(host: list, t: float) -> str:
    best = None
    for s, e, n in host:
        if s <= t <= e and n != "step" and (best is None or s > best[0]):
            best = (s, n)
    return best[1] if best else "other"
