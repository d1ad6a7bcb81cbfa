"""Reduction of a ``jax.profiler`` trace to device metrics.

The TPU's plane is ``/device:TPU:0``.  Its ``XLA Modules`` line holds one
event per executed program; the union of their intervals is the time the
device was busy.  Its ``XLA Ops`` line holds the operations inside them,
the Pallas kernels among them under their own names.  The traced window
is the host event the benchmark wraps around it (``WINDOW``), on the
same clock.

Peak rates are kept per ``device_kind`` for the roofline shares to come;
a device missing from the table is an error, not a default.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "chipbench.window"
DEVICE_PLANE = "/device:TPU:0"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

# kernels by the name their operations carry in the trace: the masked
# propagate kernel (``hamlet_propagate.py``) and the dense closed form
# (``hamlet_dense.py``)
KERNELS = {"propagate": "_propagate", "dense": "_dense"}

# Published peaks of one chip (Google Cloud documentation, "TPU v5e").
PEAKS = {"TPU v5 lite": {"bf16_flop_per_s": 197e12,
                         "int8_op_per_s": 393e12,
                         "hbm_bytes_per_s": 819e9}}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}")
    return PEAKS[device_kind]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def events(path: str) -> list[tuple]:
    """Every event of the trace as ``(plane, line, name, start_s, dur_s)``."""
    from jax.profiler import ProfileData

    out = []
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


def op_name(text: str) -> str:
    """An operation's stable name: the HLO instruction's name without its
    ``%`` and numeric suffix (``%_propagate.1 = f32[...] custom-call(...)``
    is ``_propagate``)."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(evs: list[tuple], top: int = 10) -> dict:
    """Busy and idle time of the device inside the traced window, time by
    operation and by kernel, and the idle gaps."""
    win = [e for e in evs if e[2] == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW!r} event, found {len(win)}")
    lo, hi = win[0][3], win[0][3] + win[0][4]
    mods = [(s, s + d) for p, ln, _, s, d in evs
            if p == DEVICE_PLANE and ln == MODULES_LINE]
    ops = [(n, s, s + d) for p, ln, n, s, d in evs
           if p == DEVICE_PLANE and ln == OPS_LINE]
    busy = clip(union(mods), lo, hi)
    busy_s = float(sum(e - s for s, e in busy))
    by_op: dict = {}
    for n, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            n = op_name(n)
            by_op[n] = by_op.get(n, 0.0) + (e - s)
    kernels = {k: float(by_op.get(name, 0.0)) for k, name in KERNELS.items()}
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return {"window_s": hi - lo, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / (hi - lo),
            "modules": len(clip(mods, lo, hi)),
            "kernel_s": kernels,
            "top_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
            "gaps": gaps, "window": (lo, hi)}


def label_gaps(gaps, spans, offset: float, top: int = 10) -> list:
    """The ``top`` longest idle gaps, each named by the engine phase span
    (``(name, start, end)`` on the host's ``perf_counter`` clock) that
    covers its middle; ``offset`` maps trace time to that clock."""
    spans = sorted(spans, key=lambda s: s[2] - s[1])
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2 - offset
        name = next((n for n, a, b in spans if a <= mid <= b),
                    "unattributed")
        out.append([name, float(e - s)])
    return out
