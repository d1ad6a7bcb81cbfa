#!/usr/bin/env python3
"""Record the small trace fixture ``tests/data/trace_v5e.json.gz`` on a TPU.

    python3 benchmarks/chip/tests/record_trace.py OUT.json.gz

Runs a short traced run of ``smarthome_q25.closed`` with a half-second
trace and keeps, of the trace's events, the TPU plane's and the window
annotation, as the ``(plane, line, name, start_s, dur_s)`` tuples that
``devtrace.events`` gives.  ``tests/test_devtrace.py`` reduces them; the
reduction does not depend on the cell, and the committed fixture comes
from a traced run of the ridesharing deployment (``PERF.md``).
"""

from __future__ import annotations

import gzip
import json
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace  # noqa: E402
import run  # noqa: E402


def main(out: str) -> None:
    kept = {}
    read = devtrace.events

    def capture(path):
        evs = read(path)
        kept["evs"] = evs
        return evs

    devtrace.events = capture
    run.TRACE_S = 0.5
    run.run("smarthome_q25.closed", 20261016, 8.0, True)
    evs = kept["evs"]
    lines = Counter((p, ln) for p, ln, *_ in evs)
    for (p, ln), n in sorted(lines.items()):
        print(f"plane {p!r} line {ln!r}: {n} events")
    names = Counter(n for p, ln, n, *_ in evs
                    if p == devtrace.DEVICE_PLANE and ln == devtrace.OPS_LINE)
    print("device ops:", names.most_common(40))
    keep = [e for e in evs
            if e[0] == devtrace.DEVICE_PLANE or e[2] == devtrace.WINDOW]
    with gzip.open(out, "wt") as f:
        json.dump(keep, f)
    print(f"kept {len(keep)} of {len(evs)} events in {out}")


if __name__ == "__main__":
    main(sys.argv[1])
