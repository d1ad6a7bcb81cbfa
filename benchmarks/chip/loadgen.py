"""The load generator: client sessions in a process of their own.

Started by ``run.py`` with ``JAX_PLATFORMS=cpu``, so it can never open
the chip.  It rebuilds the cell's stream from the seed, splits it over
the configured sessions, and drives one ``ServingClient`` per session on
a thread.  It talks to ``run.py`` in JSON lines: its stdin carries the
run's parameters and then ``start`` and ``stop_at``; its stdout carries
``ready``, ``closed`` and ``done``.

Closed loop: each session submits its next pane as soon as its credits
allow and every session has sent the pane before, and advances its
watermark to the pane's end, until ``stop_at``.  The sessions carry
shares of one stream, so they move through it in step: none runs more
than a pane ahead of another.  (Left to drift, one session fell 14 panes
behind the rest; the staging gate then shuts on events that only that
session's watermark can seal, while it waits for credits the gate
withholds.)  Each session records its submits (end tick, call time,
credit wait) and every delivery with its receipt time, and writes them
to ``out``.
"""

from __future__ import annotations

import faulthandler
import json
import pickle
import sys
import threading
import time

import numpy as np

import cell
import program

WAIT_S = 600.0        # longest any blocking client call may take
STACKS_AT_S = 290.0   # thread stacks to stderr if still running


def send(msg: dict) -> None:
    sys.stdout.write(json.dumps(msg) + "\n")
    sys.stdout.flush()


class Control:
    """The parent's commands, read on a thread."""

    def __init__(self):
        self.start = threading.Event()
        self.stop_at = None

    def listen(self) -> None:
        for line in sys.stdin:
            msg = json.loads(line)
            if msg.get("cmd") == "start":
                self.start.set()
            elif msg.get("cmd") == "stop_at":
                self.stop_at = float(msg["t"])


def records(client) -> list:
    """Every delivery with the receipt time of the frame it came in."""
    ds = client.poll()
    out = []
    i = 0
    for t_enc, t_recv, n in client.wire_samples:
        for d in ds[i:i + n]:
            out.append((d.kind, d.query, d.group, d.w0, d.vals, t_recv,
                        t_enc))
        i += n
    if i != len(ds):
        raise RuntimeError(f"{len(ds) - i} deliveries without a frame")
    return out


class Lockstep:
    """The sessions' pane barrier; the last session to reach it decides,
    for all of them at once, whether the run goes on."""

    def __init__(self, n: int, ctl: Control):
        self.ctl = ctl
        self.halt = False
        self.barrier = threading.Barrier(n, action=self._decide)

    def _decide(self) -> None:
        stop = self.ctl.stop_at
        self.halt = stop is not None and time.perf_counter() >= stop

    def next_pane(self) -> bool:
        self.barrier.wait(timeout=WAIT_S)
        return not self.halt


def session(i, client, dep, part, step, log):
    pane = dep.pane
    t = part["time"]
    t_last = int(dep.stream["time"][-1]) + 1
    dep_schema = program.schema(dep.types, dep.attrs)
    sent = 0
    submits = []
    exhausted = False
    c0 = 0
    while step.next_pane():
        if c0 >= t_last:
            exhausted = True
            break
        lo = np.searchsorted(t, c0)
        hi = np.searchsorted(t, c0 + pane)
        if hi > lo:
            t0 = time.perf_counter()
            client.submit(program.event_batch(dep_schema, {
                k: v[lo:hi] for k, v in part.items()}), timeout=WAIT_S)
            submits.append((c0 + pane, t0, time.perf_counter() - t0))
            sent = int(hi)
        client.advance_to(c0 + pane)
        c0 += pane
    client.close()
    log[i] = {"sent": sent, "submits": submits, "exhausted": exhausted,
              "blocked_s": client.blocked_s}


def main() -> None:
    faulthandler.dump_traceback_later(STACKS_AT_S)
    args = json.loads(sys.stdin.readline())
    c = cell.load_cell(args["workload"], overrides=args.get("overrides"))
    dep = cell.deployment(c.config, args["seed"])
    parts = []
    for i in range(dep.sessions):
        idx = dep.session_index(i)
        parts.append({k: v[idx] for k, v in dep.stream.items()})

    program.import_program()
    from repro.serve import ServingClient

    ctl = Control()
    threading.Thread(target=ctl.listen, daemon=True).start()
    clients = [ServingClient(args["host"], args["port"],
                             tenant=dep.session_tenant(i), timeout=WAIT_S)
               for i in range(dep.sessions)]
    send({"msg": "ready"})
    ctl.start.wait()
    log: dict = {}
    errors: list = []
    step = Lockstep(dep.sessions, ctl)

    def run(i):
        try:
            session(i, clients[i], dep, parts[i], step, log)
        except Exception as e:         # reported to the parent below
            errors.append(f"session {i}: {e!r}")
            step.barrier.abort()

    threads = [threading.Thread(target=run, args=(i,), name=f"session-{i}")
               for i in range(dep.sessions)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    send({"msg": "closed", "errors": errors})
    out = {"sessions": [], "errors": errors}
    for i, cl in enumerate(clients):
        try:
            cl.wait_end(timeout=WAIT_S)
        except (TimeoutError, ConnectionError) as e:
            errors.append(f"session {i}: {e!r}")
        out["sessions"].append(dict(log.get(i, {}),
                                    tenant=dep.session_tenant(i),
                                    deliveries=records(cl)))
        cl.shutdown()
    faulthandler.cancel_dump_traceback_later()
    with open(args["out"], "wb") as f:
        pickle.dump(out, f, protocol=pickle.HIGHEST_PROTOCOL)
    send({"msg": "done", "errors": errors})


if __name__ == "__main__":
    main()
