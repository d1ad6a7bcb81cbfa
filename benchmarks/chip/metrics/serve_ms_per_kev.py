"""Serve layer: the pump's seal and result routing per 1,000 events.

``serve.seal_s`` + ``serve.route_s`` over the window (the front-end's
``serve.seal`` and ``serve.route`` spans: the seal under the staging lock,
and the ``results()`` rebuild, diff and deliveries after a flush), divided
by the events the engine finished in it.
"""

from __future__ import annotations

from _counters import counters_ms_per_kev


def read(ctx):
    return counters_ms_per_kev(ctx, "serve.seal_s", "serve.route_s")
