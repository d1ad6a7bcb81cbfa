"""The comparison that decides ``correct``.

Every window result a client received is held against the reference over
the events the clients sent.  Each number compared has its limit:

* ``max_rel_err``: the largest relative error of a delivered value
  (``|got - ref| / |ref|``, or ``|got|`` where ``ref`` is 0).  Its limit
  is the configuration's ``rel_err_limit``, set between the program's
  readings and the lower-precision control's (``PERF.md``);
* ``missing``, ``unexpected``, ``duplicate``, ``revised``,
  ``nan_mismatch``: window results never delivered to a subscribed
  session, delivered with no such window, delivered twice, withdrawn or
  amended, or NaN on one side only: each must be 0 (every window result
  delivered once, exactly);
* ``lost_events``: events shed, refused or dropped anywhere: 0;
* ``unsealed_events``: events sent that the engine never consumed: 0.
"""

from __future__ import annotations

import math


def compare(sessions: list[dict], ref: dict, groups_per_tenant: int,
            rel_err_limit: float, scope=None) -> dict:
    """``sessions``: per session ``{"tenant", "deliveries": [(kind, query,
    group, w0, vals, t_recv, t_enc), ...]}``.  ``scope(key)`` says which
    windows count towards ``attempted``/``failed``.  Returns the numbers
    compared, ``attempted`` and ``failed``."""
    n = {"missing": 0, "unexpected": 0, "duplicate": 0, "revised": 0,
         "nan_mismatch": 0}
    worst = 0.0
    where = None
    attempted = failed = 0
    for s in sessions:
        t = s["tenant"]
        lo, hi = t * groups_per_tenant, (t + 1) * groups_per_tenant
        got: dict = {}
        for kind, q, g, w0, vals, *_ in s["deliveries"]:
            key = (q, g, w0)
            if kind != "emit":
                n["revised"] += 1
            elif key in got:
                n["duplicate"] += 1
            got[key] = vals
        expected = [k for k in ref if lo <= k[1] < hi]
        n["unexpected"] += len(got.keys() - set(expected))
        for key in expected:
            in_scope = scope is None or scope(key)
            attempted += in_scope
            vals = got.get(key)
            if vals is None:
                n["missing"] += 1
                failed += in_scope
                continue
            bad = False
            for a, r in ref[key].items():
                v = vals.get(a)
                if v is None:
                    n["missing"] += 1
                    bad = True
                    continue
                if math.isnan(r) or math.isnan(v):
                    if not (math.isnan(r) and math.isnan(v)):
                        n["nan_mismatch"] += 1
                        bad = True
                    continue
                err = abs(v - r) / abs(r) if r else abs(v)
                if err > worst:
                    worst, where = err, (key, a, v, r)
                bad |= err > rel_err_limit
            failed += in_scope and bad
    return {"numbers": dict(n, max_rel_err=worst), "worst_at": where,
            "attempted": attempted, "failed": failed}


def limits(rel_err_limit: float) -> dict:
    return {"max_rel_err": rel_err_limit, "missing": 0, "unexpected": 0,
            "duplicate": 0, "revised": 0, "nan_mismatch": 0,
            "lost_events": 0, "unsealed_events": 0}


def verdict(numbers: dict, lim: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``; a number missing or NaN
    fails."""
    out = {}
    ok = True
    for name, limit in lim.items():
        v = numbers.get(name)
        good = v is not None and not math.isnan(v) and v <= limit
        ok &= good
        out[name] = {"value": v, "limit": limit}
    return ok, out
