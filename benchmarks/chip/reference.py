"""Plain reference: exact window results of trend aggregation queries.

The semantics (paper Defs. 1-3, Eq. 1, Sec. 5), stated without the
program's graphlets, snapshots, sharing or batching:

* within one group, events are taken in stream order (time, then the
  producer's sequence number);
* a trend is a subsequence of the window's matched events in which each
  adjacent pair ``(j, i)`` follows a predecessor edge of the pattern;
* ``count(i) = start(i) + sum over predecessors j of count(j)`` is the
  number of trends ending at ``i`` (Eq. 1); a SUM unit carries
  ``sum(i) = v(i) count(i) + sum over predecessors j of sum(j)``;
* a matched NOT event cuts the edges from its ``before`` types earlier
  than it to its ``after`` types later than it; a leading NOT bars trends
  from starting after it and a trailing NOT bars them from ending before
  it;
* COUNT(*) sums ``count`` over valid ends, SUM and COUNT(E) sum their
  unit, AVG is their ratio (NaN with no trend).

Windows are ``[w0, w0 + within)`` for ``w0`` on the slide grid.  A group
reports the windows that end after the pane holding its first event and
no later than ``t_end``.

``precision="f64"`` is the reference.  ``precision="bf16x3"`` is the
control: the same recurrence in float32, each product formed as the
three bf16 passes a float32 matmul at ``Precision.HIGH`` takes.
"""

from __future__ import annotations

import numpy as np

from querylib import OPS, agg_name, analyze, pane_ticks

WINDOW_BLOCK = 256        # windows solved together; bounds the memory


def _split_bf16(x: np.ndarray):
    import ml_dtypes

    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def _dot_f64(a, b):
    return np.einsum("bj,bjr->br", a, b)


def _dot_bf16x3(a, b):
    a_hi, a_lo = _split_bf16(a.astype(np.float32))
    b_hi, b_lo = _split_bf16(b.astype(np.float32))
    return (np.einsum("bj,bjr->br", a_hi, b_hi)
            + np.einsum("bj,bjr->br", a_hi, b_lo)
            + np.einsum("bj,bjr->br", a_lo, b_hi)).astype(np.float32)


def _mul_bf16x3(a, b):
    a_hi, a_lo = _split_bf16(a.astype(np.float32))
    b_hi, b_lo = _split_bf16(b.astype(np.float32))
    return (a_hi * b_hi + a_hi * b_lo + a_lo * b_hi).astype(np.float32)


def evaluate(stream: dict, specs: list[dict], types, attrs, t_end: int,
             precision: str = "f64") -> dict:
    """``{(query, group, w0): {aggregate name: value}}`` over ``stream``
    (``type_id``, ``time``, ``attrs``, ``group`` arrays in stream order)."""
    if precision not in ("f64", "bf16x3"):
        raise ValueError(precision)
    pane = pane_ticks(specs)
    out: dict = {}
    group = stream["group"]
    for g in np.unique(group):
        idx = np.flatnonzero(group == g)
        ev = {k: stream[k][idx] for k in ("type_id", "time", "attrs")}
        first_pane = int(ev["time"][0]) // pane * pane
        for q in specs:
            for w0, vals in _query_windows(ev, q, types, attrs, first_pane,
                                           t_end, precision):
                out[(q["name"], int(g), w0)] = vals
    return out


def _query_windows(ev, q, types, attrs, first_pane, t_end, precision):
    info = analyze(q["pattern"])
    tid = {t: i for i, t in enumerate(types)}
    col = {a: i for i, a in enumerate(attrs)}

    def holds(t):
        m = ev["type_id"] == tid[t]
        for attr, op, v in q["preds"].get(t, ()):
            m &= OPS[op](ev["attrs"][:, col[attr]], v)
        return m

    keep = np.isin(ev["type_id"], [tid[t] for t in info.types]
                   + [tid[n.type] for n in info.negatives])
    ev = {k: v[keep] for k, v in ev.items()}
    n_all = len(ev["time"])
    typ = ev["type_id"]
    matched = np.zeros(n_all, bool)
    for t in info.types:
        matched |= holds(t)
    # E[succ type, pred type]
    T = len(types)
    edge = np.zeros((T, T), bool)
    for a, b in info.edges:
        edge[tid[b], tid[a]] = True
    is_start = np.isin(typ, [tid[t] for t in info.start]) & matched
    is_end = np.isin(typ, [tid[t] for t in info.end]) & matched
    negs = []
    for nc in info.negatives:
        m = holds(nc.type)
        incl = np.cumsum(m)
        ids = [None if s is None else [tid[t] for t in s]
               for s in (nc.before, nc.after)]
        negs.append((incl, incl - m, *ids))

    units = sorted({(a[1], a[2] if a[0] != "count_type" else None)
                    for a in q["aggs"] if a[0] != "count_star"}
                   | {(a[1], None) for a in q["aggs"] if a[0] == "avg"},
                   key=str)
    unit_vals = []
    for e_type, attr in units:
        v = np.ones(n_all) if attr is None else ev["attrs"][:, col[attr]]
        unit_vals.append(np.where((typ == tid[e_type]) & matched, v, 0.0))

    within, slide = q["within"], q["slide"]
    w0_lo = max(0, (first_pane - within) // slide + 1) * slide
    starts = np.arange(w0_lo, t_end - within + 1, slide)
    if n_all == 0:
        zero = np.zeros(1 + len(units))
        for w0 in starts:
            yield int(w0), _aggregate(q, units, zero)
        return
    lo = np.searchsorted(ev["time"], starts, side="left")
    hi = np.searchsorted(ev["time"], starts + within, side="left")
    for b0 in range(0, len(starts), WINDOW_BLOCK):
        sl = slice(b0, b0 + WINDOW_BLOCK)
        res = _solve_block(lo[sl], hi[sl], typ, matched, edge, is_start,
                           is_end, negs, unit_vals, precision)
        for w0, r in zip(starts[sl], res):
            yield int(w0), _aggregate(q, units, r)


def _solve_block(lo, hi, typ, matched, edge, is_start, is_end, negs,
                 unit_vals, precision):
    B = len(lo)
    n = hi - lo
    N = int(n.max()) if B else 0
    i = np.arange(N)
    valid = i[None, :] < n[:, None]
    k = np.where(valid, lo[:, None] + i[None, :], 0)
    mm = matched[k] & valid
    tk = typ[k]
    A = (edge[tk[:, :, None], tk[:, None, :]]
         & mm[:, :, None] & mm[:, None, :]
         & (i[None, :, None] > i[None, None, :]))
    start = is_start[k] & valid
    end = is_end[k] & valid
    for incl, excl, before, after in negs:
        # incl[k] / excl[k]: NOT matches at stream positions <= k / < k
        if before is None:              # leading NOT: none before i
            start &= (excl[k] - excl[lo][:, None]) == 0
        elif after is None:             # trailing NOT: none after i
            end &= (incl[hi - 1][:, None] - incl[k]) == 0
        else:                           # cut j (before) < NOT < i (after)
            cut = (np.isin(tk, after)[:, :, None]
                   & np.isin(tk, before)[:, None, :]
                   & ((excl[k][:, :, None] - incl[k][:, None, :]) > 0))
            A &= ~cut
    fdt = np.float64 if precision == "f64" else np.float32
    dot = _dot_f64 if precision == "f64" else _dot_bf16x3
    mul = np.multiply if precision == "f64" else _mul_bf16x3
    A = A.astype(fdt)
    U = len(unit_vals)
    X = np.zeros((B, N, 1 + U), fdt)
    V = [u[k].astype(fdt) * valid for u in unit_vals]
    S = start.astype(fdt)
    for r in range(N):
        acc = dot(A[:, r, :r], X[:, :r, :]) if r else np.zeros((B, 1 + U),
                                                              fdt)
        x = S[:, r] + acc[:, 0]
        X[:, r, 0] = x
        for u in range(U):
            X[:, r, 1 + u] = mul(V[u][:, r], x) + acc[:, 1 + u]
    E = end.astype(fdt)
    tot = dot(E, X)                      # [B, 1 + U]
    return tot


def _aggregate(q, units, tot) -> dict:
    out = {}
    for agg in q["aggs"]:
        kind = agg[0]
        if kind == "count_star":
            v = float(tot[0])
        elif kind in ("count_type", "sum"):
            u = (agg[1], None if kind == "count_type" else agg[2])
            v = float(tot[1 + units.index(u)])
        else:
            s = float(tot[1 + units.index((agg[1], agg[2]))])
            c = float(tot[1 + units.index((agg[1], None))])
            v = s / c if c else float("nan")
        out[agg_name(agg)] = v
    return out
