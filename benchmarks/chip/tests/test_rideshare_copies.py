"""The ridesharing deployment's copied stream generator and query builder
reproduce the program's ``tenant_stream`` and ``ridesharing_workload``, bit
for bit, at the configuration and at seeds a check draws."""

from __future__ import annotations

import numpy as np
import pytest

import cell
import program

SEEDS = [1001, 2001, 2**31 + 12345]


def _program_stream(config, seed):
    from repro.core.events import StreamSchema
    from repro.streams import generator as G

    types, attrs = config["schema"]["types"], config["schema"]["attrs"]
    sch = StreamSchema(types=tuple(types), attrs=tuple(attrs))
    a = dict(config["stream"]["args"],
             ticks_per_minute=config["ticks_per_minute"],
             minutes=config["minutes"])
    a["type_weights"] = tuple(a["type_weights"])
    return G.tenant_stream(G.TenantStreamConfig(schema=sch, seed=seed, **a))


@pytest.mark.parametrize("seed", SEEDS)
def test_tenant_stream_copy_is_bitwise(seed):
    cfg = cell.load_json("configs", "rideshare_q25")
    dep = cell.deployment(cfg, seed)
    ref = _program_stream(cfg, seed)
    for k in ("type_id", "time", "attrs", "group"):
        got, want = dep.stream[k], getattr(ref, k)
        assert got.dtype == want.dtype, k
        assert np.array_equal(got, want), k


def test_stream_is_the_source_rate():
    cfg = cell.load_json("configs", "rideshare_q25")
    a = cfg["stream"]["args"]
    assert a["n_tenants"] == cfg["tenants"]
    assert a["groups_per_tenant"] == cfg["groups_per_tenant"]
    assert a["n_tenants"] * a["base_events_per_minute"] \
        == cfg["events_per_minute"] == 10_000
    dep = cell.deployment(dict(cfg, minutes=4), 9)
    assert dep.pane == 5
    assert abs(len(dep.stream["time"]) / 4 - 10_000) < 500


def test_ridesharing_queries_match_program():
    from repro.launch.hamlet_service import ridesharing_workload

    cfg = cell.load_json("configs", "rideshare_q25")
    dep = cell.deployment(dict(cfg, minutes=1), 0)
    got = program.workload(dep.specs, dep.types, dep.attrs)
    want = ridesharing_workload(25)
    assert got.queries == want.queries
    assert got.schema == want.schema
    kinds = {a[0] for q in dep.specs for a in q["aggs"]}
    assert kinds == {"count_star", "sum", "avg"}
    assert any(p[0] == "not" for q in dep.specs for p in q["pattern"][1:])
