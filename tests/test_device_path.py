"""The device backends on the served path, rehearsed on the CPU.

* the served path with ``backend="pallas"`` (kernels interpreted here, as
  the platform decides) matches the numpy engine, and a second pass over
  the same stream compiles nothing — the executors pad every launch to a
  bounded set of shapes;
* a process-pool shard drive refuses a device backend (a chip belongs to
  one process);
* shards map onto devices round-robin: on four (virtual) devices a
  4-shard device service puts one shard per device and matches the
  1-shard run;
* the persistent compilation cache follows ``JAX_COMPILATION_CACHE_DIR``
  and otherwise lives at the fixed ``<repo>/.jax_cache``.
"""

import math
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.events import EventBatch
from repro.kernels.platform import (REPO_CACHE_DIR, CompileCounter,
                                    enable_compile_cache)
from repro.launch.hamlet_service import ridesharing_workload
from repro.overload import OverloadConfig
from repro.serve import ServingFrontend
from repro.shardsvc import ShardedHamletService, ShardServiceConfig
from repro.streams.generator import (RIDESHARING_SCHEMA, TenantStreamConfig,
                                     tenant_stream)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stream(n_tenants=2, epm=300, minutes=1, seed=3):
    s = tenant_stream(TenantStreamConfig(
        schema=RIDESHARING_SCHEMA, n_tenants=n_tenants, groups_per_tenant=2,
        base_events_per_minute=epm, minutes=minutes,
        type_weights=(1, 1, 6, 1, 1, 1), seed=seed))
    return EventBatch(schema=s.schema, type_id=s.type_id, time=s.time,
                      attrs=s.attrs, group=s.group,
                      seq=np.arange(len(s), dtype=np.int64))


def _serve(wl, stream, backend):
    """Two in-process sessions per tenant trickle their split pane by pane
    into one front-end; returns the drained results."""
    fe = ServingFrontend(wl, backend="overload",
                         overload=OverloadConfig(shed_policy="none",
                                                 micro_batch=4),
                         np_backend=backend, groups_per_tenant=2)
    n_t = int(stream.group.max()) // 2 + 1
    handles, parts = [], []
    for i in range(2 * n_t):
        t = i % n_t
        idx = np.flatnonzero(stream.group // 2 == t)[i // n_t::2]
        handles.append(fe.open_session(tenant=t))
        parts.append(stream.select(idx))
    t_end = ((int(stream.time.max()) // fe.pane) + 1) * fe.pane
    for c0 in range(0, t_end, fe.pane):
        for h, p in zip(handles, parts):
            h.submit(p.time_slice(c0, c0 + fe.pane))
            h.advance_to(c0 + fe.pane)
        fe.pump()
    for h in handles:
        h.close()
    return fe.drain()


def _assert_close(got, want, rtol):
    assert got.keys() == want.keys()
    for k, wv in want.items():
        for a, w in wv.items():
            g = got[k][a]
            if math.isnan(w):
                assert math.isnan(g), (k, a)
            else:
                assert abs(g - w) <= rtol * abs(w), (k, a, g, w)


def test_served_pallas_matches_numpy_and_warm_pass_compiles_nothing():
    wl = ridesharing_workload(3)
    stream = _stream()
    want = _serve(wl, stream, "np")
    assert want
    with CompileCounter() as cold:
        got = _serve(wl, stream, "pallas")
    # f64 in the interpreter, except the dense closed form's f32 carry
    _assert_close(got, want, rtol=1e-6)
    assert cold.compiles > 0
    with CompileCounter() as warm:
        again = _serve(wl, stream, "pallas")
    assert warm.compiles == 0
    _assert_close(again, want, rtol=1e-6)


def test_process_drive_refuses_device_backend():
    wl = ridesharing_workload(3)
    with pytest.raises(ValueError, match="chip belongs to one process"):
        ShardedHamletService(wl, ShardServiceConfig(parallel="process"),
                             backend="pallas")


def test_shards_pin_round_robin_over_devices():
    wl = ridesharing_workload(3)
    svc = ShardedHamletService(wl, ShardServiceConfig(n_shards=3),
                               backend="jax")
    assert [w.device for w in svc.workers] == [jax.devices()[0]] * 3
    svc.close()
    host = ShardedHamletService(wl, ShardServiceConfig(n_shards=2))
    assert [w.device for w in host.workers] == [None, None]
    host.close()


@pytest.mark.slow
def test_four_shards_on_four_devices_match_one_shard():
    code = """
    import jax, numpy as np
    from repro.launch.hamlet_service import ridesharing_workload
    from repro.overload import OverloadConfig
    from repro.shardsvc import ShardedHamletService, ShardServiceConfig
    from repro.streams.generator import (RIDESHARING_SCHEMA,
                                         TenantStreamConfig, tenant_stream)
    assert len(jax.devices()) == 4
    wl = ridesharing_workload(3)
    s = tenant_stream(TenantStreamConfig(
        schema=RIDESHARING_SCHEMA, n_tenants=4, groups_per_tenant=2,
        base_events_per_minute=200, minutes=1,
        type_weights=(1, 1, 6, 1, 1, 1), seed=5))
    out = {}
    for n in (1, 4):
        cfg = ShardServiceConfig(
            n_shards=n, groups_per_tenant=2, admission="none",
            overload=OverloadConfig(shed_policy="none", micro_batch=4))
        svc = ShardedHamletService(wl, cfg, backend="jax")
        assert len({w.device for w in svc.workers}) == n
        out[n] = svc.run(s)
    one, four = out[1], out[4]
    assert one and one.keys() == four.keys()
    for k, v in one.items():
        for a, x in v.items():
            y = four[k][a]
            # f32 on the device path (x64 is off here, as on a chip), and
            # the share decisions follow which groups share a shard: the
            # chip smoke test's f32 tolerance
            assert (np.isnan(x) and np.isnan(y)) or abs(x - y) <= 1e-4 * abs(x)
    print("four-devices-ok")
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "four-devices-ok" in out.stdout


@pytest.fixture
def _restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_compile_cache_follows_env(monkeypatch, tmp_path,
                                   _restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR == type(REPO_CACHE_DIR)(REPO) / ".jax_cache"
