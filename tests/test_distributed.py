"""Distributed substrate tests: sharding rules, checkpoint/restore +
elastic resharding, gradient compression, and pipeline parallelism.

Multi-device cases run in a subprocess with forced host devices so the main
test session keeps a single device (smoke tests must see 1 device)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.checkpoint import (CheckpointManager, latest_step,
                                          restore_checkpoint, save_checkpoint)
from repro.distributed.compression import (dequantize_int8, ef_compress_tree,
                                           quantize_int8)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ----------------------------------------------------------- checkpointing


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": [jnp.ones((2,), jnp.bfloat16), jnp.zeros((), jnp.int32)]}
    save_checkpoint(str(tmp_path), 7, tree)
    assert latest_step(str(tmp_path)) == 7
    got = restore_checkpoint(str(tmp_path), 7, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a, np.float64),
                              np.asarray(b, np.float64))


def test_checkpoint_manager_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), interval=1, keep=2)
    tree = {"x": jnp.zeros((4,))}
    for s in range(1, 6):
        mgr.maybe_save(s, tree)
    mgr.wait()
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path)
                   if n.startswith("step_"))
    assert steps == [4, 5]


def test_checkpoint_elastic_reshard():
    """Save on an 8-device mesh, restore onto a 4-device mesh."""
    code = """
    import jax, numpy as np, tempfile, os
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.checkpoint import save_checkpoint, restore_checkpoint
    mesh8 = jax.make_mesh((8,), ("data",))
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh8, P("data", None)))
    d = tempfile.mkdtemp()
    save_checkpoint(d, 1, {"x": xs})
    mesh4 = jax.make_mesh((4, 2), ("data", "model"))
    sh = {"x": NamedSharding(mesh4, P("model", "data"))}
    got = restore_checkpoint(d, 1, {"x": x}, shardings=sh)
    assert np.array_equal(np.asarray(got["x"]), np.asarray(x))
    assert got["x"].sharding.spec == P("model", "data")
    print("elastic-ok")
    """
    assert "elastic-ok" in _run_subprocess(code)


# ----------------------------------------------------------- compression


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000) * 5.0, jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_accumulates():
    grads = {"w": jnp.asarray([1.0, 1e-4, -1e-4], jnp.float32)}
    errors = {"w": jnp.zeros(3, jnp.float32)}
    qs, scales, new_err = ef_compress_tree(grads, errors)
    # residual carries the information the int8 payload lost
    deq = dequantize_int8(qs["w"], scales["w"])
    assert np.allclose(np.asarray(deq + new_err["w"]),
                       np.asarray(grads["w"]), atol=1e-7)


@pytest.mark.slow
def test_compressed_psum_across_pods():
    code = """
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import compressed_psum_tree
    mesh = jax.make_mesh((4,), ("pod",))
    def f(g):
        synced, err = compressed_psum_tree({"w": g}, {"w": jnp.zeros_like(g)},
                                           "pod", 4)
        return synced["w"], err["w"]
    g = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.0
    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                               out_specs=P("pod"), check_vma=False))
    synced, err = fn(g)
    want = np.asarray(g).reshape(4, 8).mean(axis=0)
    got = np.asarray(synced)[0]
    # int8 quantization error bounded by scale/2 per pod
    scale = np.abs(np.asarray(g)).max() / 127.0
    assert np.abs(got - want).max() <= scale, (got, want)
    print("psum-ok")
    """
    assert "psum-ok" in _run_subprocess(code, devices=4)


# ----------------------------------------------------------- pipeline


@pytest.mark.slow
def test_pipeline_matches_sequential():
    code = """
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.distributed.pipeline import pipelined_apply, sequential_apply
    mesh = jax.make_mesh((4,), ("stage",))
    L, B, D = 8, 16, 32
    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (L, D, D)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
    layer = lambda W, h: jnp.tanh(h @ W)
    want = sequential_apply(layer, Ws, x)
    got = pipelined_apply(layer, Ws, x, mesh=mesh, n_micro=4)
    err = np.abs(np.asarray(got) - np.asarray(want)).max()
    assert err < 1e-5, err
    print("pipe-ok")
    """
    assert "pipe-ok" in _run_subprocess(code, devices=4)


# ----------------------------------------------------------- sharding rules


def test_param_pspecs_cover_model():
    from jax.sharding import PartitionSpec as P

    code_free = True  # runs in-process: pspec computation touches no devices
    import jax as _jax
    from repro.configs import get_config, reduce_for_smoke
    from repro.distributed.sharding import param_pspecs
    from repro.models.lm import init_params

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    for arch in ("gemma2-2b", "olmoe-1b-7b", "zamba2-7b", "rwkv6-7b",
                 "whisper-tiny"):
        cfg = get_config(arch)
        shapes = _jax.eval_shape(
            lambda c=cfg: init_params(c, _jax.random.PRNGKey(0)))
        notes = []
        specs = param_pspecs(shapes, FakeMesh(), notes)
        # big matrices must be sharded on at least one axis
        flat = _jax.tree_util.tree_flatten_with_path(shapes)[0]
        spec_flat = _jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
        for (path, leaf), spec in zip(flat, spec_flat):
            if np.prod(leaf.shape) >= 1 << 22:  # >= 4M elements
                assert any(ax is not None for ax in spec), (arch, path)


@pytest.mark.slow
def test_dp_compressed_train_step():
    """Full multi-pod train step with int8 EF gradient sync: runs, and the
    parameter update stays within the int8 quantization envelope of the
    uncompressed step."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import lm
    from repro.train.optimizer import AdamW
    from repro.distributed.compression import dp_compressed_step_fn

    # Auto axes: the step partitions under GSPMD sharding constraints,
    # which jax's default Explicit mesh axes refuse
    mesh = jax.make_mesh((2, 2, 4), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    cfg = reduce_for_smoke(get_config("h2o-danube-1.8b"))
    opt = AdamW(lr=1e-3)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)),
                                   jnp.int32)}
    step, init_errors = dp_compressed_step_fn(cfg, opt, mesh, n_pods=2)
    errors = init_errors(params)
    with mesh:
        p2, o2, e2, loss = step(params, opt_state, errors, batch)
    assert jnp.isfinite(loss)

    def plain(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, batch))(params)
        return opt.update(params, grads, opt_state)[0]
    pr = jax.jit(plain)(params, opt_state, batch)
    d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                  b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(pr)))
    assert d <= 5e-3, d    # bounded by lr * O(1) quantization error
    print("dp-compressed-ok")
    """
    assert "dp-compressed-ok" in _run_subprocess(code, devices=16)
