"""Substrate tests: optimizers, compression, checkpointing, FT, data,
sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import TrainConfig, get_config, reduced
from repro.data import TokenStream
from repro.dist.compression import (compress_decompress, compressed_psum_mean,
                                    quantize_int8)
from repro.dist.sharding import (STRATEGIES, logical_to_pspec,
                                 param_pspecs)
from repro.models.layers import Param
from repro.optim import clip_by_global_norm, make_optimizer, warmup_cosine
from repro.train.checkpoint import CheckpointManager
from repro.train.ft import StragglerDetector, plan_remesh


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["adamw", "sgd", "adafactor"])
def test_optimizer_decreases_quadratic(name):
    tcfg = TrainConfig(optimizer=name, learning_rate=0.1, weight_decay=0.0)
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": Param(jnp.zeros(3), (None,))}
    init, update = make_optimizer(name)
    state = init(params, tcfg)

    def loss(p):
        return jnp.sum((p["w"].value - target) ** 2)

    l0 = float(loss(params))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = update(params, g, state, tcfg, 0.05)
    assert float(loss(params)) < l0 * 0.05, (name, float(loss(params)))


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 20.0) < 1e-4
    got = float(jnp.linalg.norm(clipped["a"]))
    assert abs(got - 1.0) < 1e-4


def test_warmup_cosine_shape():
    lrs = [float(warmup_cosine(s, peak_lr=1.0, warmup_steps=10,
                               total_steps=100)) for s in range(100)]
    assert lrs[0] == 0.0 and abs(max(lrs) - 1.0) < 1e-6
    assert lrs[-1] < 0.2 and lrs[5] < lrs[9]


# ---------------------------------------------------------------------------
# Compression
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_int8_roundtrip_error_bound(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=128) * rng.uniform(0.1, 10))
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(x) - np.asarray(q, np.float32) * float(s))
    assert err.max() <= float(s) * 0.51 + 1e-6    # half-ulp of the grid


def test_error_feedback_reduces_bias():
    """With EF, the *accumulated* quantized gradient tracks the true sum."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=64) * 0.01)
    acc_ef, err = jnp.zeros(64), None
    acc_noef = jnp.zeros(64)
    for _ in range(50):
        d, err = compress_decompress(g_true, "int8_ef", err)
        acc_ef = acc_ef + d
        d2, _ = compress_decompress(g_true, "int8_ef", None)
        acc_noef = acc_noef + d2
    target = np.asarray(g_true) * 50
    assert np.abs(np.asarray(acc_ef) - target).max() <= \
        np.abs(np.asarray(acc_noef) - target).max() + 1e-6


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_error_feedback_bounded_long_horizon(seed):
    """EF invariant: acc_t + err_t == t·g exactly, so the deviation of the
    accumulated update equals |err_t| — one quantization ulp, bounded
    independently of the horizon (it must not grow linearly in t)."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=32) * 0.01)
    acc, err = jnp.zeros(32), None
    dev = {}
    for t in range(1, 241):
        d, err = compress_decompress(g, "int8_ef", err)
        acc = acc + d
        if t in (40, 240):
            dev[t] = float(np.abs(np.asarray(acc)
                                  - np.asarray(g) * t).max())
    g_inf = float(np.abs(np.asarray(g)).max())
    assert dev[240] <= g_inf / 50.0          # ~half-ulp of the int8 grid
    assert dev[240] <= 4 * dev[40] + 1e-7    # no linear-in-t drift


def test_compress_tree_modes_and_ef_plumbing():
    """compress_tree preserves leaf wrappers and threads EF buffers."""
    from repro.dist.compression import compress_tree, init_error_feedback
    params = {"w": Param(jnp.asarray(np.linspace(-1, 1, 16)), ("embed",)),
              "b": Param(jnp.asarray(np.ones(4) * 0.3), (None,))}
    grads = jax.tree.map(lambda p: Param(p.value * 0.1, p.axes), params,
                         is_leaf=lambda x: isinstance(x, Param))
    ef = init_error_feedback(params)
    out, new_ef = compress_tree(grads, "int8_ef", ef)
    assert isinstance(out["w"], Param) and out["w"].axes == ("embed",)
    assert isinstance(new_ef["w"], Param)
    # raw-array gradient trees (micro-batch accumulators) work too
    raw = {"w": jnp.ones(16) * 0.01, "b": jnp.ones(4) * 0.02}
    out2, ef2 = compress_tree(raw, "bf16", None)
    assert not isinstance(out2["w"], Param) and ef2 is None
    # "none" is the identity
    out3, _ = compress_tree(raw, "none", None)
    assert out3 is raw


def test_compressed_psum_matches_mean():
    """shard_map int8 all-reduce-mean == plain mean on a 1-device mesh."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8,)))
    f = jax.shard_map(lambda v: compressed_psum_mean(v, "data"), mesh=mesh,
                      in_specs=P(), out_specs=P())
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x), atol=2e-2)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def _toy_state():
    return {"p": Param(jnp.arange(6.0).reshape(2, 3), ("a", "b")),
            "step": jnp.asarray(7)}


def test_checkpoint_roundtrip(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    state = _toy_state()
    cm.save(5, state)
    restored, step = cm.restore(state)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["p"].value),
                                  np.asarray(state["p"].value))


def test_checkpoint_corruption_fallback(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=5, async_write=False)
    state = _toy_state()
    cm.save(1, state)
    cm.save(2, state)
    # corrupt the newest checkpoint
    with open(os.path.join(str(tmp_path), "ckpt_2.npz"), "wb") as f:
        f.write(b"garbage")
    restored, step = cm.restore(state)
    assert step == 1                      # fell back to the older one


def test_checkpoint_gc(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    state = _toy_state()
    for s in (1, 2, 3, 4):
        cm.save(s, state)
    assert cm.available_steps() == [3, 4]


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------

def test_straggler_detector_flags_outlier():
    det = StragglerDetector(tolerance=1.5)
    for i in range(10):
        det.observe(i, 0.1)
    assert det.observe(10, 0.3) is True
    assert det.observe(11, 0.11) is False


def test_straggler_uses_perf_model_hook():
    det = StragglerDetector(tolerance=1.5, predict_s=lambda: 0.1)
    assert det.observe(0, 0.2) is True    # no history needed
    assert det.observe(1, 0.12) is False


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 4096), st.integers(1, 16))
def test_plan_remesh_properties(n_devices, min_model):
    plan = plan_remesh(n_devices, min_model=min_model)
    d, m = plan.mesh_shape
    assert d * m <= n_devices and d >= 1 and m >= 1
    # power-of-two rounding
    assert (d * m) & (d * m - 1) == 0


def test_plan_remesh_uses_predictor():
    # predictor prefers wide model axis
    plan = plan_remesh(16, predict=lambda d, m: 1.0 / m)
    assert plan.mesh_shape[1] == 16


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

def test_tokenstream_deterministic_by_step():
    s1 = TokenStream(1000, 4, 16, seed=3)
    s2 = TokenStream(1000, 4, 16, seed=3)
    np.testing.assert_array_equal(s1.batch_np(7), s2.batch_np(7))
    assert not np.array_equal(s1.batch_np(7), s1.batch_np(8))


def test_tokenstream_zipf_marginal():
    s = TokenStream(100, 64, 64, seed=0)
    toks = s.batch_np(0).ravel()
    # token 0 (rank 1) must be much more frequent than token 99
    c0 = (toks == 0).sum()
    c99 = (toks == 99).sum()
    assert c0 > c99 * 5


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

def test_logical_to_pspec_no_axis_reuse():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    strat = STRATEGIES["fsdp_tp"]
    spec = logical_to_pspec(("expert", "embed", "mlp"), mesh, strat,
                            dim_sizes=(16, 64, 128))
    flat = [a for e in spec if e for a in
            (e if isinstance(e, tuple) else (e,))]
    assert len(flat) == len(set(flat))      # each mesh axis at most once


def test_logical_to_pspec_divisibility():
    # logical_to_pspec accepts an {axis: size} mapping, so a 16-wide model
    # axis is testable without a 32-device pool.
    sizes = {"data": 2, "model": 16}
    strat = STRATEGIES["fsdp_tp"]
    # vocab 50281 is odd: divisible by neither model(16) nor data(2)
    # -> the dim must stay unsharded; embed 64 shards over data.
    spec = logical_to_pspec(("vocab", "embed"), sizes, strat,
                            dim_sizes=(50281, 64))
    assert spec[0] is None
    assert spec[1] == "data"
    # a divisible vocab (50288 = 16·3143) does shard over model
    spec2 = logical_to_pspec(("vocab", "embed"), sizes, strat,
                             dim_sizes=(50288, 64))
    assert spec2[0] == "model"
    assert spec2[1] == "data"


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2 ** 31 - 1),
       st.sampled_from(("dp", "fsdp", "tp", "fsdp_tp")),
       st.sampled_from((1, 2, 3, 4, 8)),
       st.sampled_from((1, 2, 4, 16)))
def test_logical_pspec_properties(seed, strat_name, data_sz, model_sz):
    """For randomized shapes/axes: no mesh axis is ever used twice, and
    no dim is sharded unless the assigned axes' product divides it."""
    rng = np.random.default_rng(seed)
    logicals = ("embed", "mlp", "vocab", "expert", "heads", "kv_heads",
                "layers", None)
    ndim = int(rng.integers(1, 5))
    axes = tuple(logicals[int(rng.integers(0, len(logicals)))]
                 for _ in range(ndim))
    dims = tuple(int(rng.integers(1, 200)) for _ in range(ndim))
    sizes = {"data": data_sz, "model": model_sz}
    spec = logical_to_pspec(axes, sizes, STRATEGIES[strat_name],
                            dim_sizes=dims)
    entries = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    flat = [a for e in entries if e for a in
            (e if isinstance(e, tuple) else (e,))]
    assert len(flat) == len(set(flat))
    for dim, entry in zip(dims, entries):
        if entry is None:
            continue
        prod = 1
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            prod *= sizes[a]
        assert dim % prod == 0, (axes, dims, strat_name, spec)


def test_maybe_constrain_noop_without_mesh():
    from repro.dist.sharding import BATCH, maybe_constrain
    x = jnp.ones((4, 8))
    y = maybe_constrain(x, BATCH, "model")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_batch_pspec_divisibility_aware():
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import batch_pspec
    sizes = {"pod": 2, "data": 4, "model": 2}
    assert batch_pspec(sizes, 3, 16) == P(("pod", "data"), None, None)
    # batch of 2 fits the pod axis but not pod×data=8
    assert batch_pspec(sizes, 2, 2) == P("pod", None)
    # odd batch cannot shard at all
    assert batch_pspec(sizes, 2, 3) == P(None, None)


@pytest.mark.parametrize("arch", ["smollm-360m", "deepseek-v3-671b",
                                  "mamba2-370m"])
def test_param_pspecs_cover_all_leaves(arch):
    from repro.models import model as MD
    cfg = reduced(get_config(arch))
    params = jax.eval_shape(lambda: MD.init_model(jax.random.PRNGKey(0),
                                                  cfg))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    specs = param_pspecs(params, mesh, "fsdp_tp")
    n_leaves = len(jax.tree.leaves(params))
    n_specs = len(jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    assert n_specs == n_leaves
