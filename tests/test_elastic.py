"""Elastic training: sharded checkpoints, cross-strategy reshard-on-
restore, recovery planning, and the fault-injection harness.

Headline gate: for EVERY (source, destination) strategy pair in the
registry, a run checkpointed under source on the 8-device pool and
restored under destination on half the pool must continue the loss
trajectory of the uninterrupted source run within an ulp-tiered fp32
tolerance — resharding is routed through the same ``param_pspecs``
resolution the executable step uses, so the restored state is the same
mathematical state.

Pool-dependent pieces run in subprocess snippets (the forced 8-device
pool must not leak into this session) — the same pattern as
tests/test_sharded_step.py. Disk/planning pieces run in-process.
"""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from faults import corrupt_checkpoint, kill_devices, slow_rank_times
from repro.models.layers import Param
from repro.train.checkpoint import CheckpointManager
from repro.train.ft import (StragglerDetector, _factorizations,
                            plan_recovery, plan_remesh)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
HERE = os.path.dirname(__file__)


def _run(snippet, timeout=1200):
    env = {**os.environ, "PYTHONPATH": SRC}
    return subprocess.run([sys.executable, "-c", snippet],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def _toy_state():
    return {"p": Param(jnp.arange(6.0).reshape(2, 3), ("a", "b")),
            "step": jnp.asarray(7)}


# ---------------------------------------------------------------------------
# Satellite: GC suffix audit
# ---------------------------------------------------------------------------

def test_gc_keep1_leaves_exactly_two_files(tmp_path):
    """keep=1 must leave exactly the newest data file + its sidecar —
    the regression for the GC suffix pair (_DATA_SUFFIX/_META_SUFFIX)."""
    cm = CheckpointManager(str(tmp_path), keep=1, async_write=False)
    state = _toy_state()
    for s in (1, 2, 3):
        cm.save(s, state)
    assert sorted(os.listdir(str(tmp_path))) == \
        ["ckpt_3.npz", "ckpt_3.npz.json"]
    assert cm.available_steps() == [3]


def test_gc_removes_orphan_sidecars_and_temps(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    state = _toy_state()
    cm.save(1, state)
    # a sidecar whose data file vanished, and a torn temp write
    with open(os.path.join(str(tmp_path), "ckpt_9.npz.json"), "w") as f:
        f.write("{}")
    with open(os.path.join(str(tmp_path), ".tmp_ckpt_5.npz"), "wb") as f:
        f.write(b"torn")
    cm.save(2, state)                   # save triggers GC
    assert sorted(os.listdir(str(tmp_path))) == [
        "ckpt_1.npz", "ckpt_1.npz.json", "ckpt_2.npz", "ckpt_2.npz.json"]


# ---------------------------------------------------------------------------
# Satellite: checkpoint round-trips across dtypes + fault injection
# ---------------------------------------------------------------------------

def test_bf16_roundtrip_bit_exact(tmp_path):
    """bf16 params survive the fp32 npz upcast bit-exactly: every bf16
    value is exactly representable in fp32, and the restore casts back
    to the skeleton's dtype."""
    vals = jnp.asarray(np.linspace(-3.0, 3.0, 64), jnp.bfloat16)
    state = {"w": Param(vals, ("a",))}
    cm = CheckpointManager(str(tmp_path), async_write=False)
    cm.save(1, state)
    restored, _ = cm.restore(state)
    got = np.asarray(restored["w"].value)
    assert got.dtype == np.asarray(vals).dtype
    np.testing.assert_array_equal(got.view(np.uint16),
                                  np.asarray(vals).view(np.uint16))


@pytest.mark.parametrize("mode", ["garble", "truncate"])
def test_corrupt_checkpoint_falls_back(tmp_path, mode):
    """A damaged newest checkpoint (fault-harness injector) must fall
    back to the next-older complete one."""
    cm = CheckpointManager(str(tmp_path), keep=5, async_write=False)
    state = _toy_state()
    cm.save(1, state)
    cm.save(2, state)
    hit = corrupt_checkpoint(str(tmp_path), mode=mode)
    assert hit.endswith("ckpt_2.npz")
    restored, step = cm.restore(state)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["p"].value),
                                  np.asarray(state["p"].value))


def test_dropped_sidecar_hides_checkpoint(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=5, async_write=False)
    state = _toy_state()
    cm.save(1, state)
    cm.save(2, state)
    corrupt_checkpoint(str(tmp_path), mode="drop_sidecar")
    assert cm.available_steps() == [1]
    _, step = cm.restore(state)
    assert step == 1


def test_async_save_equals_sync(tmp_path):
    state = _toy_state()
    cm_a = CheckpointManager(str(tmp_path / "a"), async_write=True)
    cm_a.save(3, state)
    cm_a.wait()
    cm_s = CheckpointManager(str(tmp_path / "s"), async_write=False)
    cm_s.save(3, state)
    assert cm_a.available_steps() == cm_s.available_steps() == [3]
    ra, sa = cm_a.restore(state)
    rs, ss = cm_s.restore(state)
    assert sa == ss == 3
    for a, b in zip(jax.tree.leaves(ra), jax.tree.leaves(rs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_save_sidecar_and_roundtrip_single_device(tmp_path):
    """save_sharded on a trivial 1-device mesh: sidecar records mesh/
    strategy/specs, and restore reassembles the identical state."""
    from repro.configs import TrainConfig, get_config, reduced
    from repro.launch.mesh import make_mesh
    from repro.train import init_sharded_train_state
    from repro.train.step import sharded_state_specs
    import dataclasses

    cfg = reduced(get_config("smollm-360m"), n_layers=1, d_model=32,
                  vocab=128, d_ff=64)
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    tcfg = TrainConfig(optimizer="adamw", remat_policy="none")
    mesh = make_mesh((1, 1), ("data", "model"))
    state = init_sharded_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    specs = sharded_state_specs(cfg, tcfg, mesh, "fsdp_tp")
    cm = CheckpointManager(str(tmp_path), async_write=False)
    cm.save_sharded(7, state, mesh=mesh, strategy="fsdp_tp", specs=specs,
                    extra_meta={"arch": cfg.name})
    meta = cm.read_meta(7)
    assert meta["format"] == "sharded-v1"
    assert meta["strategy"] == "fsdp_tp"
    assert meta["mesh"] == {"data": 1, "model": 1}
    assert meta["arch"] == cfg.name
    assert meta["specs"]                       # per-leaf PartitionSpecs
    restored, step = cm.restore(state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Satellite: StragglerDetector units
# ---------------------------------------------------------------------------

def test_straggler_perf_model_hook():
    det = StragglerDetector(tolerance=2.0, predict_s=lambda: 0.1)
    assert det.expected() == pytest.approx(0.1)
    assert det.observe(0, 0.15) is False
    assert det.observe(1, 0.25) is True
    assert det.flags == [1]


def test_straggler_boundary_equality_not_flagged():
    det = StragglerDetector(tolerance=2.0, predict_s=lambda: 0.1)
    # seconds == tol * expected sits ON the boundary: not a straggler
    assert det.observe(0, 0.2) is False
    assert det.flags == []


def test_straggler_raising_predict_falls_through():
    def boom():
        raise RuntimeError("model not fitted")
    det = StragglerDetector(tolerance=2.0, predict_s=boom)
    times = slow_rank_times(0.1, 8, slow_at=[7], factor=5.0)
    flags = [det.observe(i, t) for i, t in enumerate(times)]
    # first 5 observations: no expectation yet (hook raises, median
    # needs >= 5 samples) -> never flagged; the 5x step 7 is caught by
    # the median fallback
    assert flags[:5] == [False] * 5
    assert flags[7] is True
    assert det.flags == [7]


def test_straggler_median_fallback_tracks_history():
    det = StragglerDetector(tolerance=2.0, window=8)
    for i, t in enumerate(slow_rank_times(0.1, 6, slow_at=[], factor=1.0)):
        assert det.observe(i, t) is False
    assert det.expected() == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# Satellite: property tests for _factorizations / plan_remesh
# ---------------------------------------------------------------------------

def _pow2_floor(n):
    return 2 ** int(math.floor(math.log2(n))) if n > 1 else max(n, 1)


@settings(max_examples=80)
@given(st.integers(1, 4096))
def test_factorizations_multiply_to_n(n):
    facs = _factorizations(n)
    assert facs
    for d, m in facs:
        assert d * m == n
    assert len(set(facs)) == len(facs)


@settings(max_examples=80)
@given(st.integers(1, 512), st.integers(1, 8), st.booleans())
def test_plan_remesh_product_and_min_model(n, min_model, pow2):
    plan = plan_remesh(n, min_model=min_model, prefer_pow2=pow2)
    d, m = plan.mesh_shape
    n_eff = _pow2_floor(n) if pow2 else n
    assert d * m == n_eff
    if any(mm >= min_model for _, mm in _factorizations(n_eff)):
        assert m >= min_model


@settings(max_examples=60)
@given(st.integers(1, 512), st.integers(1, 16))
def test_plan_remesh_respects_max_model(n, max_model):
    plan = plan_remesh(n, max_model=max_model, prefer_pow2=True)
    d, m = plan.mesh_shape
    n_eff = _pow2_floor(n)
    assert d * m == n_eff
    if any(mm <= max_model for _, mm in _factorizations(n_eff)):
        assert m <= max_model


@settings(max_examples=60)
@given(st.integers(1, 256), st.floats(0.01, 5.0), st.floats(0.01, 5.0))
def test_perf_ranked_pick_never_loses_to_fallback(n, a, b):
    """Under the same predict, the perf-ranked plan is never costlier
    than the most-square fallback's shape."""
    def predict(d, m):
        return a * d + b * m * m
    ranked = plan_remesh(n, predict=predict)
    fallback = plan_remesh(n)            # most-square, same constraints
    assert ranked.reason == "perf-model ranked"
    assert predict(*ranked.mesh_shape) <= predict(*fallback.mesh_shape)


# ---------------------------------------------------------------------------
# Recovery planning (injected hooks — no planner import)
# ---------------------------------------------------------------------------

def test_kill_devices_prefix_surviving():
    devs = list(range(8))
    assert kill_devices(devs, 4) == [0, 1, 2, 3]
    assert kill_devices(devs, 0) == devs
    assert kill_devices(devs, 99) == [0]      # never empty


def test_plan_recovery_with_injected_hooks():
    calls = {}

    class FakeDecision:
        strategy = "fsdp_tp"
        reason = "fake ranking"

        def to_dict(self):
            return {"strategy": self.strategy}

    def choose(cfg, **kw):
        calls["choose"] = kw
        return FakeDecision()

    def make_predict(cfg, strategy, **kw):
        calls["strategy"] = strategy
        return lambda d, m: abs(d - 2)       # prefers data=2

    plan = plan_recovery(object(), 6, batch=8, seq=16,
                         choose=choose, make_predict=make_predict)
    # 6 devices pow2-floors to 4; fsdp_tp needs a real model axis
    assert calls["choose"]["n_devices"] == 4
    assert calls["strategy"] == "fsdp_tp"
    assert plan.strategy == "fsdp_tp"
    assert plan.mesh_shape == (2, 2)
    assert plan.n_devices == 4
    assert "fake ranking" in plan.reason
    assert plan.to_dict()["planner"] == {"strategy": "fsdp_tp"}


def test_plan_recovery_forced_strategy_skips_chooser():
    def choose(cfg, **kw):                    # must never be called
        raise AssertionError("chooser called despite forced strategy")

    def make_predict(cfg, strategy, **kw):
        return lambda d, m: d + m

    plan = plan_recovery(object(), 8, batch=8, seq=16, strategy="dp",
                         choose=choose, make_predict=make_predict)
    assert plan.strategy == "dp"
    assert plan.mesh_shape == (8, 1)          # dp pins the model axis


# ---------------------------------------------------------------------------
# Headline: cross-strategy reshard-on-restore parity, all registry pairs
# ---------------------------------------------------------------------------

PARITY_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, tempfile
import jax, numpy as np
from repro.configs import TrainConfig, get_config, reduced
from repro.data import make_batch_for
from repro.dist.sharding import STRATEGIES
from repro.launch.mesh import make_mesh
from repro.launch.specs import batch_shardings
from repro.train import init_sharded_train_state, init_train_state, \
    make_sharded_train_step, sharded_state_shardings
from repro.train.step import sharded_state_specs
from repro.train.checkpoint import CheckpointManager

cfg = reduced(get_config("smollm-360m"), n_layers=1, d_model=32,
              vocab=128, d_ff=64)
cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
B, S, STEPS, FAIL = 8, 16, 4, 2
tcfg = TrainConfig(learning_rate=1e-3, optimizer="adamw",
                   total_steps=STEPS, warmup_steps=0,
                   remat_policy="none", grad_compression="none")
batches = [make_batch_for(cfg, B, S, step=i) for i in range(STEPS)]

mesh8 = make_mesh((4, 2), ("data", "model"))
mesh4 = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])

def build(mesh, strategy):
    specs = sharded_state_specs(cfg, tcfg, mesh, strategy)
    sh = sharded_state_shardings(cfg, tcfg, mesh, strategy, specs=specs)
    bs = batch_shardings(batches[0], mesh)
    fn = jax.jit(make_sharded_train_step(cfg, tcfg, mesh, strategy,
                                         state_specs=specs),
                 in_shardings=(sh, bs), out_shardings=(sh, None))
    return specs, sh, fn

skel = jax.eval_shape(
    lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg))
exec4 = {s: build(mesh4, s) for s in sorted(STRATEGIES)}

# ulp-tiered fp32 tolerance: the restored state is bit-identical, so
# post-restore losses may differ from the reference only by collective
# reassociation — a few hundred ulps at loss magnitude, not more.
TOL = float(256 * np.spacing(np.float32(8.0)))

out = {"pairs": {}, "failures": [], "tol": TOL}
for src in sorted(STRATEGIES):
    specs8, sh8, fn8 = build(mesh8, src)
    state = jax.device_put(
        init_sharded_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh8),
        sh8)
    ref = []
    d = tempfile.mkdtemp()
    cm = CheckpointManager(d, keep=2, async_write=False)
    for i in range(STEPS):
        if i == FAIL:
            cm.save_sharded(i, state, mesh=mesh8, strategy=src,
                            specs=specs8, extra_meta={"arch": cfg.name})
        with jax.set_mesh(mesh8):
            state, m = fn8(state, batches[i])
        ref.append(float(m["loss"]))
    meta = cm.read_meta(FAIL)
    assert meta["strategy"] == src and meta["mesh"] == \
        {"data": 4, "model": 2}, meta
    for dst in sorted(STRATEGIES):
        specs4, sh4, fn4 = exec4[dst]
        st, step0 = cm.restore(skel, shardings=sh4, strict=False)
        assert step0 == FAIL
        got = []
        for i in range(FAIL, STEPS):
            with jax.set_mesh(mesh4):
                st, m = fn4(st, batches[i])
            got.append(float(m["loss"]))
        errs = [abs(a - b) for a, b in zip(got, ref[FAIL:])]
        key = src + "->" + dst
        out["pairs"][key] = {"ref": ref[FAIL:], "got": got,
                             "max_err": max(errs)}
        if max(errs) > TOL:
            out["failures"].append(key)
print(json.dumps(out))
"""


def test_reshard_restore_parity_all_strategy_pairs():
    """8-device checkpoint under every source strategy restores onto a
    4-device mesh under every destination strategy and continues the
    uninterrupted loss trajectory within ulp-tiered tolerance."""
    r = _run(PARITY_SNIPPET)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out["pairs"]) == 16          # full registry product
    assert out["failures"] == [], {
        k: out["pairs"][k] for k in out["failures"]}


# ---------------------------------------------------------------------------
# Headline: driver-level failure -> re-plan -> reshard -> resume
# ---------------------------------------------------------------------------

def _run_driver(extra, timeout=600):
    env = {**os.environ, "PYTHONPATH": SRC}
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "smollm-360m", "--reduced", "--steps", "6", "--batch", "8",
            "--seq", "32", "--dtype", "float32", "--log-every", "10"]
    return subprocess.run(args + extra, capture_output=True, text=True,
                          env=env, timeout=timeout)


def test_driver_simulated_failure_recovery_parity(tmp_path):
    ref = _run_driver(["--strategy", "fsdp"])
    assert ref.returncode == 0, ref.stderr[-3000:]
    ref_out = json.loads(ref.stdout.strip().splitlines()[-1])

    drill = _run_driver(["--strategy", "fsdp", "--ckpt-dir",
                         str(tmp_path / "ckpt"), "--ckpt-every", "2",
                         "--simulate-failure", "4",
                         "--recover-strategy", "tp"])
    assert drill.returncode == 0, drill.stderr[-3000:]
    out = json.loads(drill.stdout.strip().splitlines()[-1])

    rec = out["recovery"]
    assert rec["at_step"] == 4 and rec["lost_devices"] == 4
    assert rec["before"]["strategy"] == "fsdp"
    assert rec["after"]["strategy"] == out["strategy"] == "tp"
    assert rec["after"]["devices"] == 4
    assert rec["recovery_s"] > 0 and rec["restore_s"] > 0
    tol = float(256 * np.spacing(np.float32(8.0)))
    assert len(out["losses"]) == len(ref_out["losses"]) == 6
    for a, b in zip(out["losses"], ref_out["losses"]):
        assert abs(a - b) <= tol, (out["losses"], ref_out["losses"])


def test_driver_dry_run_reports_recovery_plan():
    r = _run_driver(["--simulate-failure", "2", "--dry-run"])
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    rec = out["recovery"]
    assert rec["at_step"] == 2 and rec["lost_devices"] == 4
    assert rec["devices"] == int(np.prod(rec["mesh"])) == 4
    assert "planner" in rec                   # auto-chosen strategy


def test_driver_simulate_failure_requires_ckpt_dir():
    r = _run_driver(["--simulate-failure", "2"])
    assert r.returncode != 0
    assert "requires --ckpt-dir" in (r.stderr + r.stdout)
