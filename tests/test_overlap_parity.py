"""Partitioned shard_map step vs single-device reference.

The shard_map step (``make_sharded_train_step``) runs real
tensor-parallel compute: Megatron column/row-split MLPs, local
attention heads, expert-local MoE stacks, and per-layer streamed fsdp
gathers inside the scan. This file pins its *numerics* family by
family — lm, ssm, moe, lenet — against the single-device full-batch
gradient, with the same tiered tolerances as tests/test_sharded_step.py
(which covers every strategy × wire format on a one-layer model):

* "none"    — fp32 reduction-ordering noise only (floor 2e-5: the
  partitioned path re-associates matmul reductions across ranks).
  The gradient is read back as (p0 - p1) / lr, which resolves it only
  to half an f32 ulp of the parameter over lr. At lr 1e-2 that was
  2.4e-5 for mamba2's dt_bias (|p| up to 6.9) — above the floor, on
  every mesh alike, while the single-device
  fp32 gradient agrees with float64 to 2e-6. So the arch cases take
  one sgd step at lr 1, where the readback resolves 2.4e-7.
* "int8"    — one shared-scale int8 ulp of the per-shard grad maxima.
* "int8_ef" — same bound step-1; the residual buffer must engage.

A case may name a micro-batch count as its third field (the launcher's
``--microbatches``); the gradient is then the mean over micro-batches.

MoE is the one family where batch sharding changes the math (capacity
is computed from *local* tokens and the aux loss is nonlinear in the
router probabilities): a pure-model mesh (data=1) is exact vs single
device, while fsdp_tp is pinned against the mean over the data shards
of the single-device gradient on each shard's rows — the sharded
semantics, so the partitioned compute must reproduce it.

Runs in subprocesses so the 8-device placeholder pool does not leak
into the rest of the session.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(snippet, timeout=1200):
    env = {**os.environ, "PYTHONPATH": SRC}
    return subprocess.run([sys.executable, "-c", snippet],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


# Shared prelude: reference grads + per-shard maxima + tolerance tiers
# for an LM-family config named ARCH with reduction overrides RED.
_ARCH_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import TrainConfig, get_config, reduced
from repro.data import make_batch_for
from repro.launch.mesh import make_mesh
from repro.models import model as MD
from repro.models.layers import is_param, pvalues
from repro.train import (init_sharded_train_state, make_sharded_train_step,
                         sharded_state_shardings)

cfg = reduced(get_config(ARCH), **RED)
cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
LR, B, S = 1.0, 8, 32
batch = make_batch_for(cfg, B, S, step=0)

ref_params = MD.init_model(jax.random.PRNGKey(0), cfg)
grad_of = jax.jit(jax.value_and_grad(
    lambda p, b: MD.loss_fn(p, cfg, b), has_aux=True))
(_, _), ref_grads = grad_of(ref_params, batch)
ref_leaves = [np.asarray(x, np.float32) for x in jax.tree.leaves(
    pvalues(ref_grads))]

shard_max = [0.0] * len(ref_leaves)
for i in range(DATA):
    sub = jax.tree.map(lambda x: x[i * (B // DATA):(i + 1) * (B // DATA)],
                       batch)
    (_, _), g = grad_of(ref_params, sub)
    for j, x in enumerate(jax.tree.leaves(pvalues(g))):
        shard_max[j] = max(shard_max[j], float(np.max(np.abs(
            np.asarray(x, np.float32)))))

def tol_for(mode, j, g):
    m = float(np.max(np.abs(g)))
    s8 = shard_max[j] / 127.0
    return {"none": 2e-5 + 1e-5 * m,
            "int8": 2e-5 + 0.75 * s8,
            "int8_ef": 2e-5 + 0.75 * s8}[mode]

mesh = make_mesh((DATA, 8 // DATA), ("data", "model"))
results = {}
for strategy, comp, *mb in CASES:
    mb = mb[0] if mb else 1
    tcfg = TrainConfig(learning_rate=LR, optimizer="sgd", beta1=0.0,
                       weight_decay=0.0, grad_clip=1e9, total_steps=10,
                       warmup_steps=0, remat_policy="none",
                       grad_compression=comp)
    state = init_sharded_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    sh = sharded_state_shardings(cfg, tcfg, mesh, strategy)
    state = jax.device_put(state, sh)
    step = jax.jit(make_sharded_train_step(cfg, tcfg, mesh, strategy,
                                           microbatches=mb),
                   in_shardings=(sh, None), out_shardings=(sh, None))
    new_state, metrics = step(state, batch)
    lr0 = float(metrics["lr"])
    p0 = [np.asarray(x, np.float32)
          for x in jax.tree.leaves(pvalues(state.params))]
    p1 = [np.asarray(x, np.float32)
          for x in jax.tree.leaves(pvalues(new_state.params))]
    worst = 0.0
    for j, (a, b, g) in enumerate(zip(p0, p1, ref_leaves)):
        got = (a - b) / lr0
        err = float(np.max(np.abs(got - g)))
        lim = tol_for(comp, j, g)
        assert err <= lim, (strategy, comp, mb, j, err, lim)
        worst = max(worst, err / lim)
    if comp == "int8_ef":
        ef = jax.tree.leaves(pvalues(new_state.ef))
        assert sum(float(np.sum(np.abs(np.asarray(e)))) for e in ef) > 0, \
            "error feedback never engaged"
    results[f"{strategy}/{comp}" + (f"/mb{mb}" if mb > 1 else "")] = worst
print(json.dumps({"ok": True, "worst_frac_of_tol": results}))
"""


def _arch_snippet(arch, red, data, cases):
    head = (f"ARCH = {arch!r}\nRED = {red!r}\nDATA = {data}\n"
            f"CASES = {cases!r}\n")
    return head + _ARCH_PRELUDE


def test_lm_partitioned_tp_matches_single_device():
    """smollm (dense lm): tp/fsdp_tp reproduce the full-batch gradient
    under none and int8 wire formats, and with two micro-batches;
    int8_ef's residual engages, on local tensor-parallel slices too."""
    r = _run(_arch_snippet(
        "smollm-360m", dict(n_layers=2, d_model=32, vocab=128, d_ff=64),
        4, [("tp", "none"), ("fsdp_tp", "none"),
            ("tp", "int8"), ("fsdp_tp", "int8"), ("tp", "int8_ef"),
            ("fsdp_tp", "int8_ef"), ("fsdp_tp", "none", 2)]))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and len(out["worst_frac_of_tol"]) == 7


def test_ssm_partitioned_tp_matches_single_device():
    """mamba2 (ssm): the partitioned inner-dim scan matches the
    single-device step within the fp32 floor, and survives int8."""
    r = _run(_arch_snippet(
        "mamba2-370m", {}, 4,
        [("tp", "none"), ("fsdp_tp", "none"), ("fsdp_tp", "int8")]))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and len(out["worst_frac_of_tol"]) == 3


def test_moe_expert_parallel_tp_matches_single_device():
    """llama4 (moe) on a pure-model mesh (data=1): expert-local compute
    sees the full token stream, so capacity and the aux loss match the
    single-device step exactly — the partitioned path must too."""
    r = _run(_arch_snippet(
        "llama4-scout-17b-a16e", {}, 1,
        [("tp", "none"), ("tp", "int8")]))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and len(out["worst_frac_of_tol"]) == 2


MOE_MEAN_OF_SHARDS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, numpy as np
from repro.configs import TrainConfig, get_config, reduced
from repro.data import make_batch_for
from repro.launch.mesh import make_mesh
from repro.models import model as MD
from repro.models.layers import pvalues
from repro.train import (init_sharded_train_state, make_sharded_train_step,
                         sharded_state_shardings)

cfg = reduced(get_config("llama4-scout-17b-a16e"))
cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
LR, B, S, DATA = 1.0, 8, 32, 4
batch = make_batch_for(cfg, B, S, step=0)

# the sharded semantics on one device: each data shard's gradient on its
# own rows (capacity and aux loss from local tokens), meaned
ref_params = MD.init_model(jax.random.PRNGKey(0), cfg)
grad_of = jax.jit(jax.value_and_grad(
    lambda p, b: MD.loss_fn(p, cfg, b), has_aux=True))
ref_leaves = None
for i in range(DATA):
    sub = jax.tree.map(lambda x: x[i * (B // DATA):(i + 1) * (B // DATA)],
                       batch)
    (_, _), g = grad_of(ref_params, sub)
    leaves = [np.asarray(x, np.float32) / DATA
              for x in jax.tree.leaves(pvalues(g))]
    ref_leaves = leaves if ref_leaves is None else [
        a + b for a, b in zip(ref_leaves, leaves)]

mesh = make_mesh((DATA, 8 // DATA), ("data", "model"))
tcfg = TrainConfig(learning_rate=LR, optimizer="sgd", beta1=0.0,
                   weight_decay=0.0, grad_clip=1e9, total_steps=10,
                   warmup_steps=0, remat_policy="none",
                   grad_compression="none")
state = init_sharded_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
sh = sharded_state_shardings(cfg, tcfg, mesh, "fsdp_tp")
state = jax.device_put(state, sh)
step = jax.jit(make_sharded_train_step(cfg, tcfg, mesh, "fsdp_tp"),
               in_shardings=(sh, None), out_shardings=(sh, None))
new_state, metrics = step(state, batch)
lr0 = float(metrics["lr"])
p0 = [np.asarray(x, np.float32)
      for x in jax.tree.leaves(pvalues(state.params))]
p1 = [np.asarray(x, np.float32)
      for x in jax.tree.leaves(pvalues(new_state.params))]
worst = 0.0
for j, (a, b, g) in enumerate(zip(p0, p1, ref_leaves)):
    err = float(np.max(np.abs((a - b) / lr0 - g)))
    lim = 2e-5 + 1e-5 * float(np.max(np.abs(g)))
    assert err <= lim, (j, err, lim)
    worst = max(worst, err / lim)
print(json.dumps({"ok": True, "worst_frac_of_tol": worst}))
"""


def test_moe_fsdp_tp_matches_mean_of_shard_gradients():
    """fsdp_tp shards the batch, which legitimately changes MoE capacity
    vs single device — so pin the partitioned body against the mean over
    the data shards of the single-device fp32 gradient on each shard's
    rows: the sharded semantics, to fp32 ordering noise."""
    r = _run(MOE_MEAN_OF_SHARDS)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert json.loads(r.stdout.strip().splitlines()[-1])["ok"]


LENET_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.configs.lenet5 import LeNet5Config
from repro.launch.mesh import make_mesh
from repro.models.layers import is_param, pvalues
from repro.data.synthetic import lenet_batch
from repro.models.lenet import init_lenet
from repro.perf.costmodel import mesh_axes_for
from repro.perf.sweep import make_iteration, make_sharded_iteration

results = {}
for strategy, comp in (("tp", "none"), ("fsdp_tp", "none"),
                       ("fsdp_tp", "int8")):
    # dropout off: per-rank masks cover different activation slices, so
    # the parity contract only holds for the deterministic forward
    cfg = LeNet5Config(strategy=strategy, n_devices=8, batch_size=32,
                       optimizer="sgd", compression=comp, dropout=0.0)
    key = jax.random.PRNGKey(0)
    params = init_lenet(key, cfg)
    batch = lenet_batch(cfg, step=0, seed=0, batch=cfg.batch_size)
    ref, _ = make_iteration(cfg, "jit")(params, batch, key)

    axes = mesh_axes_for(strategy, 8)
    # int8 scales are agreed over *per-shard* grads, whose maxima exceed
    # the full-batch mean's — bound the ulp from the data-shard maxima
    from repro.models.lenet import lenet_loss
    data = axes.get("data", 1)
    shard_max = {k: 0.0 for k in params}
    for i in range(data):
        sub = jax.tree.map(
            lambda x: x[i * (32 // data):(i + 1) * (32 // data)], batch)
        g = jax.grad(lambda p: lenet_loss(p, sub, cfg, key))(params)
        for k in params:
            shard_max[k] = max(shard_max[k], float(np.max(np.abs(
                np.asarray(g[k].value, np.float32)))))
    mesh = make_mesh(tuple(axes.values()), tuple(axes))
    it, pspecs, batch_spec = make_sharded_iteration(cfg, "jit", mesh, params)
    shardings = jax.tree.map(lambda p, s: NamedSharding(mesh, s), params,
                             pspecs, is_leaf=is_param)
    p = jax.device_put(params, shardings)
    b = jax.device_put(batch, NamedSharding(mesh, batch_spec))
    new_p, _ = it(p, b, key)

    worst = 0.0
    for k in params:
        got = np.asarray(new_p[k].value, np.float32)
        want = np.asarray(ref[k].value, np.float32)
        g = np.abs(np.asarray(params[k].value, np.float32) - want).max() \
            / cfg.learning_rate
        lim = (2e-5 + 1e-5 * g if comp == "none"
               else 2e-5 + 0.75 * shard_max[k] / 127.0) * cfg.learning_rate
        err = float(np.max(np.abs(got - want)))
        assert err <= lim, (strategy, comp, k, err, lim)
        worst = max(worst, err / max(float(lim), 1e-30))
    results[f"{strategy}/{comp}"] = float(worst)
print(json.dumps({"ok": True, "worst_frac_of_tol": results}))
"""


def test_lenet_partitioned_fc_matches_single_device():
    """The measured LeNet body with Megatron-split fc1/fc2 (tp and
    fsdp_tp on the 8-device pool, 120 % 8 == 0) reproduces the
    single-device full-batch sgd update."""
    r = _run(LENET_SNIPPET)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and len(out["worst_frac_of_tol"]) == 3
