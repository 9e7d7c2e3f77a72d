"""Sharded (shard_map) train step vs single-device reference.

The measured path must be *numerically equivalent* to the single-device
step, strategy by strategy: computing on tensor-parallel slices,
gathering the other parameter shards, computing per-device gradients on
batch shards, and all-reduce-meaning them through the compressed
collective has to reproduce the full-batch gradient within the wire
format's quantization bound. Tolerances are tiered: exact-ish for fp32
("none"), one bf16 ulp for "bf16", one shared-scale int8 ulp for
"int8"/"int8_ef". Under ``tp`` the int8_ef residual of a tensor-parallel
leaf holds only the local slice, sharded like its gradient.

Runs in a subprocess so the 8-device placeholder pool does not leak into
the rest of the session (same pattern as tests/test_system.py).
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


SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import TrainConfig, get_config, reduced
from repro.data import make_batch_for
from repro.launch.mesh import make_mesh
from repro.models import model as MD
from repro.models.layers import is_param, pvalues
from repro.train import (init_sharded_train_state, make_sharded_train_step,
                         sharded_state_shardings)

cfg = reduced(get_config("smollm-360m"), n_layers=1, d_model=32,
              vocab=128, d_ff=64)
import dataclasses
cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
LR, B, S = 1e-2, 8, 16
batch = make_batch_for(cfg, B, S, step=0)

# reference full-batch gradient, single device, no compression
ref_params = MD.init_model(jax.random.PRNGKey(0), cfg)
grad_of = jax.jit(jax.value_and_grad(
    lambda p, b: MD.loss_fn(p, cfg, b), has_aux=True))
(_, _), ref_grads = grad_of(ref_params, batch)
ref_leaves = [np.asarray(x, np.float32) for x in jax.tree.leaves(
    pvalues(ref_grads))]

# The quantization grid is agreed over the *per-device sub-batch*
# gradients (pmax), whose maxima exceed the full-batch mean's — so the
# ulp bound must be computed from the per-shard maxima.
shard_max = [0.0] * len(ref_leaves)
for i in range(4):                     # data axis = 4, shards of B/4
    sub = jax.tree.map(lambda x: x[i * (B // 4):(i + 1) * (B // 4)], batch)
    (_, _), g = grad_of(ref_params, sub)
    for j, x in enumerate(jax.tree.leaves(pvalues(g))):
        shard_max[j] = max(shard_max[j], float(np.max(np.abs(
            np.asarray(x, np.float32)))))

# tolerance tiers: fp32 ordering / one bf16 ulp / one shared int8 ulp.
# worst case all devices round the same way: mean error <= ulp/2; allow
# 0.75 ulp slack for the fp32 arithmetic around it.
def tol_for(mode, j, g):
    m = float(np.max(np.abs(g)))
    s8 = shard_max[j] / 127.0
    return {"none": 1e-5 + 1e-5 * m, "bf16": 1e-5 + shard_max[j] / 256.0,
            "int8": 1e-5 + 0.75 * s8,
            "int8_ef": 1e-5 + 0.75 * s8}[mode]

mesh = make_mesh((4, 2), ("data", "model"))
results = {}
cases = [(s, "none") for s in ("dp", "fsdp", "tp", "fsdp_tp")]
cases += [(s, "int8") for s in ("dp", "fsdp", "tp", "fsdp_tp")]
cases += [("dp", "bf16"), ("tp", "int8_ef"), ("fsdp_tp", "int8_ef")]
for strategy, comp in cases:
    # sgd with wd=0, momentum disabled via b1=0 and huge clip turns the
    # one-step param delta into the post-collective mean gradient:
    # new_p = p - lr * g  =>  g = (p - new_p) / lr
    tcfg = TrainConfig(learning_rate=LR, optimizer="sgd", beta1=0.0,
                       weight_decay=0.0, grad_clip=1e9, total_steps=10,
                       warmup_steps=0, remat_policy="none",
                       grad_compression=comp)
    state = init_sharded_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    sh = sharded_state_shardings(cfg, tcfg, mesh, strategy)
    state = jax.device_put(state, sh)
    step = jax.jit(make_sharded_train_step(cfg, tcfg, mesh, strategy),
                   in_shardings=(sh, None), out_shardings=(sh, None))
    new_state, metrics = step(state, batch)
    # lr at step 0 with warmup_steps=0 is the cosine peak = LR
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
        assert err <= lim, (strategy, comp, err, lim)
        worst = max(worst, err / lim)
    if comp == "int8_ef":
        # step-1 residual: nonzero somewhere, bounded by half an ulp of
        # the shared scale per leaf
        ef = jax.tree.leaves(pvalues(new_state.ef))
        total = sum(float(np.sum(np.abs(np.asarray(e)))) for e in ef)
        assert total > 0, "error feedback never engaged"
        for j, e in enumerate(ef):
            scale = shard_max[j] / 127.0
            assert float(np.max(np.abs(np.asarray(e)))) <= scale * 0.51 \
                + 1e-7, (strategy, comp, "residual exceeds ulp/2")
    results[f"{strategy}/{comp}"] = worst
print(json.dumps({"ok": True, "worst_frac_of_tol": results}))
"""


def test_sharded_grads_match_single_device_per_strategy():
    r = _run(SNIPPET)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"]
    # every case stayed within its tier (sanity: dict fully populated)
    assert len(out["worst_frac_of_tol"]) == 11


EF_HORIZON_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist.compression import compressed_psum_mean_ef
from repro.launch.mesh import make_mesh

# EF telescope over T steps inside a real 4-way collective: accumulated
# applied mean drifts from the accumulated true mean by <= one final ulp.
mesh = make_mesh((4,), ("data",))
T, N = 12, 64
key = jax.random.PRNGKey(0)
xs = jax.random.normal(key, (T, 4, N)) * jnp.array([1.0, 10.0, 0.1, 5.0]
                                                    )[None, :, None]

def run(xs):
    def body(xs):                      # per-device block [T, N]
        err = jnp.zeros((N,))
        applied = jnp.zeros((N,))
        for t in range(T):
            m, err = compressed_psum_mean_ef(xs[t], "data", err)
            applied = applied + m
        return applied                 # replicated (post-psum)
    return jax.shard_map(body, mesh=mesh, in_specs=P(None, "data"),
                         out_specs=P(), check_vma=False)(xs)

applied = np.asarray(run(xs.reshape(T, 4 * N)))
true = np.asarray(xs.mean(axis=1).sum(axis=0))
final_scale = float(np.abs(np.asarray(xs[-1])).max()) / 127.0
drift = float(np.max(np.abs(applied - true)))
# residual telescopes: total drift bounded by one ulp of one step (x4
# slack for the scale drifting across steps), NOT by T * ulp
assert drift <= 4 * final_scale, (drift, final_scale)
print(json.dumps({"ok": True, "drift": drift, "ulp": final_scale}))
"""


def test_ef_horizon_bounded_in_collective():
    r = _run(EF_HORIZON_SNIPPET, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert json.loads(r.stdout.strip().splitlines()[-1])["ok"]
