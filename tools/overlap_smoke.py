"""CI smoke for the partitioned tp train step: it shards activations.

  PYTHONPATH=src python tools/overlap_smoke.py

On the forced 8-device host pool, builds the sharded LM train step for
``tp`` on the (data:1, model:8) mesh and traces it under
``tp_probe_sink``, which captures the local ``mlp_hidden`` shapes inside
the shard_map body. The Megatron column/row split must leave each model
rank exactly ``d_ff / 8`` of the hidden width: every recorded shape has
to end in ``cfg.d_ff // 8``.

Numerical parity of the partitioned body is pinned family-by-family in
``tests/test_overlap_parity.py``; this smoke guards the *structural*
claim cheaply on every push.

Exit code 0 = it holds; anything else fails CI.
"""
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "src"))

# must run before the jax backend initializes
from repro.launch.train import DEFAULT_POOL, _force_host_pool  # noqa: E402

_force_host_pool(DEFAULT_POOL)

import dataclasses  # noqa: E402
import json         # noqa: E402
import time         # noqa: E402

ARCH, STRATEGY = "smollm-360m", "tp"
B, S = 8, 32


def main():
    import jax

    from repro.configs import TrainConfig, get_config, reduced
    from repro.data import make_batch_for
    from repro.launch.mesh import make_mesh
    from repro.models.layers import tp_probe_sink
    from repro.perf.sweep import arch_mesh_axes
    from repro.train import (init_sharded_train_state,
                             make_sharded_train_step,
                             sharded_state_shardings)

    t0 = time.time()
    cfg = dataclasses.replace(reduced(get_config(ARCH)),
                              dtype="float32", param_dtype="float32")
    tcfg = TrainConfig(optimizer="sgd", beta1=0.0, grad_clip=1e9,
                       total_steps=10, warmup_steps=0,
                       remat_policy="none", grad_compression="none")
    axes = arch_mesh_axes(STRATEGY, DEFAULT_POOL)
    mesh = make_mesh(tuple(axes.values()), tuple(axes))
    m = int(axes.get("model", 1))
    assert m > 1, f"tp mesh has no model axis: {axes}"

    batch = make_batch_for(cfg, B, S, step=0)
    state = init_sharded_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh)
    sh = sharded_state_shardings(cfg, tcfg, mesh, STRATEGY)
    state = jax.device_put(state, sh)
    step = jax.jit(make_sharded_train_step(cfg, tcfg, mesh, STRATEGY),
                   in_shardings=(sh, None), out_shardings=(sh, None))

    with tp_probe_sink([]) as rec:
        step.lower(state, batch)
    local = sorted({tuple(shape) for tag, shape in rec
                    if tag == "mlp_hidden"})

    # the tp body computes on model-sharded hiddens: d_ff / m per rank
    assert local, {"recorded": sorted({tag for tag, _ in rec})}
    assert all(ls[-1] == cfg.d_ff // m for ls in local), {
        "local": local, "d_ff": cfg.d_ff, "model": m}

    print(json.dumps({
        "ok": True, "arch": ARCH, "strategy": STRATEGY,
        "mesh": dict(axes), "d_ff": cfg.d_ff,
        "mlp_hidden_local": local,
        "wall_s": round(time.time() - t0, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
