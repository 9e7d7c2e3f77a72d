"""CI smoke for the observability layer: attribution end to end.

  PYTHONPATH=src python tools/obs_smoke.py

On the forced 8-device host pool, runs one strategy (``fsdp_tp`` — it
exercises all three schedule term kinds) through the full attribution
loop twice:

  1. **Calibrated path** — under the checked-in calibration
     (``load_calibration()``), predict per-term milliseconds, *measure*
     each term's real collective standalone on the live mesh, join them
     into the attribution table, and assert the table is non-empty with
     every comm term carrying a measured value and a drift verdict.
  2. **Fail-soft path** — the same loop under ``REPRO_CALIBRATION=none``
     semantics (``DEFAULT_CALIBRATION``): an uncalibrated environment
     must still produce a complete table and a drift verdict (via the
     floor band), because attribution is how a fresh host *discovers*
     it needs a calibration.

It also runs a short traced train-step loop and asserts the
attribution-sum invariant (children of each ``step`` span cover its
wall time) — the recorder contract ``benchmarks/TRACE.md`` reports on.

Exit code 0 = all hold; anything else fails CI.
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

ARCH, STRATEGY = "smollm-360m", "fsdp_tp"
B, S, STEPS = 8, 32, 4
COVERAGE_TOL = 0.10


def _attribution(calibration, mesh, axes, inp, compute_ms):
    from repro.obs import (attribution_table, detect_drift,
                           measure_collective_terms, predicted_terms)

    pred = predicted_terms(STRATEGY, inp, calibration=calibration,
                           axes=axes)
    meas = measure_collective_terms(mesh, STRATEGY, inp, axes=axes,
                                    iters=5, warmup=2)
    rows = attribution_table(pred, meas, measured_compute_ms=compute_ms)
    drift = detect_drift(rows, calibration)

    assert rows, f"empty attribution table under {calibration.label!r}"
    comm = [r for r in rows if r.term != "compute"]
    assert comm, f"no comm terms under {calibration.label!r}"
    for r in comm:
        assert r.predicted_ms > 0, (calibration.label, r.term)
        assert r.measured_ms is not None and r.measured_ms > 0, \
            (calibration.label, r.term)
    assert drift.message     # a verdict exists either way
    return rows, drift


def main():
    import jax

    from repro.configs import TrainConfig, get_config, reduced
    from repro.data import make_batch_for
    from repro.dist.compression import WIRE_BITS
    from repro.launch.mesh import make_mesh
    from repro.obs import Recorder, span_coverage
    from repro.perf.costmodel import (DEFAULT_CALIBRATION, ScheduleInputs,
                                      load_calibration)
    from repro.perf.planner.space import model_comm_sizes
    from repro.perf.sweep import arch_mesh_axes
    from repro.train import (init_sharded_train_state,
                             make_sharded_train_step,
                             sharded_state_shardings)

    t0 = time.time()
    cfg = dataclasses.replace(reduced(get_config(ARCH)),
                              dtype="float32", param_dtype="float32")
    tcfg = TrainConfig(optimizer="sgd", beta1=0.0, grad_clip=1e9,
                       total_steps=100, warmup_steps=0,
                       remat_policy="none", grad_compression="none")
    axes = arch_mesh_axes(STRATEGY, DEFAULT_POOL)
    mesh = make_mesh(tuple(axes.values()), tuple(axes))
    batch = make_batch_for(cfg, B, S, step=0)
    sh = sharded_state_shardings(cfg, tcfg, mesh, STRATEGY)
    state = jax.device_put(
        init_sharded_train_state(jax.random.PRNGKey(0), cfg, tcfg, mesh),
        sh)
    step = jax.jit(make_sharded_train_step(cfg, tcfg, mesh, STRATEGY),
                   in_shardings=(sh, None), out_shardings=(sh, None))
    with jax.set_mesh(mesh):
        state, m = step(state, batch)          # compile
    jax.block_until_ready(m["loss"])

    # -- traced steps: the attribution-sum invariant ---------------------
    rec = Recorder(enabled=True)
    for i in range(STEPS):
        with rec.span("step", category="train", step_num=i,
                      phase="steady"):
            with rec.span("dispatch", category="train"):
                with jax.set_mesh(mesh):
                    state, m = step(state, batch)
            with rec.span("wait", category="train"):
                jax.block_until_ready(m["loss"])
    cov = span_coverage(rec.spans, "step")
    assert cov["coverage"] is not None and \
        abs(1.0 - cov["coverage"]) <= COVERAGE_TOL, cov

    # -- attribution on the calibrated AND the fail-soft path ------------
    pb, ab = model_comm_sizes(cfg, B, S)
    inp = ScheduleInputs(n_devices=DEFAULT_POOL, param_bytes=pb,
                         wire_bits=WIRE_BITS["none"], act_bytes=ab)
    compute_ms = cov["parent_ms"] / max(cov["n"], 1)  # stand-in probe

    fitted = load_calibration()
    rows_cal, drift_cal = _attribution(fitted, mesh, axes, inp, compute_ms)
    rows_soft, drift_soft = _attribution(DEFAULT_CALIBRATION, mesh, axes,
                                         inp, compute_ms)
    # the two paths price differently but measure the same terms
    assert {r.term for r in rows_cal} == {r.term for r in rows_soft}

    print(json.dumps({
        "ok": True, "arch": ARCH, "strategy": STRATEGY,
        "mesh": dict(axes), "coverage": round(cov["coverage"], 4),
        "terms": sorted(r.term for r in rows_cal),
        "calibrated": {"label": fitted.label,
                       "drift_flags": len(drift_cal.flagged)},
        "fail_soft": {"label": DEFAULT_CALIBRATION.label,
                      "drift_flags": len(drift_soft.flagged),
                      "band_ms": drift_soft.band_ms},
        "wall_s": round(time.time() - t0, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
