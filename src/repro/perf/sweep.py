"""The paper's measured-time experiment: LeNet-5 hyperparameter sweep.

Per the paper (§IV.D): random-sample the Table-1 space, measure the time
of a single training iteration (median of 3, after a warm-up/compile
iteration), 1500 trials, 900 fit / 600 test.

With ``sharded=True`` (the ``benchmarks.measured_sweep`` entry point)
every trial records *two* distributed iteration times side-by-side
(docs/METHODOLOGY.md documents the full protocol):

  * ``t_simulated`` — the container adaptation of the original design:
    single-device compute time *measured* on the per-device sub-batch
    plus the per-strategy communication schedule priced by the collective
    cost model (``repro.perf.costmodel``: α-β ring primitives under the
    calibrated — or default — ``LinkParams``; the row's ``calibration``
    column names the link that priced it);
  * ``t_measured_sharded`` — the wall-clock median of a *real*
    ``shard_map`` iteration over ``n_devices`` of the host device pool:
    the global batch is sharded over the data axis of the strategy's
    mesh, tp-family meshes additionally *partition* the fc1/fc2 pair
    Megatron-style over "model" (real activation all-reduces, compute
    split m ways), remaining parameter shards are all-gathered in-body,
    and the gradient all-reduce-mean runs through the wire-compressed
    collective (``repro.dist.compression.compressed_psum_mean``). The
    collectives are real XLA collectives; on a CPU pool the devices
    timeshare cores, which is exactly the measured-vs-simulated gap the
    fit reports.

The paper's framework axis (TF/MXNet/PyTorch) maps to execution modes
{jit, jit_donate, eager}.
"""
from __future__ import annotations

import json
import time
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.lenet5 import (ACTIVATIONS, BATCH_SIZES, DATASETS,
                                  DIST_STRATEGIES, DROPOUTS,
                                  GRAD_COMPRESSIONS, KERNEL_SIZES,
                                  LEARNING_RATES, LeNet5Config, N_DEVICES,
                                  N_FILTERS, OPTIMIZERS, PADDING_MODES,
                                  POOL_SIZES, STRIDES)
from repro.data.synthetic import lenet_batch
from repro.dist.compression import WIRE_BITS, compressed_psum_mean
from repro.dist.sharding import gather_to_full, shard_of_full
from repro.models.lenet import feature_dims, init_lenet, lenet_loss
from repro.obs.trace import current_recorder
from repro.perf.costmodel import (Calibration, load_calibration,
                                  mesh_axes_for)
from repro.perf.features import get_spec, lenet_features

MODES = ("jit", "jit_donate", "eager")

# Sentinels recorded in ``SweepRow.sharded_skip`` when the measured
# column is None — documented in docs/METHODOLOGY.md (row schema).
SKIP_EAGER = "eager-mode"            # op-by-op dispatch measures python, not comm
SKIP_POOL = "pool-too-small"         # host pool < n_devices
SKIP_NOT_REQUESTED = "not-requested"  # sharded=False sweep


def lenet_act_bytes(cfg: LeNet5Config) -> int:
    """fp32 bytes of the activations at the dense-block boundaries for
    the *global* batch — the tensors a Megatron-style tp split
    all-reduces (flattened conv features entering fc1, plus the fc1/fc2
    outputs). Only tp-family schedules consume this."""
    _, _, flat = feature_dims(cfg)
    return 4 * cfg.batch_size * (flat + 120 + 84)


def comm_seconds(cfg: LeNet5Config, param_bytes: int,
                 calibration: Optional[Calibration] = None) -> float:
    """Per-iteration communication time of one sampled scenario, priced
    through the shared prediction path (``repro.perf.predict``) under
    ``calibration`` (None = the shared calibration resolved by
    ``load_calibration``: the checked-in fitted artifact when present,
    the documented defaults otherwise)."""
    from repro.perf.predict import estimate_comm
    return estimate_comm(cfg.strategy, cfg.n_devices, param_bytes,
                         wire_bits=WIRE_BITS[cfg.compression],
                         act_bytes=lenet_act_bytes(cfg),
                         calibration=calibration).seconds


def sample_config(rng: np.random.Generator) -> LeNet5Config:
    return LeNet5Config(
        kernel_size=int(rng.choice(KERNEL_SIZES)),
        pool_size=int(rng.choice(POOL_SIZES)),
        activation=str(rng.choice(ACTIVATIONS)),
        optimizer=str(rng.choice(OPTIMIZERS)),
        dataset=str(rng.choice(DATASETS)),
        n_filters=int(rng.choice(N_FILTERS)),
        learning_rate=float(rng.choice(LEARNING_RATES)),
        padding=str(rng.choice(PADDING_MODES)),
        stride=int(rng.choice(STRIDES)),
        dropout=float(rng.choice(DROPOUTS)),
        n_devices=int(rng.choice(N_DEVICES)),
        batch_size=int(rng.choice(BATCH_SIZES)),
        strategy=str(rng.choice(DIST_STRATEGIES)),
        compression=str(rng.choice(GRAD_COMPRESSIONS)),
    )


def _sgd_step(params, grads, lr):
    return jax.tree.map(lambda p, g: p - lr * g, params, grads)


def _adam_step(params, grads, m, v, lr, t):
    m = jax.tree.map(lambda mm, g: 0.9 * mm + 0.1 * g, m, grads)
    v = jax.tree.map(lambda vv, g: 0.999 * vv + 0.001 * g * g, v, grads)
    params = jax.tree.map(
        lambda p, mm, vv: p - lr * (mm / (1 - 0.9 ** t)) /
        (jnp.sqrt(vv / (1 - 0.999 ** t)) + 1e-8), params, m, v)
    return params, m, v


def make_iteration(cfg: LeNet5Config, mode: str):
    """One training iteration on the per-device sub-batch."""

    def iteration(params, batch, rng):
        loss, grads = jax.value_and_grad(
            lambda p, b, r: lenet_loss(p, b, cfg, r))(params, batch, rng)
        if cfg.optimizer == "sgd":
            new_params = _sgd_step(params, grads, cfg.learning_rate)
        else:   # adam (stateless single-step approximation: t=1 moments)
            m0 = jax.tree.map(jnp.zeros_like, params)
            new_params, _, _ = _adam_step(params, grads, m0, m0,
                                          cfg.learning_rate, 1)
        return new_params, loss

    if mode == "eager":
        return iteration
    donate = (0,) if mode == "jit_donate" else ()
    return jax.jit(iteration, donate_argnums=donate)


@dataclass
class SweepRow:
    features: Dict
    mode: str
    measured_ms: float          # median single-device iteration time
    comm_ms: float              # cost-model simulated collective time
    time_ms: float              # measured/n-scaled + comm  (fit target)
    param_bytes: int
    # measured-vs-simulated pair (docs/METHODOLOGY.md): the schedule-
    # priced total and the wall-clock of the real shard_map step over
    # n_devices. When the measured column is None, ``sharded_skip``
    # carries the explicit reason sentinel ("eager-mode",
    # "pool-too-small", "not-requested") so downstream consumers never
    # misread an implicit default as a measurement of 0.0.
    t_simulated: float = 0.0
    t_measured_sharded: Optional[float] = None
    sharded_skip: Optional[str] = None
    # provenance of the simulated columns: which link priced the
    # schedule ("default" or the fitted calibration's label) and the
    # activation footprint the tp-family schedules were billed for.
    calibration: str = "default"
    act_bytes: int = 0
    # cross-architecture rows (``run_arch_sweep``): which family produced
    # the row and the fixed-work unit its fit target normalizes by —
    # "sample" (LeNet, REF_SAMPLES) or "token" (LM/MoE/SSM, REF_TOKENS;
    # an iteration over twice the sequence does twice the work, which a
    # per-sample unit would misread as the model getting slower).
    family: str = "lenet"
    norm_unit: str = "sample"


def _strategy_pspecs(params, strategy: str, axes_sizes: Dict[str, int]):
    """Explicit per-strategy PartitionSpecs for the (unannotated) LeNet
    params: each mesh axis in the strategy's shard order is assigned to
    the first still-unassigned dimension it divides.

    dp replicates; fsdp shards over "data"; tp over "model"; fsdp_tp
    assigns "data" then "model" to (different) divisible dims — the
    LeNet-scale counterpart of the logical-rule registry the LM path
    uses (docs/METHODOLOGY.md)."""
    from repro.models.layers import is_param

    order = {"dp": (), "fsdp": ("data",), "tp": ("model",),
             "fsdp_tp": ("data", "model")}[strategy]

    def one(p):
        shape = p.value.shape
        entries: List[Optional[str]] = [None] * len(shape)
        queue = [a for a in order if axes_sizes.get(a, 1) > 1]
        for i, d in enumerate(shape):
            if not queue:
                break
            a = queue[0]
            if d % axes_sizes[a] == 0 and d >= axes_sizes[a]:
                entries[i] = a
                queue.pop(0)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    return jax.tree.map(one, params, is_leaf=is_param)


def lenet_partition_specs(cfg: LeNet5Config, params,
                          axes_sizes: Dict[str, int]):
    """(entry_specs, gather_specs, part_axes): how the measured LeNet
    body shards each leaf on shard_map entry, which of that sharding it
    gathers back in-body, and the ``LocalDim``-marked axes of the
    partitioned fc1/fc2 pair (empty when the mesh has no usable model
    axis). Shared by the measured path and the planner's memory model,
    so both always price the same layout."""
    from repro.models.layers import LocalDim

    m = axes_sizes.get("model", 1)
    partition = (m > 1 and 120 % m == 0
                 and cfg.strategy in ("tp", "fsdp_tp"))
    # Base specs: the strategy's data-axis behaviour (tp is dp plus the
    # model split; fsdp_tp is fsdp plus it). Partitioned leaves then
    # shard over "model" on entry and are *not* gathered in-body.
    analog = ({"tp": "dp", "fsdp_tp": "fsdp"}[cfg.strategy]
              if partition else cfg.strategy)
    gather_specs = dict(_strategy_pspecs(params, analog, axes_sizes))
    entry_specs = dict(gather_specs)
    part_axes: Dict[str, tuple] = {}
    if partition:
        col = LocalDim("mlp", "model", m)
        entry_specs["fc1"] = P(None, "model")
        entry_specs["fc2"] = P("model", None)
        gather_specs["fc1"] = gather_specs["fc2"] = P()
        part_axes = {"fc1": (None, col), "fc2": (col, None)}
    return entry_specs, gather_specs, part_axes


def make_sharded_iteration(cfg: LeNet5Config, mode: str, mesh: Mesh,
                           params):
    """One *real* distributed training iteration under ``shard_map``.

    Works for all four registry strategies on the strategy's own mesh
    (``mesh_axes_for``): the batch is sharded over the "data" axis when
    the mesh has one (replicated over "model"), params enter sharded per
    ``_strategy_pspecs`` and are all-gathered in-body — the parameter
    traffic the fsdp-family schedules charge for — and gradients
    all-reduce-mean through the compressed collective; the optimizer
    then updates local shards.

    When the mesh has a model axis that divides the 120-wide fc hidden,
    the fc1/fc2 pair is *partitioned* Megatron-style instead of
    gathered: fc1 columns and fc2 rows stay local slices
    (``LocalDim`` markers make ``lenet_forward`` run its manual tp path
    — ``tp_f`` entry, partial fc2 product closed by ``tp_g``), so the
    model axis now moves the schedule's *activation* all-reduces
    op-for-op rather than proxy parameter traffic. Partitioned-leaf
    gradients are complete per model rank and reduce over data axes
    only (a pure tp mesh reduces nothing); replicated-leaf gradients
    reduce over all axes (their model-axis contributions are identical
    because ``tp_f``'s backward already completed the input cotangent,
    so the mean stays exact).
    """
    from repro.models.layers import Param

    axes_sizes = dict(mesh.shape)
    axis_names = tuple(mesh.axis_names)
    entry_specs, gather_specs, part_axes = lenet_partition_specs(
        cfg, params, axes_sizes)
    batch_spec = P("data") if "data" in axes_sizes else P()
    data_axes = tuple(a for a in axis_names if a != "model")

    def body(params, batch, rng):
        compute = {
            k: (Param(p.value, part_axes[k]) if k in part_axes else
                Param(gather_to_full(p.value, gather_specs[k]), p.axes))
            for k, p in params.items()}
        loss, grads = jax.value_and_grad(
            lambda p, b, r: lenet_loss(p, b, cfg, r))(compute, batch, rng)
        red = {}
        for k, g in grads.items():
            gv = g.value
            if k in part_axes:
                if data_axes:
                    gv = compressed_psum_mean(gv, data_axes,
                                              cfg.compression)
                red[k] = Param(gv, params[k].axes)
            else:
                gv = compressed_psum_mean(gv, axis_names, cfg.compression)
                red[k] = Param(shard_of_full(gv, gather_specs[k], mesh),
                               params[k].axes)
        if cfg.optimizer == "sgd":
            new_params = _sgd_step(params, red, cfg.learning_rate)
        else:
            m0 = jax.tree.map(jnp.zeros_like, params)
            new_params, _, _ = _adam_step(params, red, m0, m0,
                                          cfg.learning_rate, 1)
        return new_params, jax.lax.pmean(loss, axis_names)

    it = jax.shard_map(body, mesh=mesh,
                       in_specs=(entry_specs, batch_spec, P()),
                       out_specs=(entry_specs, P()), check_vma=False)
    if mode == "eager":
        return it, entry_specs, batch_spec
    donate = (0,) if mode == "jit_donate" else ()
    return jax.jit(it, donate_argnums=donate), entry_specs, batch_spec


def measure_sharded_trial(cfg: LeNet5Config, mode: str, *,
                          n_iters: int = 3, seed: int = 0
                          ) -> Tuple[Optional[float], Optional[str]]:
    """(median wall-clock seconds of the global-batch shard_map iteration
    over ``cfg.n_devices`` pool devices, skip sentinel): the measurement
    when the pool fits the trial, else (None, SKIP_POOL)."""
    devs = jax.devices()
    if len(devs) < cfg.n_devices:
        return None, SKIP_POOL
    key = jax.random.PRNGKey(seed)
    axes = mesh_axes_for(cfg.strategy, cfg.n_devices)
    mesh = Mesh(np.asarray(devs[:cfg.n_devices]).reshape(
        tuple(axes.values())), tuple(axes))
    from repro.models.layers import is_param
    params = init_lenet(key, cfg)
    batch = lenet_batch(cfg, step=0, seed=seed, batch=cfg.batch_size)
    it, pspecs, batch_spec = make_sharded_iteration(cfg, mode, mesh, params)
    shardings = jax.tree.map(lambda p, s: NamedSharding(mesh, s), params,
                             pspecs, is_leaf=is_param)
    p = jax.device_put(params, shardings)
    b = jax.device_put(batch, NamedSharding(mesh, batch_spec))

    p, _ = it(p, b, key)                          # warm-up / compile
    jax.block_until_ready(p)
    times = []
    for i in range(n_iters):
        t0 = time.perf_counter()
        p, loss = it(p, b, key)
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), None


def measure_trial(cfg: LeNet5Config, mode: str, *, n_iters: int = 3,
                  seed: int = 0, sharded: bool = False,
                  calibration: Optional[Calibration] = None) -> SweepRow:
    cal = calibration if calibration is not None else load_calibration()
    key = jax.random.PRNGKey(seed)
    params = init_lenet(key, cfg)    # Param tree; tree ops map through
    # Compute runs on the per-device compute-equivalent sub-batch: the
    # batch shards over the data axis and the measured shard_map path
    # additionally partitions tensor-parallel compute over "model", so a
    # device performs ~batch/n of the per-iteration math for every
    # strategy (dp/fsdp have model=1, so this is the plain data split).
    per_dev = max(cfg.batch_size // max(cfg.n_devices, 1), 1)
    batch = lenet_batch(cfg, step=0, seed=seed, batch=per_dev)
    it = make_iteration(cfg, mode)

    rec = current_recorder()
    p = params
    with rec.span("compute_probe", category="sweep", mode=mode):
        p, _ = it(p, batch, key)                  # warm-up / compile
        jax.block_until_ready(p)
        times = []
        for i in range(n_iters):
            t0 = time.perf_counter()
            p, loss = it(p, batch, key)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
        measured = float(np.median(times))

    pb = sum(int(np.prod(x.shape)) * 4 for x in jax.tree.leaves(params))
    comm = comm_seconds(cfg, pb, calibration=cal)
    t_sim = measured * 1e3 + comm * 1e3
    t_meas, skip = None, SKIP_NOT_REQUESTED
    # The sharded column is only meaningful compiled: a shard_map program
    # dispatched op-by-op measures python dispatch x n_devices (~700x the
    # compiled step on this host), not communication — so eager-mode rows
    # keep t_measured_sharded=None and the jit/jit_donate rows cover
    # every (strategy, compression, n_devices) cell.
    if sharded:
        if mode == "eager":
            skip = SKIP_EAGER
        else:
            with rec.span("sharded_probe", category="sweep", mode=mode):
                t_meas, skip = measure_sharded_trial(cfg, mode,
                                                     n_iters=n_iters,
                                                     seed=seed)
            if t_meas is not None:
                t_meas *= 1e3
    return SweepRow(features=lenet_features(cfg), mode=mode,
                    measured_ms=measured * 1e3, comm_ms=comm * 1e3,
                    time_ms=t_sim, param_bytes=pb,
                    t_simulated=t_sim, t_measured_sharded=t_meas,
                    sharded_skip=skip, calibration=cal.label,
                    act_bytes=lenet_act_bytes(cfg))


def run_sweep(n_trials: int = 300, modes: Sequence[str] = MODES,
              seed: int = 0, out_path: Optional[str] = None,
              verbose_every: int = 50, sharded: bool = False,
              calibration: Optional[Calibration] = None) -> List[Dict]:
    """``sharded=True`` (the benchmarks.measured_sweep entry point) adds
    the real shard_map measurement per trial — roughly doubling trial
    cost; simulated-only consumers keep the default off. ``calibration``
    prices every simulated column (None = the shared loaded one)."""
    cal = calibration if calibration is not None else load_calibration()
    rng = np.random.default_rng(seed)
    rows: List[Dict] = []
    t0 = time.time()
    rec = current_recorder()        # disabled default: spans are no-ops
    for i in range(n_trials):
        cfg = sample_config(rng)
        mode = modes[i % len(modes)]
        try:
            with rec.span("trial", category="sweep", index=i, mode=mode,
                          n_devices=cfg.n_devices,
                          strategy=str(cfg.strategy),
                          batch=cfg.batch_size):
                row = measure_trial(cfg, mode, seed=seed + i,
                                    sharded=sharded, calibration=cal)
        except Exception as e:      # a pathological config; record & skip
            rows.append({"error": str(e), "mode": mode,
                         "features": lenet_features(cfg)})
            continue
        rows.append(asdict(row))
        if verbose_every and (i + 1) % verbose_every == 0:
            print(f"  sweep {i+1}/{n_trials} ({time.time()-t0:.0f}s)",
                  flush=True)
            if out_path:                       # incremental checkpoint
                json.dump(rows, open(out_path, "w"))
    if out_path:
        json.dump(rows, open(out_path, "w"))
    return rows


REF_SAMPLES = 128     # fixed work unit for sample-normalized rows (LeNet)
REF_TOKENS = 4096     # fixed work unit for token-normalized rows (seq models)


def fit_target_ms(row: Dict, source: str = "simulated") -> float:
    """Fit target: time to process a fixed unit of work at the sampled
    (batch, n_devices) — iteration time × (REF_SAMPLES / batch) for
    sample-normalized rows, × (REF_TOKENS / (batch × seq_len)) for
    token-normalized rows (``row["norm_unit"]``; absent = "sample", so
    pre-existing LeNet artifacts keep their original targets). A
    per-sample unit is *wrong* for token-based sequence models: two rows
    differing only in seq_len do different amounts of work per sample,
    and normalizing by batch alone would fold that work into the
    intrinsic powers as a spurious slowdown.

    Rationale (DESIGN.md §5): the paper's Table-6 finding is q_batch ≈
    q_gpus ≈ −1, i.e. *per-iteration* time inversely proportional to both.
    That is the signature of a fixed-work metric (at LeNet scale a single
    iteration is overhead-dominated, so time-per-fixed-samples scales as
    1/batch and, under data parallelism with a fixed global batch, 1/n).
    Using raw per-iteration time of the *sub*-batch would leave almost no
    extrinsic signal on this hardware and degenerate the fit.

    ``source`` picks the iteration time: "simulated" (per-device measured
    compute + schedule-priced comm, the container default), "measured"
    (the real shard_map step — raises if the row has no measured column),
    or "compute" (the per-device compute time alone, no comm term — the
    target the planner's decomposed prediction fits, so its compute and
    schedule terms stay separable).
    """
    b = row["features"]["batch_size"]
    if source == "measured":
        t = row.get("t_measured_sharded")
        if t is None:
            raise ValueError("row has no t_measured_sharded "
                             "(sweep ran without a device pool?)")
    elif source == "simulated":
        t = row["measured_ms"] + row["comm_ms"]
    elif source == "compute":
        t = row["measured_ms"]
    else:
        raise ValueError(f"unknown fit-target source {source!r}")
    if row.get("norm_unit", "sample") == "token":
        return t * REF_TOKENS / (b * row["features"]["seq_len"])
    return t * REF_SAMPLES / b


def split_rows(rows: List[Dict], mode: str, n_fit: int = 900,
               source: str = "simulated"):
    """Paper split: 900 fit / 600 test (scaled to available rows)."""
    ok = [r for r in rows if "error" not in r and r["mode"] == mode]
    if source == "measured":
        ok = [r for r in ok if r.get("t_measured_sharded") is not None]
    k = min(n_fit, int(len(ok) * 0.6))
    fit, test = ok[:k], ok[k:]
    f_s = [r["features"] for r in fit]
    f_t = [r["features"] for r in test]
    return (f_s, [fit_target_ms(r, source) for r in fit],
            f_t, [fit_target_ms(r, source) for r in test])


# ---------------------------------------------------------------------------
# Cross-architecture sweep: lm / moe / ssm families
# ---------------------------------------------------------------------------
#
# The same measured-vs-simulated protocol as the LeNet sweep, but the
# subject is a family-preserving ``reduced()`` of a real architecture
# config and the distributed iteration is the *actual* LM train step
# (``repro.train.step.make_sharded_train_step`` — registry-rule param
# shards, tensor-parallel slices and per-layer streamed gathers,
# wire-compressed gradient all-reduce), not
# the LeNet-specific shard_map body. Intrinsics per family come from the
# ``repro.perf.features`` registry; extrinsics are shared with LeNet.

ARCH_N_DEVICES = (1, 2, 4, 8)
ARCH_BATCH_SIZES = (8, 16, 32)
# wire formats the sharded LM step implements (``tcfg.grad_compression``):
# int8 rides through the error-feedback collective on this path.
ARCH_COMPRESSIONS = ("none", "bf16", "int8_ef")


@dataclass(frozen=True)
class ArchPoint:
    """One sampled cross-architecture trial.

    Intrinsics a family does not use stay 0 and are absent from that
    family's FeatureSpec (the encoder never sees them — it would reject
    non-positive numerics)."""
    family: str
    arch_id: str
    seq_len: int
    d_model: int
    n_layers: int
    d_ff: int = 0
    n_experts: int = 0
    top_k: int = 0
    d_state: int = 0
    n_devices: int = 1
    batch_size: int = 8
    strategy: str = "dp"
    compression: str = "none"

    @property
    def wire_bits(self) -> int:
        return WIRE_BITS[self.compression]

    def model_config(self):
        """The family-preserving ``reduced()`` ModelConfig this point
        trains; intrinsics the reducer pins (MoE top_k, SSD state dim)
        are re-opened so the sweep actually varies them."""
        import dataclasses

        from repro.configs import get_config
        from repro.configs.base import reduced

        cfg = reduced(get_config(self.arch_id), n_layers=self.n_layers,
                      d_model=self.d_model, vocab=256,
                      d_ff=self.d_ff or 128,
                      n_experts=self.n_experts or 4,
                      seq_cap=self.seq_len)
        if self.top_k and cfg.moe is not None:
            cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, top_k=min(self.top_k, cfg.moe.n_experts)))
        if self.d_state and cfg.ssm is not None:
            cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(
                cfg.ssm, d_state=self.d_state))
        return cfg

    def features(self) -> Dict:
        return get_spec(self.family).features(self)


def sample_arch_point(family: str, rng: np.random.Generator) -> ArchPoint:
    """Random point of ``family``'s intrinsic space × the shared
    extrinsic grid (the arch-sweep analogue of ``sample_config``)."""
    aspec = get_spec(family)
    intr = {k: int(rng.choice(v)) for k, v in aspec.intrinsic_space.items()}
    return ArchPoint(family=family, arch_id=aspec.arch_id,
                     n_devices=int(rng.choice(ARCH_N_DEVICES)),
                     batch_size=int(rng.choice(ARCH_BATCH_SIZES)),
                     strategy=str(rng.choice(DIST_STRATEGIES)),
                     compression=str(rng.choice(ARCH_COMPRESSIONS)),
                     **intr)


def arch_mesh_axes(strategy: str, n_devices: int) -> Dict[str, int]:
    """``mesh_axes_for`` plus a size-1 "data" axis when the strategy has
    none: the LM sharded train step all-reduces gradients over the batch
    axes and refuses a mesh without one, so tp meshes replicate the batch
    over a degenerate data axis (exactly what the LeNet measured path
    does implicitly by replicating the batch over "model")."""
    axes = dict(mesh_axes_for(strategy, n_devices))
    if "data" not in axes:
        axes = {"data": 1, **axes}
    return axes


def measure_sharded_arch_trial(point: ArchPoint, cfg, tcfg, mode: str, *,
                               n_iters: int = 2, seed: int = 0
                               ) -> Tuple[Optional[float], Optional[str]]:
    """(median wall-clock seconds of the real sharded LM train step over
    ``point.n_devices`` pool devices, skip sentinel)."""
    devs = jax.devices()
    if len(devs) < point.n_devices:
        return None, SKIP_POOL
    from repro.data.synthetic import make_batch_for
    from repro.launch.specs import batch_shardings
    from repro.train.step import (init_sharded_train_state,
                                  make_sharded_train_step,
                                  sharded_state_specs,
                                  sharded_state_shardings)

    axes = arch_mesh_axes(point.strategy, point.n_devices)
    mesh = Mesh(np.asarray(devs[:point.n_devices]).reshape(
        tuple(axes.values())), tuple(axes))
    specs = sharded_state_specs(cfg, tcfg, mesh, point.strategy)
    shardings = sharded_state_shardings(cfg, tcfg, mesh, point.strategy,
                                        specs)
    step_raw = make_sharded_train_step(cfg, tcfg, mesh, point.strategy,
                                       state_specs=specs)
    key = jax.random.PRNGKey(seed)
    state = init_sharded_train_state(key, cfg, tcfg, mesh)
    batch = make_batch_for(cfg, point.batch_size, point.seq_len, seed=seed)
    b_shard = batch_shardings(batch, mesh)
    donate = (0,) if mode == "jit_donate" else ()
    step = jax.jit(step_raw, in_shardings=(shardings, b_shard),
                   out_shardings=(shardings, None), donate_argnums=donate)
    state = jax.device_put(state, shardings)
    b = jax.device_put(batch, b_shard)

    state, _ = step(state, b)                     # warm-up / compile
    jax.block_until_ready(state)
    times = []
    for _ in range(n_iters):
        t0 = time.perf_counter()
        state, m = step(state, b)
        jax.block_until_ready(m["loss"])
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), None


def measure_arch_trial(point: ArchPoint, mode: str = "jit", *,
                       n_iters: int = 2, seed: int = 0,
                       sharded: bool = True,
                       calibration: Optional[Calibration] = None
                       ) -> SweepRow:
    """The cross-architecture counterpart of ``measure_trial``: same row
    schema, token norm unit, the LM train step as the subject."""
    from repro.configs.base import TrainConfig
    from repro.data.synthetic import make_batch_for
    from repro.perf.planner.space import model_comm_sizes
    from repro.perf.predict import estimate_comm
    from repro.train.step import init_train_state, make_train_step

    cal = calibration if calibration is not None else load_calibration()
    cfg = point.model_config()
    # Single-device compute on the compute-equivalent sub-batch: the
    # overlap step partitions tensor-parallel compute over "model", so a
    # device performs ~batch/n of the math for every strategy —
    # compression off here, it is wire format, not compute.
    tc_comp = TrainConfig(optimizer="sgd", grad_compression="none",
                          remat_policy="none")
    per_dev = max(point.batch_size // max(point.n_devices, 1), 1)
    key = jax.random.PRNGKey(seed)
    state = init_train_state(key, cfg, tc_comp)
    batch = make_batch_for(cfg, per_dev, point.seq_len, seed=seed)
    step = make_train_step(cfg, tc_comp)
    if mode != "eager":
        step = jax.jit(step,
                       donate_argnums=(0,) if mode == "jit_donate" else ())
    rec = current_recorder()
    with rec.span("compute_probe", category="sweep", mode=mode):
        state, _ = step(state, batch)             # warm-up / compile
        jax.block_until_ready(state)
        times = []
        for _ in range(n_iters):
            t0 = time.perf_counter()
            state, m = step(state, batch)
            jax.block_until_ready(m["loss"])
            times.append(time.perf_counter() - t0)
        measured = float(np.median(times))

    pb, ab = model_comm_sizes(cfg, point.batch_size, point.seq_len)
    comm = estimate_comm(point.strategy, point.n_devices, pb,
                         wire_bits=point.wire_bits, act_bytes=ab,
                         calibration=cal).seconds
    t_sim = measured * 1e3 + comm * 1e3
    t_meas, skip = None, SKIP_NOT_REQUESTED
    if sharded:
        if mode == "eager":
            skip = SKIP_EAGER
        else:
            tcfg = TrainConfig(optimizer="sgd",
                               grad_compression=point.compression,
                               remat_policy="none")
            with rec.span("sharded_probe", category="sweep", mode=mode):
                t_meas, skip = measure_sharded_arch_trial(
                    point, cfg, tcfg, mode, n_iters=n_iters, seed=seed)
            if t_meas is not None:
                t_meas *= 1e3
    return SweepRow(features=point.features(), mode=mode,
                    measured_ms=measured * 1e3, comm_ms=comm * 1e3,
                    time_ms=t_sim, param_bytes=pb,
                    t_simulated=t_sim, t_measured_sharded=t_meas,
                    sharded_skip=skip, calibration=cal.label,
                    act_bytes=ab, family=point.family,
                    norm_unit=get_spec(point.family).norm_unit)


def run_arch_sweep(family: str, n_trials: int = 48, mode: str = "jit",
                   seed: int = 0, out_path: Optional[str] = None,
                   verbose_every: int = 5, sharded: bool = True,
                   calibration: Optional[Calibration] = None,
                   n_iters: int = 2) -> List[Dict]:
    """Random sweep of one architecture family (the arch-sweep analogue
    of ``run_sweep``; jit-only by default — the framework axis is the
    LeNet sweep's subject, not this one's)."""
    cal = calibration if calibration is not None else load_calibration()
    rng = np.random.default_rng(seed)
    rows: List[Dict] = []
    t0 = time.time()
    rec = current_recorder()        # disabled default: spans are no-ops
    for i in range(n_trials):
        point = sample_arch_point(family, rng)
        try:
            with rec.span("trial", category="sweep", index=i,
                          family=family, mode=mode,
                          n_devices=point.n_devices,
                          strategy=str(point.strategy),
                          batch=point.batch_size):
                row = measure_arch_trial(point, mode, n_iters=n_iters,
                                         seed=seed + i, sharded=sharded,
                                         calibration=cal)
        except Exception as e:      # a pathological point; record & skip
            rows.append({"error": str(e), "mode": mode, "family": family,
                         "features": point.features()})
            continue
        rows.append(asdict(row))
        if verbose_every and (i + 1) % verbose_every == 0:
            print(f"  [{family}] sweep {i+1}/{n_trials} "
                  f"({time.time()-t0:.0f}s)", flush=True)
            if out_path:                       # incremental checkpoint
                json.dump(rows, open(out_path, "w"))
    if out_path:
        json.dump(rows, open(out_path, "w"))
    return rows
