"""Train step: loss → (micro-batched) grads → compression → clip → update.

Two execution paths share the same TrainState, numerics and update tail:

* ``make_train_step`` — the GSPMD path: a pure function for ``jax.jit``
  with explicit in/out shardings; all distribution is expressed through
  sharding annotations (params/opt-state inherit logical-axis rules;
  batch shards over (pod, data)) and XLA inserts the collectives.

* ``make_sharded_train_step`` — the manual-collectives path: the same
  step expressed with ``shard_map``, where every collective is written
  out explicitly so it can be *measured* and *compressed*. Each
  parameter leaf follows its plan (``_leaf_plans``): model-sharded dims
  the layer code can split stay local (Megatron tensor parallelism),
  other sharded dims of scanned layer stacks are gathered per layer
  inside the scan, and the rest are all-gathered before the loss.
  Gradients are all-reduce-meaned over the batch axes through
  ``repro.dist.compression.compressed_psum_mean`` (the wire-compressed
  collective), and each device applies the optimizer to its own shard.
  This is the path the measured sweep (docs/METHODOLOGY.md) times
  against the α-β simulation.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.dist.compression import (compress_tree, compressed_psum_mean,
                                    compressed_psum_mean_ef,
                                    init_error_feedback)
from repro.dist.sharding import (BATCH_AXES, LocalDim, axis_sizes,
                                 gather_to_full, manual_mode, param_pspecs,
                                 resolve_strategy, shard_of_full,
                                 spec_entries)
from repro.models import model as MD
from repro.models.layers import Param, StreamDim, is_param, pvalues
from repro.optim import clip_by_global_norm, make_optimizer, warmup_cosine
from repro.optim.optimizers import OptState


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    ef: Any            # error-feedback buffers (grad compression) or None


def init_train_state(key, cfg: ModelConfig, tcfg: TrainConfig) -> TrainState:
    params = MD.init_model(key, cfg)
    opt_init, _ = make_optimizer(tcfg.optimizer)
    opt = opt_init(params, tcfg)
    ef = (init_error_feedback(params)
          if tcfg.grad_compression == "int8_ef" else None)
    return TrainState(params, opt, ef)


def _split_microbatches(batch: Dict[str, jax.Array], n: int):
    def split(x):
        B = x.shape[0]
        assert B % n == 0, (B, n)
        return x.reshape(n, B // n, *x.shape[1:])
    return jax.tree.map(split, batch)


def _make_grad_fn(cfg: ModelConfig, tcfg: TrainConfig):
    def loss_for(params, mb):
        return MD.loss_fn(params, cfg, mb, remat=tcfg.remat_policy,
                          ce_impl=tcfg.ce_impl)

    return jax.value_and_grad(loss_for, has_aux=True)


def _loss_and_grads(grad_fn, params, batch, microbatches: int):
    """(loss, metrics, grads) with optional micro-batch accumulation.

    With ``microbatches <= 1`` grads keep their Param wrappers; the
    accumulated path returns raw fp32 arrays at the Param positions —
    both shapes of tree are accepted downstream.
    """
    if microbatches <= 1:
        (loss, metrics), grads = grad_fn(params, batch)
        return loss, metrics, grads
    mbs = _split_microbatches(batch, microbatches)
    acc0 = jax.tree.map(
        lambda p: jnp.zeros(p.value.shape, jnp.float32),
        params, is_leaf=is_param)

    def body(acc, mb):
        (l, m), g = grad_fn(params, mb)
        acc = jax.tree.map(
            lambda a, gg: a + gg.astype(jnp.float32) / microbatches,
            acc, pvalues(g))
        return acc, (l, m)

    grads_acc, (losses, mstack) = jax.lax.scan(body, acc0, mbs)
    return losses.mean(), jax.tree.map(lambda x: x.mean(), mstack), grads_acc


def _apply_update(params, grads, opt_state, gnorm, metrics, loss,
                  tcfg: TrainConfig, opt_update):
    """Schedule, optimizer update and metrics merge of a clipped step:
    ``(new_params, new_opt, metrics)``."""
    lr = warmup_cosine(opt_state.step, peak_lr=tcfg.learning_rate,
                       warmup_steps=tcfg.warmup_steps,
                       total_steps=tcfg.total_steps)
    new_params, new_opt = opt_update(params, grads, opt_state, tcfg, lr)
    metrics = dict(metrics)
    metrics.update(grad_norm=gnorm, lr=lr, loss=loss)
    return new_params, new_opt, metrics


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    microbatches: int = 1):
    """Returns train_step(state, batch) -> (state, metrics)."""
    _, opt_update = make_optimizer(tcfg.optimizer)
    grad_fn = _make_grad_fn(cfg, tcfg)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        params = state.params
        loss, metrics, grads = _loss_and_grads(grad_fn, params, batch,
                                               microbatches)

        with jax.named_scope("optimizer"):
            # wire-format compression (numerics-exact w.r.t. a shared-scale
            # compressed all-reduce; see dist/compression.py)
            new_ef = state.ef
            if tcfg.grad_compression != "none":
                grads, new_ef = compress_tree(grads, tcfg.grad_compression,
                                              state.ef)

            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
            new_params, new_opt, metrics = _apply_update(
                params, grads, state.opt, gnorm, metrics, loss, tcfg,
                opt_update)
        return TrainState(new_params, new_opt, new_ef), metrics

    return train_step


# ---------------------------------------------------------------------------
# Manual-collectives (shard_map) path
# ---------------------------------------------------------------------------

def _mesh_batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in BATCH_AXES if a in axis_sizes(mesh))


def n_batch_shards(mesh) -> int:
    sizes = axis_sizes(mesh)
    n = 1
    for a in _mesh_batch_axes(mesh):
        n *= sizes[a]
    return n


def _batch_entry(mesh):
    axes = _mesh_batch_axes(mesh)
    return axes if len(axes) > 1 else axes[0]


def _zip_params(f, params, *aligned):
    """Map ``f(param_leaf, *aligned_leaves)`` over a Param tree, where each
    aligned tree has one node (e.g. a PartitionSpec) per Param position."""
    leaves, treedef = jax.tree_util.tree_flatten(params, is_leaf=is_param)
    cols = [treedef.flatten_up_to(t) for t in aligned]
    out = [f(leaf, *(c[i] for c in cols)) for i, leaf in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def init_sharded_train_state(key, cfg: ModelConfig, tcfg: TrainConfig,
                             mesh: Mesh) -> TrainState:
    """Like ``init_train_state`` but with *per-device* error-feedback
    buffers: each data-parallel rank keeps its own quantization residual
    (that is what error feedback means — the residual belongs to the
    device whose contribution was rounded), so EF leaves get a leading
    ``n_batch_shards(mesh)`` dimension sharded over the batch axes."""
    state = init_train_state(key, cfg, tcfg)
    if state.ef is None:
        return state
    n = n_batch_shards(mesh)
    ef = jax.tree.map(
        lambda p: Param(jnp.zeros((n,) + tuple(p.value.shape), jnp.float32),
                        (None,) + tuple(p.axes)),
        state.params, is_leaf=is_param)
    return TrainState(state.params, state.opt, ef)


def sharded_state_specs(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                        strategy) -> TrainState:
    """PartitionSpec tree (TrainState-shaped) for the shard_map path.

    Params/opt-moments follow the strategy's logical-rule pspecs; the
    optimizer step scalar is replicated; EF buffers shard their leading
    per-rank dimension over the batch axes and are sharded like the
    gradient they correct: over ``model`` on each dim the leaf plan keeps
    local (``LocalDim``), replicated elsewhere.
    """
    strat = resolve_strategy(strategy)
    state_shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg))

    def pspecs(tree):
        return None if tree is None else param_pspecs(tree, mesh, strat)

    p_specs = pspecs(state_shapes.params)
    opt = state_shapes.opt
    opt_specs = OptState(P(), pspecs(opt.mu), pspecs(opt.nu))
    ef_specs = None
    if tcfg.grad_compression == "int8_ef":
        def ef_spec(pl):
            d = [a.axis if isinstance(a, LocalDim) else None
                 for a in pl.axes]
            while d and d[-1] is None:
                d.pop()
            return P(_batch_entry(mesh), *d)

        ef_specs = _zip_params(
            lambda p, pl: ef_spec(pl), state_shapes.params,
            _leaf_plans(cfg, tcfg, mesh, p_specs, state_shapes.params))
    return TrainState(p_specs, opt_specs, ef_specs)


def sharded_state_shardings(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                            strategy, specs: Optional[TrainState] = None
                            ) -> TrainState:
    """``sharded_state_specs`` wrapped as NamedShardings on ``mesh``.

    Pass ``specs`` when already computed — the spec derivation traces
    the full model/optimizer init under ``jax.eval_shape``."""
    if specs is None:
        specs = sharded_state_specs(cfg, tcfg, mesh, strategy)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def sharded_batch_ok(mesh, global_batch: int) -> bool:
    """shard_map needs the batch evenly divided over the batch axes."""
    return global_batch % n_batch_shards(mesh) == 0


class _LeafPlan(NamedTuple):
    """Per-leaf decision for the shard_map body."""
    axes: Tuple        # rewritten axes tuple with LocalDim/StreamDim markers
    gather: P          # eager-gather spec (entries only on eager dims)
    streamed: bool     # any StreamDim -> grads arrive pre-reduced + sliced
    repl: float        # replication of this leaf's grad at clip time


def _streamable_tree(cfg: ModelConfig, tcfg: TrainConfig, param_shapes):
    """Bool-at-Param-positions tree: True where per-layer streaming is safe.

    Only scanned segment stacks stream (their gathers then sit *inside*
    the layer scan, interleaved with compute). Zamba groups share weights
    across a nested inner scan and encoder-decoder models read segment
    weights outside the marker-aware paths (``_stacked_cross_kv``), so
    both keep eager whole-tree gathers. Under ``int8_ef`` nothing
    streams: the residual is state, which cannot pass through
    ``stream_gather``'s backward, so every leaf reduces (and keeps its
    residual) after the loss.
    """
    flags = jax.tree.map(lambda p: False, param_shapes, is_leaf=is_param)
    if cfg.is_encoder_decoder or tcfg.grad_compression == "int8_ef":
        return flags
    for i, seg in enumerate(MD.build_segments(cfg)):
        if seg.kind == "zamba_group":
            continue
        flags["segments"][i] = jax.tree.map(
            lambda p: True, param_shapes["segments"][i], is_leaf=is_param)
    return flags


def _leaf_plans(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh, p_specs,
                shapes):
    """Classify every parameter dim for the shard_map body.

    Per sharded dim, in priority order:

    * **partitioned** (``LocalDim``) — model-sharded and ``tp_live_axes``
      says the layer code can compute on the local slice (Megatron
      column/row split, expert-local MoE, local attention heads);
    * **streamed** (``StreamDim``) — any other sharded dim of a leaf in a
      scanned segment stack: left sharded, all-gathered per layer inside
      the scan, gradient reduce-scattered by ``stream_gather``'s backward;
    * **eager** — everything else is all-gathered whole before the
      loss (top-level leaves: embedding, final norm, lm_head, mtp).

    ``repl`` counts how many ranks hold each element of the leaf's
    *reduced* gradient at clip time: eager dims are gathered full
    everywhere, so only local dims (and, for streamed leaves, their
    stream axes) divide the device count.
    """
    sizes = axis_sizes(mesh)
    n_total = 1
    for s in sizes.values():
        n_total *= s
    live = MD.tp_live_axes(cfg, sizes.get("model", 1))
    streamable = _streamable_tree(cfg, tcfg, shapes)

    def one(p, spec, can_stream):
        nd = len(p.axes)
        entries = spec_entries(spec, nd)
        axes, gather = [], []
        shard = 1
        streamed = False
        for i, (logical, entry) in enumerate(zip(p.axes, entries)):
            if entry is None:
                axes.append(logical)
                gather.append(None)
                continue
            ax = entry if isinstance(entry, tuple) else (entry,)
            # The MoE router's expert dim is its *output* (last) dim: the
            # routing math is replicated, so it must stay full even when
            # expert-parallelism is live for the expert stacks.
            if (ax == ("model",) and logical in live
                    and not (logical == "expert" and i == nd - 1)):
                axes.append(LocalDim(logical, "model", sizes["model"]))
                gather.append(None)
                shard *= sizes["model"]
            elif can_stream:
                axes.append(StreamDim(logical, entry))
                gather.append(None)
                streamed = True
                for a in ax:
                    shard *= sizes[a]
            else:
                axes.append(logical)
                gather.append(entry)
        return _LeafPlan(tuple(axes), P(*gather), streamed,
                         float(n_total // shard))

    return _zip_params(one, shapes, p_specs, streamable)


def overlap_transient_bytes(cfg: ModelConfig, tcfg: TrainConfig, mesh,
                            strategy="dp", state_specs=None
                            ) -> Tuple[int, int]:
    """(eager_bytes, stream_chunk_bytes) the shard_map body's gathers
    add per device beyond the persistent parameter shards.

    Eager leaves (embedding, lm_head, norms, zamba groups, enc-dec
    segments) hold their whole gathered array for the step; streamed
    segment stacks materialize at most one layer's gathered slice at a
    time inside the scan, so their term is the largest single-layer
    chunk across segments — the number the planner's memory model
    charges instead of the whole-tree transient (docs/PLANNER.md).
    Partitioned (``LocalDim``) dims are never gathered and contribute to
    neither term. ``mesh`` may be a Mesh or a plain ``{axis: size}``
    mapping (the planner prices candidate meshes without devices).
    """
    strat = resolve_strategy(strategy)
    if state_specs is None:
        state_specs = sharded_state_specs(cfg, tcfg, mesh, strat)
    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg)).params
    plans = _leaf_plans(cfg, tcfg, mesh, state_specs.params, shapes)
    sizes = axis_sizes(mesh)

    def one(p, pl):
        nbytes = p.value.dtype.itemsize
        for d in p.value.shape:
            nbytes *= int(d)
        local = 1
        stream_div = 1
        for ax in pl.axes:
            if isinstance(ax, LocalDim):
                local *= int(ax.size)
            elif isinstance(ax, StreamDim):
                entry = ax.entry
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    stream_div *= int(sizes.get(a, 1))
        if pl.streamed and stream_div > 1:
            layers = max(int(p.value.shape[0]), 1)
            return ("stream", (nbytes // local) // layers)
        if pl.streamed:      # degenerate mesh: nothing actually sharded
            return ("eager", 0)
        gdiv = 1
        for entry in tuple(pl.gather):
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                gdiv *= int(sizes.get(a, 1))
        return ("eager", nbytes // local - nbytes // (local * gdiv))

    terms = _zip_params(one, shapes, plans)
    is_term = lambda x: isinstance(x, tuple) and len(x) == 2 and \
        isinstance(x[0], str)
    eager = sum(v for k, v in jax.tree_util.tree_leaves(
        terms, is_leaf=is_term) if k == "eager")
    chunk = 0
    if isinstance(terms, dict) and "segments" in terms:
        for seg in terms["segments"]:
            chunk = max(chunk, sum(
                v for k, v in jax.tree_util.tree_leaves(
                    seg, is_leaf=is_term) if k == "stream"))
    return int(eager), int(chunk)


def make_sharded_train_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                            strategy="dp", microbatches: int = 1,
                            state_specs: Optional[TrainState] = None):
    """The measured multi-device path: shard_map with explicit collectives.

    ``_leaf_plans`` rewrites each parameter's axes with ``LocalDim``
    (Megatron tensor-parallel slice over ``model`` — column/row split
    MLPs, local attention heads, expert-local MoE) and ``StreamDim``
    (ZeRO-style per-layer gather inside the layer scan) markers. Per
    step, on each device:

      1. all-gather the *eager* dims of each parameter shard: those of
         leaves outside the scanned stacks, and under ``int8_ef`` every
         dim not kept local. Local dims stay sliced; streamed dims are
         gathered layer by layer while the loss runs, interleaved with
         compute;
      2. compute gradients of the *local* sub-batch (micro-batched if
         asked) on the model rank's slice of the tensor-parallel work;
      3. all-reduce-mean the gradients over the batch axes through the
         compressed collective (``compressed_psum_mean`` /
         ``compressed_psum_mean_ef`` for int8 error feedback — the
         residual stays on this device, sharded like the gradient). A
         streamed leaf's gradient arrives already reduced and sliced by
         ``stream_gather``'s backward;
      4. clip by the global norm of the full gradient (a partition-aware
         sum of squares, one psum over the mesh), slice each gradient
         back to this device's shard, and apply the optimizer update
         locally — the update is elementwise, so sharded params/moments
         stay sharded.

    See docs/DIST.md ("Partitioned tp body and streaming gathers").

    Restrictions: optimizer must be elementwise (adamw/sgd — adafactor's
    factored moments take row/col means over dims this path shards), the
    mesh must carry at least one batch axis, and the global batch must
    divide evenly over it (``sharded_batch_ok``).
    """

    if tcfg.optimizer == "adafactor":
        raise NotImplementedError(
            "sharded path supports elementwise optimizers (adamw/sgd); "
            "adafactor's factored moments need full-dim means")
    batch_axes = _mesh_batch_axes(mesh)
    if not batch_axes:
        raise ValueError(f"mesh {dict(mesh.shape)} has no batch axis "
                         f"({BATCH_AXES}); the gradient all-reduce needs one")
    _, opt_update = make_optimizer(tcfg.optimizer)
    grad_fn = _make_grad_fn(cfg, tcfg)
    strat = resolve_strategy(strategy)
    mode = tcfg.grad_compression

    if state_specs is None:     # deriving specs traces the full init
        state_specs = sharded_state_specs(cfg, tcfg, mesh, strat)
    p_specs = state_specs.params
    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg)).params
    plans = _leaf_plans(cfg, tcfg, mesh, p_specs, shapes)
    sizes = axis_sizes(mesh)
    mesh_axes = tuple(sizes)
    sorted_sizes = tuple(sorted(sizes.items()))

    def body(state: TrainState, batch):
        # jax.named_scope labels are trace-time only: they name the HLO
        # regions after the cost model's terms (visible in jax.profiler /
        # Perfetto) and cost nothing in the compiled program.
        with manual_mode(), MD.stream_context(sorted_sizes, batch_axes,
                                              mode):
            params = state.params
            with jax.named_scope("obs:gather_params"):
                # eager gathers only — streamed/partitioned leaves gather
                # inside the layer scan, interleaved with compute
                compute_params = _zip_params(
                    lambda p, pl: Param(gather_to_full(p.value, pl.gather),
                                        pl.axes),
                    params, plans)
            with jax.named_scope("obs:grad_compute"):
                loss, metrics, grads = _loss_and_grads(
                    grad_fn, compute_params, batch, microbatches)
            gvals = pvalues(grads) if microbatches <= 1 else grads

            new_ef = state.ef
            with jax.named_scope("obs:grad_reduce"):
                if mode == "int8_ef":
                    # pairs holds (mean, new_err) tuples at Param
                    # positions; always unzip against the params treedef
                    # so the tuples are never mistaken for pytree
                    # internals. Nothing streams under int8_ef.
                    pairs = _zip_params(
                        lambda p, g, e: compressed_psum_mean_ef(
                            g.astype(jnp.float32), batch_axes, e.value[0]),
                        params, gvals, state.ef)
                    reduced = _zip_params(lambda p, t: t[0], params, pairs)
                    new_ef = _zip_params(
                        lambda p, t, e: Param(t[1][None], e.axes),
                        params, pairs, state.ef)
                else:
                    reduced = _zip_params(
                        lambda p, g, pl: (
                            g.astype(jnp.float32) if pl.streamed else
                            compressed_psum_mean(g.astype(jnp.float32),
                                                 batch_axes, mode)),
                        params, gvals, plans)
                loss = jax.lax.pmean(loss, batch_axes)
                metrics = jax.tree.map(
                    lambda m: jax.lax.pmean(m, batch_axes), metrics)

            # Partition-aware global-norm clip: every rank contributes its
            # local sum-of-squares weighted by 1/replication, one psum over
            # the whole mesh makes the full-gradient norm — then the same
            # scale as clip_by_global_norm applies elementwise (scaling
            # commutes with the later slice).
            with jax.named_scope("obs:update"), \
                    jax.named_scope("optimizer"):
                contribs = _zip_params(
                    lambda p, g, pl: jnp.sum(
                        jnp.square(g.astype(jnp.float32))) / pl.repl,
                    params, reduced, plans)
                total = jax.lax.psum(
                    sum(jax.tree_util.tree_leaves(contribs)), mesh_axes)
                gnorm = jnp.sqrt(total)
                scale = jnp.minimum(1.0, tcfg.grad_clip /
                                    jnp.maximum(gnorm, 1e-9))
                clipped = jax.tree.map(
                    lambda g: (g.astype(jnp.float32) * scale).astype(
                        g.dtype),
                    reduced)
                grads_shard = _zip_params(
                    lambda p, g, pl: Param(
                        shard_of_full(g, pl.gather, mesh), p.axes),
                    params, clipped, plans)
                new_params, new_opt, metrics = _apply_update(
                    params, grads_shard, state.opt, gnorm, metrics, loss,
                    tcfg, opt_update)
            return TrainState(new_params, new_opt, new_ef), metrics

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(state_specs, P(_batch_entry(mesh))),
                         out_specs=(state_specs, P()),
                         check_vma=False)
