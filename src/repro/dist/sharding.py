"""Logical-axis -> PartitionSpec resolution per parallelism strategy.

Model code annotates every ``Param`` dimension with a *logical* axis name
("embed", "mlp", "vocab", "expert", "heads", "kv_heads", "layers", or
``None``); activations are constrained with ``maybe_constrain`` using the
``BATCH`` sentinel plus raw mesh-axis names. This module owns the mapping
from those logical names to the *physical* mesh axes of whatever mesh is
active, under a named strategy:

  dp       pure data parallelism — params replicated, batch over (pod, data)
  fsdp     ZeRO-3: params sharded over the data axis (one dim per param)
  tp       Megatron tensor parallelism over the model axis
  fsdp_tp  2-D: embed over data, mlp/heads/experts/vocab over model

Two invariants hold for every resolved spec (property-tested):

  * a mesh axis is used by at most one dimension of a given array
    (GSPMD rejects reuse, so we resolve left-to-right and first-hit-wins);
  * a dimension is only sharded if its size is divisible by the product
    of the mesh axes assigned to it — otherwise the dim is left
    unsharded (e.g. a 50281-row vocab on a 16-wide model axis).

Everything in the resolution layer is shape-arithmetic only: functions
accept a concrete ``Mesh``, an ``AbstractMesh``, or a plain
``{axis: size}`` mapping, so the rules are testable without a device
pool.

Registry semantics (the contract docs/DIST.md documents in full):

  * ``STRATEGIES[name].rules[logical]`` is an *ordered fallback list* of
    candidates; the first candidate whose mesh axes are all present,
    unused by an earlier dim of the same array, and divisibility-
    compatible wins. ``rules["vocab"] = ("model", "data")`` therefore
    means "model, else data" — joint 2-D sharding of one dim is written
    as a nested tuple ``(("model", "data"),)``.
  * Resolution is deterministic and per-array: the same (axes, shape,
    mesh, strategy) always yields the same PartitionSpec, so shardings
    computed from ``jax.eval_shape`` skeletons match the real arrays.
  * A strategy never errors on a mesh that lacks its axes — missing axes
    simply drop out, which is what lets one strategy string serve the
    1-device CI mesh and the 512-chip pod.

The module also owns the *manual-collectives* helpers used by the
``shard_map`` train path (``repro.train.step.make_sharded_train_step``):
``gather_to_full`` / ``shard_of_full`` invert a resolved PartitionSpec
inside a ``shard_map`` body (all-gather a local block up to the full
array; slice this device's block back out), and ``manual_mode`` disables
``maybe_constrain`` while per-device code traces — sharding constraints
are a GSPMD concept and must not leak into manually-partitioned code.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

# LocalDim / tp_f / tp_probe live in repro.models.layers (which must not
# import repro.dist.*) and are re-exported here as the canonical API the
# distribution-side code imports them from.
from repro.models.layers import (LocalDim, StreamDim,  # noqa: F401
                                 is_param, local_dim, tp_f, tp_g, tp_probe,
                                 tp_probe_sink)


class _BatchSentinel:
    """Logical marker for 'the batch dimension' in activation constraints."""

    def __repr__(self):
        return "BATCH"


BATCH = _BatchSentinel()

# Mesh axes that carry the batch, outermost first (multi-pod meshes put a
# "pod" axis in front of "data"; both shard the batch).
BATCH_AXES = ("pod", "data")

# A rule candidate: either one mesh axis, or a tuple of mesh axes that
# shard the same dimension jointly. NB the rules map to *tuples of
# candidates*: rules["vocab"] = ("model", "data") is an ordered fallback
# list of two single-axis candidates; joint 2-D sharding of one dim must
# be written (("model", "data"),).
Candidate = Union[str, Tuple[str, ...]]


@dataclass(frozen=True)
class Strategy:
    """Named parallelism strategy: logical axis -> mesh-axis candidates.

    ``rules[logical]`` is tried in order; the first candidate whose mesh
    axes are all present, unused by earlier dims of the same array, and
    size-compatible with the dimension wins.
    """
    name: str
    rules: Mapping[str, Tuple[Candidate, ...]] = field(default_factory=dict)
    description: str = ""

    def candidates(self, logical: Optional[str]) -> Tuple[Candidate, ...]:
        if logical is None:
            return ()
        return tuple(self.rules.get(logical, ()))


STRATEGIES: Dict[str, Strategy] = {
    "dp": Strategy("dp", rules={}, description=(
        "Pure data parallelism: parameters replicated, batch sharded; "
        "gradients all-reduced every step.")),
    "fsdp": Strategy("fsdp", rules={
        "embed": ("data",), "vocab": ("data",), "mlp": ("data",),
        "expert": ("data",), "heads": ("data",), "kv_heads": ("data",),
    }, description=(
        "ZeRO-3 style: each parameter sharded along its first shardable "
        "dim over the data axis; params are all-gathered per layer.")),
    "tp": Strategy("tp", rules={
        "mlp": ("model",), "heads": ("model",), "kv_heads": ("model",),
        "expert": ("model",), "vocab": ("model",),
    }, description=(
        "Megatron tensor parallelism: hidden/head/expert/vocab dims over "
        "the model axis; activations all-reduced inside each block.")),
    "fsdp_tp": Strategy("fsdp_tp", rules={
        "embed": ("data",),
        "mlp": ("model",), "heads": ("model",), "kv_heads": ("model",),
        "expert": ("model",),
        "vocab": ("model", "data"),
    }, description=(
        "2-D sharding: tensor-parallel over model, parameter (ZeRO) "
        "sharding of the remaining embed dim over data.")),
}


def resolve_strategy(strategy: Union[str, Strategy]) -> Strategy:
    if isinstance(strategy, Strategy):
        return strategy
    try:
        return STRATEGIES[strategy]
    except KeyError:
        raise ValueError(f"unknown strategy {strategy!r}; "
                         f"have {sorted(STRATEGIES)}") from None


# ---------------------------------------------------------------------------
# Per-strategy collective descriptions (consumed by the cost model)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CollectiveDesc:
    """One abstract collective a strategy issues per training iteration.

    This is the *shape* of the strategy's communication — which ring
    primitive moves which tensor class over which mesh axis, how many
    times — with no sizes attached. ``repro.perf.costmodel.schedules``
    binds it to concrete byte counts and per-axis device counts; the
    measured shard_map paths (``repro.train.step`` and the LeNet sweep)
    are the executable counterparts it abstracts.

      op      ring primitive name (repro.perf.costmodel.primitives)
      tensor  what moves: "grad" (wire-compressed), "param" (fp32 wire),
              or "act" (activations, batch-sharded over the data axis)
      axis    mesh axis the ring spans: "data" or "model"
      count   occurrences per iteration (e.g. fsdp all-gathers params
              once forward + once backward)
    """
    op: str
    tensor: str
    axis: str
    count: int = 1


# The canonical per-iteration schedules (docs/DIST.md spells out the
# provenance of each term):
#   dp       ring all-reduce of the wire-compressed gradients.
#   fsdp     canonical ZeRO-3: all-gather the fp32 parameter shards for
#            forward and again for backward, reduce-scatter compressed
#            gradients back to their owners.
#   tp       Megatron: two activation all-reduces forward and two
#            backward per tensor-parallel block (the g/ḡ operators);
#            parameter gradients stay local to their model-axis shard.
#   fsdp_tp  the 2-D mesh decomposed per axis: each model rank ZeRO-
#            shards its 1/|model| parameter slice over data (same
#            gather/scatter pattern as fsdp at 1/|model| volume), while
#            the model axis carries the Megatron activation all-reduces.
STRATEGY_COLLECTIVES: Dict[str, Tuple[CollectiveDesc, ...]] = {
    "dp": (
        CollectiveDesc("all_reduce", "grad", "data"),
    ),
    "fsdp": (
        CollectiveDesc("all_gather", "param", "data", count=2),
        CollectiveDesc("reduce_scatter", "grad", "data"),
    ),
    "tp": (
        CollectiveDesc("all_reduce", "act", "model", count=4),
    ),
    "fsdp_tp": (
        CollectiveDesc("all_gather", "param", "data", count=2),
        CollectiveDesc("reduce_scatter", "grad", "data"),
        CollectiveDesc("all_reduce", "act", "model", count=4),
    ),
}
assert set(STRATEGY_COLLECTIVES) == set(STRATEGIES), \
    "every registry strategy needs a collective description"


# ---------------------------------------------------------------------------
# Mesh introspection
# ---------------------------------------------------------------------------

MeshLike = Union[Mesh, Mapping[str, int]]


def axis_sizes(mesh: MeshLike) -> Dict[str, int]:
    """{axis: size} from a Mesh, AbstractMesh, or plain mapping."""
    shape = getattr(mesh, "shape", mesh)
    return dict(shape)


def active_mesh() -> Optional[AbstractMesh]:
    """The mesh installed by an enclosing ``with jax.set_mesh(mesh):``.

    Returns None outside any mesh context so single-device eager/jit
    paths stay unconstrained.
    """
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


# ---------------------------------------------------------------------------
# Core resolution
# ---------------------------------------------------------------------------

def _axes_of(candidate: Candidate) -> Tuple[str, ...]:
    return candidate if isinstance(candidate, tuple) else (candidate,)


def _fits(cand_axes: Sequence[str], sizes: Mapping[str, int], used: set,
          dim: Optional[int]) -> bool:
    prod = 1
    for a in cand_axes:
        if a not in sizes or a in used:
            return False
        prod *= sizes[a]
    if dim is not None and (prod == 0 or dim % prod != 0):
        return False
    return True


def logical_to_pspec(axes: Sequence[Optional[str]], mesh: MeshLike,
                     strategy: Union[str, Strategy],
                     dim_sizes: Optional[Sequence[int]] = None) -> P:
    """Resolve one array's logical axes to a PartitionSpec.

    ``dim_sizes`` (when given) enables divisibility-aware skipping: a dim
    whose size is not a multiple of the assigned mesh-axes product stays
    unsharded. Resolution is left-to-right; a mesh axis consumed by an
    earlier dim is never reused by a later one.
    """
    strat = resolve_strategy(strategy)
    sizes = axis_sizes(mesh)
    if dim_sizes is not None and len(dim_sizes) != len(axes):
        raise ValueError(f"dim_sizes {tuple(dim_sizes)} does not match "
                         f"axes {tuple(axes)}")
    used: set = set()
    entries = []
    for i, logical in enumerate(axes):
        dim = None if dim_sizes is None else int(dim_sizes[i])
        entry = None
        for cand in strat.candidates(logical):
            cand_axes = _axes_of(cand)
            if _fits(cand_axes, sizes, used, dim):
                used.update(cand_axes)
                entry = cand_axes if len(cand_axes) > 1 else cand_axes[0]
                break
        entries.append(entry)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_pspecs(params, mesh: MeshLike, strategy: Union[str, Strategy]):
    """Pytree of PartitionSpec matching the Param leaves of ``params``.

    Works on real arrays and on ``jax.eval_shape`` skeletons alike (only
    ``.value.shape`` is read).
    """
    strat = resolve_strategy(strategy)

    def one(p):
        return logical_to_pspec(p.axes, mesh, strat,
                                dim_sizes=tuple(p.value.shape))

    return jax.tree.map(one, params, is_leaf=is_param)


def param_shardings(params, mesh: Mesh, strategy: Union[str, Strategy]):
    """Like ``param_pspecs`` but wrapped as NamedShardings on ``mesh``."""
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        param_pspecs(params, mesh, strategy),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Batch / activation constraints
# ---------------------------------------------------------------------------

def _batch_entry(sizes: Mapping[str, int], used: set,
                 dim: Optional[int]):
    """Greedy (pod, data) batch sharding honouring divisibility."""
    chosen = []
    prod = 1
    for a in BATCH_AXES:
        if a not in sizes or a in used:
            continue
        if dim is not None and dim % (prod * sizes[a]) != 0:
            continue
        chosen.append(a)
        prod *= sizes[a]
    if not chosen:
        return None
    return tuple(chosen) if len(chosen) > 1 else chosen[0]


def batch_pspec(mesh: MeshLike, ndim: int = 1,
                batch_size: Optional[int] = None) -> P:
    """PartitionSpec sharding dim 0 over the mesh's batch axes."""
    sizes = axis_sizes(mesh)
    entry = _batch_entry(sizes, set(), batch_size)
    return P(*([entry] + [None] * (ndim - 1)))


def maybe_constrain(x: jax.Array, *entries) -> jax.Array:
    """``with_sharding_constraint`` iff a mesh context is active.

    ``entries`` align with the leading dims of ``x`` (missing trailing
    entries mean replicated). Each entry is ``None``, the ``BATCH``
    sentinel (expands to the mesh's pod/data axes), a mesh-axis name, or
    a tuple of mesh-axis names. Axes absent from the mesh, already used
    by an earlier dim, or incompatible with the dim size are dropped —
    so the same model code traces cleanly on a 1-CPU mesh and a
    512-chip (pod, data, model) mesh.
    """
    if in_manual_mode():
        return x
    mesh = active_mesh()
    if mesh is None:
        return x
    sizes = axis_sizes(mesh)
    used: set = set()
    padded = tuple(entries) + (None,) * (x.ndim - len(entries))
    resolved = []
    for dim, e in zip(x.shape, padded):
        dim = int(dim)
        if e is None:
            resolved.append(None)
            continue
        if isinstance(e, _BatchSentinel):
            entry = _batch_entry(sizes, used, dim)
        else:
            cand_axes = _axes_of(e)
            ok = _fits(cand_axes, sizes, used, dim)
            entry = ((cand_axes if len(cand_axes) > 1 else cand_axes[0])
                     if ok else None)
        if entry is not None:
            used.update(_axes_of(entry))
        resolved.append(entry)
    while resolved and resolved[-1] is None:
        resolved.pop()
    if not any(e is not None for e in resolved):
        return x
    sharding = NamedSharding(mesh, P(*resolved))
    return jax.lax.with_sharding_constraint(x, sharding)


# ---------------------------------------------------------------------------
# Manual-collectives mode (shard_map bodies)
# ---------------------------------------------------------------------------

_MANUAL = threading.local()


def in_manual_mode() -> bool:
    return bool(getattr(_MANUAL, "depth", 0))


@contextmanager
def manual_mode():
    """Disable ``maybe_constrain`` while tracing per-device code.

    Inside a ``shard_map`` body every array is a local block and the
    named mesh axes are bound as collective axes; a GSPMD
    ``with_sharding_constraint`` against the global mesh is meaningless
    there (and rejected by jax). Model code calls ``maybe_constrain``
    unconditionally, so the sharded train step wraps its body in this
    context while it traces. Thread-local and re-entrant.
    """
    _MANUAL.depth = getattr(_MANUAL, "depth", 0) + 1
    try:
        yield
    finally:
        _MANUAL.depth -= 1


def spec_entries(spec: P, ndim: int) -> Tuple:
    """PartitionSpec entries padded with None to ``ndim`` dims."""
    entries = tuple(spec)
    return entries + (None,) * (ndim - len(entries))


# ---------------------------------------------------------------------------
# PartitionSpec (de)serialization + shard-grid arithmetic
# ---------------------------------------------------------------------------
# The sharded checkpoint format (repro.train.checkpoint) records every
# leaf's resolved PartitionSpec in the JSON sidecar so a restore can
# reassemble full arrays from per-shard blocks written under *any*
# (mesh, strategy) and re-place them under any other. Keeping the
# serialization and the block arithmetic here — next to the resolver —
# is what guarantees reshard rules and executable rules can never drift:
# both sides go through the same ``param_pspecs`` resolution.

def spec_to_json(spec: P) -> list:
    """JSON-friendly entry list: None | "axis" | ["axis", ...]."""
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
        elif isinstance(entry, tuple):
            out.append(list(entry))
        else:
            out.append(str(entry))
    return out


def spec_from_json(entries) -> P:
    """Inverse of ``spec_to_json``."""
    return P(*[tuple(e) if isinstance(e, list) else e for e in entries])


def shard_grid(spec: P, shape: Sequence[int],
               mesh: MeshLike) -> Tuple[int, ...]:
    """Blocks per dimension an array splits into under ``spec`` on
    ``mesh``. Dims whose assigned mesh-axes product does not divide the
    dim size count as unsharded (grid 1) — mirroring the resolver's
    divisibility skipping, so a spec resolved by ``param_pspecs`` never
    hits the guard."""
    sizes = axis_sizes(mesh)
    grid = []
    for dim, entry in zip(shape, spec_entries(spec, len(shape))):
        dim = int(dim)
        if entry is None:
            grid.append(1)
            continue
        prod = 1
        for a in _axes_of(entry):
            prod *= int(sizes.get(a, 1))
        grid.append(prod if prod > 0 and dim % prod == 0 else 1)
    return tuple(grid)


def shard_coord(index: Sequence, shape: Sequence[int],
                grid: Sequence[int]) -> Tuple[int, ...]:
    """Grid coordinate of one device's shard from its global-index
    slices (``jax.Array.addressable_shards[i].index``). Positional in
    the global array, so assembly is independent of which mesh axis —
    or axis order, for jointly-sharded dims — produced the block."""
    coord = []
    for sl, dim, g in zip(tuple(index) + (slice(None),) * len(grid),
                          shape, grid):
        start = 0 if sl.start is None else int(sl.start)
        block = int(dim) // int(g)
        coord.append(start // block if block else 0)
    return tuple(coord)


def assemble_shards(blocks: Mapping[Tuple[int, ...], "object"],
                    shape: Sequence[int], grid: Sequence[int]):
    """Stitch a ``{grid-coordinate: block}`` map back into the full
    array — the host-side inverse of sharding under any spec."""
    import numpy as np

    shape = tuple(int(s) for s in shape)
    grid = tuple(int(g) for g in grid)
    if all(g == 1 for g in grid):
        blk = blocks[(0,) * len(shape) if shape else ()]
        return np.asarray(blk)
    sample = next(iter(blocks.values()))
    full = np.empty(shape, dtype=np.asarray(sample).dtype)
    for coord, blk in blocks.items():
        blk = np.asarray(blk)
        slices = tuple(
            slice(c * (dim // g), (c + 1) * (dim // g))
            for c, dim, g in zip(coord, shape, grid))
        if blk.shape != tuple(dim // g for dim, g in zip(shape, grid)):
            raise ValueError(f"shard block {blk.shape} does not tile "
                             f"{shape} on grid {grid}")
        full[slices] = blk
    return full


def assemble_region(blocks: Mapping[Tuple[int, ...], "object"],
                    shape: Sequence[int], grid: Sequence[int],
                    region: Sequence[slice]):
    """Stitch only the sub-array at ``region`` (per-dim global slices)
    from the ``{grid-coordinate: block}`` map — the partial inverse of
    sharding that shard-to-shard checkpoint restore needs: a target
    device's shard is assembled from just the *overlapping* source
    blocks, never the full array.

    ``region`` slices may use ``None`` start/stop (full dim); trailing
    dims may be omitted. ``blocks`` only needs ``__getitem__``, so a
    lazy mapping can defer reading blocks the region never touches.
    """
    import numpy as np

    shape = tuple(int(s) for s in shape)
    grid = tuple(int(g) for g in grid)
    if not shape:
        return np.asarray(blocks[()])
    region = tuple(region) + (slice(None),) * (len(shape) - len(region))
    bounds = []
    for dim, sl in zip(shape, region):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        bounds.append((max(start, 0), min(stop, dim)))
    out_shape = tuple(max(e - s, 0) for s, e in bounds)
    block_dims = tuple(d // g for d, g in zip(shape, grid))
    if 0 in out_shape:
        probe = np.asarray(blocks[(0,) * len(shape)])
        return np.empty(out_shape, dtype=probe.dtype)
    lo = tuple(s // b for (s, _), b in zip(bounds, block_dims))
    hi = tuple((e - 1) // b for (_, e), b in zip(bounds, block_dims))
    out = None
    for offset in np.ndindex(*[h - l + 1 for l, h in zip(lo, hi)]):
        coord = tuple(l + o for l, o in zip(lo, offset))
        blk = np.asarray(blocks[coord])
        if blk.shape != block_dims:
            raise ValueError(f"shard block {blk.shape} does not tile "
                             f"{shape} on grid {grid}")
        if out is None:
            out = np.empty(out_shape, dtype=blk.dtype)
        src, dst = [], []
        for (s, e), c, b in zip(bounds, coord, block_dims):
            gs = c * b
            is_, ie = max(s, gs), min(e, gs + b)
            src.append(slice(is_ - gs, ie - gs))
            dst.append(slice(is_ - s, ie - s))
        out[tuple(dst)] = blk[tuple(src)]
    return out


def gather_to_full(x: jax.Array, spec: P) -> jax.Array:
    """Inside ``shard_map``: all-gather a local block up to the full array.

    ``spec`` is the PartitionSpec the array entered the shard_map with.
    Multi-axis entries like ``("model", "data")`` are gathered minor axis
    first so block order matches the major-axis-first layout GSPMD uses
    for nested specs.
    """
    for dim, entry in enumerate(spec_entries(spec, x.ndim)):
        if entry is None:
            continue
        for a in reversed(_axes_of(entry)):
            x = jax.lax.all_gather(x, a, axis=dim, tiled=True)
    return x


def shard_of_full(x: jax.Array, spec: P, mesh: MeshLike) -> jax.Array:
    """Inside ``shard_map``: slice this device's block back out of a full
    array — the inverse of ``gather_to_full`` under the same spec."""
    sizes = axis_sizes(mesh)
    for dim, entry in enumerate(spec_entries(spec, x.ndim)):
        if entry is None:
            continue
        axes = _axes_of(entry)
        prod = 1
        idx = jax.numpy.zeros((), "int32")
        for a in axes:                       # major axis first
            idx = idx * sizes[a] + jax.lax.axis_index(a)
            prod *= sizes[a]
        block = x.shape[dim] // prod
        x = jax.lax.dynamic_slice_in_dim(x, idx * block, block, axis=dim)
    return x


# ---------------------------------------------------------------------------
# Streaming (per-layer) parameter gathers with fused backward reduce-scatter
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def stream_gather(entries: Tuple, sizes: Tuple[Tuple[str, int], ...],
                  batch_axes: Tuple[str, ...], mode: str,
                  x: jax.Array) -> jax.Array:
    """All-gather a ZeRO-sharded leaf *inside* the compute it feeds.

    Forward is ``gather_to_full`` for one leaf; backward fuses the
    gradient mean-reduction over the batch axes (in the wire-compressed
    format ``mode``) with the slice back to this device's block — i.e.
    the fsdp reduce-scatter. Called from inside the per-layer
    ``lax.scan`` body, this interleaves parameter gathers and gradient
    reduce-scatters with each layer's matmuls instead of serializing one
    whole-tree gather before the loss and one whole-tree reduction after
    it — which is what lets XLA hide collective latency behind compute,
    and shrinks the peak transient-gather footprint from all parameter
    bytes to one layer's worth.

    ``entries``/``sizes``/``batch_axes``/``mode`` are static (hashable)
    so the pair of transfers stays a single jaxpr primitive pair:
    ``entries`` are the per-dim PartitionSpec entries the leaf entered
    the shard_map with, ``sizes`` the mesh ``{axis: size}`` as sorted
    pairs. The gradient that reaches the optimizer for a streamed leaf
    is therefore *already* reduced and sliced — the step body must not
    reduce it again.
    """
    for dim, entry in enumerate(entries):
        if entry is None:
            continue
        for a in reversed(_axes_of(entry)):
            x = jax.lax.all_gather(x, a, axis=dim, tiled=True)
    return x


def _stream_gather_fwd(entries, sizes, batch_axes, mode, x):
    return stream_gather(entries, sizes, batch_axes, mode, x), None


def _stream_gather_bwd(entries, sizes, batch_axes, mode, _, g):
    from repro.dist.compression import compressed_psum_mean
    if batch_axes:
        g = compressed_psum_mean(g, batch_axes, mode=mode)
    g = shard_of_full(g, P(*entries), dict(sizes))
    return (g,)


stream_gather.defvjp(_stream_gather_fwd, _stream_gather_bwd)
