"""Align measurements against the cost model's own per-term predictions.

The calibrated schedule layer predicts a step as a sum of *terms*:

    t_step ≈ compute + Σ_term comm_term          (serialized, ρ = 0)
    t_step ≈ compute + max(0, Σ comm − ρ·compute)  (overlap-fitted)

where each communication term is one ``op/axis/tensor`` group of the
strategy's schedule (``repro.perf.costmodel.schedules.build_schedule``).
End-to-end validation can only say the *sum* is wrong; this module makes
each term individually falsifiable:

* ``predicted_terms`` — the model's per-term milliseconds under a
  calibration (fail-soft: the uncalibrated defaults price too, labelled
  ``"default"``);
* ``measure_collective_terms`` — runs each term's *real* collective
  (psum / all_gather / psum_scatter / all_to_all) on the live mesh, over
  the actual axis with the actual byte count, and times it — the
  measured side of the table;
* ``attribution_table`` / ``render_markdown`` — the measured-vs-
  predicted residual table per term;
* ``span_coverage`` — checks that a step span's children partition its
  wall time (the attribution-sum invariant: instrumentation that loses
  time cannot attribute it);
* ``detect_drift`` — flags terms whose live error exceeds the
  calibration-time band and recommends a refit (the regeneration command
  is ``repro.perf.costmodel.calibrate.REGEN_HINT``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Mapping, Optional, Sequence

from repro.perf.costmodel.calibrate import (REGEN_HINT, Calibration,
                                            load_calibration)
from repro.perf.costmodel.schedules import ScheduleInputs, build_schedule

TERM_COMPUTE = "compute"          # the non-communication term's key


def term_key(call) -> str:
    """The stable name of a schedule term: ``op/axis/tensor``."""
    return f"{call.op}/{call.axis}/{call.tensor}"


def predicted_terms(strategy, inp: ScheduleInputs, *,
                    calibration: Optional[Calibration] = None,
                    axes: Optional[Dict[str, int]] = None
                    ) -> Dict[str, Dict[str, Any]]:
    """Per-term predicted milliseconds of one iteration's schedule.

    Identical calls collapse into one term with a ``count`` (e.g. tp's
    four activation all-reduces); ``ms`` is the α-β total of the whole
    group under the calibration's links.
    """
    if calibration is None:
        calibration = load_calibration()
    links = calibration.links()
    out: Dict[str, Dict[str, Any]] = {}
    for call in build_schedule(strategy, inp, axes=axes):
        key = term_key(call)
        t = out.setdefault(key, {"op": call.op, "axis": call.axis,
                                 "tensor": call.tensor,
                                 "ring": call.n_devices,
                                 "bytes": 0.0, "count": 0, "ms": 0.0})
        t["bytes"] += float(call.nbytes)
        t["count"] += 1
        t["ms"] += call.seconds(links) * 1e3
    return out


def predicted_step_ms(strategy, inp: ScheduleInputs, *,
                      compute_ms: float,
                      calibration: Optional[Calibration] = None,
                      axes: Optional[Dict[str, int]] = None
                      ) -> Dict[str, float]:
    """The model's end-to-end step prediction, decomposed.

    ``total_ms = compute + max(0, comm − ρ·compute)`` with the fitted
    per-strategy overlap factor (ρ = 0 uncalibrated — fully serialized).
    """
    if calibration is None:
        calibration = load_calibration()
    terms = predicted_terms(strategy, inp, calibration=calibration,
                            axes=axes)
    comm_ms = sum(t["ms"] for t in terms.values())
    rho = calibration.overlap_for(strategy)
    exposed_ms = max(0.0, comm_ms - rho * float(compute_ms))
    return {"compute_ms": float(compute_ms), "comm_ms": comm_ms,
            "exposed_comm_ms": exposed_ms, "overlap": rho,
            "total_ms": float(compute_ms) + exposed_ms}


# ---------------------------------------------------------------------------
# Measured side: run each term's real collective on the live mesh
# ---------------------------------------------------------------------------

def _collective_body(op: str, axis: str):
    import jax

    if op == "all_reduce":
        return lambda x: jax.lax.psum(x, axis)
    if op == "reduce_scatter":
        return lambda x: jax.lax.psum_scatter(x, axis, scatter_dimension=0,
                                              tiled=True)
    if op == "all_gather":
        return lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True)
    if op == "all_to_all":
        return lambda x: jax.lax.all_to_all(x, axis, split_axis=0,
                                            concat_axis=0, tiled=True)
    raise ValueError(f"unknown collective {op!r}")


def _term_operand(op: str, axis: str, ring: int, nbytes: float):
    """(global array, in_spec) whose per-device payload matches the α-β
    convention: ``nbytes`` is the *full logical tensor* the collective
    moves — all_reduce/reduce_scatter/all_to_all inputs hold it per
    device (reduced / scattered / exchanged), all_gather inputs hold the
    1/ring shard that gathers up to it. The operand is sharded only over
    ``axis`` and replicated over every other mesh axis, so each ring
    runs concurrently — exactly like the real step's per-axis
    collectives."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    elems = max(int(nbytes) // 4, ring)          # fp32
    elems -= elems % ring                        # divisible shards
    if op == "all_gather":
        x = jnp.arange(elems, dtype=jnp.float32)
    else:
        x = jnp.arange(ring * elems, dtype=jnp.float32)
    return x, P(axis)


def measure_collective_terms(mesh, strategy, inp: ScheduleInputs, *,
                             axes: Optional[Dict[str, int]] = None,
                             iters: int = 10, warmup: int = 3,
                             clock=None) -> Dict[str, Dict[str, Any]]:
    """Measured milliseconds of each schedule term, on the real mesh.

    Each ``op/axis/tensor`` group is rebuilt as the *actual* JAX
    collective over the *actual* mesh axis with the *actual* byte count,
    jitted standalone in a shard_map, warmed up, and timed
    (min-of-``iters``, robust on a timeshared pool); the group's ``ms``
    is one call's time × the schedule's call count. This is the
    measured column ``attribution_table`` aligns against
    ``predicted_terms`` — the keys match by construction.
    """
    import time

    import jax
    from jax.sharding import PartitionSpec as P

    if clock is None:
        clock = time.perf_counter
    from repro.perf.costmodel.schedules import mesh_axes_for
    if axes is None:
        axes = mesh_axes_for(strategy, inp.n_devices)

    groups: Dict[str, Dict[str, Any]] = {}
    for call in build_schedule(strategy, inp, axes=axes):
        key = term_key(call)
        g = groups.setdefault(key, {"op": call.op, "axis": call.axis,
                                    "tensor": call.tensor,
                                    "ring": call.n_devices,
                                    "nbytes": float(call.nbytes),
                                    "count": 0})
        g["count"] += 1

    out: Dict[str, Dict[str, Any]] = {}
    for key, g in groups.items():
        op, axis, ring = g["op"], g["axis"], g["ring"]
        x, spec = _term_operand(op, axis, ring, g["nbytes"])
        body = _collective_body(op, axis)
        out_spec = P() if op in ("all_reduce", "all_gather") else spec
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                                   out_specs=out_spec, check_vma=False))
        with jax.set_mesh(mesh):
            xd = jax.device_put(
                x, jax.sharding.NamedSharding(mesh, spec))
            for _ in range(max(warmup, 1)):
                jax.block_until_ready(fn(xd))
            best = math.inf
            for _ in range(max(iters, 1)):
                t0 = clock()
                jax.block_until_ready(fn(xd))
                best = min(best, clock() - t0)
        out[key] = {**{k: g[k] for k in ("op", "axis", "tensor",
                                         "ring", "count")},
                    "bytes": g["nbytes"] * g["count"],
                    "ms_per_call": best * 1e3,
                    "ms": best * 1e3 * g["count"]}
    return out


# ---------------------------------------------------------------------------
# The table
# ---------------------------------------------------------------------------

@dataclass
class TermRow:
    """One line of the measured-vs-predicted attribution table."""
    term: str
    predicted_ms: float
    measured_ms: Optional[float] = None
    count: int = 1
    nbytes: float = 0.0
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def residual_ms(self) -> Optional[float]:
        if self.measured_ms is None:
            return None
        return self.measured_ms - self.predicted_ms

    @property
    def ratio(self) -> Optional[float]:
        if self.measured_ms is None or self.predicted_ms <= 0:
            return None
        return self.measured_ms / self.predicted_ms

    def to_dict(self) -> Dict[str, Any]:
        return {"term": self.term, "predicted_ms": self.predicted_ms,
                "measured_ms": self.measured_ms,
                "residual_ms": self.residual_ms, "ratio": self.ratio,
                "count": self.count, "bytes": self.nbytes,
                **self.attrs}


def attribution_table(predicted: Mapping[str, Mapping[str, Any]],
                      measured: Optional[Mapping[str, Mapping[str, Any]]]
                      = None, *,
                      compute_ms: Optional[float] = None,
                      measured_compute_ms: Optional[float] = None
                      ) -> List[TermRow]:
    """Join predicted and measured per-term milliseconds into rows.

    ``predicted`` / ``measured`` are the dicts of ``predicted_terms`` /
    ``measure_collective_terms`` (keys ``op/axis/tensor``). The compute
    term rides along when given — predicted compute *is* the measured
    single-device probe by the model's definition, so its predicted
    column defaults to the measured value unless a fitted
    ``compute_ms`` is supplied. Terms only one side knows stay in the
    table with the other column empty — a missing term is a finding,
    not an error."""
    rows: List[TermRow] = []
    if measured_compute_ms is not None or compute_ms is not None:
        pred_c = compute_ms if compute_ms is not None \
            else measured_compute_ms
        rows.append(TermRow(TERM_COMPUTE, float(pred_c),
                            measured_compute_ms,
                            attrs={"kind": "compute"}))
    measured = measured or {}
    for key in sorted(set(predicted) | set(measured)):
        p = predicted.get(key)
        m = measured.get(key)
        src = p or m or {}
        rows.append(TermRow(
            term=key,
            predicted_ms=float(p["ms"]) if p else 0.0,
            measured_ms=(None if m is None else float(m["ms"])),
            count=int(src.get("count", 1)),
            nbytes=float(src.get("bytes", 0.0)),
            attrs={"kind": "comm", "op": src.get("op", ""),
                   "axis": src.get("axis", ""),
                   "ring": src.get("ring", 0)}))
    return rows


def _fmt_ms(v: Optional[float]) -> str:
    return "—" if v is None else f"{v:.3f}"


def render_markdown(rows: Sequence[TermRow], *, title: str = "") -> str:
    """The attribution table as GitHub markdown."""
    lines: List[str] = []
    if title:
        lines += [f"#### {title}", ""]
    lines += ["| term | count | bytes | predicted ms | measured ms "
              "| residual ms | meas/pred |",
              "|---|---:|---:|---:|---:|---:|---:|"]
    for r in rows:
        ratio = "—" if r.ratio is None else f"{r.ratio:.2f}×"
        nb = "—" if r.nbytes <= 0 else f"{int(r.nbytes):,}"
        lines.append(f"| `{r.term}` | {r.count} | {nb} "
                     f"| {_fmt_ms(r.predicted_ms)} "
                     f"| {_fmt_ms(r.measured_ms)} "
                     f"| {_fmt_ms(r.residual_ms)} | {ratio} |")
    tot_p = sum(r.predicted_ms for r in rows)
    meas = [r.measured_ms for r in rows if r.measured_ms is not None]
    tot_m = sum(meas) if meas else None
    lines.append(f"| **total** |  |  | **{_fmt_ms(tot_p)}** "
                 f"| **{_fmt_ms(tot_m)}** "
                 f"| **{_fmt_ms(None if tot_m is None else tot_m - tot_p)}**"
                 f" |  |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Span coverage (the attribution-sum invariant)
# ---------------------------------------------------------------------------

def span_coverage(spans: Sequence, parent_name: str,
                  ) -> Dict[str, Any]:
    """How much of each ``parent_name`` span its children account for.

    Returns per-child-name total milliseconds plus ``coverage`` =
    Σ children / Σ parents over all closed instances. Instrumented
    phases must *partition* their step (tests pin coverage within
    tolerance of 1.0): time no child claims is time attribution
    cannot see."""
    parents = [s for s in spans
               if s.name == parent_name and s.t_end is not None]
    ids = {s.span_id for s in parents}
    child_ms: Dict[str, float] = {}
    child_total = 0.0
    for s in spans:
        if s.parent_id in ids and s.t_end is not None:
            ms = s.duration_s * 1e3
            child_ms[s.name] = child_ms.get(s.name, 0.0) + ms
            child_total += ms
    parent_ms = sum(s.duration_s for s in parents) * 1e3
    return {"parent": parent_name, "n": len(parents),
            "parent_ms": parent_ms, "children_ms": child_ms,
            "children_total_ms": child_total,
            "coverage": (child_total / parent_ms if parent_ms > 0
                         else None)}


# ---------------------------------------------------------------------------
# Drift detection
# ---------------------------------------------------------------------------

@dataclass
class DriftReport:
    """Which terms drifted outside the calibration-time error band."""
    band_ms: float
    rel_tol: float
    flagged: List[Dict[str, Any]] = field(default_factory=list)
    calibration_label: str = "default"

    @property
    def refit_recommended(self) -> bool:
        return bool(self.flagged)

    @property
    def message(self) -> str:
        if not self.flagged:
            return (f"all terms within the calibration band "
                    f"(±{self.band_ms:.3f} ms or ±{self.rel_tol:.0%}) of "
                    f"{self.calibration_label!r}")
        names = ", ".join(f["term"] for f in self.flagged)
        return (f"{len(self.flagged)} term(s) drifted beyond the "
                f"calibration band (±{self.band_ms:.3f} ms and "
                f"±{self.rel_tol:.0%}) of {self.calibration_label!r}: "
                f"{names} — refit recommended; {REGEN_HINT}")

    def to_dict(self) -> Dict[str, Any]:
        return {"band_ms": self.band_ms, "rel_tol": self.rel_tol,
                "calibration": self.calibration_label,
                "flagged": list(self.flagged),
                "refit_recommended": self.refit_recommended,
                "message": self.message}


def detect_drift(rows: Sequence[TermRow],
                 calibration: Optional[Calibration] = None, *,
                 band_factor: float = 2.0, floor_ms: float = 0.25,
                 rel_tol: float = 0.5) -> DriftReport:
    """Flag terms whose live residual exceeds the calibration-time band.

    The band is ``band_factor ×`` the fit's own residual MAE
    (``meta["mae_ms_fitted"]``, what the calibration admits it cannot
    explain), floored at ``floor_ms`` for noise on a timeshared pool. A
    term drifts only if it misses the band *and* the relative tolerance
    — both gates, so microsecond terms are not flagged on jitter and
    large terms are not excused by a loose absolute band. Uncalibrated
    runs (label ``"default"``, no fitted MAE) use the floor, so the
    fail-soft path still produces a drift verdict."""
    if calibration is None:
        calibration = load_calibration()
    mae = calibration.meta.get("mae_ms_fitted") if calibration.meta else None
    band_ms = max(band_factor * float(mae), floor_ms) \
        if mae is not None else floor_ms
    flagged: List[Dict[str, Any]] = []
    for r in rows:
        if r.measured_ms is None:
            continue
        resid = abs(r.residual_ms)
        if resid > band_ms and resid > rel_tol * max(r.predicted_ms, 1e-9):
            flagged.append({"term": r.term,
                            "predicted_ms": r.predicted_ms,
                            "measured_ms": r.measured_ms,
                            "residual_ms": r.residual_ms,
                            "band_ms": band_ms})
    return DriftReport(band_ms=band_ms, rel_tol=rel_tol, flagged=flagged,
                       calibration_label=calibration.label)
