"""Observability layer: spans, metrics, export, attribution, drift.

Tier-1 (single device): the recorder/metrics/export mechanics are pure
host code and test deterministically with an injectable clock; the
attribution math is exercised against hand-built predicted/measured
dicts (the live multi-device measurement path is covered by
``benchmarks.trace_report`` and ``tools/obs_smoke.py``).
"""
import json
import time

import pytest

from repro.obs import (LAYER_SCOPES, CompileCounts, Metrics, Recorder,
                       StragglerMonitor, TermRow, attribution_table,
                       collective_bytes, compile_counts, current_recorder,
                       detect_drift, observe_step, predicted_step_ms,
                       predicted_terms, read_jsonl, render_markdown,
                       set_recorder, span_coverage, straggler_skew,
                       trace_lines, use_recorder, write_jsonl)
from repro.perf.costmodel import Calibration, LinkParams, ScheduleInputs


class FakeClock:
    """Deterministic clock: each call advances by ``tick`` seconds."""

    def __init__(self, tick=0.001):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


# ---------------------------------------------------------------------------
# Recorder / spans
# ---------------------------------------------------------------------------

def test_span_nesting_ids_and_depths():
    rec = Recorder(clock=FakeClock())
    with rec.span("step", category="train", step_num=0):
        with rec.span("data"):
            pass
        with rec.span("dispatch"):
            with rec.span("inner"):
                pass
    assert rec.open_spans == 0
    step = rec.find("step")[0]
    assert step.parent_id is None and step.depth == 0
    data, dispatch = rec.find("data")[0], rec.find("dispatch")[0]
    assert data.parent_id == step.span_id and data.depth == 1
    assert dispatch.parent_id == step.span_id
    inner = rec.find("inner")[0]
    assert inner.parent_id == dispatch.span_id and inner.depth == 2
    assert {s.name for s in rec.children_of(step)} == {"data", "dispatch"}
    # spans close inner-first; every span has an end after its start
    assert all(s.t_end > s.t_start for s in rec.spans)


def test_span_exception_unwinds_and_records_error():
    rec = Recorder(clock=FakeClock())
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise ValueError("boom")
    assert rec.open_spans == 0
    assert all(s.t_end is not None for s in rec.spans)
    assert "error" in rec.find("inner")[0].attrs
    assert "error" in rec.find("outer")[0].attrs


def test_disabled_recorder_records_nothing():
    rec = Recorder(enabled=False)
    with rec.span("step", step_num=3) as sp:
        sp.set(ms=1.0)
        assert sp.sync(42) == 42      # identity, no jax import
    rec.event("straggler", step=3)
    assert rec.spans == [] and rec.events == []
    assert rec.open_spans == 0


def test_disabled_recorder_overhead_bound():
    """The disabled hot path must stay within single-digit microseconds
    per span — the 'zero overhead when disabled' contract, bounded
    absolutely so a loaded CI host cannot flake a relative check."""
    rec = Recorder(enabled=False)
    n = 20_000
    # warm the path, then time n span enter/exits with attrs
    for _ in range(100):
        with rec.span("step", category="train", step_num=0):
            pass
    t0 = time.perf_counter()
    for i in range(n):
        with rec.span("step", category="train", step_num=i):
            pass
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    assert per_span_us < 25.0, f"{per_span_us:.2f}µs per disabled span"


def test_traced_decorator_and_events():
    rec = Recorder(clock=FakeClock())

    @rec.traced("fit", category="calib")
    def f(x):
        rec.event("mark", x=x)
        return x + 1

    assert f(1) == 2
    span = rec.find("fit")[0]
    assert span.category == "calib"
    assert rec.events[0]["name"] == "mark"
    assert rec.events[0]["parent_id"] == span.span_id


def test_current_recorder_default_disabled_and_scoped_install():
    assert current_recorder().enabled is False
    rec = Recorder(clock=FakeClock())
    with use_recorder(rec):
        assert current_recorder() is rec
        with current_recorder().span("trial"):
            pass
    assert current_recorder().enabled is False
    assert rec.find("trial")
    old = set_recorder(rec)
    try:
        assert current_recorder() is rec
    finally:
        set_recorder(old)


def test_sync_policy_boundary_blocks():
    import jax.numpy as jnp
    rec = Recorder(sync_policy="boundary")
    with rec.span("dispatch") as sp:
        out = sp.sync(jnp.ones((4,)) * 2)
    assert float(out.sum()) == 8.0
    with pytest.raises(ValueError):
        Recorder(sync_policy="bogus")


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_metrics_registry_and_kinds():
    m = Metrics()
    m.counter("steps").inc()
    m.counter("steps").inc(2)
    m.gauge("lr").set(0.1)
    h = m.histogram("ms")
    for v in (1.0, 2.0, 3.0, 10.0):
        h.observe(v)
    d = m.to_dict()
    assert d["steps"]["value"] == 3
    assert d["lr"]["value"] == 0.1
    assert d["ms"]["count"] == 4 and d["ms"]["mean"] == 4.0
    assert h.median in (2.0, 3.0) and h.percentile(100) == 10.0
    assert h.percentile(0) == 1.0
    with pytest.raises(TypeError):
        m.gauge("steps")          # kind collision is an error


def test_observe_step_throughput_units():
    m = Metrics()
    observe_step(m, seconds=0.5, batch=8, seq=32)
    d = m.to_dict()
    assert d["steps"]["value"] == 1
    assert d["samples"]["value"] == 8
    assert d["tokens"]["value"] == 8 * 32
    assert d["samples_per_s"]["value"] == pytest.approx(16.0)
    assert d["tokens_per_s"]["value"] == pytest.approx(512.0)
    assert d["step_time_ms"]["count"] == 1


def test_straggler_skew():
    assert straggler_skew([]) == 1.0
    assert straggler_skew([0.1]) == 1.0
    assert straggler_skew([0.1, 0.1, 0.1, 0.3]) == pytest.approx(3.0)


def test_straggler_monitor_emits_structured_event():
    from repro.train.ft import StragglerDetector
    rec = Recorder(clock=FakeClock())
    m = Metrics()
    mon = StragglerMonitor(StragglerDetector(tolerance=1.5),
                           metrics=m, recorder=rec)
    flagged = []
    for step, s in enumerate([0.1] * 8 + [0.9]):
        flagged.append(mon.observe(step, s))
    assert flagged[-1] and not any(flagged[:-1])
    assert m.to_dict()["straggler_flags"]["value"] == 1
    assert m.to_dict()["straggler_skew"]["value"] > 1.0
    ev = [e for e in rec.events if e["name"] == "straggler"][0]
    assert ev["attrs"]["step"] == 8
    assert ev["attrs"]["seconds"] == pytest.approx(0.9)
    assert ev["attrs"]["expected_s"] is not None
    assert ev["attrs"]["tolerance"] == pytest.approx(1.5)


def test_collective_bytes_terms_match_schedules():
    per = collective_bytes("dp", 8, 1000)
    assert set(per) == {"all_reduce/data/grad"}
    assert per["all_reduce/data/grad"] > 0
    # wire compression halves the payload
    half = collective_bytes("dp", 8, 1000, wire_bits=16)
    assert half["all_reduce/data/grad"] == pytest.approx(
        per["all_reduce/data/grad"] / 2)
    both = collective_bytes("fsdp_tp", 8, 1000, act_bytes=500,
                            axes={"data": 4, "model": 2})
    assert {"all_gather/data/param", "reduce_scatter/data/grad",
            "all_reduce/model/act"} <= set(both)


# ---------------------------------------------------------------------------
# Export round-trips
# ---------------------------------------------------------------------------

def _sample_recorder():
    rec = Recorder(clock=FakeClock())
    with rec.span("step", category="train", step_num=0):
        with rec.span("dispatch"):
            pass
        rec.event("straggler", step=0, skew=2.0)
    return rec


def test_jsonl_round_trip(tmp_path):
    rec = _sample_recorder()
    m = Metrics()
    m.counter("steps").inc()
    p = tmp_path / "trace.jsonl"
    write_jsonl(p, rec, metrics=m.to_dict(), meta={"arch": "lenet5"})
    data = read_jsonl(p)
    assert [s.to_dict() for s in data.spans] == \
        [s.to_dict() for s in rec.spans]
    assert data.events[0]["name"] == "straggler"
    assert data.metrics["steps"]["value"] == 1
    assert data.meta == {"arch": "lenet5"}
    step = data.find("step")[0]
    assert [c.name for c in data.children_of(step)] == ["dispatch"]
    # every line is standalone JSON (the format contract)
    for line in trace_lines(rec):
        json.loads(line)


def test_spans_on_the_profiler_host_plane(tmp_path):
    """An enabled recorder's spans land by name on the host plane of a
    jax.profiler trace, on the clock the device events use."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    rec = Recorder()
    x = jnp.ones((8,))
    with jax.profiler.trace(str(tmp_path)):
        for i in range(2):
            with rec.span("step", category="train", step_num=i):
                with rec.span("data"):
                    y = x + i
                with rec.span("dispatch"):
                    y = y * 2
                with rec.span("wait"):
                    jax.block_until_ready(y)
    paths = list(tmp_path.rglob("*.xplane.pb"))
    assert len(paths) == 1
    names = [e.name for p in ProfileData.from_file(str(paths[0])).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events]
    for span in ("step", "data", "dispatch", "wait"):
        assert names.count(span) == 2, span


# ---------------------------------------------------------------------------
# Compile counter and layer scopes
# ---------------------------------------------------------------------------

def test_compile_counter_counts_one_compile_per_shape():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2 + 1)
    a, b = jax.block_until_ready((jnp.ones((3,)), jnp.ones((5,))))
    c0 = compile_counts()
    f(a)
    f(a)
    c1 = compile_counts()
    f(b)
    c2 = compile_counts()
    assert (c1 - c0).compiles == 1 and (c1 - c0).compile_s > 0
    assert (c2 - c1).programs == 1
    assert (c2 - c0) == (c2 - c1) + (c1 - c0)
    assert (c2 - c0).to_dict()["cache_loads"] == 0


def test_compile_counter_tells_cache_loads_from_compiles(tmp_path):
    """A program found in the persistent cache counts as a load."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    try:
        f = jax.jit(lambda x: jnp.sin(x) * 3 - 1)
        x = jax.block_until_ready(jnp.ones((7,)))
        c0 = compile_counts()
        f(x)
        c1 = compile_counts()
        jax.clear_caches()          # forget the compiled program in memory
        f(x)
        c2 = compile_counts()
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert (c1 - c0).compiles == 1 and (c1 - c0).cache_loads == 0
    assert (c2 - c1).compiles == 0 and (c2 - c1).cache_loads == 1
    assert (c2 - c1).cache_load_s > 0


def test_compile_lands_as_an_event_in_the_open_span():
    import jax
    import jax.numpy as jnp
    x = jax.block_until_ready(jnp.ones((11,)))
    rec = Recorder(clock=FakeClock())
    with use_recorder(rec):
        with rec.span("step", step_num=0):
            jax.jit(lambda v: v - 3)(x)
    step = rec.find("step")[0]
    ev = [e for e in rec.events if e["name"] == "compile"]
    assert ev and all(e["parent_id"] == step.span_id for e in ev)
    assert ev[-1]["attrs"]["kind"] == "backend"
    assert ev[-1]["attrs"]["seconds"] > 0
    # a disabled current recorder records nothing; the counter still counts
    c0 = compile_counts()
    jax.jit(lambda v: v - 4)(x)
    assert (compile_counts() - c0).compiles == 1
    assert current_recorder().events == []
    assert CompileCounts().programs == 0


def test_layer_scopes_name_the_train_step():
    """A 2-layer reduced smollm train step with remat compiled on the CPU:
    every matmul carries a layer scope, attention and mlp have both a
    forward and a backward, remat's recompute counts as forward, and the
    embedding, head and optimizer are scoped."""
    import dataclasses
    import re
    import jax
    import jax.numpy as jnp
    from bench.trace.scopes import layer_keys, layer_of
    from repro.configs import TrainConfig, get_config, reduced
    from repro.train.step import init_train_state, make_train_step
    cfg = dataclasses.replace(reduced(get_config("smollm-360m")),
                              n_layers=2)
    tcfg = TrainConfig(remat_policy="full")
    state = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    txt = jax.jit(make_train_step(cfg, tcfg)).lower(
        state, batch).compile().as_text()
    keys = layer_keys(txt, LAYER_SCOPES)
    dots = re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \S+ dot\(", txt,
                      re.M)
    assert len(dots) >= 10
    assert all(keys[d] is not None for d in dots), \
        [d for d in dots if keys.get(d) is None]
    assert {("attention", "fwd"), ("attention", "bwd"), ("mlp", "fwd"),
            ("mlp", "bwd"), ("embed", "fwd"), ("embed", "bwd"),
            ("head", "fwd"), ("head", "bwd"),
            ("optimizer", "fwd")} <= set(keys.values())
    recompute = [n for n in re.findall(r'op_name="([^"]*)"', txt)
                 if "rematted_computation" in n and "/attention/" in n]
    assert recompute and {layer_of(n, LAYER_SCOPES)
                          for n in recompute} == {("attention", "fwd")}


# ---------------------------------------------------------------------------
# Attribution
# ---------------------------------------------------------------------------

def _calib(mae_ms=1.0, rho=0.0):
    link = LinkParams(alpha_s=1e-5, bw_bytes_per_s=1e9)
    return Calibration(label="test", default=link,
                       overlap={"dp": rho},
                       meta={"mae_ms_fitted": mae_ms})


def test_predicted_terms_and_step_decomposition():
    cal = _calib(rho=0.5)
    inp = ScheduleInputs(n_devices=8, param_bytes=1 << 20)
    terms = predicted_terms("dp", inp, calibration=cal)
    assert set(terms) == {"all_reduce/data/grad"}
    t = terms["all_reduce/data/grad"]
    assert t["ms"] > 0 and t["count"] == 1 and t["bytes"] > 0
    dec = predicted_step_ms("dp", inp, compute_ms=10.0, calibration=cal)
    assert dec["comm_ms"] == pytest.approx(t["ms"])
    assert dec["exposed_comm_ms"] == pytest.approx(
        max(0.0, dec["comm_ms"] - 0.5 * 10.0))
    assert dec["total_ms"] == pytest.approx(10.0 + dec["exposed_comm_ms"])
    # enough compute hides all comm
    dec2 = predicted_step_ms("dp", inp, compute_ms=1e6, calibration=cal)
    assert dec2["exposed_comm_ms"] == 0.0


def test_attribution_table_union_and_sum():
    pred = {"all_reduce/data/grad": {"op": "all_reduce", "axis": "data",
                                     "tensor": "grad", "ring": 8,
                                     "bytes": 100.0, "count": 1,
                                     "ms": 2.0},
            "all_gather/data/param": {"op": "all_gather", "axis": "data",
                                      "tensor": "param", "ring": 8,
                                      "bytes": 50.0, "count": 2,
                                      "ms": 1.0}}
    meas = {"all_reduce/data/grad": {"op": "all_reduce", "axis": "data",
                                     "tensor": "grad", "ring": 8,
                                     "bytes": 100.0, "count": 1,
                                     "ms": 1.5},
            "all_to_all/data/act": {"op": "all_to_all", "axis": "data",
                                    "tensor": "act", "ring": 8,
                                    "bytes": 10.0, "count": 1,
                                    "ms": 0.5}}
    rows = attribution_table(pred, meas, measured_compute_ms=4.0)
    by_term = {r.term: r for r in rows}
    # compute rides first; predicted defaults to the measured probe
    assert rows[0].term == "compute"
    assert rows[0].predicted_ms == rows[0].measured_ms == 4.0
    r = by_term["all_reduce/data/grad"]
    assert r.residual_ms == pytest.approx(-0.5)
    assert r.ratio == pytest.approx(0.75)
    # terms only one side knows survive with the other column empty
    assert by_term["all_gather/data/param"].measured_ms is None
    assert by_term["all_to_all/data/act"].predicted_ms == 0.0
    md = render_markdown(rows, title="t")
    assert "| `compute` |" in md and "**total**" in md
    # attribution-sum: the total row is the column sums
    tot_p = sum(r.predicted_ms for r in rows)
    assert f"**{tot_p:.3f}**" in md


def test_span_coverage_partition_invariant():
    rec = Recorder(clock=FakeClock(tick=1.0))
    for i in range(3):
        with rec.span("step", step_num=i):     # 6 ticks each
            with rec.span("data"):             # 1 tick
                pass
            with rec.span("dispatch"):         # 1 tick
                pass
    cov = span_coverage(rec.spans, "step")
    assert cov["n"] == 3
    # fake clock: every span is open-tick→close-tick = 1s = 1000ms
    assert cov["children_ms"]["data"] == pytest.approx(3 * 1000.0)
    assert cov["coverage"] == pytest.approx(
        cov["children_total_ms"] / cov["parent_ms"])
    assert 0.0 < cov["coverage"] <= 1.0
    assert span_coverage(rec.spans, "absent")["coverage"] is None


def test_detect_drift_band_and_relative_gates():
    cal = _calib(mae_ms=1.0)                    # band = 2×1.0 = 2ms
    rows = [
        TermRow("compute", 10.0, 10.1),                  # tiny residual
        TermRow("all_reduce/data/grad", 10.0, 11.0),     # inside band
        TermRow("all_gather/data/param", 1.0, 3.5),      # fails both
        TermRow("reduce_scatter/data/grad", 0.001, 0.9),  # < band: ok
        TermRow("all_to_all/data/act", 100.0, 103.0),    # > band, < 50%
        TermRow("unmeasured/x/y", 5.0, None),            # skipped
    ]
    rep = detect_drift(rows, cal)
    assert rep.band_ms == pytest.approx(2.0)
    assert [f["term"] for f in rep.flagged] == ["all_gather/data/param"]
    assert rep.refit_recommended and "refit recommended" in rep.message
    assert "regenerate" in rep.message          # carries REGEN_HINT
    # fail-soft: an unfitted calibration still produces a verdict via
    # the floor band
    from repro.perf.costmodel import DEFAULT_CALIBRATION
    rep2 = detect_drift(rows, DEFAULT_CALIBRATION)
    assert rep2.band_ms == pytest.approx(0.25)
    assert {f["term"] for f in rep2.flagged} >= {"all_gather/data/param"}
    clean = detect_drift([TermRow("compute", 10.0, 10.1)], cal)
    assert not clean.refit_recommended
