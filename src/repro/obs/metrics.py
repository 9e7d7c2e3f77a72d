"""Counters / gauges / histograms + the model-derived metric helpers.

The metrics half of the observability layer is deliberately tiny and
dependency-free: a ``Metrics`` registry of three instrument kinds, plus
helpers that derive the metrics the performance model itself speaks in —
per-collective bytes from the calibrated schedules, device memory
watermarks via ``Device.memory_stats()``, throughput in the sweep's own
normalization units (samples/sec, tokens/sec), and straggler skew.

``compile_counts`` snapshots a process-wide counter of backend compiles
and persistent-cache loads, fed by one ``jax.monitoring`` listener; the
difference of two snapshots counts what was compiled between them.

``StragglerMonitor`` is the live wiring of ``repro.train.ft.
StragglerDetector``: it feeds the detector every measured step time,
keeps the straggler-skew gauge current, and emits a
*structured* straggler event (step, measured, expected, tolerance)
through the recorder when the detector trips — instead of the train
driver's former bare log line.
"""
from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

from repro.obs.trace import Recorder, current_recorder


@dataclass
class Counter:
    name: str
    value: float = 0.0

    def inc(self, v: float = 1.0) -> None:
        self.value += v

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": "counter", "value": self.value}


@dataclass
class Gauge:
    name: str
    value: Optional[float] = None

    def set(self, v: float) -> None:
        self.value = float(v)

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": "gauge", "value": self.value}


@dataclass
class Histogram:
    """Exact small-run histogram: keeps every observation.

    Runs here are thousands of steps at most; keeping the raw values
    makes percentiles exact and the export trivially replayable. Set
    ``max_samples`` to cap memory on very long runs (oldest dropped,
    count/total stay exact)."""
    name: str
    max_samples: int = 100_000
    values: List[float] = field(default_factory=list)
    count: int = 0
    total: float = 0.0

    def observe(self, v: float) -> None:
        self.count += 1
        self.total += v
        self.values.append(float(v))
        if len(self.values) > self.max_samples:
            del self.values[:len(self.values) - self.max_samples]

    @property
    def mean(self) -> Optional[float]:
        return self.total / self.count if self.count else None

    def percentile(self, p: float) -> Optional[float]:
        if not self.values:
            return None
        h = sorted(self.values)
        idx = min(int(round((p / 100.0) * (len(h) - 1))), len(h) - 1)
        return h[idx]

    @property
    def median(self) -> Optional[float]:
        return self.percentile(50.0)

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": "histogram", "count": self.count,
                "mean": self.mean, "p50": self.median,
                "p95": self.percentile(95.0),
                "min": min(self.values) if self.values else None,
                "max": max(self.values) if self.values else None}


class Metrics:
    """Get-or-create registry; one namespace per run."""

    def __init__(self):
        self._by_name: Dict[str, Any] = {}

    def _get(self, name: str, kind, **kw):
        inst = self._by_name.get(name)
        if inst is None:
            inst = kind(name=name, **kw)
            self._by_name[name] = inst
        elif not isinstance(inst, kind):
            raise TypeError(f"metric {name!r} already registered as "
                            f"{type(inst).__name__}")
        return inst

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str, **kw) -> Histogram:
        return self._get(name, Histogram, **kw)

    def to_dict(self) -> Dict[str, Dict[str, Any]]:
        return {name: m.to_dict() for name, m in
                sorted(self._by_name.items())}


# ---------------------------------------------------------------------------
# Compile counter
# ---------------------------------------------------------------------------

# JAX times every backend compile request with this event, a persistent-
# cache hit included; a hit records CACHE_HIT first, in the same thread.
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass(frozen=True)
class CompileCounts:
    """Backend compiles and persistent-cache loads, with their seconds;
    ``later - earlier`` of two snapshots counts what happened between."""
    compiles: int = 0
    compile_s: float = 0.0
    cache_loads: int = 0
    cache_load_s: float = 0.0

    def __sub__(self, other: "CompileCounts") -> "CompileCounts":
        return CompileCounts(*(a - b for a, b in zip(
            dataclasses.astuple(self), dataclasses.astuple(other))))

    def __add__(self, other: "CompileCounts") -> "CompileCounts":
        return CompileCounts(*(a + b for a, b in zip(
            dataclasses.astuple(self), dataclasses.astuple(other))))

    @property
    def programs(self) -> int:
        """Programs made ready to run: compiled or loaded."""
        return self.compiles + self.cache_loads

    @property
    def seconds(self) -> float:
        return self.compile_s + self.cache_load_s

    def to_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


class _CompileListener:
    """The ``jax.monitoring`` listener behind ``compile_counts``. It also
    records a ``compile`` event in the open span of the current recorder
    (a no-op while that recorder is disabled)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._hit = threading.local()
        self.counts = CompileCounts()

    def on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT:
            self._hit.pending = True

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        if event != BACKEND_COMPILE:
            return
        loaded = getattr(self._hit, "pending", False)
        self._hit.pending = False
        with self._lock:
            c = self.counts
            self.counts = (dataclasses.replace(
                c, cache_loads=c.cache_loads + 1,
                cache_load_s=c.cache_load_s + seconds) if loaded else
                dataclasses.replace(c, compiles=c.compiles + 1,
                                    compile_s=c.compile_s + seconds))
        current_recorder().event(
            "compile", kind="cache_load" if loaded else "backend",
            seconds=float(seconds), fun=str(kw.get("fun_name", "")))


_listener: Optional[_CompileListener] = None
_listener_lock = threading.Lock()


def compile_counts() -> CompileCounts:
    """A snapshot of the process's compile counter.

    The first call registers the listener (once per process: JAX's
    listeners are process-wide), so what compiled before it is not
    counted; a caller that wants set-up counted calls this first."""
    global _listener
    with _listener_lock:
        if _listener is None:
            from jax import monitoring
            _listener = _CompileListener()
            monitoring.register_event_listener(_listener.on_event)
            monitoring.register_event_duration_secs_listener(
                _listener.on_duration)
        return _listener.counts


# ---------------------------------------------------------------------------
# Model-derived metric helpers
# ---------------------------------------------------------------------------

def observe_step(metrics: Metrics, *, seconds: float, batch: int,
                 seq: Optional[int] = None) -> None:
    """One training step's worth of throughput metrics: step-time
    histogram plus samples/sec (and tokens/sec when ``seq`` is known) —
    the same work units the sweep's fit targets normalize by
    (``repro.perf.sweep.REF_SAMPLES`` / ``REF_TOKENS``)."""
    metrics.histogram("step_time_ms").observe(seconds * 1e3)
    metrics.counter("steps").inc()
    metrics.counter("samples").inc(batch)
    metrics.gauge("samples_per_s").set(batch / max(seconds, 1e-12))
    if seq is not None:
        metrics.counter("tokens").inc(batch * seq)
        metrics.gauge("tokens_per_s").set(
            batch * seq / max(seconds, 1e-12))


def collective_bytes(strategy, n_devices: int, param_bytes: int, *,
                     wire_bits: int = 32, act_bytes: int = 0,
                     axes: Optional[Dict[str, int]] = None
                     ) -> Dict[str, float]:
    """Per-collective payload bytes of one training iteration, derived
    from the calibrated schedule layer — keyed ``op/axis/tensor`` (the
    same term keys ``repro.obs.attribution`` aligns measurements to)."""
    from repro.perf.costmodel import ScheduleInputs, build_schedule

    inp = ScheduleInputs(n_devices=n_devices, param_bytes=param_bytes,
                         wire_bits=wire_bits, act_bytes=act_bytes)
    out: Dict[str, float] = {}
    for call in build_schedule(strategy, inp, axes=axes):
        key = f"{call.op}/{call.axis}/{call.tensor}"
        out[key] = out.get(key, 0.0) + float(call.nbytes)
    return out


def record_collective_bytes(metrics: Metrics, strategy, n_devices: int,
                            param_bytes: int, **kw) -> Dict[str, float]:
    """``collective_bytes`` written into per-term counters
    (``comm_bytes/<op>/<axis>/<tensor>``) as per-step increments."""
    per_term = collective_bytes(strategy, n_devices, param_bytes, **kw)
    for key, nbytes in per_term.items():
        metrics.counter(f"comm_bytes/{key}").inc(nbytes)
    return per_term


def device_memory_watermarks(devices: Optional[Sequence] = None
                             ) -> Dict[str, Dict[str, int]]:
    """Per-device ``memory_stats()`` watermarks, fail-soft.

    Accelerator backends report ``bytes_in_use`` / ``peak_bytes_in_use``;
    CPU placeholder devices typically return ``None`` or raise — those
    devices are simply absent from the result, so instrumented code can
    call this unconditionally on any host."""
    import jax

    out: Dict[str, Dict[str, int]] = {}
    for d in (devices if devices is not None else jax.devices()):
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        keep = {k: int(v) for k, v in stats.items()
                if k in ("bytes_in_use", "peak_bytes_in_use",
                         "bytes_limit", "largest_alloc_size")}
        if keep:
            out[str(d)] = keep
    return out


def record_memory_watermarks(metrics: Metrics,
                             devices: Optional[Sequence] = None
                             ) -> Dict[str, Dict[str, int]]:
    """Watermarks written into gauges (max across devices)."""
    marks = device_memory_watermarks(devices)
    if marks:
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            vals = [m[key] for m in marks.values() if key in m]
            if vals:
                metrics.gauge(f"memory/{key}_max").set(max(vals))
    return marks


def record_recovery(metrics: Metrics, recovery: Dict) -> None:
    """The driver's measured recovery breakdown written into gauges.

    ``recovery`` is the dict ``repro.launch.train`` assembles after a
    drill (plan_s / compile_s / restore_s / first_step_s / recovery_s);
    each present term lands in a ``recovery/<term>_ms`` gauge so traces
    carry the same breakdown benchmarks/ELASTIC.md tabulates, plus a
    ``recoveries`` counter and a ``recovery/steps_replayed`` gauge."""
    metrics.counter("recoveries").inc()
    for term in ("plan_s", "compile_s", "restore_s", "first_step_s",
                 "recovery_s"):
        v = recovery.get(term)
        if v is not None:
            metrics.gauge(f"recovery/{term[:-2]}_ms").set(float(v) * 1e3)
    if recovery.get("steps_replayed") is not None:
        metrics.gauge("recovery/steps_replayed").set(
            float(recovery["steps_replayed"]))


def straggler_skew(step_seconds: Sequence[float]) -> float:
    """max/median step-time ratio over a window — 1.0 means no skew.

    On a single-controller pool every step is a global barrier, so a
    straggling device shows up as a slow *step*; the skew of the recent
    step-time distribution is the observable proxy."""
    vals = [float(v) for v in step_seconds if v > 0]
    if len(vals) < 2:
        return 1.0
    h = sorted(vals)
    med = h[len(h) // 2]
    return h[-1] / max(med, 1e-12)


class StragglerMonitor:
    """Feeds measured step times to ``ft.StragglerDetector`` through the
    metrics layer and emits a structured event when it trips.

    The detector keeps its predictor-exposed threshold semantics
    (fitted-model expectation when available, running median otherwise);
    this class is the wiring the train loop was missing: every observed
    step updates the skew gauge AND the detector, and a trip
    becomes a machine-readable ``straggler`` event on the recorder
    (step, measured seconds, the expectation that was exceeded, and the
    tolerance), not just a console flag."""

    def __init__(self, detector, metrics: Optional[Metrics] = None,
                 recorder: Optional[Recorder] = None,
                 skew_window: int = 32):
        self.detector = detector
        self.metrics = metrics if metrics is not None else Metrics()
        self._recorder = recorder
        self.skew_window = skew_window

    @property
    def recorder(self) -> Recorder:
        return (self._recorder if self._recorder is not None
                else current_recorder())

    @property
    def flags(self) -> List[int]:
        return self.detector.flags

    def observe(self, step: int, seconds: float) -> bool:
        expected = self.detector.expected()     # pre-observe: the value
        flagged = self.detector.observe(step, seconds)  # the trip used
        self.metrics.gauge("straggler_skew").set(straggler_skew(
            self.detector.history[-self.skew_window:]))
        if flagged:
            self.metrics.counter("straggler_flags").inc()
            self.recorder.event(
                "straggler", step=int(step), seconds=float(seconds),
                expected_s=(None if expected is None else float(expected)),
                tolerance=float(self.detector.tolerance),
                skew=straggler_skew(
                    self.detector.history[-self.skew_window:]))
        return flagged
