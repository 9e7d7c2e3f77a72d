#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the accelerator it starts on.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``). Set-up makes the weights and the
batches from the seed, builds the program's train step and drives it
through its first steps, which the plain reference checks after the
window. The window then runs whole steps, each dispatched and waited
for, until ``--seconds`` have passed. ``--trace 1`` adds a few traced
steps after the window and reports the per-layer metrics
(``bench/metrics/<metric>.py``) instead of the end-to-end ones.

The last line of stdout is one JSON object; the numbers compared with
the reference, each with its limit, come last there and as the last
lines of stderr. With no TPU, or fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import List, Mapping, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CHECK_STEPS = 3      # first steps the reference follows (also the warm-up)
POOL = 16            # distinct batches the window cycles through
TRACE_STEPS = 3      # steps in the traced window


class CellError(Exception):
    """The cell cannot be run here; no result is printed."""


@dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    per_layer: list          # BENCHMARK.json per_layer entries for the cell
    end_to_end: list


def load_cell(root: str, name: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def applies(metric):
        return name in metric.get("workloads", [name])

    return Cell(w, config, traffic,
                [m for m in bench["per_layer"] if applies(m)],
                [m for m in bench["end_to_end"] if applies(m)])


@dataclass
class RunContext:
    """What a per-layer metric reader may read."""
    cell: Cell
    device_kind: str
    chips: int
    tokens_per_s: float
    dispatch_s: List[float]
    step_bytes: Optional[int]        # the compiled step's device bytes
    trace: Optional[object]          # bench.trace.reduce.Trace


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def step_once(prog, state, batch):
    import jax
    with jax.set_mesh(prog.mesh):
        return prog.step(state, batch)


def first_steps(prog, key, batches):
    """Drive a fresh state from the seed's weights through the checked
    steps. Returns the state and a callable that gives the readings: each
    loss, the first gradient (from AdamW's state after step 1) and each
    leaf's change after the last step. The weights before and after are
    copied to the host, so no second copy sits on the device beside the
    state."""
    import jax
    from bench.correct import Readings
    from bench.program import change_norms
    weights = prog.weights(key)
    before = jax.device_get(weights)
    state = prog.init(key, weights)
    losses, grads = [], None
    for i, batch in enumerate(batches):
        state, m = step_once(prog, state, batch)
        losses.append(float(m["loss"]))
        if i == 0:
            grads = jax.device_get(prog.grad_norms(state))
    after = jax.device_get(prog.params(state))

    def readings():
        return Readings(losses, {k: float(v) for k, v in grads.items()},
                        change_norms(after, before))
    return state, readings


def timed_window(prog, state, batches, seconds: float):
    """Whole steps until ``seconds`` have passed: dispatch, then block on
    that step's loss, every step. Returns the state, each step's loss and
    dispatch time, and the time since the start at which each ended."""
    import jax
    dispatch, losses, ends = [], [], []
    t_start = time.perf_counter()
    n = 0
    while True:
        t0 = time.perf_counter()
        state, m = step_once(prog, state, batches[n % len(batches)])
        dispatch.append(time.perf_counter() - t0)
        jax.block_until_ready(m["loss"])
        losses.append(float(m["loss"]))
        n += 1
        ends.append(time.perf_counter() - t_start)
        if ends[-1] >= seconds:
            return state, losses, dispatch, ends


def traced_steps(prog, state, batches, n: int):
    """``n`` steps under the profiler, each split into host spans."""
    import jax
    from bench.trace.reduce import Trace
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            for i in range(n):
                with jax.profiler.TraceAnnotation("prepare"):
                    batch = batches[i % len(batches)]
                with jax.profiler.TraceAnnotation("dispatch"):
                    state, m = step_once(prog, state, batch)
                with jax.profiler.TraceAnnotation("wait"):
                    jax.block_until_ready(m["loss"])
        finally:
            jax.profiler.stop_trace()
        paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp)
                 for f in fs if f.endswith(".xplane.pb")]
        if len(paths) != 1:
            raise CellError(f"expected one trace file, found {paths}")
        return state, Trace.load(paths[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def reference_readings(model: Mapping, hp: Mapping, weights, tokens,
                       devices, lowp: Optional[str] = None):
    """The plain reference over the checked steps, data-parallel over the
    cell's devices, from the same weights as the program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bench.correct import Readings
    from bench.reference.train import reference_steps

    mesh = Mesh(np.asarray(devices), ("d",))

    def run(values, toks):
        p0 = {k: v.astype(jnp.float32) for k, v in values.items()}
        return reference_steps(p0, model, hp, toks, len(devices), lowp)

    whole = NamedSharding(mesh, P())
    fn = jax.jit(run, in_shardings=(whole, NamedSharding(mesh, P(None, "d"))),
                 out_shardings=whole)
    with jax.default_matmul_precision("highest"):
        losses, grads, change = jax.device_get(
            fn(jax.device_put(weights, whole), tokens))
    return Readings([float(x) for x in losses],
                    {k: float(v) for k, v in grads.items()},
                    {k: float(v) for k, v in change.items()})


def step_bytes(prog, state, batch) -> int:
    """Device bytes of the compiled step, per chip, as the compiler plans
    them: arguments, outputs not aliased to them, and temporaries."""
    import jax
    with jax.set_mesh(prog.mesh):
        m = prog.step.lower(state, batch).compile().memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        raise CellError("the device reports no peak_bytes_in_use")
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def read_metric(name: str, ctx: RunContext):
    mod = importlib.import_module(f"bench.metrics.{name}")
    return mod.read(ctx)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t0: float = T0, log=print) -> dict:
    import jax
    from bench import correct, program
    from bench.reference import weights as W
    from bench.generator import token_batches
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    # every program of the run, however quick to compile, is cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg, traffic = cell.config, cell.traffic
    if "limits" not in cfg:
        raise CellError(f"{cfg['name']} has no limits for correct: "
                        f"calibrate them first (bench/calibrate.py)")
    model, B, S = cfg["model"], cfg["global_batch"], traffic["seq_len"]
    marks = [("start", time.perf_counter() - t0)]
    prog = program.build(cfg, S, devices)
    key = W.seed_key(seed)
    tokens = token_batches(traffic, model["vocab_size"], B, seed,
                           range(CHECK_STEPS + POOL))
    batches = [{"tokens": jax.device_put(t, prog.batch_sharding)}
               for t in tokens]
    marks.append(("build+batches", time.perf_counter() - t0))
    state, readings = first_steps(prog, key, batches[:CHECK_STEPS])
    setup_s = time.perf_counter() - t0
    marks.append(("checked steps", setup_s))
    log(f"set-up {setup_s:.3f} s " + " ".join(
        f"{n}@{t:.3f}" for n, t in marks))

    window = batches[CHECK_STEPS:]
    state, losses, dispatch, ends = timed_window(prog, state, window,
                                                 seconds)
    tokens_per_s = len(losses) * B * S / ends[-1]
    peak = peak_bytes(devices)
    log(f"memory_stats {devices[0].memory_stats()}")
    log(f"window {len(losses)} steps in {ends[-1]:.3f} s: "
        f"{tokens_per_s:.1f} tokens/s; peak {peak} bytes; step ends "
        f"{[round(t, 4) for t in ends]}")
    tr = sbytes = None
    if trace:
        state, tr = traced_steps(prog, state, window, TRACE_STEPS)
        t = time.perf_counter()
        sbytes = step_bytes(prog, state, window[0])
        log(f"compiled step {sbytes} bytes per chip, read in "
            f"{time.perf_counter() - t:.3f} s")
    del state, batches, window

    prog_read = readings()
    ref_read = reference_readings(model, cfg["train"], prog.weights(key),
                                  tokens[:CHECK_STEPS], devices)
    nums = correct.numbers(prog_read, ref_read)
    ok, checks = correct.judge(nums, cfg["limits"])
    log(f"program {prog_read}")
    log(f"reference {ref_read}")

    d = devices[0]
    result = {"correct": ok, "attempted": len(losses),
              "failed": sum(not math.isfinite(x) for x in losses)}
    if trace:
        ctx = RunContext(cell, d.device_kind, len(devices), tokens_per_s,
                         dispatch, sbytes, tr)
        metrics = {}
        for m in cell.per_layer:
            v = read_metric(m["name"], ctx)
            if v is None:
                log(f"metric {m['name']}: its reader found nothing to read "
                    f"in this run, so it is left out")
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = {"platform": d.platform, "kind": d.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": peak}
    if trace:
        result["device"].update(busy_s=tr.busy_s(), window_s=tr.window_s())
        result["breakdown"] = {"device_ops": tr.op_seconds(10),
                               "idle_gaps": tr.idle_gaps(10)}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = load_cell(ROOT, args.workload)
        src = os.path.join(ROOT, "src")
        if not os.path.isdir(os.path.join(src, "repro")):
            raise CellError(f"no program under {src}")
        sys.path.insert(1, src)
        import jax
        devs = jax.devices()
        chips = cell.workload["chips"]
        if devs[0].platform != "tpu" or len(devs) < chips:
            raise CellError(f"cell asks for {chips} TPU chips; JAX found "
                            f"{len(devs)} {devs[0].platform} device(s)")
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devs[:chips], log=log)
    except (CellError, OSError, KeyError, ValueError) as e:
        log(f"bench: {type(e).__name__}: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
