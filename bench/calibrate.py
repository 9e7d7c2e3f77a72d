#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--faults half_batch,exchange] \
        [--fault-seeds 1,2,3] [--out chiprun_out/calib.jsonl]

For each seed: the program's checked steps at the cell's own size and the
plain reference's, and the numbers compared (one JSON line each). Then
the control (the reference with fp8 products) against the reference on
``--control-seeds``, and each planted fault (``bench/faults.py``) on
``--fault-seeds``. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", type=lambda s: [f for f in s.split(",") if f],
                    default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    from bench import correct, faults, program
    from bench.generator import token_batches
    from bench.reference import weights as W
    from bench.run import (CHECK_STEPS, first_steps, load_cell,
                           reference_readings)
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    cell = load_cell(ROOT, args.workload)
    cfg, traffic = cell.config, cell.traffic
    model, B, S = cfg["model"], cfg["global_batch"], traffic["seq_len"]
    devices = jax.devices()[:cell.workload["chips"]]
    out = open(args.out, "a") if args.out else None

    def emit(**rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def inputs(seed):
        toks = token_batches(traffic, model["vocab_size"], B, seed,
                             range(CHECK_STEPS))
        return W.seed_key(seed), toks

    def program_readings(prog, seed):
        key, toks = inputs(seed)
        batches = [{"tokens": jax.device_put(t, prog.batch_sharding)}
                   for t in toks]
        state, readings = first_steps(prog, key, batches)
        del state, batches
        return readings()

    refs = {}

    def ref(seed, lowp=None):
        if (seed, lowp) not in refs:
            key, toks = inputs(seed)
            t = time.perf_counter()
            refs[seed, lowp] = reference_readings(
                model, cfg["train"], prog.weights(key), toks, devices, lowp)
            emit(kind="reference" if lowp is None else "control",
                 seed=seed, seconds=time.perf_counter() - t,
                 losses=refs[seed, lowp].losses)
        return refs[seed, lowp]

    prog = program.build(cfg, S, devices)
    for seed in args.seeds:
        t = time.perf_counter()
        read = program_readings(prog, seed)
        secs = time.perf_counter() - t
        emit(kind="program", seed=seed, seconds=secs, losses=read.losses,
             nonfinite=correct.finite_readings(read),
             numbers=correct.numbers(read, ref(seed)),
             readings=read._asdict(), reference=ref(seed)._asdict())
    for seed in args.control_seeds:
        emit(kind="control", seed=seed,
             numbers=correct.numbers(ref(seed, "fp8"), ref(seed)))
    for name in args.faults:
        with faults.FAULTS[name]():          # open while the step traces
            fprog = program.build(cfg, S, devices)
            for seed in args.fault_seeds:
                read = program_readings(fprog, seed)
                emit(kind="fault", fault=name, seed=seed, losses=read.losses,
                     numbers=correct.numbers(read, ref(seed)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
