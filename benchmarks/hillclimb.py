"""Perf hillclimbing driver (§Perf): run one (arch × shape × mesh) cell
with a sequence of knob settings, each in a subprocess, and print the
roofline-term deltas so every hypothesis → change → measure → validate
cycle is recorded.

  PYTHONPATH=src python -m benchmarks.hillclimb --arch smollm-360m \
      --shape train_4k --variants baseline,ce_onehot,remat_dots
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

# named variants: cli flags for repro.launch.dryrun
VARIANTS = {
    "baseline": [],
    "ce_onehot": ["--ce-impl", "onehot"],
    "remat_none": ["--remat", "none"],
    "remat_dots": ["--remat", "dots"],
    "attn_block_2k": ["--attn-block", "2048"],
    "attn_block_4k": ["--attn-block", "4096"],
    "attn_block_512": ["--attn-block", "512"],
    "adafactor": ["--optimizer", "adafactor"],
    "mb4": ["--microbatches", "4"],
    "mb8": ["--microbatches", "8"],
    "fsdp_pod": ["--strategy", "fsdp_pod"],
    "best": ["--ce-impl", "onehot", "--remat", "dots"],
}


def run_variant(arch: str, shape: str, mesh: str, extra_flags, timeout=3600):
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out = f.name
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh, "--out", out] + list(extra_flags)
    # a CPU-only placeholder pool: never takes an accelerator
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=512",
           "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..",
                                      "src")}
    t0 = time.time()
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env)
    if proc.returncode != 0:
        return {"status": "FAIL", "error": proc.stderr[-1500:],
                "wall_s": time.time() - t0}
    row = json.load(open(out))
    os.unlink(out)
    row["wall_s"] = round(time.time() - t0, 1)
    return row


def fmt(row):
    if row.get("status") != "OK":
        return f"FAIL: {row.get('error', '?')[:300]}"
    r = row["roofline"]
    return (f"compute {r['compute_s']:8.4f}s  memory {r['memory_s']:8.4f}s  "
            f"collective {r['collective_s']:8.4f}s  "
            f"-> t_step {r['t_step']:8.4f}s [{r['bottleneck']}] "
            f"useful {r['useful_fraction']:.2%}  "
            f"(compile {row['compile_s']}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="pod")
    ap.add_argument("--variants", default="baseline,ce_onehot")
    ap.add_argument("--log", default="benchmarks/artifacts/hillclimb.jsonl")
    args = ap.parse_args()

    results = {}
    base = None
    for name in args.variants.split(","):
        flags = VARIANTS[name] if name in VARIANTS else name.split()
        row = run_variant(args.arch, args.shape, args.mesh, flags)
        results[name] = row
        tag = f"{args.arch}/{args.shape}/{args.mesh}"
        print(f"[{tag}] {name:14s} {fmt(row)}", flush=True)
        if row.get("status") == "OK":
            t = row["roofline"]["t_step"]
            if base is None:
                base = t
            else:
                print(f"{'':{len(tag)+3}s}{name:14s} Δ vs baseline: "
                      f"{(base - t) / base:+.1%}", flush=True)
        os.makedirs(os.path.dirname(args.log), exist_ok=True)
        with open(args.log, "a") as f:
            f.write(json.dumps({"cell": [args.arch, args.shape, args.mesh],
                                "variant": name, "row": row}) + "\n")


if __name__ == "__main__":
    main()
