"""Benchmark harness — one entry per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only table5,...]

Outputs CSV-ish lines ``name,key=value,...`` plus formatted tables, and
writes a JSON artifact per run under benchmarks/artifacts/.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer seeds/generations (CI-scale)")
    ap.add_argument("--only", default="",
                    help="comma list: table2..table6,fig7,fig8,roofline,"
                         "measured,planner,elastic,ft,trace")
    args = ap.parse_args()

    from benchmarks.common import ART, emit

    seeds = 3 if args.quick else 10
    small = 2 if args.quick else 3
    maxiter = 150 if args.quick else 300

    def _pool_subprocess(cmd, see):
        # subprocess: these entry points must force their device pool
        # before jax initializes. They all run before this process
        # touches jax (see ``jobs``), so none is locked out of a device.
        import subprocess
        import sys
        r = subprocess.run([sys.executable, "-m"] + cmd,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))),
                           capture_output=True, text=True)
        print(r.stdout[-4000:])
        if r.returncode != 0:
            raise RuntimeError(r.stderr[-2000:])
        return {"see": see}

    def measured():
        cmd = ["benchmarks.measured_sweep"]
        cmd += ["--quick"] if args.quick else ["--trials", "1500"]
        return _pool_subprocess(cmd, "benchmarks/MEASURED_SWEEP.md")

    def planner():
        cmd = ["benchmarks.plan", "--validate"]
        cmd += ["--quick"] if args.quick else []
        return _pool_subprocess(cmd, "benchmarks/PLANNER.md")

    def elastic():
        cmd = ["benchmarks.elastic"]
        cmd += ["--dry-run"] if args.quick else []
        return _pool_subprocess(cmd, "benchmarks/ELASTIC.md")

    def trace():
        cmd = ["benchmarks.trace_report"]
        cmd += ["--dry-run"] if args.quick else []
        return _pool_subprocess(cmd, "benchmarks/TRACE.md")

    def ft():
        # supervised fault-tolerance drill — a script entry point (it
        # forces its own pool), so the argv shape differs from -m jobs
        import subprocess
        import sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run([sys.executable,
                            os.path.join(root, "tools", "ft_smoke.py")],
                           cwd=root, capture_output=True, text=True)
        print(r.stdout[-4000:])
        if r.returncode != 0:
            raise RuntimeError(r.stderr[-2000:])
        return {"see": "tools/ft_smoke.py"}

    def tables():
        # imported only here: these jobs run jax in this process, so
        # they come after every job that starts a child process
        from benchmarks import tables
        return tables

    def roofline():
        from benchmarks.roofline_fit import roofline_fit
        return roofline_fit()

    # in execution order: child-process jobs first, in-process jobs last
    jobs = {
        "measured": measured,
        "planner": planner,
        "elastic": elastic,
        "ft": ft,
        "trace": trace,
        "table2": lambda: tables().table2_fit(seeds, maxiter),
        "table3": lambda: tables().table3_fit_l2(seeds, maxiter),
        "table4": lambda: tables().table4_reg_compare(
            max(seeds // 2, 2), maxiter),
        "table5": lambda: tables().table5_model_compare(seeds, maxiter),
        "table6": lambda: tables().table6_scaling(seeds, maxiter),
        "fig7": lambda: tables().fig7_lambda_sweep("jit", small, maxiter),
        "fig8": lambda: tables().fig8_coeff_paths("jit", small, maxiter),
        "roofline": roofline,
    }
    only = [s for s in args.only.split(",") if s]
    results = {}
    for name, job in jobs.items():
        if only and name not in only:
            continue
        if not only and name == "measured":
            continue        # hours-long; opt in with --only measured
        t0 = time.time()
        try:
            results[name] = job()
            emit(f"{name}_done", seconds=f"{time.time()-t0:.1f}")
        except Exception as e:  # keep the harness running
            import traceback
            traceback.print_exc()
            emit(f"{name}_FAILED", error=str(e)[:200])
            results[name] = {"error": str(e)}

    os.makedirs(ART, exist_ok=True)
    out_path = os.path.join(ART, "bench_results.json")

    def default(o):
        import numpy as np
        if isinstance(o, (np.floating, np.integer)):
            return float(o)
        if isinstance(o, np.ndarray):
            return o.tolist()
        return str(o)

    json.dump(results, open(out_path, "w"), indent=1, default=default)
    print(f"[benchmarks] wrote {out_path}")


if __name__ == "__main__":
    main()
