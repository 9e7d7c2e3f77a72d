import os
# placeholder pool on the host CPU; never takes an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract memory / cost / collective analyses.

MUST be the first jax-touching import in the process (the XLA flag above
is read at first backend init). Run as:

  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
      --shape train_4k --mesh pod --out results.json      # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --outdir benchmarks/dryrun_results
                                                          # full sweep
The ``--all`` orchestrator runs each cell in a subprocess so one cell's
failure (or compiler OOM) cannot take down the sweep.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Optional

# --- everything below may import jax -------------------------------------
import jax

from repro.configs import (ALL_SHAPES, ARCH_IDS, TrainConfig,
                           cell_is_runnable, get_config, get_shape)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.perf.roofline import (Roofline, model_flops_for, parse_collectives,
                                 roofline_from_compiled)


def run_cell(arch: str, shape_id: str, mesh_kind: str = "pod",
             strategy: str = "fsdp_tp", optimizer: str = "adamw",
             remat: str = "full", verbose: bool = True,
             ce_impl: str = "gather", attn_block: int = 0,
             microbatches: int = 1) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_id)
    ok, why = cell_is_runnable(cfg, shape)
    row: dict = {"arch": arch, "shape": shape_id, "mesh": mesh_kind,
                 "strategy": strategy, "ce_impl": ce_impl,
                 "attn_block": attn_block, "remat": remat,
                 "optimizer": optimizer, "microbatches": microbatches}
    if not ok:
        row.update(status="SKIP", reason=why)
        return row

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_chips = mesh.size
    tcfg = TrainConfig(optimizer=optimizer, remat_policy=remat,
                       ce_impl=ce_impl)
    if attn_block:
        import dataclasses
        cfg = dataclasses.replace(cfg, attn_block=attn_block)
    if microbatches > 1:
        import dataclasses
        shape = dataclasses.replace(shape, microbatches=microbatches)
    t0 = time.time()
    prog = input_specs(cfg, shape, mesh, tcfg, strategy)
    # maybe_constrain reads the active mesh during tracing
    with jax.set_mesh(mesh):
        jitted = jax.jit(prog.fn, in_shardings=prog.in_shardings,
                         donate_argnums=prog.donate_argnums)
        lowered = jitted.lower(*prog.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_stats = {}
    for k in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            mem_stats[k] = int(v)

    hlo_text = compiled.as_text()
    from repro.perf.hlo_analysis import analyze_hlo
    st = analyze_hlo(hlo_text)
    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, (list, tuple)):
        xla_cost = xla_cost[0]
    rf = roofline_from_compiled(compiled, n_chips,
                                model_flops=model_flops_for(cfg, shape),
                                hlo_text=hlo_text)
    row.update(
        status="OK",
        n_chips=n_chips,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=mem_stats,
        bytes_per_device=mem_stats.get("argument_size_in_bytes", 0)
        + mem_stats.get("temp_size_in_bytes", 0),
        collective_counts={k: float(v) for k, v in st.coll_counts.items()},
        xla_flops_per_module=float(xla_cost.get("flops", 0.0)),
        roofline=rf.to_dict(),
    )
    if verbose:
        print(json.dumps(row, indent=1))
    return row


# ---------------------------------------------------------------------------
# Orchestrator: all cells × meshes in subprocesses
# ---------------------------------------------------------------------------

def _cell_cmd(arch, shape_id, mesh_kind, outfile, strategy, optimizer, remat):
    return [sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape_id, "--mesh", mesh_kind,
            "--strategy", strategy, "--optimizer", optimizer,
            "--remat", remat, "--out", outfile]


def run_all(outdir: str, meshes=("pod", "multipod"), archs=None, shapes=None,
            strategy="fsdp_tp", optimizer="adamw", remat="full",
            timeout=3600) -> list:
    import pathlib
    outp = pathlib.Path(outdir)
    outp.mkdir(parents=True, exist_ok=True)
    rows = []
    for mesh_kind in meshes:
        for arch in (archs or ARCH_IDS):
            for shape in (shapes or [s.name for s in ALL_SHAPES]):
                cfg = get_config(arch)
                sh = get_shape(shape)
                name = f"{arch}_{shape}_{mesh_kind}".replace("/", "_")
                outfile = str(outp / f"{name}.json")
                ok, why = cell_is_runnable(cfg, sh)
                if not ok:
                    row = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "SKIP", "reason": why}
                    json.dump(row, open(outfile, "w"), indent=1)
                    rows.append(row)
                    print(f"[skip] {name}: {why}")
                    continue
                if os.path.exists(outfile):
                    row = json.load(open(outfile))
                    if row.get("status") == "OK":
                        rows.append(row)
                        print(f"[cached] {name}")
                        continue
                t0 = time.time()
                proc = subprocess.run(
                    _cell_cmd(arch, shape, mesh_kind, outfile, strategy,
                              optimizer, remat),
                    capture_output=True, text=True, timeout=timeout,
                    env={**os.environ,
                         "XLA_FLAGS": "--xla_force_host_platform_device_count=512"})
                if proc.returncode == 0 and os.path.exists(outfile):
                    row = json.load(open(outfile))
                    print(f"[ok] {name} ({time.time()-t0:.0f}s) "
                          f"bottleneck={row.get('roofline', {}).get('bottleneck')}")
                else:
                    row = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "FAIL",
                           "error": proc.stderr[-2000:]}
                    json.dump(row, open(outfile, "w"), indent=1)
                    print(f"[FAIL] {name}:\n{proc.stderr[-800:]}")
                rows.append(row)
    json.dump(rows, open(outp / "summary.json", "w"), indent=1)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--strategy", default="fsdp_tp")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--ce-impl", default="gather")
    ap.add_argument("--attn-block", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--outdir", default="benchmarks/dryrun_results")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--meshes", default="pod,multipod")
    args = ap.parse_args()

    if args.all:
        run_all(args.outdir, meshes=tuple(args.meshes.split(",")),
                strategy=args.strategy, optimizer=args.optimizer,
                remat=args.remat)
        return

    try:
        row = run_cell(args.arch, args.shape, args.mesh, args.strategy,
                       args.optimizer, args.remat, ce_impl=args.ce_impl,
                       attn_block=args.attn_block,
                       microbatches=args.microbatches)
    except Exception:
        row = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "FAIL", "error": traceback.format_exc()}
        print(row["error"], file=sys.stderr)
        if args.out:
            json.dump(row, open(args.out, "w"), indent=1)
        sys.exit(1)
    if args.out:
        json.dump(row, open(args.out, "w"), indent=1)


if __name__ == "__main__":
    main()
