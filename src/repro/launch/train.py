"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Features exercised here (the production path in miniature):
  * config → model → sharded train_step, on one of two paths:
      - "sharded": the measured multi-device path — a real ``shard_map``
        step on the device pool, explicit all-gathers per strategy, and
        the gradient all-reduce through the wire-compressed collective
        (``repro.dist.compression.compressed_psum_mean``);
      - "gspmd": jit with logical-rule shardings; XLA inserts the
        collectives. The fallback for adafactor / indivisible batches.
    ``--mode auto`` (default) picks "sharded" whenever it can.
  * on a CPU-only host an 8-device placeholder pool is forced, so the
    default invocation exercises real collectives; override with
    --devices N or an explicit XLA_FLAGS. On an accelerator the run uses
    the accelerator's devices and no pool is made. The first line of
    output names the platform and device kind.
  * deterministic step-indexed data (resume-safe)
  * checkpoint/restart: atomic async checkpoints, auto-resume from latest
  * straggler detection via the fitted performance model when available
    (falls back to running median), logged per step
  * elastic planning: if the device count changed since the checkpoint,
    a new mesh is planned and the state is resharded on restore
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

DEFAULT_POOL = 8      # placeholder pool forced on single-CPU hosts
PROFILE_STEPS = 3     # steady steps in the --trace-dir profiler trace


def _force_host_pool(n: int) -> None:
    """Give the CPU backend an n-device pool when the run will be on CPU.

    A no-op when an accelerator is present, and when a host device
    count was already asked for, by XLA_FLAGS or an earlier call (the
    first request wins). Must run before anything else in the process
    touches jax devices.
    """
    import jax
    from jax.extend.backend import clear_backends
    if ("xla_force_host_platform_device_count" in
            os.environ.get("XLA_FLAGS", "")
            or jax.config.jax_num_cpu_devices > 0):
        return
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        if jax.default_backend() != "cpu":
            return
        # only the CPU was found: rebuild it with the pool
        clear_backends()
    jax.config.update("jax_num_cpu_devices", n)


def build_parser() -> argparse.ArgumentParser:
    from repro.dist.sharding import STRATEGIES
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale config of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgd", "adafactor"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8", "int8_ef"])
    ap.add_argument("--strategy", default="fsdp_tp",
                    choices=sorted(STRATEGIES) + ["auto"],
                    help="parallelism strategy; 'auto' defers to the "
                         "scenario planner (repro.perf.planner), which "
                         "ranks the feasible registry strategies by "
                         "calibrated collective cost + memory headroom")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "sharded", "gspmd"],
                    help="sharded = shard_map with measured collectives; "
                         "gspmd = jit-with-shardings; auto prefers sharded")
    ap.add_argument("--devices", type=int, default=0,
                    help=f"run on the first N devices (0 = all); on a "
                         f"CPU-only host also the size of the forced pool "
                         f"(0 = {DEFAULT_POOL})")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--dtype", default="",
                    help="override model compute/param dtype (e.g. "
                         "float32 for bit-parity recovery drills)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--straggler-tol", type=float, default=2.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--die-at-step", type=int, default=0,
                    help="fault-injection: crash at this step (FT test)")
    ap.add_argument("--simulate-failure", type=int, default=0,
                    help="fault-injection: at this step, lose devices "
                         "in-process, re-plan (strategy, mesh) on the "
                         "survivors via ft.plan_recovery, restore the "
                         "latest checkpoint resharded, and resume "
                         "(requires --ckpt-dir)")
    ap.add_argument("--fail-devices", type=int, default=0,
                    help="devices lost at --simulate-failure "
                         "(0 = half the pool)")
    ap.add_argument("--recover-strategy", default="auto",
                    choices=sorted(STRATEGIES) + ["auto"],
                    help="strategy after the simulated failure; auto = "
                         "planner pick on the surviving pool")
    ap.add_argument("--precompile-survivors", type=int, default=0,
                    help="AOT-compile step programs for the N largest "
                         "pow2-floor survivor pools in a background "
                         "thread while training runs, so a recovery "
                         "skips the re-jit tail (0 = off)")
    ap.add_argument("--precompile-block", action="store_true",
                    help="at recovery, wait for the background compile "
                         "to land instead of falling back to re-jit — "
                         "drills use this to model a failure arriving "
                         "in steady state, after the compile finished")
    ap.add_argument("--inject-ckpt-fault", type=int, default=0,
                    help="fault-injection: the first N checkpoint "
                         "writes raise a transient OSError, exercising "
                         "the supervisor's retry/backoff path")
    ap.add_argument("--max-retries", type=int, default=4,
                    help="supervisor retry budget (attempts, not "
                         "re-tries) for transient checkpoint-I/O "
                         "failures")
    ap.add_argument("--straggler-escalate", type=int, default=0,
                    help="K consecutive straggler-flagged steps trigger "
                         "a proactive checkpoint (0 = off)")
    ap.add_argument("--report-comm", action="store_true",
                    help="estimate per-step collective time from the "
                         "calibrated cost model (repro.perf.costmodel) "
                         "and include it in the plan output")
    ap.add_argument("--trace-dir", default="",
                    help="record spans/metrics and write trace.jsonl "
                         "here, with a jax.profiler trace of the first "
                         f"{PROFILE_STEPS} steady steps (the spans on its "
                         "host plane); empty (default) keeps the "
                         "zero-overhead disabled recorder")
    ap.add_argument("--trace-sync", default="none",
                    choices=["none", "boundary"],
                    help="device-sync policy at span boundaries: 'none' "
                         "never adds a sync the untraced path lacks "
                         "(preserves comm/compute overlap); 'boundary' "
                         "blocks for precise span durations")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the execution plan as JSON and exit")
    return ap


def _comm_estimate(cfg, args, n_dev: int):
    """Schedule-level collective estimate for the run's strategy, via
    the shared prediction path (repro.perf.predict) — the same assembly
    the sweep simulation and the planner price with."""
    from repro.perf.planner.space import model_comm_sizes
    from repro.perf.predict import estimate_comm

    from repro.dist.compression import WIRE_BITS

    param_bytes, act_bytes = model_comm_sizes(cfg, args.batch, args.seq)
    return estimate_comm(args.strategy, n_dev, param_bytes,
                         wire_bits=WIRE_BITS[args.compression],
                         act_bytes=act_bytes, detail=True).to_dict()


def _pick_mode(args, tcfg, mesh, n_dev: int):
    """(path, reason) — which step implementation this run uses."""
    from repro.train import sharded_batch_ok
    from repro.train.step import n_batch_shards
    why_not = None
    if n_dev <= 1:
        why_not = "single device"
    elif tcfg.optimizer == "adafactor":
        why_not = "adafactor needs full-dim factored moments"
    elif not sharded_batch_ok(mesh, args.batch):
        why_not = (f"batch {args.batch} not divisible over the batch axes "
                   f"of mesh {dict(mesh.shape)}")
    elif (args.batch // n_batch_shards(mesh)) % args.microbatches != 0:
        why_not = (f"per-device batch {args.batch // n_batch_shards(mesh)} "
                   f"not divisible by {args.microbatches} microbatches")
    if args.mode == "gspmd":
        return "gspmd", "requested"
    if args.mode == "sharded":
        if why_not:
            raise SystemExit(f"--mode sharded impossible: {why_not}")
        return "sharded", "requested"
    if why_not:
        return "gspmd", f"auto fallback: {why_not}"
    return "sharded", "auto"


class _StepProfile:
    """A ``jax.profiler`` trace of the first ``steps`` steady steps, under
    ``trace_dir`` (no trace when it is empty)."""

    def __init__(self, trace_dir: str, steps: int):
        self.trace_dir, self.left, self.on = trace_dir, steps, False

    def before_step(self, phase: str) -> None:
        if self.trace_dir and self.left and not self.on and \
                phase == "steady":
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self.on = True

    def after_step(self) -> None:
        if self.on:
            self.left -= 1
            if not self.left:
                self.close()

    def close(self) -> None:
        if self.on:
            import jax
            jax.profiler.stop_trace()
            self.on = False


def main(argv=None):
    args = build_parser().parse_args(argv)
    _force_host_pool(args.devices or DEFAULT_POOL)
    from repro.obs import Recorder, use_recorder

    # the current recorder: a compile lands as an event in its open span
    rec = Recorder(enabled=bool(args.trace_dir),
                   sync_policy=args.trace_sync)
    profile = _StepProfile(args.trace_dir, PROFILE_STEPS)
    try:
        with use_recorder(rec):
            return _train(args, rec, profile)
    finally:
        profile.close()


def _train(args, rec, profile: _StepProfile):
    import jax
    import numpy as np

    from repro.launch.mesh import describe_platform, enable_compile_cache
    print(describe_platform(), flush=True)
    enable_compile_cache()

    from repro.configs import TrainConfig, get_config, reduced
    from repro.data import make_batch_for
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import batch_shardings, state_shardings
    from repro.train import (init_sharded_train_state, init_train_state,
                             make_sharded_train_step, make_train_step,
                             sharded_state_shardings)
    from repro.train.step import sharded_state_specs
    from repro.train.checkpoint import CheckpointManager
    from repro.train.ft import StragglerDetector, plan_recovery, plan_remesh
    from repro.train.supervisor import (RetryPolicy, Supervisor,
                                        SurvivorPrecompiler, pow2_floor)
    from repro.obs import (CompileCounts, Metrics, StragglerMonitor,
                           collective_bytes, compile_counts, observe_step,
                           record_memory_watermarks, record_recovery,
                           write_jsonl)

    run_compiles = compile_counts()
    obs_metrics = Metrics()
    sup = Supervisor(policy=RetryPolicy(max_attempts=max(args.max_retries,
                                                         1)),
                     recorder=rec, metrics=obs_metrics,
                     escalate_after=max(args.straggler_escalate, 1))

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype,
                                  param_dtype=args.dtype)
    if args.simulate_failure and not args.dry_run and not args.ckpt_dir:
        raise SystemExit("--simulate-failure requires --ckpt-dir "
                         "(recovery restores from the latest checkpoint)")
    tcfg = TrainConfig(learning_rate=args.lr, optimizer=args.optimizer,
                       total_steps=args.steps, warmup_steps=args.steps // 10,
                       remat_policy=args.remat,
                       grad_compression=args.compression, seed=args.seed,
                       checkpoint_every=args.ckpt_every,
                       checkpoint_dir=args.ckpt_dir or "/tmp/repro_ckpt")

    devices = jax.devices()[:args.devices or None]
    n_dev = len(devices)
    plan = plan_remesh(n_dev)
    mesh = make_mesh(plan.mesh_shape, ("data", "model"), devices=devices)
    decision = None
    if args.strategy == "auto":
        from repro.perf.planner import choose_strategy
        # feasibility is judged on the mesh this run will actually use
        decision = choose_strategy(cfg, batch=args.batch, seq=args.seq,
                                   n_devices=n_dev,
                                   optimizer=args.optimizer,
                                   compression=args.compression,
                                   mesh_axes=dict(mesh.shape))
        args.strategy = decision.strategy
        note = "" if decision.calibrated else \
            "  [uncalibrated α-β defaults in use]"
        print(f"planner: --strategy auto -> {args.strategy} "
              f"({decision.reason}){note}")
    path, path_reason = _pick_mode(args, tcfg, mesh, n_dev)
    print(f"devices={n_dev} mesh={plan.mesh_shape} "
          f"strategy={args.strategy} path={path} ({plan.reason}; "
          f"{path_reason})")
    comm = _comm_estimate(cfg, args, n_dev) if args.report_comm else None
    if comm is not None:
        print(f"comm estimate [{comm['calibration']}]: "
              f"{comm['per_step_ms']:.3f} ms/step over "
              f"{comm['mesh_axes']}")
    if args.dry_run:
        out = {"dry_run": True, "arch": cfg.name, "devices": n_dev,
               "mesh": list(plan.mesh_shape), "strategy": args.strategy,
               "compression": args.compression, "path": path,
               "steps": args.steps, "batch": args.batch, "seq": args.seq}
        if comm is not None:
            out["comm"] = comm
        if decision is not None:
            out["planner"] = decision.to_dict()
        if args.simulate_failure:
            # plan (but do not execute) the post-failure recovery, so a
            # drill can be inspected without running it
            lost = args.fail_devices or n_dev // 2
            rplan = plan_recovery(
                cfg, max(n_dev - lost, 1), batch=args.batch, seq=args.seq,
                optimizer=args.optimizer, compression=args.compression,
                strategy=(None if args.recover_strategy == "auto"
                          else args.recover_strategy))
            out["recovery"] = {"at_step": args.simulate_failure,
                               "lost_devices": lost, **rplan.to_dict()}
        print(json.dumps(out))
        return {"dry_run": True, "path": path, "comm": comm,
                "recovery": out.get("recovery"),
                "planner": None if decision is None else decision.to_dict()}

    key = jax.random.PRNGKey(args.seed)
    example_batch = make_batch_for(cfg, args.batch, args.seq, step=0,
                                   seed=args.seed)

    from repro.train.step import n_batch_shards

    def build_exec(mesh, strategy, path):
        """(skeleton, st_specs, st_shard, jitted step) for one
        (mesh, strategy) — rebuilt from scratch on recovery so the
        post-failure executable and the reshard target come from the
        same ``param_pspecs`` resolution."""
        if path == "sharded":
            # Real shard_map step: params enter sharded per the
            # strategy's logical-rule pspecs; tensor-parallel dims stay
            # local (each model rank computes its slice), the rest are
            # gathered in-body, and gradients all-reduce through the
            # compressed collective (repro.train.step.
            # make_sharded_train_step).
            skel = jax.eval_shape(
                lambda: init_sharded_train_state(key, cfg, tcfg, mesh))
            st_specs = sharded_state_specs(cfg, tcfg, mesh, strategy)
            st_shard = sharded_state_shardings(cfg, tcfg, mesh, strategy,
                                               specs=st_specs)
            raw = make_sharded_train_step(
                cfg, tcfg, mesh, strategy,
                microbatches=args.microbatches, state_specs=st_specs)
        else:
            # GSPMD step: all distribution via sharding annotations; on
            # one CPU device every spec degenerates to replicated and
            # the same program runs unchanged.
            skel = jax.eval_shape(
                lambda: init_train_state(key, cfg, tcfg))
            st_specs = None
            st_shard = state_shardings(skel, mesh, strategy)
            raw = make_train_step(cfg, tcfg,
                                  microbatches=args.microbatches)
        b_shard = batch_shardings(example_batch, mesh)
        # out_shardings pins the new state to the same specs, so the
        # donated state round-trips the jit boundary without a
        # resharding mismatch.
        fn = jax.jit(raw, in_shardings=(st_shard, b_shard),
                     out_shardings=(st_shard, None), donate_argnums=(0,))
        return skel, st_specs, st_shard, fn

    def save_ckpt(at_step, state, st_specs):
        # save + wait under the supervisor: the async writer's failure
        # surfaces at wait(), so a transient I/O error re-runs the whole
        # (idempotent, atomic-rename) write with backoff instead of
        # killing the run, while a fatal error still fails fast.
        def _write():
            if path == "sharded" and st_specs is not None:
                ckpt.save_sharded(at_step, state, mesh=mesh,
                                  strategy=args.strategy, specs=st_specs,
                                  extra_meta={"arch": cfg.name})
            else:
                ckpt.save(at_step, state, extra_meta={"arch": cfg.name})
            ckpt.wait()
        sup.run("checkpoint_save", _write)

    skel, st_specs, st_shard, step_fn = build_exec(mesh, args.strategy,
                                                   path)
    start_step = 0
    ckpt = None
    state = None
    if args.ckpt_dir:
        fault_hook = None
        if args.inject_ckpt_fault > 0:
            budget = {"n": args.inject_ckpt_fault}

            def fault_hook(op, at_step):
                if op == "write" and budget["n"] > 0:
                    budget["n"] -= 1
                    raise OSError(f"injected transient ckpt fault at "
                                  f"step {at_step} "
                                  f"({budget['n']} remaining)")
        ckpt = CheckpointManager(args.ckpt_dir, keep=3,
                                 fault_hook=fault_hook)
        if ckpt.latest_step() is not None:
            # restore *after* the specs exist: the checkpoint may come
            # from a different (mesh, strategy) — reshard on restore
            state, start_step = ckpt.restore(skel, shardings=st_shard,
                                             strict=False)
            if ckpt.last_restore_report:
                print(f"restore re-initialized "
                      f"{len(ckpt.last_restore_report)} leaves: "
                      f"{ckpt.last_restore_report[:4]}...")
            print(f"resumed from step {start_step}")
    if state is None:
        if path == "sharded":
            state = init_sharded_train_state(key, cfg, tcfg, mesh)
        else:
            state = init_train_state(key, cfg, tcfg)

    precomp = None
    if args.precompile_survivors > 0:
        precomp = SurvivorPrecompiler(recorder=rec, metrics=obs_metrics)

    def _submit_precompiles():
        """Queue AOT builds for the N largest pow2 survivor pools.

        Each build plans the post-failure (strategy, mesh) exactly as
        the recovery path would (``ft.plan_recovery`` on a prefix of
        the pool), then ``lower().compile()``s the step program in the
        precompiler's worker thread while healthy steps keep running.
        AOT compilation does not seed the jit dispatch cache, so the
        bundle carries the ``Compiled`` object itself and recovery
        calls it directly.
        """
        batch_skel = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            example_batch)
        n_surv = pow2_floor(n_dev)
        for _ in range(args.precompile_survivors):
            n_surv //= 2
            if n_surv < 1:
                break

            def build(n=n_surv):
                rplan = plan_recovery(
                    cfg, n, batch=args.batch, seq=args.seq,
                    optimizer=args.optimizer,
                    compression=args.compression,
                    strategy=(None if args.recover_strategy == "auto"
                              else args.recover_strategy))
                m = make_mesh(rplan.mesh_shape, rplan.axis_names,
                              devices=devices[:rplan.n_devices])
                ns = argparse.Namespace(**vars(args))
                ns.strategy = rplan.strategy
                p2, _ = _pick_mode(ns, tcfg, m, rplan.n_devices)
                skel2, specs2, shard2, fn2 = build_exec(m, rplan.strategy,
                                                        p2)
                compiled = fn2.lower(skel2, batch_skel).compile()
                return rplan, (m, p2, skel2, specs2, shard2, compiled)

            precomp.submit((n_surv,), build)

    def _comm_byte_terms():
        """Per-collective bytes of one step (op/axis/tensor keyed), for
        the comm_bytes/* counters — derived from the calibrated schedule
        layer, recomputed whenever (strategy, mesh) changes."""
        if not rec.enabled:
            return {}
        from repro.dist.compression import WIRE_BITS
        from repro.perf.planner.space import model_comm_sizes
        pb, ab = model_comm_sizes(cfg, args.batch, args.seq)
        return collective_bytes(
            args.strategy, n_dev, pb,
            wire_bits=WIRE_BITS[args.compression], act_bytes=ab,
            axes={k: int(v) for k, v in mesh.shape.items()})

    detector = StragglerDetector(tolerance=args.straggler_tol)
    monitor = StragglerMonitor(detector, metrics=obs_metrics, recorder=rec)
    comm_terms = _comm_byte_terms()
    phase = "warmup"             # the first step pays the jit compile
    steady_compiles = CompileCounts()
    precomp_submitted = False
    loss_by_step = {}
    step_times = []
    recovery = None
    t_run = time.time()
    step = start_step
    while step < args.steps:
        if args.die_at_step and step == args.die_at_step:
            print(f"fault injection: dying at step {step}", flush=True)
            os._exit(42)
        if (args.simulate_failure and step >= args.simulate_failure
                and recovery is None):
            # ---- simulated device loss: re-plan, reshard, resume ----
            lost = args.fail_devices or n_dev // 2
            rec.event("failure", step=int(step), lost_devices=int(lost))
            survivors = devices[:max(n_dev - lost, 1)]
            prog = None
            compile_s = 0.0
            if precomp is not None:
                # the compile span here measures the *exposed* wait for
                # the background AOT compile — zero once it has landed
                with rec.span("recovery/compile", category="recovery",
                              step_num=step):
                    t_c = time.perf_counter()
                    prog = precomp.get(len(survivors),
                                       block=args.precompile_block,
                                       timeout=600.0)
                    compile_s = time.perf_counter() - t_c
            with rec.span("recovery/plan", category="recovery",
                          step_num=step):
                t0 = time.perf_counter()
                if prog is not None:
                    # use the plan the bundle was compiled against —
                    # re-planning could disagree (compute_ref drifts
                    # with measured step times) and miss the cache
                    rplan = prog.plan
                else:
                    compute_ref = None
                    if step_times:
                        h = sorted(step_times)
                        compute_ref = (h[len(h) // 2],
                                       n_batch_shards(mesh))
                    rplan = plan_recovery(
                        cfg, len(survivors), batch=args.batch,
                        seq=args.seq, optimizer=args.optimizer,
                        compression=args.compression,
                        strategy=(None if args.recover_strategy == "auto"
                                  else args.recover_strategy),
                        compute_ref=compute_ref)
                plan_s = time.perf_counter() - t0
            before = {"mesh": list(mesh.devices.shape),
                      "strategy": args.strategy, "devices": n_dev}
            n_dev = rplan.n_devices
            args.strategy = rplan.strategy
            t1 = time.perf_counter()
            if prog is not None:
                mesh, path, skel, st_specs, st_shard, step_fn = prog.bundle
                path_reason = "precompiled"
            else:
                mesh = make_mesh(rplan.mesh_shape, rplan.axis_names,
                                 devices=survivors[:rplan.n_devices])
                path, path_reason = _pick_mode(args, tcfg, mesh, n_dev)
                with rec.span("recovery/rebuild", category="recovery",
                              step_num=step):
                    skel, st_specs, st_shard, step_fn = build_exec(
                        mesh, args.strategy, path)
            print(f"failure at step {step}: lost {lost} devices; "
                  f"recovery plan: {rplan.reason}; path={path} "
                  f"({path_reason})", flush=True)
            with rec.span("recovery/restore", category="recovery",
                          step_num=step):
                try:
                    state, ckpt_step = ckpt.restore(skel,
                                                    shardings=st_shard,
                                                    strict=False)
                except FileNotFoundError:
                    raise SystemExit(
                        f"--simulate-failure {args.simulate_failure}: no "
                        f"checkpoint to recover from (set --ckpt-every <= "
                        f"the failure step)")
            restore_s = time.perf_counter() - t1
            recovery = {
                "at_step": step, "lost_devices": lost,
                "before": before,
                "after": {"mesh": list(rplan.mesh_shape),
                          "strategy": args.strategy, "devices": n_dev},
                "reason": rplan.reason,
                "restored_step": ckpt_step,
                "steps_replayed": step - ckpt_step,
                "reinit_leaves": list(ckpt.last_restore_report),
                "precompiled": prog is not None,
                "restore_mode": ckpt.last_restore_mode,
                "plan_s": round(plan_s, 4),
                "compile_s": round(compile_s, 4),
                "restore_s": round(restore_s, 4)}
            print(f"recovered: resumed from step {ckpt_step} on "
                  f"mesh {rplan.mesh_shape} strategy {args.strategy} "
                  f"(plan {plan_s*1e3:.0f}ms, compile "
                  f"{compile_s*1e3:.0f}ms, restore "
                  f"{restore_s*1e3:.0f}ms, "
                  f"{ckpt.last_restore_mode})", flush=True)
            detector = StragglerDetector(tolerance=args.straggler_tol)
            monitor = StragglerMonitor(detector, metrics=obs_metrics,
                                       recorder=rec)
            comm_terms = _comm_byte_terms()
            phase = "recovery/first_step"   # pays the re-jit compile
            step_times = []
            step = ckpt_step
            continue
        profile.before_step(phase)
        compiled_before = compile_counts()
        with rec.span("step", category="train", step_num=step,
                      phase=phase) as sp:
            with rec.span("data", category="train"):
                batch = make_batch_for(cfg, args.batch, args.seq,
                                       step=step, seed=args.seed)
            t0 = time.perf_counter()
            with rec.span("dispatch", category="train"):
                with jax.set_mesh(mesh):
                    state, metrics = step_fn(state, batch)
            with rec.span("wait", category="train"):
                # the loss block the untraced loop already performs —
                # the span only times it, it adds no new sync
                jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            sp.set(ms=dt * 1e3)
        profile.after_step()
        if phase == "steady":
            steady_compiles += compile_counts() - compiled_before
        if recovery is not None and "first_step_s" not in recovery:
            # first post-recovery step: on the re-jit path it includes
            # the compile (the largest share of measured recovery
            # time); on the precompiled path it is a plain step
            recovery["first_step_s"] = round(dt, 4)
            recovery["recovery_s"] = round(
                recovery["plan_s"] + recovery["compile_s"]
                + recovery["restore_s"] + dt, 4)
            if rec.enabled:
                record_recovery(obs_metrics, recovery)
        step_times.append(dt)
        if precomp is not None and not precomp_submitted:
            # submit after the first healthy step so the background
            # compile does not contend with the main program's own jit
            precomp_submitted = True
            _submit_precompiles()
        flagged = monitor.observe(step, dt)
        if (ckpt and args.straggler_escalate
                and sup.note_straggler(step, flagged)):
            # a persistently slow pool member is a failure precursor:
            # snapshot now so the eventual recovery replays fewer steps
            save_ckpt(step + 1, state, st_specs)
            print(f"proactive checkpoint at step {step} "
                  f"(persistent straggler)", flush=True)
        if rec.enabled:
            observe_step(obs_metrics, seconds=dt, batch=args.batch,
                         seq=args.seq)
            for k, v in comm_terms.items():
                obs_metrics.counter(f"comm_bytes/{k}").inc(v)
            if step % args.log_every == 0:
                record_memory_watermarks(obs_metrics)
        phase = "steady"
        loss_by_step[step] = float(metrics["loss"])
        if step % args.log_every == 0 or flagged:
            msg = (f"step {step:5d} loss {loss_by_step[step]:.4f} "
                   f"gnorm {float(metrics['grad_norm']):.3f} "
                   f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
            if flagged:
                msg += "  [STRAGGLER FLAGGED]"
            print(msg, flush=True)
        step += 1
        if ckpt and step % args.ckpt_every == 0 and step < args.steps:
            save_ckpt(step, state, st_specs)
    if ckpt:
        save_ckpt(args.steps, state, st_specs)
        ckpt.wait()

    losses = [loss_by_step[s] for s in sorted(loss_by_step)]
    # where the state lives: the mesh's devices and the shards of the
    # largest parameter (one device per shard on a real mesh)
    big = max(jax.tree.leaves(state.params), key=lambda x: x.size)
    out = {"arch": cfg.name, "steps": args.steps,
           "first_loss": losses[0] if losses else None,
           "final_loss": float(np.mean(losses[-10:])) if losses else None,
           "wall_s": round(time.time() - t_run, 1),
           "losses": losses,
           "step_ms": [t * 1e3 for t in step_times],
           "strategy": args.strategy, "mesh": list(mesh.devices.shape),
           "placement": {
               "mesh_devices": mesh.device_ids.tolist(),
               "largest_param": list(big.shape),
               "shards": [[s.device.id, list(s.data.shape)]
                          for s in big.addressable_shards]},
           "straggler_flags": detector.flags,
           "compiles": {"steady_steps": steady_compiles.to_dict(),
                        "run": (compile_counts() - run_compiles).to_dict()}}
    out["supervisor"] = {"retries": sup.retries,
                         "proactive_checkpoints": sup.proactive_checkpoints}
    if precomp is not None:
        out["supervisor"]["precompile"] = precomp.stats()
    if recovery is not None:
        out["recovery"] = recovery
    if rec.enabled:
        os.makedirs(args.trace_dir, exist_ok=True)
        meta = {"arch": cfg.name, "strategy": args.strategy,
                "path": path, "devices": n_dev,
                "batch": args.batch, "seq": args.seq,
                "sync_policy": args.trace_sync}
        write_jsonl(os.path.join(args.trace_dir, "trace.jsonl"), rec,
                    metrics=obs_metrics.to_dict(), meta=meta)
        profile.close()
        if hasattr(step_fn, "lower"):     # not a precompiled survivor
            # the profile's device events name the instructions of this
            # text, which carry the op_name (layer scope) of their code
            with jax.set_mesh(mesh):
                text = step_fn.lower(state, example_batch).compile()
            with open(os.path.join(args.trace_dir, "step.hlo.txt"),
                      "w") as f:
                f.write(text.as_text())
        out["trace"] = {"dir": args.trace_dir, "spans": len(rec.spans),
                        "events": len(rec.events),
                        "xplane": _xplanes(args.trace_dir)}
        out["metrics"] = obs_metrics.to_dict()
    print(json.dumps(out))
    return out


def _xplanes(trace_dir: str):
    """The profiler traces written under ``trace_dir``."""
    return sorted(os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                  for f in fs if f.endswith(".xplane.pb"))


if __name__ == "__main__":
    main()
