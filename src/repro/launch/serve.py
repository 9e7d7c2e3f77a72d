"""Batched serving driver: prefill + greedy decode with ring KV caches,
mesh-aware under the same strategy registry as training.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
      --batch 4 --prompt-len 32 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
      --batch 4 --prompt-len 32 --gen 32 --strategy tp

The first line of output names the platform and device kind. With
``--strategy`` the driver plans a (data, model) mesh over the devices
(on a CPU-only host it first forces the host device pool, like the
train driver) and serves *sharded*: params
follow the strategy's logical-rule PartitionSpecs, KV caches shard per
their role (batch over data, kv-heads over model — see
``repro.launch.specs._cache_pspec``), and every decode step runs jit
with explicit in-shardings so XLA inserts the tensor-parallel
collectives. Requesting a strategy that cannot actually shard (a
1-device pool) warns loudly instead of silently running single-device.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    from repro.dist.sharding import STRATEGIES
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--strategy", default="",
                    choices=[""] + sorted(STRATEGIES),
                    help="serve sharded under this registry strategy "
                         "(empty = single-device)")
    ap.add_argument("--devices", type=int, default=0,
                    help="host pool size to force on CPU (0 = auto: 8 "
                         "when --strategy is set, else no pool)")
    ap.add_argument("--trace-dir", default="",
                    help="record prefill/decode spans and write "
                         "trace.jsonl here, with a jax.profiler trace of "
                         "the run (the spans on its host plane); empty "
                         "(default) keeps the zero-overhead disabled "
                         "recorder")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the serving plan as JSON and exit")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.devices or args.strategy:
        from repro.launch.train import DEFAULT_POOL, _force_host_pool
        _force_host_pool(args.devices or DEFAULT_POOL)

    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import describe_platform, enable_compile_cache
    print(describe_platform(), flush=True)
    enable_compile_cache()

    from repro.configs import get_config, reduced
    from repro.data import make_batch_for
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import cache_specs, params_only_shardings
    from repro.models import model as MD
    from repro.obs import Metrics, Recorder, use_recorder, write_jsonl
    from repro.train.ft import plan_remesh

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    rec = Recorder(enabled=bool(args.trace_dir))
    obs_metrics = Metrics()

    n_dev = len(jax.devices())
    sharded = bool(args.strategy)
    if sharded and n_dev <= 1:
        print(f"WARNING: --strategy {args.strategy} requested but only "
              f"{n_dev} device is visible — the mesh cannot shard anything "
              f"and serving runs effectively single-device. Force a pool "
              f"with --devices N (CPU) or run on a multi-device host.",
              file=sys.stderr, flush=True)
    plan = plan_remesh(n_dev) if sharded else None
    mesh = (make_mesh(plan.mesh_shape, ("data", "model")) if sharded
            else make_mesh((1, 1), ("data", "model")))
    print(f"devices={n_dev} mesh={tuple(mesh.shape.values())} "
          f"strategy={args.strategy or 'none (single-device)'}")
    if args.dry_run:
        print(json.dumps({
            "dry_run": True, "arch": cfg.name, "devices": n_dev,
            "mesh": list(mesh.shape.values()),
            "strategy": args.strategy or None, "batch": args.batch,
            "prompt_len": args.prompt_len, "gen": args.gen}))
        return {"dry_run": True}

    key = jax.random.PRNGKey(args.seed)
    params = MD.init_model(key, cfg)
    batch = make_batch_for(cfg, args.batch, args.prompt_len, step=0,
                           seed=args.seed)
    prompt = batch["tokens"]
    B, S = prompt.shape
    cap = S + args.gen

    enc_kv = None
    if cfg.is_encoder_decoder:
        with jax.set_mesh(mesh):
            enc_out = MD.encoder_forward(params, cfg, batch["frames"])
            enc_kv = MD._stacked_cross_kv(params, cfg, enc_out)

    caches = MD.init_decode_caches(cfg, B, cap)
    jit_kwargs = {"donate_argnums": (1,)}
    reput_tok = lambda t: t
    if sharded:
        # Sharded serving: params by logical rules, caches by role, the
        # incoming token over the batch axes. device_put up front so the
        # steady-state decode loop never reshards.
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.dist.sharding import batch_pspec
        p_shard = params_only_shardings(params, mesh, args.strategy)
        _, c_shard = cache_specs(cfg, B, cap, mesh)
        t_shard = NamedSharding(mesh, batch_pspec(mesh, 2, B))
        params = jax.device_put(params, p_shard)
        caches = jax.device_put(caches, c_shard)
        jit_kwargs.update(
            in_shardings=(p_shard, c_shard, t_shard,
                          NamedSharding(mesh, P())),
            out_shardings=(t_shard, c_shard))
        # the greedy argmax runs outside the jit; pin its result back to
        # the token sharding so the decode loop stays reshard-free
        reput_tok = lambda t: jax.device_put(t, t_shard)

    decode = jax.jit(
        lambda p, c, t, pos: MD.decode_step(p, cfg, c, t, pos,
                                            enc_kv=enc_kv),
        **jit_kwargs)

    t0 = time.time()
    logits = None
    profile = (jax.profiler.trace(args.trace_dir) if rec.enabled
               else contextlib.nullcontext())
    with jax.set_mesh(mesh), use_recorder(rec), profile:
        with rec.span("prefill", category="serve", batch=B, tokens=S):
            for pos in range(S):               # batched prefill-by-decode
                logits, caches = decode(params, caches,
                                        prompt[:, pos:pos + 1], pos)
            # the barrier the untraced path already has; the span times it
            jax.block_until_ready(logits)
            t_prefill = time.time() - t0

        prompt_logits = logits             # at the last prompt position
        out_tokens = []
        tok = reput_tok(jnp.argmax(logits, axis=-1)[:, None])
        t0 = time.time()
        with rec.span("decode", category="serve", batch=B,
                      tokens=args.gen):
            for i in range(args.gen):
                out_tokens.append(tok)
                with rec.span("decode_step", category="serve",
                              step_num=i):
                    logits, caches = decode(params, caches, tok, S + i)
                    tok = reput_tok(jnp.argmax(logits, axis=-1)[:, None])
            jax.block_until_ready(logits)
            t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    report = {
        "arch": cfg.name, "batch": B, "prompt_len": S, "generated": args.gen,
        "strategy": args.strategy or None, "devices": n_dev,
        "mesh": list(mesh.shape.values()),
        "prefill_s": round(t_prefill, 3), "decode_s": round(t_decode, 3),
        "decode_tok_per_s": round(B * args.gen / max(t_decode, 1e-9), 1),
        "sample_tokens": gen[0, :8].tolist(),
    }
    if rec.enabled:
        obs_metrics.gauge("prefill_ms").set(t_prefill * 1e3)
        obs_metrics.gauge("decode_tok_per_s").set(
            B * args.gen / max(t_decode, 1e-9))
        for s in rec.find("decode_step"):
            obs_metrics.histogram("decode_dispatch_ms").observe(
                s.duration_s * 1e3)
        os.makedirs(args.trace_dir, exist_ok=True)
        write_jsonl(os.path.join(args.trace_dir, "trace.jsonl"), rec,
                    metrics=obs_metrics.to_dict(),
                    meta={"arch": cfg.name, "mode": "serve",
                          "strategy": args.strategy or None,
                          "devices": n_dev})
        report["trace"] = {"dir": args.trace_dir,
                           "spans": len(rec.spans)}
    print(json.dumps(report))
    # arrays for in-process callers that check the output; not printed
    return {**report, "tokens": gen, "prompt_logits": prompt_logits}


if __name__ == "__main__":
    main()
