#!/usr/bin/env python3
"""Smoke test of the main path on a TPU, at published widths.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: sharded training only

One process, no device-using children: it calls the train and serve
drivers in-process (``repro.launch.train.main`` / ``serve.main``).

One chip, in order:
  1. the Pallas kernels (flash prefill + decode at qwen2.5-3b and
     smollm-360m widths, the mamba2-370m SSD scan, the int8 wire codec)
     against their float32 references;
  2. train smollm-360m at published widths for a few steps;
  3. serve qwen2.5-3b at published widths, and check the logits at the
     last prompt position against one full forward over the prompt.

Four chips (``--chips 4``): smollm-360m trained with the sharded
shard_map step on a 2x2 mesh (fsdp_tp; once with an fp32 wire, once
with int8 + error feedback) against the same steps and data on one of
the four devices.

Weights are random, from a seed. Any failed phase exits non-zero; the
last line of stdout is one JSON object naming the device. With no
accelerator, or outside a checkout of the repo, it exits non-zero
before printing any result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def setup(chips: int):
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SmokeFailure(f"no repro package under {SRC}: run from a "
                           f"checkout of the repository")
    if os.environ.get("REPRO_DISABLE_PALLAS", "0") != "0":
        raise SmokeFailure("REPRO_DISABLE_PALLAS is set: this smoke test "
                           "checks the Pallas path")
    sys.path.insert(0, SRC)
    import jax

    from repro.kernels.ops import use_pallas
    from repro.launch.mesh import enable_compile_cache

    cache = enable_compile_cache()
    devs = jax.devices()
    print(f"devices: {devs}", flush=True)
    print(f"device_kind={devs[0].device_kind} compile_cache={cache}",
          flush=True)
    check(devs[0].platform == "tpu",
          f"platform is {devs[0].platform!r}, not 'tpu'")
    check(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    check(use_pallas(), "use_pallas() is false: the kernels would not run")
    return devs


def report(name: str, err: float, tol: float, why: str) -> None:
    print(f"  {name}: max err {err!r} <= tol {tol!r} ({why})", flush=True)
    check(err <= tol, f"{name}: max err {err!r} > tol {tol!r}")


# ---------------------------------------------------------------------------
# 1. kernels
# ---------------------------------------------------------------------------

def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist.compression import quantize_int8
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.quantize import (dequantize_int8_pallas,
                                        quantize_int8_pallas)
    from repro.kernels.ref import attention_ref, ssd_ref
    from repro.kernels.ssd_scan import ssd_scan
    from repro.models.attention import AttnSpec

    print("phase kernels", flush=True)
    key = jax.random.PRNGKey(0)
    f32 = jnp.float32

    def max_err(a, b):
        return float(np.max(np.abs(np.asarray(a, np.float32)
                                   - np.asarray(b, np.float32))))

    spec = AttnSpec(causal=True)
    S, batch_dec = 2048, 8
    # the outputs are bf16: half an ulp of |o| < 2 is 3.9e-3; the
    # repo's interpret-mode bf16 tolerance (tests/test_kernels.py) is 2e-2
    flash_tol = 2e-2
    for arch, (hq, hkv, hd) in {"qwen2.5-3b": (16, 2, 128),
                                "smollm-360m": (15, 5, 64)}.items():
        ks = jax.random.split(jax.random.fold_in(key, hq), 6)
        q = jax.random.normal(ks[0], (1, S, hq, hd), f32).astype(jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, S, hkv, hd), f32).astype(jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, S, hkv, hd), f32).astype(jnp.bfloat16)
        pos = jnp.arange(S)
        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, pos, pos, spec, block_kv=1024))(q, k, v)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, k, v: attention_ref(
                q.astype(f32), k.astype(f32), v.astype(f32), pos, pos,
                spec))(q, k, v)
        report(f"flash prefill {arch} S={S}", max_err(out, ref), flash_tol,
               "bf16 output")

        qd = jax.random.normal(ks[3], (batch_dec, 1, hq, hd),
                               f32).astype(jnp.bfloat16)
        kc = jax.random.normal(ks[4], (batch_dec, S, hkv, hd),
                               f32).astype(jnp.bfloat16)
        vc = jax.random.normal(ks[5], (batch_dec, S, hkv, hd),
                               f32).astype(jnp.bfloat16)
        qpos = jnp.array([S - 1])
        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, qpos, pos, spec, block_kv=1024))(qd, kc, vc)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda q, k, v: attention_ref(
                q.astype(f32), k.astype(f32), v.astype(f32), qpos, pos,
                spec))(qd, kc, vc)
        report(f"flash decode {arch} cap={S}", max_err(out, ref), flash_tol,
               "bf16 output")

    # mamba2-370m: 32 heads of 64, state 128, one B/C group, chunk 256
    b, l, h, p, g, n = 1, S, 32, 64, 1, 128
    ks = jax.random.split(jax.random.fold_in(key, 1), 5)
    x = (jax.random.normal(ks[0], (b, l, h, p)) * 0.5).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.2
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, l, g, n)) * 0.3).astype(jnp.bfloat16)
    C = (jax.random.normal(ks[4], (b, l, g, n)) * 0.3).astype(jnp.bfloat16)
    D = jnp.ones((h,))
    y, st = jax.jit(lambda *a: ssd_scan(*a, chunk=256))(x, dt, A, B, C, D)
    with jax.default_matmul_precision("highest"):
        yr, str_ = jax.jit(lambda x, dt, A, B, C, D: ssd_ref(
            x.astype(f32), dt, A, B.astype(f32), C.astype(f32), D,
            chunk=256))(x, dt, A, B, C, D)
    # y and the state come out in bf16 (8 significant bits): allow 1% of
    # the largest reference value — two bf16 ulps, plus f32 order noise
    for name, got, want in (("y", y, yr), ("final state", st, str_)):
        scale = float(np.max(np.abs(np.asarray(want, np.float32))))
        report(f"ssd_scan mamba2-370m {name} (max |ref| {scale!r})",
               max_err(got, want), 1e-2 * scale,
               "bf16 output: 1% of max |ref|")

    # int8 wire codec on one [4096, 1024] gradient leaf, against the
    # codec's definition computed on the host
    leaf = jax.random.normal(jax.random.fold_in(key, 2), (4096, 1024)) * 3
    qk, sk = jax.jit(quantize_int8_pallas)(leaf)
    deq = jax.jit(dequantize_int8_pallas)(qk, sk)
    xf = np.asarray(leaf, np.float32)
    s_ref = np.float32(np.max(np.abs(xf))) / np.float32(127.0)
    report("int8 scale vs max|x|/127, in f32 ulps",
           abs(float(sk) - float(s_ref)) / float(np.spacing(s_ref)), 1.0,
           "XLA may divide by 127 as a multiply by its reciprocal")
    q_ref = np.clip(np.round(xf / s_ref), -127, 127)
    steps = int(np.max(np.abs(np.asarray(qk, np.int32) - q_ref)))
    report("int8 quantize, levels off the host codec", float(steps), 1.0,
           "a value at a half-level may round the other way")
    report("int8 round trip |deq - x| / scale", max_err(deq, leaf)
           / float(sk), 0.5 + 1e-3, "round-to-nearest: half a level")
    # the model's dispatcher picks the kernel on the chip
    q2, _ = jax.jit(quantize_int8)(leaf)
    check(bool(np.array_equal(np.asarray(q2), np.asarray(qk))),
          "repro.dist.compression.quantize_int8 did not dispatch the "
          "Pallas codec")


# ---------------------------------------------------------------------------
# 2. train
# ---------------------------------------------------------------------------

def memory(dev) -> str:
    stats = dev.memory_stats()
    check(stats is not None and "peak_bytes_in_use" in stats,
          f"{dev} reports no peak_bytes_in_use")
    return (f"bytes_in_use {stats['bytes_in_use']} peak_bytes_in_use "
            f"{stats['peak_bytes_in_use']} of {stats.get('bytes_limit')}")


def phase_train(dev) -> None:
    import numpy as np

    from repro.configs import get_config
    from repro.launch import train

    print(f"phase train smollm-360m ({memory(dev)})", flush=True)
    out = train.main(["--arch", "smollm-360m", "--steps", "6",
                      "--batch", "8", "--seq", "1024", "--remat", "full",
                      "--log-every", "1"])
    losses = out["losses"]
    check(len(losses) == 6, f"{len(losses)} losses for 6 steps")
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    vocab = get_config("smollm-360m").vocab_size
    ln_v = math.log(vocab)
    report("train step-0 loss vs ln(vocab)", abs(losses[0] - ln_v), 0.5,
           f"random init predicts ~uniform: ln({vocab}) = {ln_v!r}")
    # the first step pays the compile: the median of the rest
    print(f"  step_ms median (steps 1..5) "
          f"{float(np.median(out['step_ms'][1:]))!r}; not a benchmark",
          flush=True)
    print(f"  {memory(dev)}", flush=True)


# ---------------------------------------------------------------------------
# 3. serve
# ---------------------------------------------------------------------------

def phase_serve(dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.data import make_batch_for
    from repro.launch import serve
    from repro.models import model as MD

    print(f"phase serve qwen2.5-3b ({memory(dev)})", flush=True)
    batch, prompt_len, gen = 4, 128, 16
    cfg = get_config("qwen2.5-3b")
    # the one-pass forward first, over the prompt and seeded weights the
    # driver will use; its weights are freed before the driver makes its
    # own (two 6.2 GB copies plus init temporaries crowd 16 GB)
    params = MD.init_model(jax.random.PRNGKey(0), cfg)
    prompt = make_batch_for(cfg, batch, prompt_len, step=0,
                            seed=0)["tokens"]
    full = jax.jit(lambda p, t: MD.prefill(p, cfg, {"tokens": t})[0])(
        params, prompt)
    full = np.asarray(full, np.float32)
    del params

    out = serve.main(["--arch", "qwen2.5-3b", "--batch", str(batch),
                      "--prompt-len", str(prompt_len), "--gen", str(gen)])
    tokens = np.asarray(out["tokens"])
    check(tokens.shape == (batch, gen), f"tokens {tokens.shape}")
    check(bool(((tokens >= 0) & (tokens < cfg.vocab_size)).all()),
          "token ids out of the vocabulary")
    served = np.asarray(out["prompt_logits"], np.float32)
    check(served.shape == full.shape == (batch, cfg.vocab_size),
          f"logits {served.shape} vs {full.shape}")
    check(bool(np.isfinite(served).all()), "non-finite served logits")
    # both are bf16 logits of 36 bf16 layers, reached one token at a
    # time through the cache vs in one pass: allow 5% of the largest
    # logit
    scale = float(np.max(np.abs(full)))
    report(f"serve last-prompt logits vs full forward (max |logit| "
           f"{scale!r})", float(np.max(np.abs(served - full))),
           0.05 * scale, "bf16 activations, two summation orders")
    agree = int((served.argmax(-1) == full.argmax(-1)).sum())
    print(f"  greedy token agrees on {agree}/{batch} rows", flush=True)
    print(f"  {memory(dev)}", flush=True)


# ---------------------------------------------------------------------------
# four chips: sharded training against one chip
# ---------------------------------------------------------------------------

def phase_four_chips() -> None:
    from repro.launch import train

    print("phase four chips: smollm-360m fsdp_tp on 2x2 vs one device",
          flush=True)
    common = ["--arch", "smollm-360m", "--steps", "4", "--batch", "8",
              "--seq", "1024", "--remat", "full", "--log-every", "1"]
    ref = train.main(common + ["--devices", "1"])
    check(ref["mesh"] == [1, 1], f"reference mesh {ref['mesh']}")
    runs = {}
    for comp in ("none", "int8_ef"):
        out = train.main(common + ["--devices", "4", "--mode", "sharded",
                                   "--strategy", "fsdp_tp",
                                   "--compression", comp])
        pl = out["placement"]
        print(f"  {comp}: mesh {out['mesh']} devices {pl['mesh_devices']} "
              f"largest param {pl['largest_param']} shards {pl['shards']}",
              flush=True)
        check(len({d for d, _ in pl["shards"]}) == 4,
              f"{comp}: shards sit on {pl['shards']}")
        runs[comp] = out["losses"]
    print(f"  losses one device   {ref['losses']}", flush=True)
    for comp, losses in runs.items():
        print(f"  losses 2x2 {comp:8s} {losses}", flush=True)
        check(all(math.isfinite(x) for x in losses), f"{comp}: {losses}")
    diff = lambda a: max(abs(x - y) for x, y in zip(a, ref["losses"]))
    # same weights and data; bf16 activations reduced in another order
    # (sharded matmuls, psum over the mesh): a few bf16 ulps of a loss
    # near 10 is ~0.05
    report("2x2 none vs one device, max |loss diff|", diff(runs["none"]),
           0.05, "bf16, sharded reduction order")
    # step 0 precedes any update, so the wire format cannot move it
    report("2x2 int8_ef vs one device, step-0 loss diff",
           abs(runs["int8_ef"][0] - ref["losses"][0]), 0.05,
           "no update yet")
    # each later step applies gradients rounded to 127 levels of the
    # leaf's max (error feedback repays the rounding a step later)
    report("2x2 int8_ef vs one device, max |loss diff|",
           diff(runs["int8_ef"]), 0.25, "int8 gradient rounding")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: kernels, train and serve on one chip; "
                         "4: the sharded training comparison only")
    args = ap.parse_args(argv)
    try:
        devs = setup(args.chips)
        if args.chips == 4:
            phase_four_chips()
        else:
            phase_kernels()
            phase_train(devs[0])
            phase_serve(devs[0])
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    d = devs[0]
    print(json.dumps({"ok": True,
                      "device": {"platform": d.platform,
                                 "kind": d.device_kind,
                                 "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
