"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (tests/test_kernels.py) checks numerics but not what the
TPU compiler accepts: block shapes that break the (8, 128) tiling, 1-D
vectors whose Mosaic layout disagrees with XLA's, scalars stored to
VMEM. Here each kernel is lowered and compiled for one chip of a
described (not attached) v5e:2x2, so a layout the compiler refuses
fails a test instead of a chip run, and each is checked to carry its
kernel name into the compiled custom-call. A cut train step checks
that the program's layer scopes reach the ops the chip runs, and the
shard_map step compiles for all four chips of the 2x2. Nothing
executes.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, and a worker that
cannot load it skips this file's tests instead of failing collection.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantize import (dequantize_int8_pallas,
                                    quantize_int8_pallas)
from repro.kernels.ssd_scan import ssd_scan
from repro.models.attention import AttnSpec


@pytest.fixture(scope="module")
def v5e():
    """The described v5e:2x2 topology."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernels(txt):
    """Names of the Pallas kernels in a compiled module: a named
    ``pallas_call`` names its custom-call instruction (``%flash_fwd.3``)."""
    return {m.group(1) for m in re.finditer(
        r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*custom_call_target='
        r'"tpu_custom_call"', txt)}


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (q heads, kv heads, head dim): qwen2.5-3b and smollm-360m
ATTN_WIDTHS = {"qwen2.5-3b": (16, 2, 128), "smollm-360m": (15, 5, 64)}
SEQ = 2048
KV_BLOCK = 1024        # ModelConfig.attn_block, what the model passes


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_prefill_compiles(one_chip, arch):
    hq, hkv, hd = ATTN_WIDTHS[arch]
    spec = AttnSpec(causal=True)
    shapes = [_spec(one_chip, (1, SEQ, hq, hd), jnp.bfloat16),
              _spec(one_chip, (1, SEQ, hkv, hd), jnp.bfloat16),
              _spec(one_chip, (1, SEQ, hkv, hd), jnp.bfloat16),
              _spec(one_chip, (SEQ,), jnp.int32),
              _spec(one_chip, (SEQ,), jnp.int32)]
    txt = _compiled_text(
        lambda q, k, v, qp, kp: flash_attention(q, k, v, qp, kp, spec,
                                                block_kv=KV_BLOCK), *shapes)
    assert _kernels(txt) == {"flash_fwd"}


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_decode_compiles(one_chip, arch):
    hq, hkv, hd = ATTN_WIDTHS[arch]
    batch, cap = 8, SEQ
    spec = AttnSpec(causal=True)
    shapes = [_spec(one_chip, (batch, 1, hq, hd), jnp.bfloat16),
              _spec(one_chip, (batch, cap, hkv, hd), jnp.bfloat16),
              _spec(one_chip, (batch, cap, hkv, hd), jnp.bfloat16),
              _spec(one_chip, (1,), jnp.int32),
              _spec(one_chip, (cap,), jnp.int32)]
    txt = _compiled_text(
        lambda q, k, v, qp, kp: flash_attention(q, k, v, qp, kp, spec,
                                                block_kv=KV_BLOCK), *shapes)
    assert _kernels(txt) == {"flash_fwd"}


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_grad_compiles(one_chip, arch):
    """Training's path: the forward, and the backward's log-sum-exp, dQ
    and dK/dV kernels, all accepted by Mosaic at real widths."""
    hq, hkv, hd = ATTN_WIDTHS[arch]
    spec = AttnSpec(causal=True)
    shapes = [_spec(one_chip, (1, SEQ, hq, hd), jnp.bfloat16),
              _spec(one_chip, (1, SEQ, hkv, hd), jnp.bfloat16),
              _spec(one_chip, (1, SEQ, hkv, hd), jnp.bfloat16),
              _spec(one_chip, (SEQ,), jnp.int32),
              _spec(one_chip, (SEQ,), jnp.int32)]

    def loss(q, k, v, qp, kp):
        # a scope under the transforms, as the model's layers give, so
        # the kernels keep their own names
        with jax.named_scope("attention"):
            o = flash_attention(q, k, v, qp, kp, spec, block_kv=KV_BLOCK)
        return jnp.sum(o.astype(jnp.float32))

    txt = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *shapes)
    assert _kernels(txt) == {"flash_fwd", "flash_bwd_lse", "flash_bwd_dq",
                             "flash_bwd_dkv"}


def test_ssd_scan_compiles_mamba2_widths(one_chip):
    # mamba2-370m: 32 heads of 64, state 128, one B/C group, chunk 256
    b, l, h, p, g, n = 1, SEQ, 32, 64, 1, 128
    shapes = [_spec(one_chip, (b, l, h, p), jnp.bfloat16),
              _spec(one_chip, (b, l, h), jnp.float32),
              _spec(one_chip, (h,), jnp.float32),
              _spec(one_chip, (b, l, g, n), jnp.bfloat16),
              _spec(one_chip, (b, l, g, n), jnp.bfloat16),
              _spec(one_chip, (h,), jnp.float32)]
    txt = _compiled_text(lambda *a: ssd_scan(*a, chunk=256), *shapes)
    assert _kernels(txt) == {"ssd_scan_fwd"}


def _ssd_grad_text(sharding, h, p, g, n):
    """Training's path through the scan compiled, one layer of 2048
    positions at the given widths: the value too, since a gradient alone
    does not need the forward's results."""
    b, l = 1, SEQ
    shapes = [_spec(sharding, (b, l, h, p), jnp.bfloat16),
              _spec(sharding, (b, l, h), jnp.float32),
              _spec(sharding, (h,), jnp.float32),
              _spec(sharding, (b, l, g, n), jnp.bfloat16),
              _spec(sharding, (b, l, g, n), jnp.bfloat16),
              _spec(sharding, (h,), jnp.float32)]

    def loss(*a):
        with jax.named_scope("ssd_scan"):
            y, st = ssd_scan(*a, chunk=256)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(st.astype(
            jnp.float32))

    return _compiled_text(
        jax.value_and_grad(loss, argnums=tuple(range(6))), *shapes)


SSD_KERNELS = {"ssd_scan_fwd", "ssd_scan_bwd_states", "ssd_scan_bwd_grads"}


def test_ssd_scan_grad_compiles_mamba2_widths(one_chip):
    """mamba2-370m (32 heads of 64, state 128, one B/C group, chunk 256):
    the Pallas forward and the backward's two Pallas kernels. The
    benchmark's roofline readers take the gradients call for the
    backward's shapes and never for the forward's."""
    from bench.metrics import ssd_scan_bwd_roofline, ssd_scan_fwd_roofline
    txt = _ssd_grad_text(one_chip, 32, 64, 1, 128)
    assert _kernels(txt) == SSD_KERNELS
    grads = [line.strip() for line in txt.splitlines()
             if re.match(r"\s*%ssd_scan_bwd_grads[.\d]* = ", line)]
    assert len(grads) == 1
    assert ssd_scan_bwd_roofline.call_shape(grads[0]) == (
        1, SEQ, 32, 64, 1, 128, 2)
    assert ssd_scan_fwd_roofline.call_shape(grads[0]) is None


def test_ssd_scan_grad_compiles_zamba2_widths(one_chip):
    """zamba2-1.2b's SSD blocks: 64 heads of 64, state 64, one group."""
    assert _kernels(_ssd_grad_text(one_chip, 64, 64, 1, 64)) == SSD_KERNELS


LEAF = (4096, 1024)    # one gradient leaf on the int8 wire


def test_quantize_int8_compiles(one_chip):
    txt = _compiled_text(quantize_int8_pallas,
                         _spec(one_chip, LEAF, jnp.float32))
    assert _kernels(txt) == {"int8_absmax", "int8_quantize"}


def test_dequantize_int8_compiles(one_chip):
    txt = _compiled_text(dequantize_int8_pallas,
                         _spec(one_chip, LEAF, jnp.int8),
                         _spec(one_chip, (), jnp.float32))
    assert _kernels(txt) == {"int8_dequantize"}


def test_train_step_ops_carry_layer_scopes(one_chip, monkeypatch):
    """smollm-360m's train step cut to 2 layers (batch 2 x 512, remat
    full, Pallas on) compiled for one v5e: every fusion that computes
    carries a layer scope, what carries none only moves the layer scan's
    data, the flash kernel's two calls (forward and remat's recompute)
    are attention's forward, and the backward's kernels its backward."""
    import dataclasses
    from bench.trace.scopes import MOVES_DATA, layer_keys
    from repro.configs import TrainConfig, get_config
    from repro.kernels import ops
    from repro.obs import LAYER_SCOPES
    from repro.train.step import init_train_state, make_train_step
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=2)
    tcfg = TrainConfig(remat_policy="full")
    state = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg))
    state = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), state)
    batch = {"tokens": _spec(one_chip, (2, 512), jnp.int32)}
    txt = jax.jit(make_train_step(cfg, tcfg)).lower(
        state, batch).compile().as_text()
    keys = layer_keys(txt, LAYER_SCOPES)
    body, opcodes, fusions = None, {}, []
    for line in txt.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+) ", line)
        if head:
            body = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (?:\(.*?\)|\S+) "
                     r"([\w\-]+)\(", line)
        if m:
            opcodes.setdefault(body, set()).add(m.group(2))
            calls = re.search(r"\bcalls=%([\w.\-]+)", line)
            if m.group(2) == "fusion" and calls:
                fusions.append((m.group(1), calls.group(1)))
    computing = [f for f, c in fusions if opcodes[c] - MOVES_DATA]
    assert len(computing) > 50
    assert all(keys.get(f) is not None for f in computing), \
        [f for f in computing if keys.get(f) is None]
    assert all(opcodes[c] <= MOVES_DATA for f, c in fusions
               if keys.get(f) is None)
    flash = [n for n in keys if re.fullmatch(r"flash_fwd(\.\d+)?", n)]
    assert len(flash) == 2
    assert {keys[n] for n in flash} == {("attention", "fwd")}
    bwd = [n for n in keys if re.fullmatch(r"flash_bwd_\w+?(\.\d+)?", n)]
    assert {re.sub(r"\.\d+$", "", n) for n in bwd} == {
        "flash_bwd_lse", "flash_bwd_dq", "flash_bwd_dkv"}
    assert {keys[n] for n in bwd} == {("attention", "bwd")}
    assert {("attention", "bwd"), ("mlp", "fwd"), ("mlp", "bwd"),
            ("head", "bwd"), ("optimizer", "fwd")} <= set(keys.values())


def test_mamba2_step_scan_carries_its_scope(one_chip, monkeypatch):
    """mamba2-370m's train step cut to 2 layers (batch 1 x 512, remat
    full, Pallas on) compiled for one v5e: the scan kernel's two calls a
    layer (forward and remat's recompute) are ``ssd_scan``'s forward, the
    custom VJP's backward kernels its backward, and the projections
    ``ssd``'s."""
    import dataclasses
    from bench.trace.scopes import layer_keys
    from repro.configs import TrainConfig, get_config
    from repro.kernels import ops
    from repro.obs import LAYER_SCOPES
    from repro.train.step import init_train_state, make_train_step
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("mamba2-370m"), n_layers=2)
    tcfg = TrainConfig(remat_policy="full")
    state = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg))
    state = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), state)
    batch = {"tokens": _spec(one_chip, (1, 512), jnp.int32)}
    txt = jax.jit(make_train_step(cfg, tcfg)).lower(
        state, batch).compile().as_text()
    keys = layer_keys(txt, LAYER_SCOPES)
    scan = [k for k in keys if re.fullmatch(r"ssd_scan_fwd(\.\d+)?", k)]
    assert len(scan) == 2
    assert {keys[k] for k in scan} == {("ssd_scan", "fwd")}
    assert {("ssd_scan", "bwd"), ("ssd", "fwd"), ("ssd", "bwd")} <= set(
        keys.values())
    bwd = [k for k in keys if re.fullmatch(r"ssd_scan_bwd_\w+?(\.\d+)?", k)]
    assert {re.sub(r"\.\d+$", "", k) for k in bwd} == {
        "ssd_scan_bwd_states", "ssd_scan_bwd_grads"}
    assert {keys[k] for k in bwd} == {("ssd_scan", "bwd")}


def test_sharded_step_compiles_for_four_chips(v5e, monkeypatch):
    """smollm-360m at published widths, cut to 2 layers, as the shard_map
    step trains it on a (data 2, model 2) mesh of the v5e:2x2's four
    chips: fsdp_tp, bf16, remat full, batch 4 x 2048. The compiled step
    carries the flash forward and the backward's three kernels, and each
    model rank's MLP hidden holds half of d_ff (2560 / 2)."""
    import dataclasses
    from repro.configs import TrainConfig, get_config
    from repro.kernels import ops
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import batch_shardings
    from repro.models.layers import tp_probe_sink
    from repro.train.step import (init_sharded_train_state,
                                  make_sharded_train_step,
                                  sharded_state_shardings,
                                  sharded_state_specs)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=2)
    assert cfg.dtype == "bfloat16" and cfg.d_ff == 2560
    tcfg = TrainConfig(remat_policy="full")
    mesh = make_mesh((2, 2), ("data", "model"), devices=v5e.devices)
    specs = sharded_state_specs(cfg, tcfg, mesh, "fsdp_tp")
    st_shard = sharded_state_shardings(cfg, tcfg, mesh, "fsdp_tp",
                                       specs=specs)
    # the jit's shardings place the shapes on the described chips
    state = jax.eval_shape(
        lambda: init_sharded_train_state(jax.random.PRNGKey(0), cfg, tcfg,
                                         mesh))
    batch = {"tokens": jax.ShapeDtypeStruct((4, SEQ), jnp.int32)}
    b_shard = batch_shardings(batch, mesh)
    step = jax.jit(make_sharded_train_step(cfg, tcfg, mesh, "fsdp_tp",
                                           state_specs=specs),
                   in_shardings=(st_shard, b_shard),
                   out_shardings=(st_shard, None))
    with tp_probe_sink([]) as rec:
        lowered = step.lower(state, batch)
    hidden = {tuple(shape) for tag, shape in rec if tag == "mlp_hidden"}
    assert hidden and all(h[-1] == 1280 for h in hidden), hidden
    txt = lowered.compile().as_text()
    assert _kernels(txt) == {"flash_fwd", "flash_bwd_lse", "flash_bwd_dq",
                             "flash_bwd_dkv"}
