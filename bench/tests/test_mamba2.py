"""The Mamba2 cell's program on the CPU at a cut size: against the plain
reference where the SSD decays underflow, and the scope that splits the
block's device time between the SSD scan and the rest of the block."""
import dataclasses
import gzip
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest

from bench import program
from bench.metrics import ssd_ms, ssd_scan_bwd_ms, ssd_scan_fwd_ms
from bench.reference import weights as W
from bench.reference.model import loss_sum
from bench.tests import tiny
from bench.trace import scopes
from bench.trace.reduce import Op, Trace, _nest

CONFIG = "mamba2-370m.1chip"
DT = 3.5        # softplus(dt_bias): dt·|A| reaches 56 at A = -16


def test_strong_decay_program_matches_reference():
    """dt_bias puts dt near 3.5, so dt·|A| passes 44 at single positions
    and each chunk of 32 sums dt·A to about -1e3: the program's loss and
    every gradient stay finite and match the float32 reference."""
    import repro.models.model as MD
    cfg = tiny.load_config(CONFIG)
    m = dict(cfg["model"], dtype="float32", param_dtype="float32",
             n_layers=2)
    with tiny.program_cut_to(cfg):
        pcfg = program.model_config(cfg["arch"], dict(
            m, dtype="bfloat16", param_dtype="bfloat16"))
    pcfg = dataclasses.replace(pcfg, dtype="float32", param_dtype="float32")
    key = W.seed_key(2 ** 31 + 9)
    values = W.make_params(m, key)
    bias = "segments/0/mamba/dt_bias"
    values[bias] = jnp.full_like(values[bias], math.log(math.expm1(DT)))
    skel = jax.eval_shape(lambda: MD.init_model(key, pcfg))
    params = program._with_params(skel, values)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                m["vocab_size"])

    def prog_loss(p):
        return MD.loss_fn(p, pcfg, {"tokens": tokens}, remat="full")[0]

    def ref_loss(v):
        return loss_sum(v, m, tokens) / (tokens.size - tokens.shape[0])

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog_loss)(params)
        lr, gr = jax.value_and_grad(ref_loss)(values)
    gp = program._leaf_values(gp)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in gp.values())
    assert float(lp) == pytest.approx(float(lr), rel=2e-5)
    errs = {k: float(jnp.max(jnp.abs(gp[k] - g)) / jnp.max(jnp.abs(g)))
            for k, g in gr.items()}
    # the program rounds the logits to bf16 (2^-9 relative) even at f32
    assert max(errs.values()) < 4e-3, errs


def _op_names(text):
    """{instruction: op_name} of the instructions that carry one."""
    out = {}
    for line in text.splitlines():
        m = scopes._INSTR.match(line)
        n = m and scopes._OP_NAME.search(line)
        if n:
            out[m.group(1)] = n.group(1)
    return out


def test_ssd_scan_scope_splits_the_block():
    """A cut mamba2 train step (remat full) compiled on the CPU: the
    scan's ops, forward, recompute and backward, map to ``ssd_scan``;
    the projections and the conv to ``ssd``."""
    from repro.obs import LAYER_SCOPES
    c = tiny.cell(CONFIG)
    seq, batch = c.traffic["seq_len"], c.config["global_batch"]
    with tiny.program_cut_to(c.config):
        prog = program.build(c.config, seq, jax.devices()[:1])
        seed = W.seed_key(0)
        state = jax.eval_shape(prog.init, seed,
                               jax.eval_shape(prog.weights, seed))
        text = scopes.compiled_text(prog, state, {
            "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)})
    keys = scopes.layer_keys(text, LAYER_SCOPES)
    names = _op_names(text)

    def keys_of(pattern):
        return {keys[i] for i, n in names.items() if re.search(pattern, n)}

    assert keys_of(r"/ssd_scan/") == {("ssd_scan", "fwd"),
                                     ("ssd_scan", "bwd")}
    recompute = keys_of(r"rematted_computation/ssd/ssd_scan/.*dot_general")
    assert recompute == {("ssd_scan", "fwd")}
    # in_proj and out_proj
    assert keys_of(r"/ssd/\.\.\.d,df->\.\.\.f/dot_general") == {
        ("ssd", "fwd"), ("ssd", "bwd")}
    # the causal conv's left padding and its taps
    assert keys_of(r"/ssd/(jit\(_pad\)/pad|pad)$") == {("ssd", "fwd"),
                                                       ("ssd", "bwd")}
    assert {k[0] for k in keys.values() if k} == {
        "embed", "ssd", "ssd_scan", "head", "optimizer"}


def test_dense_mapping_unchanged():
    """The dense cell's step as the chip compiled it (the recorded text
    of ``test_scopes``) maps every instruction as it did before the
    ``ssd_scan`` scope was added."""
    from repro.obs import LAYER_SCOPES
    path = os.path.join(os.path.dirname(__file__), "data",
                        "smollm_2layer_scoped.hlo.txt.gz")
    with gzip.open(path, "rt") as f:
        text = f.read()
    before = tuple(s for s in LAYER_SCOPES if s != "ssd_scan")
    assert "ssd_scan" in LAYER_SCOPES
    assert scopes.layer_keys(text, LAYER_SCOPES) == scopes.layer_keys(
        text, before)


TEXT = """HloModule m

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %dot.1 = f32[4]{0} dot(%a, %a), metadata={op_name="jit(f)/jvp()/checkpoint/ssd/...d,df->...f/dot_general"}
  %ssd_scan_fwd.2 = f32[4]{0} custom-call(%dot.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp()/checkpoint/ssd/ssd_scan/ssd_scan_fwd/pallas_call"}
  %fusion.3 = f32[4]{0} multiply(%ssd_scan_fwd.2, %a), metadata={op_name="jit(f)/transpose(jvp())/checkpoint/ssd/ssd_scan/mul"}
  ROOT %dot.4 = f32[4]{0} dot(%fusion.3, %a), metadata={op_name="jit(f)/transpose(jvp())/checkpoint/ssd/...d,df->...f/dot_general"}
}
"""


def test_ssd_readers(monkeypatch):
    ops = [Op("%dot.1 = f32[4] dot(f32[4] %a)", 10, 20),
           Op("%ssd_scan_fwd.2 = f32[4] custom-call(f32[4] %a)", 20, 50),
           Op("%fusion.3 = f32[4] multiply(f32[4] %a)", 50, 110),
           Op("%dot.4 = f32[4] dot(f32[4] %a)", 110, 115)]
    _nest(ops)
    tr = Trace({"/device:TPU:0": ops}, [("dispatch", 0, 5),
                                        ("wait", 5, 120)])
    monkeypatch.setattr(scopes, "step_text", lambda cell, chips: TEXT)
    ctx = types.SimpleNamespace(trace=tr, cell=None, chips=1)
    assert ssd_ms.read(ctx) == pytest.approx(15e-6)
    assert ssd_scan_fwd_ms.read(ctx) == pytest.approx(30e-6)
    assert ssd_scan_bwd_ms.read(ctx) == pytest.approx(60e-6)
    # a program without the scan's scope: ``ssd`` holds the scan too, so
    # none of the three has anything to read
    from repro.obs import LAYER_SCOPES
    monkeypatch.setattr(scopes, "layer_scopes", lambda: tuple(
        s for s in LAYER_SCOPES if s != "ssd_scan"))
    for mod in (ssd_ms, ssd_scan_fwd_ms, ssd_scan_bwd_ms):
        assert mod.read(ctx) is None, mod.__name__
