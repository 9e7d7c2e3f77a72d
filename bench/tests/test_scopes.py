"""The join of a device trace to the program's layer scopes
(``bench.trace.scopes``), on rules written out and on a pair recorded on
a TPU v5e chip by ``bench/tests/record_scoped_trace.py``: smollm-360m at
published widths cut to 2 layers, batch 2 × 512, two traced steps, and
the compiled step's HLO text."""
import gzip
import os
import shutil
import types

import pytest

from bench.metrics import (attn_bwd_ms, attn_fwd_ms, embed_head_ms,
                           flash_fwd_roofline, mlp_ms, optimizer_ms,
                           unscoped_ms)
from bench.trace import scopes
from bench.trace.reduce import Op, Trace, _nest

DATA = os.path.join(os.path.dirname(__file__), "data")
PAIR = "smollm_2layer_scoped"
SCOPES = ("embed", "attention", "mlp", "moe", "ssd", "head", "optimizer")
LAYER_METRICS = (attn_fwd_ms, attn_bwd_ms, mlp_ms, embed_head_ms,
                 optimizer_ms, unscoped_ms)


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/jvp(embed)/gather", ("embed", "fwd")),
    ("jit(train_step)/transpose(jvp(head))/mul", ("head", "bwd")),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "attention/dot_general", ("attention", "bwd")),
    # remat's recompute inside the backward counts as forward work
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/flash_fwd/pallas_call",
     ("attention", "fwd")),
    ("jit(train_step)/jvp()/while/body/checkpoint/mlp/dot_general",
     ("mlp", "fwd")),
    # the last scope wins: optimizer code called from inside another scope
    ("jit(train_step)/obs:update/optimizer/mul", ("optimizer", "fwd")),
    ("jit(train_step)/jvp()/while/body/dynamic_slice", None),
    ("", None),
])
def test_layer_of(op_name, want):
    assert scopes.layer_of(op_name, SCOPES) == want


TEXT = """HloModule m

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/transpose(jvp())/mlp/mul"}
}

ENTRY %main (a: f32[4]) -> (f32[4], s32[]) {
  %a = f32[4]{0} parameter(0)
  %copy.0 = f32[4]{0:T(128)} copy(%a)
  %fusion.1 = f32[4]{0} fusion(%copy.0), kind=kLoop, calls=%fused_computation.1
  %flash_fwd.2 = f32[4]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(f)/attention/flash_fwd/pallas_call"}
  %copy.3 = f32[4]{0} copy(%flash_fwd.2), metadata={op_name="jit(f)/copy"}
  %constant.1 = s32[] constant(0)
  %add.4 = s32[] add(%constant.1, %constant.1), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple.5 = (f32[4]{0}, s32[]) tuple(%copy.3, %add.4)
}
"""


def test_layer_keys_from_text():
    keys = scopes.layer_keys(TEXT, SCOPES)
    # a fusion with no metadata of its own takes its computation's layer
    assert keys["fusion.1"] == ("mlp", "bwd")
    assert keys["flash_fwd.2"] == ("attention", "fwd")
    # a layout copy is charged to the layer that reads what it moved,
    # else to the layer that wrote it; loop arithmetic stays unscoped
    assert keys["copy.0"] == ("mlp", "bwd")
    assert keys["copy.3"] == ("attention", "fwd")
    assert keys["add.4"] is None


def test_layer_ms_per_step_and_window():
    ops = [Op("%fusion.1 = f32[4] fusion(f32[4] %a)", 10, 30),
           Op("%flash_fwd.2 = f32[4] custom-call(f32[4] %b)", 30, 70),
           Op("%add.4 = s32[] add(s32[] %c)", 70, 80),
           Op("%fusion.1 = f32[4] fusion(f32[4] %a)", 110, 130),
           Op("%copy.3 = f32[4] copy(f32[4] %b)", 130, 170),
           Op("%add.4 = s32[] add(s32[] %c)", 200, 230)]      # outside
    _nest(ops)
    host = [("dispatch", 0, 5), ("wait", 5, 90),
            ("dispatch", 100, 105), ("wait", 105, 190)]
    tr = Trace({"/device:TPU:0": ops}, host)
    ms = scopes.layer_ms(tr, TEXT, SCOPES)
    assert ms == pytest.approx({("mlp", "bwd"): 20e-6,
                                ("attention", "fwd"): 40e-6, None: 5e-6})


def test_readers_read_nothing_without_what_they_need(monkeypatch):
    monkeypatch.setattr(scopes, "step_text", lambda cell, chips: TEXT)
    ctx = types.SimpleNamespace(trace=None, cell=None, chips=1)
    for mod in LAYER_METRICS:
        assert mod.read(ctx) is None, mod.__name__
    # a program without layer scopes, as before they were added
    monkeypatch.setattr(scopes, "layer_scopes", lambda: None)
    ctx.trace = Trace({"/device:TPU:0": []}, [("dispatch", 0, 5)])
    assert attn_fwd_ms.read(ctx) is None


def _named_ops(text):
    """Each instruction's head (name, shape, opcode) and op_name."""
    out = []
    for line in text.splitlines():
        m = scopes._INSTR.match(line)
        if m:
            n = scopes._OP_NAME.search(line)
            out.append((m.group(0), n and n.group(1)))
    return out


def test_step_text_is_the_text_of_the_step_that_ran():
    """The readers' own build of the step compiles to the text, and so
    the instruction names, of the step the harness ran."""
    import jax
    from bench import program
    from bench.reference import weights as W
    from bench.run import step_once
    from bench.tests import tiny

    c = tiny.cell("smollm-360m")
    seq, batch = c.traffic["seq_len"], c.config["global_batch"]
    with tiny.program_cut_to(c.config):
        prog = program.build(c.config, seq, jax.devices()[:1])
        key = W.seed_key(2 ** 31 + 5)
        state = prog.init(key, prog.weights(key))
        tokens = jax.device_put(
            jax.numpy.zeros((batch, seq), jax.numpy.int32),
            prog.batch_sharding)
        state, m = step_once(prog, state, {"tokens": tokens})
        jax.block_until_ready(m["loss"])
        ran = scopes.compiled_text(prog, state, {"tokens": tokens})
        rebuilt = scopes.step_text(c, 1)
    # the texts differ only in where the code was called from
    assert len(_named_ops(ran)) > 100
    assert _named_ops(rebuilt) == _named_ops(ran)
    assert scopes.layer_keys(rebuilt, SCOPES) == scopes.layer_keys(ran,
                                                                   SCOPES)
    keys = scopes.layer_keys(rebuilt, SCOPES)
    assert {k[0] for k in keys.values() if k} >= {
        "embed", "attention", "mlp", "head", "optimizer"}


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("pair")
    xplane = tmp / (PAIR + ".xplane.pb")
    with gzip.open(os.path.join(DATA, PAIR + ".xplane.pb.gz")) as src, \
            open(xplane, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(DATA, PAIR + ".hlo.txt.gz"), "rt") as f:
        text = f.read()
    return Trace.load(str(xplane)), text


def test_recorded_pair_is_small():
    size = sum(os.path.getsize(os.path.join(DATA, PAIR + ext))
               for ext in (".xplane.pb.gz", ".hlo.txt.gz"))
    assert size < 2 * 1024 * 1024


def test_layer_metrics_sum_to_the_window(pair, monkeypatch):
    trace, text = pair
    monkeypatch.setattr(scopes, "step_text", lambda cell, chips: text)
    ctx = types.SimpleNamespace(trace=trace, cell=None, chips=1,
                                device_kind="TPU v5 lite")
    got = {m.__name__.rsplit(".", 1)[-1]: m.read(ctx) for m in LAYER_METRICS}
    lo, hi = trace.window()
    steps = sum(h[0] == "dispatch" for h in trace.host)
    total = sum(o.self_ns for ops in trace.devices.values() for o in ops
                if o.start >= lo and o.end <= hi) / steps / 1e6
    assert steps == 2
    assert sum(got.values()) == pytest.approx(total, rel=1e-9)
    assert got["unscoped_ms"] < 0.05 * total, got
    assert all(v > 0 for v in got.values()), got


def test_named_flash_kernel_still_found(pair):
    trace, text = pair
    events = trace.kernel_events(
        lambda o: flash_fwd_roofline.call_shape(o.text))
    # 2 steps x 2 layers x (forward + the remat recompute)
    assert len(events) == 8
    assert all(e.text.startswith("%flash_fwd") for e in events)
    keys = scopes.layer_keys(text, SCOPES)
    assert {keys[e.text.split(" ", 1)[0][1:]] for e in events} == {
        ("attention", "fwd")}
    ctx = types.SimpleNamespace(trace=trace, device_kind="TPU v5 lite")
    assert 0 < flash_fwd_roofline.read(ctx) < 100
