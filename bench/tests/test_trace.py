"""The trace reduction, on a trace recorded on a TPU v5e chip:
smollm-360m cut to 2 layers, batch 2 × 512, two train steps with the
harness's host spans (prepare, dispatch, wait), Pallas kernels on."""
import os
import types

import pytest

from bench.metrics import flash_fwd_roofline, ssd_scan_fwd_roofline
from bench.trace.reduce import (Op, Trace, _nest, clip, operand_shapes,
                                result_shapes, subtract, union)

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "smollm_tiny_2layer.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return Trace.load(DATA)


def test_interval_arithmetic():
    assert union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert clip([(0, 5), (8, 9)], 1, 8.5) == [(1, 5), (8, 8.5)]


def test_self_time_of_nested_ops():
    ops = [Op("%while.1 = f32[] while(f32[] %a)", 0, 10),
           Op("%fusion.1 = f32[] fusion(f32[] %a)", 1, 4),
           Op("%fusion.2 = f32[] fusion(f32[] %a)", 5, 9),
           Op("%copy.1 = f32[] copy(f32[] %a)", 12, 13)]
    _nest(ops)
    assert [o.self_ns for o in ops] == [3, 3, 4, 1]
    assert [o.leaf for o in ops] == [False, True, True, True]


def test_exposed_collective_time():
    ops = [Op("%fusion.1 = f32[8] fusion(f32[8] %a)", 0, 4),
           Op("%all-reduce.1 = f32[8] all-reduce(f32[8] %a)", 2, 7),
           Op("%all-gather.2 = f32[8] all-gather(f32[4] %b)", 9, 10)]
    _nest(ops)
    tr = Trace({"/device:TPU:0": ops}, [("dispatch", 0, 10)])
    assert tr.collective_exposed_s() == pytest.approx(4e-9)
    tr = Trace({"/device:TPU:0": ops[:1]}, [("dispatch", 0, 10)])
    assert tr.collective_exposed_s() is None


def test_shapes_from_hlo_text():
    text = ("%closed_call.10 = bf16[30,512,64]{2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(bf16[30,512,64]{2,1,0:T(8,128)(2,1)S(1)} %b.5, "
            "s32[512,1]{1,0:T(8,128)S(1)} %iota.36), "
            'custom_call_target="tpu_custom_call"')
    assert operand_shapes(text) == [("bf16", (30, 512, 64)),
                                    ("s32", (512, 1))]
    ssd = ("%closed_call.19 = (bf16[2,32,512,64]{3,2,1,0}, "
           "bf16[2,32,64,128]{3,2,1,0}) custom-call("
           "bf16[2,32,512,64]{3,2,1,0} %a, f32[2,32,512,1]{3,2,1,0} %b, "
           "f32[2,32,1,512]{3,2,1,0} %c, f32[32]{0} %d, "
           "bf16[2,1,512,128]{3,2,1,0} %e, bf16[2,1,512,128]{3,2,1,0} %f, "
           'f32[32]{0} %g), custom_call_target="tpu_custom_call"')
    assert ssd_scan_fwd_roofline.call_shape(ssd) == (2, 512, 32, 64, 1,
                                                     128, 2)
    assert flash_fwd_roofline.call_shape(ssd) is None
    assert result_shapes(ssd) == [("bf16", (2, 32, 512, 64)),
                                  ("bf16", (2, 32, 64, 128))]


def _flash_text(result, extra):
    return (f"%closed_call.7 = {result} custom-call("
            "bf16[30,512,64]{2,1,0:T(8,128)(2,1)} %q, "
            "bf16[10,512,64]{2,1,0:T(8,128)(2,1)} %k, "
            "bf16[10,512,64]{2,1,0:T(8,128)(2,1)} %v" + extra +
            '), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("result,extra,want", [
    ("bf16[30,512,64]{2,1,0}", ", s32[512,1]{1,0} %p, s32[1,512]{1,0} %r",
     (30, 10, 512, 512, 64, 2)),
    # another operand (segment ids, say) does not hide the kernel
    ("bf16[30,512,64]{2,1,0}", ", s32[512,1]{1,0} %p, s32[1,512]{1,0} %r, "
     "s32[2,512]{1,0} %seg", (30, 10, 512, 512, 64, 2)),
    ("bf16[30,512,64]{2,1,0}", "", (30, 10, 512, 512, 64, 2)),
    # a backward kernel, with one result per input, is not the forward
    ("(bf16[30,512,64]{2,1,0}, bf16[10,512,64]{2,1,0}, "
     "bf16[10,512,64]{2,1,0})", "", None),
])
def test_flash_forward_signature(result, extra, want):
    assert flash_fwd_roofline.call_shape(_flash_text(result, extra)) == want


def test_recorded_trace(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert [h[0] for h in trace.host] == ["prepare", "dispatch", "wait"] * 2
    assert 0 < trace.busy_s() < trace.window_s() < 0.1
    ops = trace.op_seconds(10)
    assert len(ops) == 10
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = trace.idle_gaps(10)
    assert {g[0] for g in gaps} <= {"prepare", "dispatch", "wait", "other"}
    assert trace.collective_exposed_s() is None


def test_flash_kernel_found_and_below_its_roofline(trace):
    events = trace.kernel_events(
        lambda o: flash_fwd_roofline.call_shape(o.text))
    # 2 steps x 2 layers x (forward + the remat recompute)
    assert len(events) == 8
    assert flash_fwd_roofline.call_shape(events[0].text) == (
        30, 10, 512, 512, 64, 2)
    ctx = types.SimpleNamespace(trace=trace, device_kind="TPU v5 lite")
    share = flash_fwd_roofline.read(ctx)
    assert 0 < share < 100
    assert not trace.kernel_events(
        lambda o: ssd_scan_fwd_roofline.call_shape(o.text))
