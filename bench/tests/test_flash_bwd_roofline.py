"""``flash_bwd_roofline`` on a synthetic trace of the backward's three
kernels (instruction texts as the chip's trace names them, at the
smollm-360m cell's shapes), on the recorded trace of a program whose
backward has no such kernels, and against ``flash_fwd_roofline``, which
must not take a backward kernel for a forward."""
import gzip
import os
import shutil
import types

import pytest

from bench.metrics import flash_bwd_roofline, flash_fwd_roofline
from bench.roofline.peaks import peak_for
from bench.trace.reduce import Op, Trace, _nest

DATA = os.path.join(os.path.dirname(__file__), "data")
KIND = "TPU v5 lite"
_T = "{3,2,1,0:T(8,128)(2,1)S(1)}"
_K = "{2,1,0:T(8,128)(2,1)S(1)}"
_STAT = "f32[60,3,1,2048]{3,2,1,0:T(1,128)S(1)}"
_TAIL = (', custom_call_target="tpu_custom_call", '
         'frontend_attributes={kernel_metadata={}}')
LSE = (f"%flash_bwd_lse.7 = {_STAT} custom-call(s32[16]{{0}} %fusion.1, "
       f"s32[20]{{0}} %concatenate.3, bf16[60,3,2048,64]{_T} %bitcast.5, "
       f"bf16[60,2048,64]{_K} %bitcast.6, s32[1,2048]{{1,0}} %bitcast.7, "
       f"s32[2048,1]{{1,0}} %copy.8){_TAIL}")
DQ = (f"%flash_bwd_dq.9 = bf16[60,3,2048,64]{_T} custom-call(s32[16]{{0}} "
      f"%fusion.1, s32[20]{{0}} %concatenate.3, bf16[60,3,2048,64]{_T} "
      f"%bitcast.9, {_STAT} %flash_bwd_lse.7, {_STAT} %fusion.10, "
      f"bf16[60,3,2048,64]{_T} %bitcast.5, bf16[60,2048,64]{_K} %bitcast.6, "
      f"bf16[60,2048,64]{_K} %bitcast.11, s32[2048,1]{{1,0}} %copy.8, "
      f"s32[1,2048]{{1,0}} %bitcast.7){_TAIL}")
DKV = (f"%flash_bwd_dkv.12 = (bf16[60,2048,64]{_K}, bf16[60,2048,64]{_K}) "
       f"custom-call(s32[16]{{0}} %fusion.1, s32[20]{{0}} %concatenate.3, "
       f"bf16[60,3,2048,64]{_T} %bitcast.5, bf16[60,2048,64]{_K} "
       f"%bitcast.6, bf16[60,2048,64]{_K} %bitcast.11, "
       f"bf16[60,3,2048,64]{_T} %bitcast.9, {_STAT} %flash_bwd_lse.7, "
       f"{_STAT} %fusion.10, s32[1,2048]{{1,0}} %bitcast.7, "
       f"s32[2048,1]{{1,0}} %copy.8){_TAIL}")
FWD = (f"%flash_fwd.18 = bf16[180,2048,64]{_K} custom-call("
       f"bf16[180,2048,64]{_K} %bitcast.1, bf16[60,2048,64]{_K} %bitcast.2, "
       f"bf16[60,2048,64]{_K} %bitcast.3, s32[2048,1]{{1,0}} %iota.4, "
       f"s32[1,2048]{{1,0}} %iota.5){_TAIL}")


def _ctx(ops):
    _nest(ops)
    return types.SimpleNamespace(device_kind=KIND,
                                 trace=Trace({"/device:TPU:0": ops}))


def _least_s():
    """One smollm-360m backward at 12 x 2048 by hand: 10 FLOPs per head
    dim per attendable pair per q head; bytes q, o, dO, dq and k, v, dk,
    dv."""
    peak = peak_for(KIND)
    flops = 10.0 * 180 * (2048 * 2049 / 2) * 64
    nbytes = 2 * 64 * (4 * 2048 * 180 + 4 * 2048 * 60)
    assert flops / peak.flops_bf16 > nbytes / peak.hbm_bytes_per_s
    return flops / peak.flops_bf16


def test_call_shape():
    assert flash_bwd_roofline.call_shape(DQ) == (180, 60, 2048, 2048, 64, 2)
    for text in (LSE, DKV, FWD):
        assert flash_bwd_roofline.call_shape(text) is None
    assert [flash_bwd_roofline.is_backward(t) for t in (LSE, DQ, DKV, FWD)] \
        == [True, True, True, False]


@pytest.mark.parametrize("steps", [1, 3])
def test_reads_least_time_over_all_three_kernels(steps):
    """Two backwards a step; the three kernels' events sum to the time.
    The forward's events and other ops count for nothing."""
    ops, t, ms = [], 0.0, {LSE: 1.8e6, DQ: 3.3e6, DKV: 2.8e6,
                           FWD: 5.0e6, "%fusion.3 = f32[4] fusion()": 1e6}
    for _ in range(2 * steps):
        for text, dur in ms.items():
            ops.append(Op(text, t, t + dur))
            t += dur
    got = flash_bwd_roofline.read(_ctx(ops))
    want = 100.0 * _least_s() / ((1.8 + 3.3 + 2.8) * 1e-3)
    assert got == pytest.approx(want, rel=1e-12)
    assert 0.0 < got <= 100.0


def test_reads_100_at_the_least_time():
    least_ns = _least_s() * 1e9
    ops = [Op(LSE, 0.0, 0.2 * least_ns), Op(DQ, 0.2 * least_ns,
                                              0.6 * least_ns),
           Op(DKV, 0.6 * least_ns, least_ns)]
    assert flash_bwd_roofline.read(_ctx(ops)) == pytest.approx(100.0)


def test_left_out_without_backward_kernels(tmp_path):
    """The parent program's trace (a jnp backward): nothing to read."""
    path = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, "smollm_2layer_scoped.xplane.pb.gz")) \
            as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    trace = Trace.load(str(path))
    assert trace.kernel_events(lambda o: True)      # it has the forward
    ctx = types.SimpleNamespace(device_kind=KIND, trace=trace)
    assert flash_bwd_roofline.read(ctx) is None
    assert flash_fwd_roofline.read(ctx) is not None


def test_forward_reader_takes_no_backward_kernel():
    assert flash_fwd_roofline.call_shape(FWD) == (180, 60, 2048, 2048, 64, 2)
    for text in (LSE, DQ, DKV):
        assert flash_fwd_roofline.call_shape(text) is None
