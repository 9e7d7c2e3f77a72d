"""``ssd_scan_bwd_roofline`` on a synthetic trace of the backward's two
kernels (instruction texts as the chip's trace names them, at the
mamba2-370m cell's shapes), on a trace whose backward has no such kernels,
and against ``ssd_scan_fwd_roofline``, which must not take a backward
kernel for a forward."""
import types

import pytest

from bench.metrics import ssd_scan_bwd_roofline, ssd_scan_fwd_roofline
from bench.roofline.peaks import peak_for
from bench.trace.reduce import Op, Trace, _nest

KIND = "TPU v5 lite"
# the backward's blocks put positions on lanes: x, dy, dx [b, h, p, l];
# B, C, dB, dC [b, g, n, l] (the results of the chip trace's
# ``%ssd_scan_bwd_grads.9`` in the mamba2-370m cell)
_X = "bf16[16,32,64,2048]{3,2,1,0:T(8,128)(2,1)}"
_G = "bf16[16,1,128,2048]{3,2,1,0:T(8,128)(2,1)}"
_XF = "bf16[16,32,2048,64]{3,2,1,0:T(8,128)(2,1)}"
_GF = "bf16[16,1,2048,128]{3,2,1,0:T(8,128)(2,1)}"
_ROWS = "f32[16,32,2,2048]{3,2,1,0:T(2,128)}"
_S0 = "f32[16,32,8,64,128]{4,3,2,1,0:T(8,128)}"
_TAIL = (', custom_call_target="tpu_custom_call", '
         'frontend_attributes={kernel_metadata={}}')
STATES = (f"%ssd_scan_bwd_states.1 = {_S0} custom-call({_X} %bitcast.1, "
          f"{_ROWS} %fusion.2, {_G} %bitcast.3){_TAIL}")
GRADS = (f"%ssd_scan_bwd_grads.2 = ({_X}, "
         "f32[16,32,3,2048]{3,2,1,0:T(4,128)}, "
         f"{_G}, {_G}) custom-call({_X} %bitcast.1, {_X} %bitcast.4, "
         f"{_ROWS} %fusion.2, f32[32]{{0}} %copy.5, {_G} %bitcast.3, "
         f"{_G} %bitcast.6, {_S0} %ssd_scan_bwd_states.1, "
         "bf16[16,32,64,128]{3,2,1,0:T(8,128)(2,1)} %broadcast.7)" + _TAIL)
FWD = (f"%ssd_scan_fwd.15 = ({_XF}, bf16[16,32,64,128]{{3,2,1,0}}) "
       f"custom-call({_XF} %a, f32[16,32,2048,1]{{3,2,1,0}} %b, "
       f"f32[16,32,1,2048]{{3,2,1,0}} %c, f32[32]{{0}} %d, {_GF} %e, "
       f"{_GF} %f, f32[32]{{0}} %g){_TAIL}")
CELL = types.SimpleNamespace(config={"model": {"ssm": {"chunk_size": 256}}})


def _ctx(ops):
    _nest(ops)
    return types.SimpleNamespace(device_kind=KIND, cell=CELL,
                                 trace=Trace({"/device:TPU:0": ops}))


def test_least_work_by_hand():
    """One mamba2-370m layer at 16 x 2048 (h 32, p 64, one group, n 128,
    chunk 256, 8 chunks): 144 GFLOP."""
    flops, nbytes = ssd_scan_bwd_roofline.ssd_scan_bwd_cost(
        16, 2048, 32, 64, 1, 128, 256, itemsize=2)
    per_chunk = (6 * 256 * 256 * 128
                 + 32 * (4 * 256 * 256 * 64 + 8 * 256 * 64 * 128))
    assert flops == 16 * 8 * per_chunk == 143_881_404_416
    # x, dy, dx; B, C, dB, dC; dt and ddt in f32
    assert nbytes == (3 * 2 * 16 * 2048 * 32 * 64 + 4 * 2 * 16 * 2048 * 128
                      + 2 * 4 * 16 * 2048 * 32)
    peak = peak_for(KIND)
    assert flops / peak.flops_bf16 > nbytes / peak.hbm_bytes_per_s


def test_call_shape():
    assert ssd_scan_bwd_roofline.call_shape(GRADS) == (16, 2048, 32, 64, 1,
                                                       128, 2)
    for text in (STATES, FWD):
        assert ssd_scan_bwd_roofline.call_shape(text) is None
    assert [ssd_scan_bwd_roofline.is_backward(t)
            for t in (STATES, GRADS, FWD)] == [True, True, False]


@pytest.mark.parametrize("steps", [1, 3])
def test_reads_least_time_over_both_kernels(steps):
    """48 backwards a step; both kernels' events sum to the time. The
    forward's events and other ops count for nothing."""
    ops, t, ms = [], 0.0, {STATES: 0.8e6, GRADS: 7.2e6, FWD: 9.6e6,
                           "%fusion.3 = f32[4] fusion()": 1e6}
    for _ in range(48 * steps):
        for text, dur in ms.items():
            ops.append(Op(text, t, t + dur))
            t += dur
    got = ssd_scan_bwd_roofline.read(_ctx(ops))
    least = 143_881_404_416 / peak_for(KIND).flops_bf16
    assert got == pytest.approx(100.0 * least / 8e-3, rel=1e-12)
    assert 0.0 < got <= 100.0


def test_left_out_without_backward_kernels():
    """The parent program's backward is jnp: only the forward kernel and
    fusions reach the trace, so there is nothing to read."""
    ops = [Op(FWD, 0.0, 9.6e6), Op("%fusion.3 = f32[4] fusion()", 9.6e6,
                                   20e6)]
    assert ssd_scan_bwd_roofline.read(_ctx(ops)) is None


def test_forward_reader_takes_no_backward_kernel():
    assert ssd_scan_fwd_roofline.call_shape(FWD) == (16, 2048, 32, 64, 1,
                                                     128, 2)
    for text in (STATES, GRADS):
        assert ssd_scan_fwd_roofline.call_shape(text) is None
