"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps
plus hypothesis property tests (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import flash_attention
from repro.kernels.quantize import (dequantize_int8_pallas,
                                    quantize_int8_pallas)
from repro.kernels.ref import attention_ref, ssd_ref
from repro.kernels.ssd_scan import ssd_scan
from repro.models.attention import AttnSpec, attend_blockwise

KEY = jax.random.PRNGKey(0)


def _qkv(B, Sq, Skv, Hq, Hkv, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, hd), jnp.float32).astype(dtype)
    return q, k, v


FLASH_CASES = [
    # B, Sq, Skv, Hq, Hkv, hd, causal, window, softcap
    (1, 128, 128, 2, 2, 16, True, 0, 0.0),
    (2, 64, 192, 4, 2, 32, True, 0, 0.0),
    (1, 128, 128, 4, 1, 16, True, 32, 0.0),
    (1, 96, 96, 2, 2, 16, True, 0, 20.0),
    (2, 1, 256, 4, 2, 16, True, 0, 0.0),          # decode
    (1, 64, 64, 3, 1, 8, False, 0, 0.0),          # non-causal (encoder)
    (1, 80, 144, 6, 3, 24, True, 48, 30.0),       # window + softcap, ragged
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, Sq, Skv, Hq, Hkv, hd, causal, window, cap = case
    q, k, v = _qkv(B, Sq, Skv, Hq, Hkv, hd, dtype)
    q_pos = jnp.arange(Skv - Sq, Skv)
    kv_pos = jnp.arange(Skv)
    spec = AttnSpec(causal=causal, window=window, logit_softcap=cap)
    out = flash_attention(q, k, v, q_pos, kv_pos, spec,
                          block_q=64, block_kv=64, interpret=True)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), q_pos, kv_pos, spec)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_ring_cache_positions():
    """Out-of-order kv_pos (ring buffer) must mask identically to ref."""
    B, S, H, hd = 1, 64, 2, 16
    q, k, v = _qkv(B, 1, S, H, H, hd, jnp.float32)
    # ring: slots hold positions [64..95, 32..63] (wrapped)
    kv_pos = jnp.concatenate([jnp.arange(64, 96), jnp.arange(32, 64)])
    q_pos = jnp.array([95])
    spec = AttnSpec(causal=True, window=40)
    out = flash_attention(q, k, v, q_pos, kv_pos, spec, block_q=32,
                          block_kv=32, interpret=True)
    ref = attention_ref(q, k, v, q_pos, kv_pos, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 2), st.sampled_from([32, 48, 64]),
       st.sampled_from([1, 2, 4]), st.sampled_from([8, 16]),
       st.booleans())
def test_flash_attention_property(B, S, Hkv, hd, causal):
    """Property: kernel == oracle for random GQA geometry."""
    Hq = Hkv * 2
    q, k, v = _qkv(B, S, S, Hq, Hkv, hd, jnp.float32)
    pos = jnp.arange(S)
    spec = AttnSpec(causal=causal)
    out = flash_attention(q, k, v, pos, pos, spec, block_q=32, block_kv=32,
                          interpret=True)
    ref = attention_ref(q, k, v, pos, pos, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def _ring(n, shift):
    """Slot positions of a ring buffer written ``shift`` slots past its
    start: out of order, so blocks hold non-contiguous positions."""
    return (jnp.arange(n) + shift) % n


GRAD_CASES = {
    # B, Sq, Skv, Hq, Hkv, hd, causal, window, softcap, kv positions
    "causal_window": (1, 96, 96, 4, 2, 16, True, 40, 0.0, None),
    "causal_g3_ragged": (1, 640, 640, 3, 1, 64, True, 0, 0.0, None),
    # q block 0 holds positions 1..512 and kv block 1 starts at 512: the
    # pair shares one attendable pair, which block skipping must keep
    "causal_g1_offset": (2, 1023, 1024, 2, 2, 32, True, 0, 0.0, None),
    # blocks of 512: (q2, k0) and (q3, k1) share one pair each at the
    # window's edge, (q3, k0) none
    "window_skips_blocks": (1, 1600, 1600, 3, 1, 16, True, 514, 0.0, None),
    "softcap": (1, 600, 600, 2, 1, 32, True, 0, 30.0, None),
    "noncausal_cross": (2, 200, 700, 4, 2, 16, False, 0, 0.0, None),
    "hd192": (1, 300, 300, 2, 2, 192, True, 0, 0.0, None),
    "ring_positions": (1, 1024, 1024, 2, 1, 16, True, 400, 0.0, 300),
}
# worst |grad - ref|: 2e-5 for float32 operands; for bfloat16 ones (P and
# dS rounded to bf16, unit roundoff 2^-9) 2^-6 of the largest |ref|
GRAD_TOL = {jnp.float32: lambda ref: 2e-5,
            jnp.bfloat16: lambda ref: 2 ** -6 * np.abs(ref).max()}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(GRAD_CASES))
def test_flash_attention_grads_match_ref(case, dtype):
    """The kernel's custom VJP (the Pallas backward) gives the
    reference's gradients — what a training step on the chip uses."""
    B, Sq, Skv, Hq, Hkv, hd, causal, window, cap, ring = GRAD_CASES[case]
    q, k, v = _qkv(B, Sq, Skv, Hq, Hkv, hd, dtype)
    kv_pos = jnp.arange(Skv) if ring is None else _ring(Skv, ring)
    q_pos = jnp.arange(Skv - Sq, Skv)
    spec = AttnSpec(causal=causal, window=window, logit_softcap=cap)
    w = jax.random.normal(jax.random.fold_in(KEY, 7), (B, Sq, Hq, hd))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    got = jax.jit(jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, q_pos, kv_pos, spec, block_q=128, block_kv=128,
        interpret=True)), argnums=(0, 1, 2)))(q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = jax.jit(jax.grad(loss(lambda q, k, v: attention_ref(
        q, k, v, q_pos, kv_pos, spec)), argnums=(0, 1, 2)))(*f32)
    for g, r in zip(got, want):
        assert g.dtype == dtype
        r = np.asarray(r)
        err = np.abs(np.asarray(g, np.float32) - r).max()
        assert err <= GRAD_TOL[dtype](r), (err, case)


def test_blockwise_jnp_matches_naive():
    """The model's CPU fallback path must equal the oracle too."""
    B, S, Hq, Hkv, hd = 2, 256, 4, 2, 16
    q, k, v = _qkv(B, S, S, Hq, Hkv, hd, jnp.float32)
    pos = jnp.arange(S)
    spec = AttnSpec(causal=True, window=100)
    out = attend_blockwise(q, k, v, pos, pos, spec, block=64)
    ref = attention_ref(q, k, v, pos, pos, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


SSD_CASES = [
    # b, l, h, p, g, n, chunk
    (1, 128, 2, 16, 1, 8, 32),
    (2, 64, 4, 8, 2, 16, 16),
    (1, 256, 8, 16, 1, 32, 64),
    (1, 32, 2, 8, 1, 8, 32),         # single chunk
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(case, dtype):
    b, l, h, p, g, n, chunk = case
    ks = jax.random.split(KEY, 5)
    x = (jax.random.normal(ks[0], (b, l, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.2
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, l, g, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (b, l, g, n)) * 0.3).astype(dtype)
    D = jnp.ones((h,))
    y, st_final = ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    yr, str_ = ssd_ref(x.astype(jnp.float32), dt, A, B.astype(jnp.float32),
                       C.astype(jnp.float32), D, chunk=chunk)
    tol = 5e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(st_final, np.float32),
                               np.asarray(str_, np.float32), atol=tol)


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_grads_match_ref(case, dtype):
    """The scan's custom VJP (the Pallas backward) gives the reference's
    gradients of all six inputs, for cotangents on y and on the final
    state: float32 to 1e-4, bfloat16 to a share of the largest reference
    gradient (its MXU operands are bf16)."""
    b, l, h, p, g, n, chunk = case
    ks = jax.random.split(KEY, 7)
    x = (jax.random.normal(ks[0], (b, l, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.2
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, l, g, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (b, l, g, n)) * 0.3).astype(dtype)
    D = jnp.full((h,), 0.7)
    wy = jax.random.normal(ks[5], (b, l, h, p))
    ws = jax.random.normal(ks[6], (b, h, p, n))

    def loss(fn):
        def f(*args):
            y, st_final = fn(*args)
            return (jnp.sum(y.astype(jnp.float32) * wy)
                    + jnp.sum(st_final.astype(jnp.float32) * ws))
        return f

    args = (x, dt, A, B, C, D)
    got = jax.jit(jax.grad(loss(lambda *a: ssd_scan(
        *a, chunk=chunk, interpret=True)), argnums=tuple(range(6))))(*args)
    want = jax.jit(jax.grad(loss(lambda *a: ssd_ref(*a, chunk=chunk)),
                            argnums=tuple(range(6))))(
        *[a.astype(jnp.float32) for a in args])
    for gv, r, a in zip(got, want, args):
        assert gv.dtype == a.dtype
        r = np.asarray(r)
        if dtype == jnp.float32:
            np.testing.assert_allclose(np.asarray(gv), r, atol=1e-4,
                                       rtol=1e-4)
        else:
            err = np.abs(np.asarray(gv, np.float32) - r).max()
            assert err <= GRAD_TOL[dtype](r), (err, case)


def _ssd_quadratic(x, dt, A, B, C, D):
    """Plain SSD, its quadratic form: y_t = sum_{s<=t} C_t·B_s
    exp(sum_{s<k<=t} dt_k A) dt_s x_s + D x_t, and the final state
    sum_s exp(sum_{s<k<=T} dt_k A) dt_s x_s B_sᵀ. No chunks."""
    l, rep = x.shape[1], x.shape[2] // B.shape[2]
    cs = jnp.cumsum(dt * A, axis=1)
    seg = cs[:, :, None, :] - cs[:, None, :, :]              # [b, t, s, h]
    causal = jnp.tril(jnp.ones((l, l), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    Bh, Ch = jnp.repeat(B, rep, axis=2), jnp.repeat(C, rep, axis=2)
    w = jnp.einsum("bthn,bshn->btsh", Ch, Bh) * decay * dt[:, None]
    y = jnp.einsum("btsh,bshp->bthp", w, x) + x * D[:, None]
    st = jnp.einsum("bsh,bshp,bshn->bhpn", decay[:, -1] * dt, x, Bh)
    return y, st


@pytest.mark.parametrize("case", ["spikes", "saturated"])
def test_ssd_strong_decay_matches_quadratic(case):
    """Past dt·|A| ≈ 44 at one position, exp(dt·A)² underflows: a
    backward that divides by exp(dt·A) then gives inf/NaN gradients.
    Here one chunk of 256 sums dt·A below −100 (``spikes``: dt·A = −64
    every 37th position; ``saturated``: −32 to −80 everywhere, chunk
    sums near −1.4e4). The chunked reference and the scan (its custom
    VJP) must match the quadratic form, computed in float64, in the
    values and in the gradients of every input."""
    b, l, h, p, g, n, chunk = 1, 512, 2, 16, 1, 16, 256
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, l, h, p)) * 0.5
    A = -jnp.array([1.0, 16.0])
    if case == "spikes":
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.2
        dt = dt.at[:, ::37].set(4.0)
    else:
        dt = jax.random.uniform(ks[1], (b, l, h), minval=2.0, maxval=5.0)
    B = jax.random.normal(ks[2], (b, l, g, n)) * 0.3
    C = jax.random.normal(ks[3], (b, l, g, n)) * 0.3
    D = jnp.ones((h,))
    wy = jax.random.normal(ks[4], (b, l, h, p))
    args = (x, dt, A, B, C, D)
    assert float(jnp.min((dt * A).reshape(b, -1, chunk, h).sum(2))) < -100

    def loss(fn):
        def f(*a):
            y, st_final = fn(*a)
            return jnp.sum(y * wy) + jnp.sum(st_final)
        return f

    with jax.enable_x64(True):
        a64 = [jnp.asarray(np.asarray(a), jnp.float64) for a in args]
        want = [np.asarray(v) for v in jax.jit(lambda *a: (
            *_ssd_quadratic(*a),
            *jax.grad(loss(_ssd_quadratic), argnums=tuple(range(6)))(*a)))(
                *a64)]
    for fn in (lambda *a: ssd_ref(*a, chunk=chunk),
               lambda *a: ssd_scan(*a, chunk=chunk, interpret=True)):
        got = jax.jit(lambda *a: (*fn(*a), *jax.grad(
            loss(fn), argnums=tuple(range(6)))(*a)))(*args)
        for gv, r in zip(got, want):
            gv = np.asarray(gv, np.float64)
            assert np.all(np.isfinite(gv))
            # float32 cumulative sums of dt·A reach 1.5e4, where one ulp
            # is 1e-3: an exponent off by δ is a decay off by δ relative
            np.testing.assert_allclose(gv, r, rtol=0,
                                       atol=1e-3 * np.max(np.abs(r)))


# ---------------------------------------------------------------------------
# int8 wire codec: Pallas kernels vs the jnp reference in repro.dist
# ---------------------------------------------------------------------------

QUANT_SHAPES = [
    (5, 5, 3, 16),      # conv kernel (ragged vs the 128-lane tiling)
    (400, 120),         # fc weight
    (84,),              # bias-sized vector
    (257, 129),         # deliberately off-tile in both dims
    (8192,),            # multiple full blocks
]


def _ref_quant(x):
    """The jnp codec from repro.dist.compression (inlined so the test
    pins the *contract*, not the dispatcher)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0
    q = jnp.clip(jnp.round(xf / jnp.where(scale > 0, scale, 1.0)),
                 -127, 127).astype(jnp.int8)
    return q, scale


@pytest.mark.parametrize("shape", QUANT_SHAPES)
def test_quantize_int8_pallas_matches_ref(shape):
    x = jax.random.normal(jax.random.fold_in(KEY, len(shape) + shape[0]),
                          shape) * 3.0
    q, s = quantize_int8_pallas(x, interpret=True)
    qr, sr = _ref_quant(x)
    assert q.shape == x.shape and q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(float(s), float(sr), rtol=1e-7)
    d = dequantize_int8_pallas(q, s, interpret=True)
    np.testing.assert_allclose(np.asarray(d),
                               np.asarray(qr.astype(jnp.float32) * sr),
                               rtol=1e-7)
    # one-ulp round-trip bound, same invariant the jnp codec guarantees
    assert float(jnp.max(jnp.abs(d - x))) <= float(s) / 2 + 1e-8


def test_quantize_int8_pallas_half_ulp_boundaries():
    """Adversarial bit-identity: every element sits at a (k+0.5)·scale
    rounding boundary, where a reciprocal-multiply (or a jit-context
    constant-division rewrite) would flip round-half-to-even the other
    way. Pallas and ref must still agree bit-for-bit."""
    for i in range(20):
        key = jax.random.fold_in(KEY, 1000 + i)
        mx = float(jax.random.uniform(key, (), minval=0.5, maxval=5.0))
        scale = mx / 127.0
        k = jax.random.randint(jax.random.fold_in(key, 1), (512,),
                               -126, 126)
        x = ((k.astype(jnp.float32) + 0.5) * scale).at[0].set(mx)
        q, s = quantize_int8_pallas(x, interpret=True)
        qr, sr = _ref_quant(x)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        assert float(s) == float(sr)


def test_quantize_int8_pallas_zero_tensor():
    q, s = quantize_int8_pallas(jnp.zeros((33,)), interpret=True)
    assert float(s) == 0.0
    assert not np.asarray(q).any()
    d = dequantize_int8_pallas(q, s, interpret=True)
    assert not np.asarray(d).any()


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 600), st.floats(1e-3, 1e3))
def test_quantize_int8_pallas_property(n, mag):
    """Property: pallas == ref bit-for-bit over random sizes/magnitudes
    (incl. sizes that exercise the zero-padding path)."""
    x = jax.random.normal(jax.random.fold_in(KEY, n), (n,)) * mag
    q, s = quantize_int8_pallas(x, interpret=True)
    qr, sr = _ref_quant(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(float(s), float(sr), rtol=1e-7)


def test_compression_dispatcher_consistency():
    """The repro.dist codec (jnp path on CPU) and the pallas kernels must
    implement the same function — the dispatch in quantize_int8 swaps
    implementations, never numerics."""
    from repro.dist.compression import dequantize_int8, quantize_int8
    x = jax.random.normal(jax.random.fold_in(KEY, 99), (3, 3, 16, 32))
    q1, s1 = quantize_int8(x)
    q2, s2 = quantize_int8_pallas(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(float(s1), float(s2), rtol=1e-7)
    np.testing.assert_allclose(np.asarray(dequantize_int8(q1, s1)),
                               np.asarray(dequantize_int8_pallas(
                                   q2, s2, interpret=True)), rtol=1e-7)


def test_ssd_chunk_invariance():
    """Property: the chunked scan result must not depend on chunk size."""
    b, l, h, p, g, n = 1, 128, 2, 8, 1, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h))) * 0.2
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, g, n)) * 0.3
    C = jax.random.normal(ks[4], (b, l, g, n)) * 0.3
    D = jnp.zeros((h,))
    outs = [ssd_ref(x, dt, A, B, C, D, chunk=c)[0] for c in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=1e-4)
