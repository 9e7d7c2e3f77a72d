"""Peaks, model FLOPs and kernel costs against hand counts at tiny
shapes."""
import json
import os

import pytest

from bench.reference.weights import param_shapes
from bench.roofline import flops
from bench.roofline.peaks import peak_for

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DENSE = {"family": "dense", "n_layers": 2, "d_model": 8, "n_heads": 2,
         "n_kv_heads": 1, "head_dim": 4, "d_ff": 16, "vocab_size": 10,
         "tie_embeddings": True}
SSM = {"family": "ssm", "n_layers": 3, "d_model": 8, "vocab_size": 10,
       "tie_embeddings": True,
       "ssm": {"d_state": 4, "d_conv": 4, "expand": 2, "head_dim": 4,
               "n_groups": 1, "chunk_size": 8}}


def test_peak_table():
    p = peak_for("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bytes_per_s, p.hbm_bytes) == (197e12, 819e9,
                                                              16e9)
    with pytest.raises(KeyError):
        peak_for("TPU v4")


def test_param_count_by_hand():
    # embedding 10*8 + final norm 8; per layer q,o 2*8*8, k,v 2*8*4,
    # MLP 3*8*16, two norms 2*8
    assert flops.param_count(DENSE) == 88 + 2 * (128 + 64 + 384 + 16)
    # per layer: in_proj 8*(2*16 + 2*4 + 4), conv 4*24 + 24, A D dt 3*4,
    # out_proj 16*8, gated norm 16, layer norm 8
    assert flops.param_count(SSM) == 88 + 3 * (352 + 120 + 12 + 128 + 24)


@pytest.mark.parametrize("model", [DENSE, SSM], ids=["dense", "ssm"])
def test_param_count_matches_the_weights_made(model):
    model = dict(model)
    sizes = param_shapes(model)
    total = 0
    for shape in sizes.values():
        n = 1
        for d in shape:
            n *= d
        total += n
    assert flops.param_count(model) == total


def test_sequence_flops_by_hand():
    # 6 * L * S * H * hd
    assert flops.sequence_flops_per_token(DENSE, 16) == 6 * 2 * 16 * 8
    # per layer per token: 2*Q*N*g + h*(2*Q*P + 4*P*N), x3 for backward
    per = 2 * 8 * 4 + 4 * (2 * 8 * 4 + 4 * 4 * 4)
    assert flops.sequence_flops_per_token(SSM, 16) == 3 * 3 * per


def test_published_sizes():
    with open(os.path.join(ROOT, "bench", "configs",
                           "smollm-360m.json")) as f:
        m = json.load(f)["model"]
    assert flops.param_count(m) == 361821120
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(
        6 * 361821120 + 6 * 32 * 2048 * 960)


def test_flash_cost_by_hand():
    # B=1, Hq=2, Hkv=1, S=4, hd=8, causal: 10 attended pairs per head
    f, b = flops.flash_fwd_cost(1, 4, 4, 2, 1, 8, causal=True, itemsize=2)
    assert f == 4 * 2 * 10 * 8
    assert b == 2 * 8 * (2 * 4 * 2 + 2 * 4 * 1) + 4 * 8
    f, _ = flops.flash_fwd_cost(1, 1, 4, 2, 1, 8, causal=False)
    assert f == 4 * 2 * 4 * 8


def test_ssd_cost_by_hand():
    # b=1, l=8, h=2, p=4, g=1, n=4, chunk 4: two chunks
    f, b = flops.ssd_scan_fwd_cost(1, 8, 2, 4, 1, 4, 4, itemsize=2)
    assert f == 2 * (2 * 16 * 4 + 2 * (2 * 16 * 4 + 4 * 4 * 4 * 4))
    assert b == 2 * (2 * 8 * 2 * 4 + 2 * 8 * 4 + 2 * 4 * 4) + 4 * (16 + 4)
