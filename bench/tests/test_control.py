"""The control: the plain reference in the program's place, with every
product in fp8 (the precision below the configurations' bf16), must come
out not correct under each cell's limits. Cut to a CPU size; its readings
at the cells' own size are in PERF.md."""
import functools
import json
import os

import jax
import pytest

from bench import correct
from bench.generator import token_batches
from bench.reference import weights as W
from bench.run import CHECK_STEPS, reference_readings
from bench.tests import tiny

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as _f:
    NAMES = [c["name"] for c in json.load(_f)["configs"]]


@pytest.mark.parametrize("name", NAMES)
def test_control_is_not_correct(name):
    c = tiny.cell(name)
    m, seed = c.config["model"], 2 ** 31 + 5
    toks = token_batches(c.traffic, m["vocab_size"],
                         c.config["global_batch"], seed, range(CHECK_STEPS))
    weights = jax.jit(functools.partial(W.make_params, m))(W.seed_key(seed))
    dev = jax.devices()[:1]
    ref = reference_readings(m, c.config["train"], weights, toks, dev)
    ctl = reference_readings(m, c.config["train"], weights, toks, dev,
                             lowp="fp8")
    ok, checks = correct.judge(correct.numbers(ctl, ref), c.config["limits"])
    assert not ok, checks
