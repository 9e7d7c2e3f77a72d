"""Whole runs of each cell, cut to a CPU size, with the timed path sound
and with each fault the cell can have planted underneath
(``bench/faults.py``): ``correct`` must come out true, then false."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import tiny

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as _f:
    CONFIGS = json.load(_f)["configs"]


def faults_for(chips):
    return ["unchanged", "half_batch"] + (["exchange"] if chips > 1 else [])


CASES = [(c["name"], f) for c in CONFIGS
         for f in ["none"] + faults_for(
             tiny.load_config(c["name"])["layout"]["chips"])]


@pytest.fixture(scope="module")
def results():
    """Every case's result; four-chip configs run in a child process
    that has a pool of four CPU devices."""
    out = {}
    for c in CONFIGS:
        chips = tiny.load_config(c["name"])["layout"]["chips"]
        cases = ["none"] + faults_for(chips)
        if chips == 1:
            out.update({(c["name"], f): tiny.run(c["name"], f)
                        for f in cases})
            continue
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        proc = subprocess.run(
            [sys.executable, "-m", "bench.tests.tiny", c["name"],
             ",".join(cases)], cwd=tiny.ROOT, env=env, capture_output=True,
            text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        got = json.loads(proc.stdout.strip().splitlines()[-1])
        out.update({(c["name"], f): r for f, r in got.items()})
    return out


@pytest.mark.parametrize("config,fault", CASES,
                         ids=[f"{c}-{f}" for c, f in CASES])
def test_correct_only_when_sound(results, config, fault):
    r = results[(config, fault)]
    assert r["correct"] is (fault == "none"), r["checks"]


def test_configuration_without_limits_is_refused():
    import jax
    from bench import run as R
    c = tiny.cell("smollm-360m")
    del c.config["limits"]
    with pytest.raises(R.CellError, match="no limits"):
        R.run_cell(c, 1, 0.1, False, jax.devices()[:1], log=lambda m: None)


def test_step_bytes_count_the_temporaries():
    """The memory metric's reading: more than the step's own arguments,
    which are all that the allocator's peak would show."""
    import jax
    from bench import program, run as R
    from bench.generator import token_batches
    from bench.reference import weights as W
    c = tiny.cell("smollm-360m")
    cfg, devs = c.config, jax.devices()[:1]
    with tiny.program_cut_to(cfg):
        prog = program.build(cfg, tiny.SEQ, devs)
    key = W.seed_key(3)
    state = prog.init(key, prog.weights(key))
    toks = token_batches(c.traffic, cfg["model"]["vocab_size"],
                         cfg["global_batch"], 3, range(1))[0]
    batch = {"tokens": jax.device_put(toks, prog.batch_sharding)}
    args = sum(x.nbytes for x in jax.tree.leaves(state)) + toks.nbytes
    assert R.step_bytes(prog, state, batch) > args
