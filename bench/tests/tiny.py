"""Cells at a size a CPU test can hold: the published configurations with
their widths and depth cut, run through the whole harness.

The program is pointed at the same cut (``repro.configs.get_config`` is
patched), so the harness's check that the program runs what the file
states still applies. Run as a script on a forced host pool, it prints
one cell's results for a test in another process:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m bench.tests.tiny <config> none,half_batch,...
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SEQ = 128
CUT = {"dense": dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
                     head_dim=64, d_ff=512, vocab_size=1024),
       "ssm": dict(n_layers=2, d_model=256, vocab_size=1024)}
CUT_SSM = dict(d_state=32, head_dim=32, chunk_size=32)
BATCH = {1: 4, 4: 8}


def load_config(name: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    m = cfg["model"]
    m.update(CUT[m["family"]])
    if "ssm" in m:
        m["ssm"].update(CUT_SSM)
    cfg["global_batch"] = BATCH[cfg["layout"]["chips"]]
    return cfg


def cell(name: str):
    from bench.run import Cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = load_config(name)
    with open(os.path.join(ROOT, "bench", "traffic",
                           "train-s2048.json")) as f:
        traffic = dict(json.load(f), seq_len=SEQ)
    work = {"name": f"{name}.tiny", "chips": cfg["layout"]["chips"]}
    return Cell(work, cfg, traffic, [], bench["end_to_end"])


@contextlib.contextmanager
def program_cut_to(cfg: dict):
    """The program's registered config, cut as ``cfg`` states."""
    import repro.configs as RC
    orig = RC.get_config

    def get_config(name):
        full = orig(name)
        upd = {k: v for k, v in cfg["model"].items()
               if k in CUT[cfg["model"]["family"]]}
        if full.ssm is not None:
            upd["ssm"] = dataclasses.replace(full.ssm, **CUT_SSM)
        return dataclasses.replace(full, **upd)

    RC.get_config = get_config
    try:
        yield
    finally:
        RC.get_config = orig


def run(name: str, fault: str = "none", seed: int = 2 ** 31 + 17) -> dict:
    """One whole run of the cut cell on the CPU, with ``fault`` planted."""
    import jax
    from bench import faults, run as R
    c = cell(name)
    plant = (contextlib.nullcontext() if fault == "none"
             else faults.FAULTS[fault]())
    peak = R.peak_bytes
    R.peak_bytes = lambda devices: 0          # the CPU keeps no statistics
    try:
        with program_cut_to(c.config), plant:
            return R.run_cell(copy.deepcopy(c), seed, 0.2, False,
                              jax.devices()[:c.workload["chips"]],
                              log=lambda msg: None)
    finally:
        R.peak_bytes = peak


if __name__ == "__main__":
    print(json.dumps({f: run(sys.argv[1], f) for f in sys.argv[2].split(",")}))
