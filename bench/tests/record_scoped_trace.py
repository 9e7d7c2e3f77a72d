"""Record the trace pair that ``bench/tests/test_scopes.py`` reads, on one
TPU chip: smollm-360m at its published widths cut to 2 layers, batch
2 × 512, Pallas kernels on. After two warm-up steps, two steps run under
the profiler inside the harness's host spans (prepare, dispatch, wait);
then the compiled step's HLO text is read. Both files are gzipped.

    python -m bench.tests.record_scoped_trace <out dir>

It refuses to run without a TPU.
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import tempfile

from bench.tests.tiny import ROOT, program_cut_to

NAME = "smollm_2layer_scoped"
LAYERS, BATCH, SEQ, SEED = 2, 2, 512, 2 ** 31 + 99


def record(out_dir: str) -> dict:
    import jax
    from bench import program
    from bench.generator import token_batches
    from bench.reference import weights as W
    from bench.run import step_once
    from bench.trace.scopes import compiled_text
    from repro.obs import compile_counts

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("a recorded fixture comes from a TPU chip")
    with open(os.path.join(ROOT, "bench", "configs",
                           "smollm-360m.json")) as f:
        cfg = json.load(f)
    cfg["model"]["n_layers"] = LAYERS
    cfg["global_batch"] = BATCH
    with open(os.path.join(ROOT, "bench", "traffic",
                           "train-s2048.json")) as f:
        traffic = dict(json.load(f), seq_len=SEQ)
    with program_cut_to(cfg):
        prog = program.build(cfg, SEQ, jax.devices()[:1])
        key = W.seed_key(SEED)
        tokens = token_batches(traffic, cfg["model"]["vocab_size"], BATCH,
                               SEED, range(4))
        batches = [{"tokens": jax.device_put(t, prog.batch_sharding)}
                   for t in tokens]
        state = prog.init(key, prog.weights(key))
        for batch in batches[:2]:
            state, m = step_once(prog, state, batch)
            jax.block_until_ready(m["loss"])
        before = compile_counts()
        tmp = tempfile.mkdtemp(prefix="scoped-trace-")
        try:
            jax.profiler.start_trace(tmp)
            try:
                for batch in batches[2:]:
                    with jax.profiler.TraceAnnotation("prepare"):
                        b = batch
                    with jax.profiler.TraceAnnotation("dispatch"):
                        state, m = step_once(prog, state, b)
                    with jax.profiler.TraceAnnotation("wait"):
                        jax.block_until_ready(m["loss"])
            finally:
                jax.profiler.stop_trace()
            traced_compiles = compile_counts() - before
            paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp)
                     for f in fs if f.endswith(".xplane.pb")]
            if len(paths) != 1:
                raise SystemExit(f"expected one trace file, found {paths}")
            os.makedirs(out_dir, exist_ok=True)
            with open(paths[0], "rb") as src, gzip.open(
                    os.path.join(out_dir, NAME + ".xplane.pb.gz"),
                    "wb") as dst:
                shutil.copyfileobj(src, dst)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        text = compiled_text(prog, state, batches[0])
    with gzip.open(os.path.join(out_dir, NAME + ".hlo.txt.gz"), "wt") as f:
        f.write(text)
    return {"device": jax.devices()[0].device_kind,
            "traced_compiles": traced_compiles.to_dict(),
            "files": sorted(os.listdir(out_dir))}


if __name__ == "__main__":
    print(json.dumps(record(sys.argv[1])))
