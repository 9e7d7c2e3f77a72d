"""The float32 references against the program at a reduced size on the
CPU: the same loss and gradients from the same weights, and one AdamW
update and the schedule as the program's optimizer makes them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program
from bench.reference import weights as W
from bench.reference.model import loss_sum
from bench.reference.train import adamw, learning_rate
from bench.tests import tiny

HP = {"learning_rate": 3e-4, "warmup_steps": 3, "total_steps": 20,
      "final_lr_fraction": 0.1, "weight_decay": 0.1, "beta1": 0.9,
      "beta2": 0.95, "eps": 1e-8}


def _f32_config(name):
    cfg = tiny.load_config(name)
    cfg["model"].update(dtype="float32", param_dtype="float32",
                        n_layers=2)
    return cfg


@pytest.mark.parametrize("name", ["smollm-360m", "mamba2-370m"])
def test_reference_matches_program(name):
    import repro.models.model as MD
    from repro.models.layers import is_param
    cfg = _f32_config(name)
    m = cfg["model"]
    with tiny.program_cut_to(cfg):
        pcfg = program.model_config(cfg["arch"], dict(m, dtype="bfloat16",
                                                      param_dtype="bfloat16"))
    import dataclasses
    pcfg = dataclasses.replace(pcfg, dtype="float32", param_dtype="float32")
    key = W.seed_key(5)
    values = W.make_params(m, key)
    skel = jax.eval_shape(lambda: MD.init_model(key, pcfg))
    params = program._with_params(skel, values)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                m["vocab_size"])

    def prog_loss(p):
        return MD.loss_fn(p, pcfg, {"tokens": tokens}, remat="none")[0]

    def ref_loss(v):
        return loss_sum(v, m, tokens) / (tokens.size - tokens.shape[0])

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog_loss)(params)
        lr, gr = jax.value_and_grad(ref_loss)(values)
    assert float(lp) == pytest.approx(float(lr), rel=2e-5)
    gp = program._leaf_values(gp)
    errs = {k: float(jnp.max(jnp.abs(gp[k] - g)) / jnp.max(jnp.abs(g)))
            for k, g in gr.items()}
    # the program rounds the logits to bf16 (2^-9 relative) even at f32
    assert max(errs.values()) < 4e-3, errs


def test_adamw_and_schedule_match_program():
    from repro.configs import TrainConfig
    from repro.models.layers import Param
    from repro.optim.optimizers import adamw_init, adamw_update
    from repro.optim.schedules import warmup_cosine
    tcfg = TrainConfig(**{k: HP[k] for k in
                          ("learning_rate", "warmup_steps", "total_steps",
                           "weight_decay", "beta1", "beta2", "eps")})
    for step in range(0, 25):
        want = warmup_cosine(step, peak_lr=HP["learning_rate"],
                             warmup_steps=HP["warmup_steps"],
                             total_steps=HP["total_steps"])
        assert float(learning_rate(HP, step)) == pytest.approx(float(want),
                                                               rel=1e-6)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    p = {"w": jax.random.normal(ks[0], (16, 8))}
    g = {"w": jax.random.normal(ks[1], (16, 8)) * 0.1}
    pp = {"w": Param(p["w"], (None, None))}
    st = adamw_init(pp, tcfg)
    mu = nu = {"w": jnp.zeros((16, 8))}
    for t in (1, 2):
        pp, st = adamw_update(pp, g, st, tcfg, 1e-3)
        p, mu, nu = adamw(p, g, mu, nu, t, 1e-3, HP)
        np.testing.assert_allclose(np.asarray(pp["w"].value),
                                   np.asarray(p["w"]), rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(st.mu["w"].value),
                                   np.asarray(mu["w"]), rtol=1e-6)
