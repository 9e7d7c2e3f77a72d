"""The system under test: the program's train step, built the way
``repro.launch.train``'s ``build_exec`` builds it.

Only what the driver sets comes from the configuration file: the model,
the mesh and strategy, the path (gspmd on one device, the shard_map
step on a mesh), the wire format, the optimizer and its schedule, and
remat. Every other knob keeps the program's default, so a change of a
default shows in the cells. The weights are the benchmark's own
(``bench.reference.weights``), put in place of those the program's init
draws; the program's init builds the rest of the state.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import weights as W


class ConfigMismatch(ValueError):
    """The program does not run what the configuration file states."""


class Program(NamedTuple):
    mesh: object
    weights: Callable       # key -> {leaf: array}, placed as the step wants
    init: Callable          # (key, weights) -> state; the weights donated
    step: Callable          # (state, batch) -> (state, metrics), jitted
    batch_sharding: object
    grad_norms: Callable    # state after step 1 -> {leaf: |gradient|}
    params: Callable        # state -> {leaf: array}


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in keys)


def model_config(arch: str, model: Mapping):
    """The program's registered config, checked against the file."""
    from repro.configs import get_config
    cfg = get_config(arch)
    for k, want in model.items():
        if k == "family":
            continue
        have = getattr(cfg, k)
        if dataclasses.is_dataclass(have):
            have = {f: getattr(have, f) for f in want}
        if have != want:
            raise ConfigMismatch(f"{arch}: {k} is {have!r} in the program, "
                                 f"{want!r} in the configuration")
    return cfg


def train_config(train: Mapping, layout: Mapping):
    from repro.configs import TrainConfig
    return TrainConfig(
        learning_rate=train["learning_rate"],
        warmup_steps=train["warmup_steps"], total_steps=train["total_steps"],
        weight_decay=train["weight_decay"], beta1=train["beta1"],
        beta2=train["beta2"], eps=train["eps"], grad_clip=train["grad_clip"],
        optimizer=train["optimizer"], remat_policy=train["remat"],
        opt_state_dtype=train["opt_state_dtype"],
        grad_compression=layout["compression"])


def _check_layout(params, model: Mapping) -> None:
    from repro.models.layers import is_param
    shapes, dtypes = W.param_shapes(model), W.param_dtypes(model)
    flat = jax.tree_util.tree_flatten_with_path(params, is_leaf=is_param)[0]
    got = {_path(k): p.value for k, p in flat}
    if set(got) != set(shapes):
        raise ConfigMismatch(f"parameter leaves differ: program only "
                             f"{sorted(set(got) - set(shapes))}, "
                             f"configuration only "
                             f"{sorted(set(shapes) - set(got))}")
    for k, v in got.items():
        if tuple(v.shape) != shapes[k] or v.dtype != dtypes[k]:
            raise ConfigMismatch(f"{k}: program {v.dtype}{list(v.shape)}, "
                                 f"configuration {dtypes[k]}"
                                 f"{list(shapes[k])}")


def _with_params(params, values: Dict[str, jax.Array]):
    from repro.models.layers import Param, is_param
    return jax.tree_util.tree_map_with_path(
        lambda k, p: Param(values[_path(k)], p.axes), params,
        is_leaf=is_param)


def _leaf_values(tree) -> Dict[str, jax.Array]:
    from repro.models.layers import is_param
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_param)[0]
    return {_path(k): p.value for k, p in flat}


def change_norms(after: Mapping, before: Mapping) -> Dict[str, float]:
    """{leaf: |after - before|}, on the host: the difference in float32,
    its norm summed in float64."""
    out = {}
    for k, v in after.items():
        d = (np.asarray(v, np.float32) - np.asarray(before[k], np.float32))
        d = d.ravel().astype(np.float64)
        out[k] = float(np.sqrt(d @ d))
    return out


def build(cfgfile: Mapping, seq: int, devices) -> Program:
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import batch_shardings, state_shardings
    import repro.train.step as TS

    lay, model = cfgfile["layout"], cfgfile["model"]
    cfg = model_config(cfgfile["arch"], model)
    tcfg = train_config(cfgfile["train"], lay)
    mesh = make_mesh(tuple(lay["mesh"]), ("data", "model"), devices=devices)
    batch = {"tokens": jax.ShapeDtypeStruct((cfgfile["global_batch"], seq),
                                            jnp.int32)}
    b_shard = batch_shardings(batch, mesh)
    if lay["path"] == "sharded":
        def init_state(key):
            return TS.init_sharded_train_state(key, cfg, tcfg, mesh)
        specs = TS.sharded_state_specs(cfg, tcfg, mesh, lay["strategy"])
        st_shard = TS.sharded_state_shardings(cfg, tcfg, mesh,
                                              lay["strategy"], specs=specs)
        raw = TS.make_sharded_train_step(cfg, tcfg, mesh, lay["strategy"],
                                         state_specs=specs)
    elif lay["path"] == "gspmd":
        def init_state(key):
            return TS.init_train_state(key, cfg, tcfg)
        skel = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        st_shard = state_shardings(skel, mesh, lay["strategy"])
        raw = TS.make_train_step(cfg, tcfg)
    else:
        raise ValueError(f"unknown path {lay['path']!r}")
    _check_layout(jax.eval_shape(init_state, jax.random.PRNGKey(0)).params,
                  model)

    def init(key, values):
        st = init_state(key)
        return st._replace(params=_with_params(st.params, values))

    b1 = cfgfile["train"]["beta1"]

    def grad_norms(state):
        # after one step AdamW's first moment is (1 - b1) * gradient
        return {k: jnp.linalg.norm(v.astype(jnp.float32)) / (1 - b1)
                for k, v in _leaf_values(state.opt.mu).items()}

    # one compiled generator makes every copy of the weights, so each is
    # the same to the bit (two programs may round the draw differently)
    p_shard = {_path(k): s for k, s in
               jax.tree_util.tree_flatten_with_path(st_shard.params)[0]}
    weights = jax.jit(partial(W.make_params, model), out_shardings=p_shard)
    step = jax.jit(raw, in_shardings=(st_shard, b_shard),
                   out_shardings=(st_shard, None), donate_argnums=(0,))
    return Program(mesh, weights,
                   jax.jit(init, out_shardings=st_shard, donate_argnums=(1,)),
                   step, b_shard["tokens"], jax.jit(grad_norms),
                   lambda state: _leaf_values(state.params))
