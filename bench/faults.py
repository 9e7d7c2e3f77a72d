"""Faults planted in the program, to show that ``correct`` catches them.

Each is a context manager that patches the program's modules while a
step is built; the tests drive a whole run through each, and
``bench/calibrate.py`` reads each one's numbers on the chip.

* ``unchanged``: the step computes as usual and returns its state as it
  came in;
* ``half_batch``: the loss is the mean over the first half of the rows;
* ``exchange``: the gradient all-reduce across data shards is left out
  (each shard updates with its own gradient).
"""
from __future__ import annotations

from contextlib import contextmanager


@contextmanager
def _patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


@contextmanager
def unchanged():
    import repro.train.step as TS

    def wrap(make):
        def make_step(*a, **k):
            step = make(*a, **k)
            return lambda state, batch: (state, step(state, batch)[1])
        return make_step

    with _patched(TS, "make_train_step", wrap(TS.make_train_step)), \
            _patched(TS, "make_sharded_train_step",
                     wrap(TS.make_sharded_train_step)):
        yield


@contextmanager
def half_batch():
    import repro.models.model as MD
    full = MD.loss_fn

    def loss_fn(params, cfg, batch, **kw):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return full(params, cfg, half, **kw)

    with _patched(MD, "loss_fn", loss_fn):
        yield


@contextmanager
def exchange():
    import repro.train.step as TS
    with _patched(TS, "compressed_psum_mean", lambda g, axes, mode: g):
        yield


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "exchange": exchange}
