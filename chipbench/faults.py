"""Faults planted in the timed path, to show that ``correct`` catches them.

Each is a context manager that patches the program for its duration:

* ``state_unchanged``: the train step returns its state as it got it;
* ``half_batch``: the train step sees only the first half of each batch,
  so the mean loss and gradient are taken over the rest.

The exchange between chips is not planted: every cell runs on one chip.
"""

from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def _patch(obj, name, make):
    old = getattr(obj, name)
    setattr(obj, name, make(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


def _step_fault(change):
    from repro.runtime import trainer

    def make(orig):
        def make_train_step(model, tc, *a, **k):
            return change(orig(model, tc, *a, **k))
        return make_train_step
    return _patch(trainer, "make_train_step", make)


def state_unchanged():
    def change(step):
        def stuck(state, batch):
            return state, step(state, batch)[1]
        return stuck
    return _step_fault(change)


def half_batch():
    def change(step):
        def half(state, batch):
            return step(state, jax.tree_util.tree_map(
                lambda x: x[: x.shape[0] // 2], batch))
        return half
    return _step_fault(change)


TRAIN = {"state_unchanged": state_unchanged, "half_batch": half_batch}
