"""Faults planted in the timed path, to show that the check catches them.

Each is a context manager that breaks the program underneath the
benchmark while it is active; the benchmark itself runs unchanged:

* ``state_unchanged``: the step returns the state it was given;
* ``half_batch``: the second half of every sequence is left out of the
  loss, the mean taken over the rest;
* ``loss_altered``: the loss the step reports is 1% off.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _wrap_step(wrap):
    from repro.train import step as train_step
    build = train_step.build_train_step

    def broken(*a, **kw):
        step_fn, init_fn, art = build(*a, **kw)
        return wrap(step_fn), init_fn, art

    train_step.build_train_step = broken
    try:
        yield
    finally:
        train_step.build_train_step = build


def state_unchanged():
    def wrap(step_fn):
        return lambda state, batch: (state, step_fn(state, batch)[1])
    return _wrap_step(wrap)


def half_batch():
    def wrap(step_fn):
        def f(state, batch):
            labels = batch["labels"]
            labels = labels.at[:, labels.shape[1] // 2:].set(-1)
            return step_fn(state, dict(batch, labels=labels))
        return f
    return _wrap_step(wrap)


def loss_altered():
    def wrap(step_fn):
        def f(state, batch):
            state, metrics = step_fn(state, batch)
            return state, dict(metrics, loss=metrics["loss"] * 1.01)
        return f
    return _wrap_step(wrap)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "loss_altered": loss_altered}
