"""Faults planted in the program a window drives, to show that ``correct``
catches them: each replaces the step function the program builds, for as
long as the context is open.

* ``state_unchanged``: the train step computes its loss and returns the
  params and optimizer state it was given;
* ``half_batch``: the train step takes the first half of the batch, and
  its loss and gradient are the mean over that half;
* ``token_altered``: the decode step returns negated logits, so the token
  produced is the model's last choice instead of its first.

There is no exchange between chips to leave out: every cell runs on one
chip."""
from __future__ import annotations

import contextlib
import functools

BY_DRIVER = {"train": ("state_unchanged", "half_batch"),
             "serve": ("token_altered",)}


def _unchanged(step):
    def f(params, opt_state, batch):
        _, _, metrics = step(params, opt_state, batch)
        return params, opt_state, metrics
    return f


def _half(step):
    def f(params, opt_state, batch):
        half = batch["tokens"].shape[0] // 2
        return step(params, opt_state, {"tokens": batch["tokens"][:half]})
    return f


def _negated(step):
    def f(params, cache, tokens):
        logits, cache = step(params, cache, tokens)
        return -logits, cache
    return f


@contextlib.contextmanager
def planted(fault: str):
    from repro.runtime import serve_loop, train_loop

    module, attr, breaks = {
        "state_unchanged": (train_loop, "make_train_step", _unchanged),
        "half_batch": (train_loop, "make_train_step", _half),
        "token_altered": (serve_loop, "make_decode_step", _negated),
    }[fault]
    real = getattr(module, attr)

    @functools.wraps(real)
    def make(*args, **kwargs):
        return breaks(real(*args, **kwargs))

    setattr(module, attr, make)
    try:
        yield
    finally:
        setattr(module, attr, real)
