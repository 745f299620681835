"""The three step functions: train / prefill / decode.

Each ``make_*`` returns a pure function for ``jax.jit``: the trainer and the
server jit them on one chip, and under a mesh context the same functions
take the shardings of their placed arguments.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer
from repro.optim import adamw
from repro.parallel import compression


def make_train_step(cfg: ArchConfig, opt: adamw.AdamWConfig,
                    grad_compression: Optional[str] = None,
                    n_microbatches: int = 1) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``n_microbatches > 1`` enables gradient accumulation: the global batch
    is processed in sequential slices, bounding live activation memory at
    1/n of the full-batch footprint (grad accumulators stay FSDP-sharded).
    """

    def grads_of(params, batch):
        # value_and_grad's own vjp, split so that the device trace names
        # the forward's ops apart from the backward's
        with jax.named_scope("forward"):
            loss, backward, metrics = jax.vjp(
                lambda p: transformer.loss_fn(p, cfg, batch), params,
                has_aux=True)
        with jax.named_scope("backward"):
            grads, = backward(jnp.ones_like(loss))
        return (loss, metrics), grads

    def train_step(params, opt_state, batch):
        if n_microbatches <= 1:
            (loss, metrics), grads = grads_of(params, batch)
        else:
            n = n_microbatches

            def split(x):
                b = x.shape[0]
                assert b % n == 0, (b, n)
                return x.reshape(n, b // n, *x.shape[1:])

            micro = jax.tree_util.tree_map(split, batch)

            def body(carry, mb):
                gsum, lsum = carry
                (loss, _m), g = grads_of(params, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b_: a + b_.astype(a.dtype), gsum, g)
                return (gsum, lsum + loss), None

            gz = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(body, (gz, jnp.zeros(())), micro)
            grads = jax.tree_util.tree_map(lambda g: g / n, gsum)
            loss = lsum / n
            metrics = {"loss": loss, "ce_loss": loss}
        if grad_compression:
            grads = compression.compress_tree(grads, method=grad_compression)
        apply_fn = adamw.apply_8bit if use_8bit else adamw.apply
        with jax.named_scope("optimizer"):
            params, opt_state, opt_metrics = apply_fn(opt, params, opt_state,
                                                      grads)
        metrics = dict(metrics, **opt_metrics)
        return params, opt_state, metrics

    import os as _os
    use_8bit = _os.environ.get("REPRO_OPT8BIT") == "1"
    return train_step


def make_eval_step(cfg: ArchConfig) -> Callable:
    def eval_step(params, batch):
        loss, metrics = transformer.loss_fn(params, cfg, batch)
        return metrics

    return eval_step


def make_prefill_step(cfg: ArchConfig, cache_len: int = 0) -> Callable:
    """(params, batch) -> (next_token_logits, cache).

    ``cache_len`` sizes the KV caches for the tokens that decode will
    append (prompt + new tokens): global layers hold all of them, windowed
    rings the window or all of them if fewer; 0 sizes every cache to the
    prompt, and a decode step past it then overwrites the oldest slot."""

    def prefill_step(params, batch):
        h, _, caches = transformer.forward(params, cfg, batch, collect_cache=True)
        logits = transformer.unembed(params, cfg, h[:, -1:])
        seq_len = h.shape[1]
        if cache_len:
            caches = transformer.extend_cache(cfg, caches, cache_len)
        cache = {"pos": jnp.asarray(seq_len, jnp.int32), "groups": caches}
        return logits, cache

    return prefill_step


def make_decode_step(cfg: ArchConfig) -> Callable:
    """(params, cache, tokens (B,1)) -> (logits, new_cache)."""

    def serve_step(params, cache, tokens):
        return transformer.decode_step(params, cfg, cache, tokens)

    return serve_step
