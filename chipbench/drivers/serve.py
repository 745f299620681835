"""Drives ``Server.run``: static batches, prefill then greedy decode.

A closed loop: as one batch of requests completes, the next is sent, so
the batch's clients always have one request in flight.  Each batch gets
fresh prompts: the server's ``scfg`` is replaced by a copy whose ``seed``
is drawn from ``--seed`` and the batch's index; the weights stay as made.
The window runs from the start of the first timed batch to the end of
the batch in flight at the deadline (in a traced run, ``trace_batches``
batches).

A token's time is the entry of the ``Server.decode`` call that follows
the host's copy of it; the gaps between successive tokens of a request
are the differences of those times.

After the window the server is freed, and the reference, run once over
each sampled request's prompt and served tokens, gives the widest gap by
which a served token's logit lies below the reference's best."""
from __future__ import annotations

import contextlib
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, weights
from chipbench.drivers import GcClock, arch_config, note, peak_bytes, spanned
from chipbench.trace import WINDOW_SPAN


@contextlib.contextmanager
def benchmark_weights(params):
    """The server's initializer hands back ``params`` (checked against the
    program's tree) instead of drawing its own."""
    from repro.models import params as pmod

    own = pmod.materialize

    def materialize(defs, seed=0):
        weights.check_matches(params, pmod.abstract(defs))
        return params

    pmod.materialize = materialize
    try:
        yield
    finally:
        pmod.materialize = own


def run(ctx) -> dict:
    from repro.runtime.serve_loop import ServeConfig, Server

    m, t = ctx.model, ctx.traffic
    cfg = arch_config(m)
    B, P, T = t["batch"], t["prompt_len"], t["new_tokens"]

    def scfg(i: int) -> ServeConfig:
        return ServeConfig(batch=B, prompt_len=P, max_new_tokens=T,
                           seed=weights.seed32(ctx.seed, "batch", i))

    with benchmark_weights(weights.make(m, ctx.seed, jnp.bfloat16)):
        srv = Server(cfg, scfg(0))
    prompts, stamps = [], []
    prefill, decode = srv.prefill, srv.decode

    def prefill_kept(params, batch):
        prompts.append(batch["tokens"])
        return prefill(params, batch)

    srv.prefill = spanned("chipbench.prefill", prefill_kept)
    srv.decode = spanned("chipbench.decode", decode, stamps)

    for i in range(t["warmup_batches"]):
        srv.scfg = scfg(i)
        srv.run()
    # the device runs in order: once this is done, so is the warm-up
    (jnp.zeros(()) + 1).block_until_ready()
    first = t["warmup_batches"]
    del prompts[:], stamps[:]

    span = None
    if ctx.trace:
        jax.profiler.start_trace(str(ctx.trace_dir))
        span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        span.__enter__()
    gc_clock = GcClock().__enter__()
    t_open = time.perf_counter()
    ctx.open_window()
    outputs, gaps, retries, walls = [], [], 0, []
    while True:
        srv.scfg = scfg(first + len(outputs))
        n = len(stamps)
        rep = srv.run()
        outputs.append(rep.outputs)
        walls.append(rep.wall_s)
        retries += rep.retries
        gaps.extend(np.diff(stamps[n:]))
        now = time.perf_counter()
        if (len(outputs) >= t["trace_batches"] if ctx.trace
                else now - t_open >= ctx.seconds):
            break
    window_s = now - t_open
    gc_clock.__exit__()
    note("batches", walls, gc_clock)
    if span is not None:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    memory = peak_bytes()
    n_batches = len(outputs)
    del srv
    gc.collect()

    # the sample compared: requests of the window drawn from the seed
    rng = np.random.default_rng(weights.seed32(ctx.seed, "sample"))
    picks = rng.choice(n_batches * B, size=min(t["check_requests"],
                                                n_batches * B), replace=False)
    params = weights.make(m, ctx.seed, jnp.bfloat16)
    served_logits = reference.make_served_logits(m)
    widest, samples = 0.0, []
    for k in sorted(picks):
        b, r = divmod(int(k), B)
        prompt, served = np.asarray(prompts[b][r]), outputs[b][r]
        ref = served_logits(params, prompt, served)
        widest = max(widest, float(reference.served_gaps(ref, served).max()))
        samples.append((prompt, served))
    del params

    # a request fails if its batch had to be replayed
    failed = B * retries
    return {
        "attempted": n_batches * B, "failed": failed,
        "e2e": {"serve_tokens_per_s": n_batches * B * T / window_s,
                "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95))},
        "checks": {"logit_gap": widest},
        "memory_peak_bytes": memory,
        "counts": {"batches": n_batches, "token_gaps": len(gaps)},
        "readings": {"samples": samples},
    }
