"""Drives ``Server.run`` on a sparse-expert model, as ``serve.py`` drives
it on a dense one: the same closed loop of static batches, the same
fresh prompts a batch, the same token stamps (a token's time is the entry
of the ``Server.decode`` call that follows the host's copy of it), and the
same comparison: the reference (``chipbench/reference_moe.py``), run
once over each sampled request's prompt and served tokens, gives at each
served position the gap by which the served token's logit lies below the
reference's best (``check_numbers`` reads them).

The model file names the program's registered architecture (``arch``)
and states its shape in the published config's keys; the layer pattern is
the period of ``layer_types``.  Beside the end-to-end numbers it returns
the program's routing counters (``ServeReport.routes``) in ``counts``."""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, reference_moe, weights, weights_moe
from chipbench.drivers import GcClock, note, peak_bytes, spanned
from chipbench.drivers.serve import benchmark_weights
from chipbench.trace import WINDOW_SPAN


def arch_config(m: dict):
    """The program's ArchConfig for the model file ``m``: the registered
    architecture ``m["arch"]`` at the file's widths, depth and pattern."""
    from repro.configs.base import MoESpec, YaRNSpec, get_arch

    rope = m["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    pattern = tuple(weights_moe.KINDS[t] for t in weights_moe.period(m))
    cfg = get_arch(m["arch"]).replace(
        name=m["name"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_head=m["head_dim"],
        d_ff=m["moe_intermediate_size"], vocab_size=m["vocab_size"],
        n_layers=m["num_hidden_layers"],
        block_groups=((pattern, m["num_hidden_layers"] // len(pattern)),),
        window=m["sliding_window"],
        moe=MoESpec(n_experts=m["num_experts"],
                    top_k=m["num_experts_per_tok"], capacity_factor=None),
        rope_theta=float(sliding["rope_theta"]),
        yarn=YaRNSpec(factor=float(full["factor"]),
                      original_max_position=full[
                          "original_max_position_embeddings"],
                      beta_fast=float(full["beta_fast"]),
                      beta_slow=float(full["beta_slow"]),
                      attention_factor=float(full["attention_factor"])),
        norm_eps=m["rms_norm_eps"])
    # what chipbench/reference_moe.py implements, and so what a cell may run
    plain = (cfg.family == "moe" and cfg.ffn_gated and not cfg.qk_norm
             and not cfg.moe.shared_expert and not cfg.tie_embeddings
             and not cfg.enc_dec and not cfg.n_patches
             and cfg.attn_logit_softcap == 0.0
             and m["norm_topk_prob"] and m["hidden_act"] == "silu"
             and sliding["rope_type"] == "default"
             and full["rope_type"] == "yarn"
             and full["rope_theta"] == sliding["rope_theta"])
    if not plain:
        raise ValueError(f"{m['arch']}: not the sparse-expert decoder the "
                         f"reference implements")
    return cfg


def check_numbers(gaps) -> dict:
    """The number ``correct`` holds to its limit, from the per-position gaps
    of the sampled requests: their mean over every position
    (``mean_logit_gap``).  The widest gap, the dense cells' ``logit_gap``,
    cannot tell float8 from bfloat16 here: a random router's near-ties
    move a few routes between bfloat16 and float32, and a moved route can
    widen one position's gap as far as a lower precision does.  The mean
    is moved by such a position only in proportion to it, and by a lower
    precision, or a wrong token, at every position."""
    g = np.concatenate([np.asarray(x, np.float64) for x in gaps])
    return {"mean_logit_gap": float(g.mean())}


def run(ctx) -> dict:
    from repro.runtime.serve_loop import ServeConfig, Server

    m, t = ctx.model, ctx.traffic
    cfg = arch_config(m)
    B, P, T = t["batch"], t["prompt_len"], t["new_tokens"]

    def scfg(i: int) -> ServeConfig:
        return ServeConfig(batch=B, prompt_len=P, max_new_tokens=T,
                           seed=weights.seed32(ctx.seed, "batch", i))

    with benchmark_weights(weights_moe.make(m, ctx.seed, jnp.bfloat16)):
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
    outputs, gaps, retries, walls, routes = [], [], 0, [], []
    while True:
        srv.scfg = scfg(first + len(outputs))
        n = len(stamps)
        rep = srv.run()
        outputs.append(rep.outputs)
        walls.append(rep.wall_s)
        routes.append(rep.routes)
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
    params = weights_moe.make(m, ctx.seed, jnp.bfloat16)
    served_logits = reference_moe.make_served_logits(m)
    logit_gaps, samples = [], []
    for k in sorted(picks):
        b, r = divmod(int(k), B)
        prompt, served = np.asarray(prompts[b][r]), outputs[b][r]
        ref = served_logits(params, prompt, served)
        logit_gaps.append(reference.served_gaps(ref, served))
        samples.append((prompt, served))
    del params

    counts = {"batches": n_batches, "token_gaps": len(gaps)}
    if all(r is not None for r in routes):
        counts.update(
            experts_touched=float(np.mean([r["experts_touched"]
                                           for r in routes])),
            max_load_over_mean=max(r["max_load_over_mean"] for r in routes),
            decode_steps=sum(r["decode_steps"] for r in routes))
    # a request fails if its batch had to be replayed
    return {
        "attempted": n_batches * B, "failed": B * retries,
        "e2e": {"serve_tokens_per_s": n_batches * B * T / window_s,
                "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95))},
        "checks": check_numbers(logit_gaps),
        "memory_peak_bytes": memory,
        "counts": counts,
        "readings": {"samples": samples, "gaps": logit_gaps},
    }
