"""Batched serving loop with prefill/decode phases + fault-tolerant restart.

Serving counterpart of the training loop: requests are prefill-ed in
batches, then decoded step-by-step against the shared KV cache.  On an
injected fault the loop drops the affected batch's in-flight state, marks
the node, and replays the requests (serving "checkpoint" = the request
queue itself; decode state is cheap to rebuild relative to training)."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import params as pmod
from repro.models import transformer
from repro.models.steps import make_decode_step, make_prefill_step
from repro.obs.spans import span
from repro.runtime.fault_injection import FaultInjector, SimulatedFault


@dataclass
class ServeConfig:
    batch: int = 4
    prompt_len: int = 32
    max_new_tokens: int = 16
    seed: int = 0


@dataclass
class ServeReport:
    completed_requests: int
    retries: int
    tokens_generated: int
    wall_s: float
    outputs: np.ndarray
    # an MoE model's routing counters (see ``Server.routes``), else None
    routes: Optional[dict] = None


def decode_jit(cfg: ArchConfig):
    """The decode step as ``Server`` runs it: jitted with its cache donated,
    so that each step writes its K/V slots where they lie."""
    return jax.jit(make_decode_step(cfg), donate_argnums=(1,))


class Server:
    def __init__(self, cfg: ArchConfig, scfg: ServeConfig,
                 injector: Optional[FaultInjector] = None):
        self.cfg = cfg
        self.scfg = scfg
        self.injector = injector or FaultInjector()
        defs = pmod.cast_defs(transformer.model_defs(cfg), jnp.bfloat16)
        self.params = pmod.materialize(defs, seed=scfg.seed)
        self.prefill = jax.jit(make_prefill_step(
            cfg, cache_len=scfg.prompt_len + scfg.max_new_tokens))
        self.decode = decode_jit(cfg)
        self.batches = 0  # calls of run(): the spans' ``batch`` id

    def _requests(self) -> np.ndarray:
        rng = np.random.default_rng(self.scfg.seed)
        return rng.integers(3, self.cfg.vocab_size,
                            (self.scfg.batch, self.scfg.prompt_len),
                            dtype=np.int32)

    def run(self) -> ServeReport:
        sc = self.scfg
        b = self.batches
        self.batches += 1
        with span("repro.serve.run", batch=b) as whole:
            prompts = self._requests()
            retries = 0
            step_counter = 0
            counted = []
            while True:
                try:
                    batch = {"tokens": jnp.asarray(prompts)}
                    if self.cfg.enc_dec:
                        batch["frames"] = jnp.zeros(
                            (sc.batch, sc.prompt_len, self.cfg.d_model),
                            jnp.bfloat16)
                    logits, cache = self.prefill(self.params, batch)
                    out = np.zeros((sc.batch, sc.max_new_tokens), np.int32)
                    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                    for i in range(sc.max_new_tokens):
                        fault = self.injector.poll(step_counter)
                        step_counter += 1
                        if fault is not None and fault.kind == "crash":
                            raise SimulatedFault(fault)
                        if i == sc.max_new_tokens - 1:  # see ``routes``
                            counted = _counters(cache)
                        # the step that consumes token i is queued before
                        # the host waits for token i, so the device runs
                        # from one step straight into the next
                        with span("repro.serve.dispatch", batch=b, token=i,
                                  ahead=not tok.is_ready()):
                            logits, cache = self.decode(
                                self.params, cache, tok[:, None])
                        with span("repro.serve.copy", batch=b, token=i):
                            out[:, i] = np.asarray(tok)
                        tok = jnp.argmax(logits[:, -1],
                                         axis=-1).astype(jnp.int32)
                    break
                except SimulatedFault:
                    retries += 1
                    if retries > 8:
                        raise
            routes = self.routes(b, counted, sc.max_new_tokens - 1)
        return ServeReport(
            completed_requests=sc.batch, retries=retries,
            tokens_generated=int(sc.batch * sc.max_new_tokens),
            wall_s=whole.seconds, outputs=out, routes=routes)

    def routes(self, b: int, counted: list, steps: int) -> Optional[dict]:
        """An MoE model's routing counters ``counted`` (``_counters``),
        copied to the host once and recorded as a ``repro.serve.moe`` span;
        None for a model without experts.  They are the last decode step's
        input's, holding the prefill and the ``steps`` decode steps before
        it: they were ready when the last token was, so the copy waits on
        nothing the loop did not (the batch's last, unused step runs on).

        ``routed`` (MoE layers, experts): routes each expert received;
        ``touched`` (MoE layers,): distinct experts each decode step read,
        summed over the steps."""
        if not counted:
            return None
        routed, touched = jax.device_get(tuple(zip(*counted)))
        routed = np.concatenate(routed).astype(np.int64)  # (layers, E)
        touched = np.concatenate(touched).astype(np.int64)
        stats = {
            "experts_touched": float(touched.sum() / (touched.size * steps))
            if steps else 0.0,
            "max_load_over_mean": float(
                (routed.max(axis=1) / routed.mean(axis=1)).max()),
            "decode_steps": steps,
        }
        with span("repro.serve.moe", batch=b, **stats):
            return dict(stats, routed=routed, touched=touched)


def _counters(cache: dict) -> list:
    """Device copies of the MoE layers' (routed, touched) counters in
    ``cache``, made before the cache is donated to the next decode step
    (the copies are queued ahead of it, and wait on nothing else)."""
    return [(jnp.copy(c["routed"]), jnp.copy(c["touched"]))
            for g in cache["groups"] for c in g.values() if "routed" in c]
