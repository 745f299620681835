"""Batched serving loop with prefill/decode phases + fault-tolerant restart.

Serving counterpart of the training loop: requests are prefill-ed in
batches, then decoded step-by-step against the shared KV cache.  On an
injected fault the loop drops the affected batch's in-flight state, marks
the node, and replays the requests (serving "checkpoint" = the request
queue itself; decode state is cheap to rebuild relative to training)."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import params as pmod
from repro.models import transformer
from repro.models.steps import make_decode_step, make_prefill_step
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
        self.decode = jax.jit(make_decode_step(cfg))

    def _requests(self) -> np.ndarray:
        rng = np.random.default_rng(self.scfg.seed)
        return rng.integers(3, self.cfg.vocab_size,
                            (self.scfg.batch, self.scfg.prompt_len),
                            dtype=np.int32)

    def run(self) -> ServeReport:
        sc = self.scfg
        t0 = time.time()
        prompts = self._requests()
        retries = 0
        step_counter = 0
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
                    out[:, i] = np.asarray(tok)
                    logits, cache = self.decode(
                        self.params, cache, tok[:, None])
                    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                break
            except SimulatedFault:
                retries += 1
                if retries > 8:
                    raise
        return ServeReport(
            completed_requests=sc.batch, retries=retries,
            tokens_generated=int(sc.batch * sc.max_new_tokens),
            wall_s=time.time() - t0, outputs=out)
