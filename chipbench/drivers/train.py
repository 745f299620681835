"""Drives ``FaultTolerantTrainer.run``: the fault-tolerant training loop.

Set-up builds one trainer (its compiled step, its pipeline fed from the
seed) on the benchmark's weights, and one ``run()`` carries it through
the warm-up steps and the window.  The window is timed by the trainer's
own fault-injection interface: the injector opens it at the poll after
the warm-up steps, and at the first poll past ``--seconds`` (in a traced
run, past ``trace_steps`` steps) it returns a crash, which ends the run
(``max_attempts`` 1).  ``total_steps`` and the checkpoint cadence lie
beyond any window, so no checkpoint is written.

The first ``check_steps`` steps are the ones compared with the reference:
their losses, the first step's gradient per leaf as the optimizer applied
it (read from Adam's first moment after step 1), and each leaf's change
over them."""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, reference, weights
from chipbench.drivers import GcClock, arch_config, note, peak_bytes, spanned
from chipbench.trace import WINDOW_SPAN


class WindowInjector:
    """The trainer's ``poll(step)`` hook as the window's clock."""

    def __init__(self, ctx, warmup: int, trace_steps: int):
        self.ctx, self.warmup, self.trace_steps = ctx, warmup, trace_steps
        self.open_step = self.close_step = None
        self.t_open = self.t_close = None
        self._span = None

    def poll(self, step: int):
        from repro.runtime.fault_injection import InjectedFault

        now = time.perf_counter()
        if self.t_open is None:
            if step == self.warmup:
                if self.ctx.trace:
                    jax.profiler.start_trace(str(self.ctx.trace_dir))
                    self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
                    self._span.__enter__()
                self.open_step, self.t_open = step, time.perf_counter()
                self.ctx.open_window()
            return None
        if self.ctx.trace:
            done = step - self.open_step >= self.trace_steps
        else:
            done = now - self.t_open >= self.ctx.seconds
        if not done:
            return None
        self.close_step, self.t_close = step, now
        if self._span is not None:
            self._span.__exit__(None, None, None)
        # the end of the window, not a fault of the workload
        return InjectedFault("system_services", node_id=0, kind="crash")


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.ravel())
                      for x in jax.tree_util.tree_leaves(tree)])


def run(ctx) -> dict:
    from repro.models import params as pmod
    from repro.optim import adamw
    from repro.runtime.train_loop import FaultTolerantTrainer, TrainerConfig

    m, t = ctx.model, ctx.traffic
    opt = m["optimizer"]
    cfg = arch_config(m)
    n_check, total = t["check_steps"], t["total_steps"]
    tcfg = TrainerConfig(
        total_steps=total, global_batch=t["global_batch"],
        seq_len=t["seq_len"], ckpt_dir=str(ctx.tmp_dir / "ckpt"),
        ckpt_async=True, ckpt_every_steps=total, n_nodes=4,
        max_attempts=1, seed=weights.seed32(ctx.seed, "data"), lr=opt["lr"])
    injector = WindowInjector(ctx, t["warmup_steps"], t["trace_steps"])
    trainer = FaultTolerantTrainer(cfg, tcfg, injector)

    def init_state():
        params = weights.make(m, ctx.seed, jnp.float32)
        weights.check_matches(params, pmod.abstract(trainer.defs))
        return params, adamw.init(params)

    # the benchmark's weights in place of the program's initializer
    trainer._init_state = init_state

    readings = {"grad": None, "delta": None}
    batches = []
    step_fn, next_batch = trainer.step_fn, trainer.pipeline.next_batch

    def step(params, opt_state, batch):
        out = step_fn(params, opt_state, batch)
        if len(batches) == 1 and readings["grad"] is None:
            readings["grad"] = np.asarray(_leaf_norms(out[1].m)) / (1 - opt["b1"])
        if len(batches) == n_check and readings["delta"] is None:
            readings["delta"] = reference.leaf_delta_norms(m, ctx.seed, out[0])
        return out

    def batch():
        b = next_batch()
        if len(batches) < n_check:
            batches.append(b["tokens"])
        return b

    trainer.step_fn = spanned("chipbench.step", step)
    trainer.pipeline.next_batch = spanned("chipbench.next_batch", batch)
    trainer.manager.save = spanned("chipbench.save", trainer.manager.save)

    with GcClock() as gc_clock:
        report = trainer.run()
    if ctx.trace:
        jax.profiler.stop_trace()
    if injector.close_step is None:
        raise RuntimeError(f"the window never closed: {report.attempts}")
    window_s = injector.t_close - injector.t_open
    steps = injector.close_step - injector.open_step
    window_losses = report.losses[injector.open_step:injector.close_step]
    failed = int(np.sum(~np.isfinite(window_losses)))
    note("steps", report.step_wall_s[injector.open_step:injector.close_step],
         gc_clock)
    memory = peak_bytes()
    del trainer
    gc.collect()

    prog = {"losses": report.losses[:n_check], **readings}
    ref = reference.train_readings(m, ctx.seed, batches, total)
    tokens = steps * t["global_batch"] * t["seq_len"]
    return {
        "attempted": steps, "failed": failed,
        "e2e": {"train_tokens_per_s": tokens / window_s},
        "checks": compare.train_numbers(prog, ref),
        "memory_peak_bytes": memory,
        "counts": {"steps": steps},
        "readings": {"program": prog, "reference": ref, "batches": batches},
    }
