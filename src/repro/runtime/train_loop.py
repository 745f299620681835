"""Fault-tolerant training loop — the paper's job lifecycle, live.

One ``FaultTolerantTrainer.run()`` is a *job run* in the paper's sense: a
sequence of attempts (scheduler jobs) separated by injected infra failures.
Each attempt restores the newest complete checkpoint (params + optimizer +
data-pipeline state, bit-exact), trains until fault or completion, and
checkpoints at the Daly-Young-optimal cadence.  The trainer accounts
productive vs unproductive wall time exactly as §II-D defines ETTR, so the
measured ETTR of a run with Poisson fault injection can be validated
against the analytical estimator (tests/test_runtime.py).

Health-check semantics: on a crash fault, the "node" is marked unhealthy
and excluded from the next attempt's placement (no second job failure from
a bad node); lemon nodes accumulate NodeHistory and get excluded by the
LemonDetector after repeated offenses.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy
from repro.configs.base import ArchConfig
from repro.core.lemon import LemonDetector, NodeHistory
from repro.core.taxonomy import TAXONOMY, most_likely_cause
from repro.data.pipeline import DataConfig, SyntheticLMPipeline
from repro.models import params as pmod
from repro.models import transformer
from repro.models.steps import make_train_step
from repro.obs.spans import span
from repro.optim import adamw
from repro.runtime.fault_injection import FaultInjector, SimulatedFault


@dataclass
class TrainerConfig:
    total_steps: int = 100
    global_batch: int = 8
    seq_len: int = 64
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_async: bool = True
    ckpt_every_steps: int = 0      # 0 -> wall-time Daly-Young policy
    n_nodes: int = 4               # simulated node count (for accounting)
    r_f_per_node_day: float = 6.50e-3
    sim_u0_s: float = 0.0          # simulated restart overhead (sleep)
    max_attempts: int = 64
    seed: int = 0
    lr: float = 1e-3
    grad_compression: Optional[str] = None
    n_microbatches: int = 1


@dataclass
class AttemptRecord:
    attempt: int
    start_step: int
    end_step: int
    wall_s: float
    outcome: str              # completed | fault:<symptom>
    excluded_nodes: tuple = ()
    restore_s: float = 0.0    # init or checkpoint restore, before step 1


@dataclass
class TrainReport:
    attempts: list
    losses: list
    total_wall_s: float
    productive_wall_s: float
    checkpoint_block_s: float
    restart_overhead_s: float
    lost_step_wall_s: float
    final_step: int
    excluded_nodes: set
    lemon_verdicts: list
    step_wall_s: list = field(default_factory=list)  # per step, in order

    @property
    def measured_ettr(self) -> float:
        if self.total_wall_s <= 0:
            return 0.0
        return self.productive_wall_s / self.total_wall_s


class FaultTolerantTrainer:
    def __init__(self, cfg: ArchConfig, tcfg: TrainerConfig,
                 injector: Optional[FaultInjector] = None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.injector = injector or FaultInjector()
        self.defs = transformer.model_defs(cfg)
        opt_cfg = adamw.AdamWConfig(lr=tcfg.lr, warmup_steps=5,
                                    total_steps=max(tcfg.total_steps, 10))
        # params and optimizer state are donated: each step updates them in
        # place (a checkpoint copies them to the host before the next step)
        self.step_fn = jax.jit(make_train_step(
            cfg, opt_cfg, grad_compression=tcfg.grad_compression,
            n_microbatches=tcfg.n_microbatches), donate_argnums=(0, 1))
        self.pipeline = SyntheticLMPipeline(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=tcfg.seq_len,
            global_batch=tcfg.global_batch, seed=tcfg.seed))
        policy = CheckpointPolicy(
            n_nodes=tcfg.n_nodes, r_f_per_node_day=tcfg.r_f_per_node_day)
        self.policy = policy
        self.manager = CheckpointManager(tcfg.ckpt_dir, keep=2,
                                         async_mode=tcfg.ckpt_async)
        self.node_histories = {i: NodeHistory(i)
                               for i in range(tcfg.n_nodes)}
        self.detector = LemonDetector()
        self.excluded: set[int] = set()

    # ------------------------------------------------------------------
    def _init_state(self):
        params = pmod.materialize(self.defs, seed=self.tcfg.seed)
        opt_state = adamw.init(params)
        return params, opt_state

    def _restore_or_init(self):
        start_step = 0
        # an async write still in flight when the attempt failed is the
        # newest checkpoint: its writer outlives the attempt, so let it land
        self.manager.wait()
        if self.manager.latest_step() is not None:
            # shapes only: a materialized template would hold a second copy
            # of the state on the device
            template_p = pmod.abstract(self.defs)
            template = (template_p, jax.eval_shape(adamw.init, template_p))
            step, (params, opt_state), extra = self.manager.restore(template)
            params = jax.tree_util.tree_map(jax.numpy.asarray, params)
            opt_state = jax.tree_util.tree_map(jax.numpy.asarray, opt_state)
            start_step = int(extra.get("data_step", step))
            self.pipeline.restore(start_step)
        else:
            params, opt_state = self._init_state()
            self.pipeline.restore(0)
        return params, opt_state, start_step

    def _handle_fault(self, fault, step: int) -> None:
        """Health-check response: attribute, record lemon signals, exclude."""
        h = self.node_histories.setdefault(
            fault.node_id, NodeHistory(fault.node_id))
        if fault.symptom.startswith("gpu"):
            h.xid_cnt += 1
        h.multi_node_node_fails += 1
        h.out_count += 1
        sev = TAXONOMY[fault.symptom].severity
        if sev == "high":
            self.excluded.add(fault.node_id)  # drain immediately
        verdict = self.detector.evaluate(h)
        if verdict.is_lemon:
            self.excluded.add(fault.node_id)

    # ------------------------------------------------------------------
    def run(self) -> TrainReport:
        tc = self.tcfg
        attempts: list[AttemptRecord] = []
        losses: list[float] = []
        run_t0 = time.perf_counter()
        ckpt_block_s = 0.0
        restart_s = 0.0
        lost_s = 0.0
        lemon_verdicts = []
        step = 0
        attempt_no = 0
        step_walls: list[float] = []

        while step < tc.total_steps and attempt_no < tc.max_attempts:
            attempt_no += 1
            a_t0 = time.perf_counter()
            if tc.sim_u0_s:
                time.sleep(tc.sim_u0_s)
            # drop the failed attempt's state before restoring a new copy
            params = opt_state = None
            with span("repro.train.restore", attempt=attempt_no) as restore:
                params, opt_state, step = self._restore_or_init()
            restart_s += time.perf_counter() - a_t0
            last_ckpt_t = time.perf_counter()
            since_ckpt_wall = 0.0
            outcome = "completed"
            start_step = step
            try:
                while step < tc.total_steps:
                    fault = self.injector.poll(step)
                    if fault is not None and fault.kind == "crash":
                        raise SimulatedFault(fault)
                    # ``step`` ids count from 0: the step that consumes
                    # batch ``step`` of the pipeline
                    with span("repro.train.step", attempt=attempt_no,
                              step=step) as one:
                        with span("repro.train.data", step=step):
                            batch = self.pipeline.next_batch()
                            batch = {k: jax.numpy.asarray(v)
                                     for k, v in batch.items()}
                        if fault is not None and fault.kind == "straggler":
                            time.sleep(fault.slowdown * 0.01)
                        with span("repro.train.dispatch", step=step):
                            params, opt_state, metrics = self.step_fn(
                                params, opt_state, batch)
                        with span("repro.train.sync", step=step):
                            loss = float(metrics["loss"])
                    losses.append(loss)
                    step += 1
                    step_walls.append(one.seconds)
                    since_ckpt_wall += one.seconds
                    save_now = (
                        (tc.ckpt_every_steps and
                         step % tc.ckpt_every_steps == 0)
                        or (not tc.ckpt_every_steps and
                            self.policy.should_save(last_ckpt_t,
                                                    time.perf_counter()))
                        or step == tc.total_steps)
                    if save_now:
                        # the checkpoint's step: the steps it holds
                        with span("repro.train.save", step=step) as save:
                            self.manager.save(step, (params, opt_state),
                                              extra={"data_step": step})
                        ckpt_block_s += save.seconds
                        last_ckpt_t = time.perf_counter()
                        since_ckpt_wall = 0.0
            except SimulatedFault as e:
                outcome = f"fault:{e.fault.symptom}"
                self._handle_fault(e.fault, step)
                lost_s += since_ckpt_wall  # work since last checkpoint
            attempts.append(AttemptRecord(
                attempt_no, start_step, step, time.perf_counter() - a_t0,
                outcome, tuple(sorted(self.excluded)), restore.seconds))

        self.manager.wait()
        lemon_verdicts = self.detector.scan(self.node_histories.values())
        total_wall = time.perf_counter() - run_t0
        productive = max(total_wall - ckpt_block_s - restart_s - lost_s, 0.0)
        return TrainReport(
            attempts=attempts, losses=losses, total_wall_s=total_wall,
            productive_wall_s=productive, checkpoint_block_s=ckpt_block_s,
            restart_overhead_s=restart_s, lost_step_wall_s=lost_s,
            final_step=step, excluded_nodes=set(self.excluded),
            lemon_verdicts=[v for v in lemon_verdicts if v.is_lemon],
            step_wall_s=step_walls)
