"""Fault-tolerant runtime: requeue, bit-exact resume, ETTR accounting,
straggler + collective diagnostics, serving retry."""
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, smoke_config
from repro.runtime.fault_injection import FaultInjector, InjectedFault
from repro.runtime.monitor import CollectiveTracer, StragglerMonitor
from repro.runtime.serve_loop import ServeConfig, Server
from repro.runtime.train_loop import FaultTolerantTrainer, TrainerConfig


@pytest.fixture
def cfg():
    return smoke_config(get_arch("rsc-llm"))


def _train(cfg, tmp, schedule=None, steps=24, ckpt_every=4, seed=0):
    inj = FaultInjector(schedule=schedule or {})
    tcfg = TrainerConfig(total_steps=steps, global_batch=4, seq_len=32,
                         ckpt_dir=str(tmp), ckpt_every_steps=ckpt_every,
                         ckpt_async=False, n_nodes=4, seed=seed)
    tr = FaultTolerantTrainer(cfg, tcfg, inj)
    return tr, tr.run()


def test_completes_despite_faults(cfg, tmp_path):
    sched = {6: InjectedFault("pcie_errors", node_id=1),
             14: InjectedFault("ib_link_error", node_id=2)}
    tr, rep = _train(cfg, tmp_path / "a", schedule=sched)
    assert rep.final_step == 24
    assert len(rep.attempts) == 3
    outcomes = [a.outcome for a in rep.attempts]
    assert outcomes[0] == "fault:pcie_errors"
    assert outcomes[-1] == "completed"
    assert {1, 2} <= rep.excluded_nodes  # high-severity drains
    assert 0.0 < rep.measured_ettr <= 1.0


def test_faulty_run_matches_clean_run_bit_exact(cfg, tmp_path):
    """Crash + restore replays the same data and lands on identical params
    (determinism is what makes ETTR the *only* cost of a failure)."""
    _, clean = _train(cfg, tmp_path / "clean", steps=16, ckpt_every=4, seed=7)
    tr_f, faulty = _train(
        cfg, tmp_path / "faulty", steps=16, ckpt_every=4, seed=7,
        schedule={10: InjectedFault("gpu_memory_errors", node_id=0)})
    assert faulty.final_step == clean.final_step == 16
    # compare final checkpoints
    from repro.checkpoint.manager import CheckpointManager
    from repro.models import params as pmod
    from repro.models import transformer
    from repro.optim import adamw

    defs = transformer.model_defs(cfg)
    p0 = pmod.materialize(defs, seed=7)
    template = (p0, adamw.init(p0))
    _, (pc, _), _ = CheckpointManager(tmp_path / "clean").restore(template)
    _, (pf, _), _ = CheckpointManager(tmp_path / "faulty").restore(template)
    for a, b in zip(jax.tree_util.tree_leaves(pc),
                    jax.tree_util.tree_leaves(pf)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_restore_waits_for_an_async_write_in_flight(cfg, tmp_path,
                                                    monkeypatch):
    """A fault while the last async checkpoint is still being written
    resumes from that checkpoint, not from an older one or from scratch."""
    import time

    from repro.checkpoint.manager import CheckpointManager

    write = CheckpointManager._write

    def slow_write(self, *a):
        time.sleep(0.5)
        write(self, *a)

    monkeypatch.setattr(CheckpointManager, "_write", slow_write)
    tcfg = TrainerConfig(total_steps=6, global_batch=4, seq_len=32,
                         ckpt_dir=str(tmp_path), ckpt_every_steps=2,
                         ckpt_async=True, n_nodes=4, seed=0)
    rep = FaultTolerantTrainer(cfg, tcfg, FaultInjector(schedule={
        3: InjectedFault("gpu_memory_errors", node_id=0)})).run()
    assert [a.start_step for a in rep.attempts] == [0, 2]
    assert rep.final_step == 6
    assert rep.losses[2] == rep.losses[3]  # step 2, before and after


def test_loss_decreases(cfg, tmp_path):
    _, rep = _train(cfg, tmp_path / "l", steps=30)
    assert np.mean(rep.losses[-5:]) < np.mean(rep.losses[:5])


def test_poisson_injection_ettr_reasonable(cfg, tmp_path):
    inj = FaultInjector(rate_per_step=0.15, n_nodes=4, seed=2)
    tcfg = TrainerConfig(total_steps=30, global_batch=4, seq_len=32,
                         ckpt_dir=str(tmp_path / "p"), ckpt_every_steps=3,
                         ckpt_async=False, n_nodes=4, seed=2)
    rep = FaultTolerantTrainer(cfg, tcfg, inj).run()
    assert rep.final_step == 30
    assert len(rep.attempts) >= 2
    assert 0.2 <= rep.measured_ettr <= 1.0


def test_lemon_node_excluded_after_repeat_offenses(cfg, tmp_path):
    sched = {5: InjectedFault("ethlink_errors", node_id=3),
             9: InjectedFault("ethlink_errors", node_id=3),
             13: InjectedFault("ethlink_errors", node_id=3)}
    tr, rep = _train(cfg, tmp_path / "lemon", schedule=sched, steps=20)
    assert 3 in rep.excluded_nodes
    assert any(v.node_id == 3 for v in rep.lemon_verdicts)


# -- monitors ----------------------------------------------------------------
def test_straggler_monitor_flags_slow_node():
    mon = StragglerMonitor(n_nodes=4, threshold=1.5, patience=2)
    newly = set()
    for step in range(4):
        times = {0: 1.0, 1: 1.0, 2: 1.0, 3: 3.0}
        newly |= mon.observe(step, times)
    assert mon.flagged == {3} and newly == {3}


def test_straggler_monitor_ignores_uniform_slowdown():
    mon = StragglerMonitor(n_nodes=4)
    for step in range(5):
        mon.observe(step, {i: 2.0 for i in range(4)})
    assert not mon.flagged


def test_collective_tracer_finds_missing_rank():
    tr = CollectiveTracer(n_ranks=4)
    for cid in ("ar_0", "ar_1"):
        for r in range(4):
            tr.enter(cid, r)
            tr.exit(cid, r)
    for r in (0, 1, 3):  # rank 2 never arrives at ar_2
        tr.enter("ar_2", r)
    d = tr.diagnose()
    assert d["collective"] == "ar_2"
    assert d["kind"] == "missing_entry" and d["culprit_ranks"] == [2]


def test_collective_tracer_finds_stuck_rank():
    tr = CollectiveTracer(n_ranks=2)
    tr.enter("ar_0", 0)
    tr.enter("ar_0", 1)
    tr.exit("ar_0", 0)  # rank 1 stuck inside (network/HW suspect)
    d = tr.diagnose()
    assert d["kind"] == "stuck_inside" and d["culprit_ranks"] == [1]


def test_straggler_monitor_strike_reset_on_healthy_step():
    """A slow step that does not persist never trips the patience
    counter: one healthy step resets the strikes to zero."""
    mon = StragglerMonitor(n_nodes=3, threshold=1.5, patience=3)
    slow = {0: 1.0, 1: 1.0, 2: 4.0}
    healthy = {0: 1.0, 1: 1.0, 2: 1.0}
    assert mon.observe(0, slow) == set()
    assert mon.observe(1, slow) == set()       # 2 strikes, one short
    assert mon.observe(2, healthy) == set()    # resets node 2
    assert mon.observe(3, slow) == set()
    assert mon.observe(4, slow) == set()       # back to 2 strikes only
    assert not mon.flagged
    assert mon.observe(5, slow) == {2}         # third consecutive strike


def test_straggler_monitor_flags_once():
    """A flagged node is reported as *newly* flagged exactly once, even
    though it keeps exceeding the threshold afterwards."""
    mon = StragglerMonitor(n_nodes=2, threshold=1.5, patience=1)
    slow = {0: 1.0, 1: 5.0}
    assert mon.observe(0, slow) == {1}
    for step in range(1, 4):
        assert mon.observe(step, slow) == set()
    assert mon.flagged == {1}


def test_collective_tracer_missing_entry_precedes_stuck():
    """When both pathologies exist, the first missing-entry collective
    wins — a rank that never arrived explains every later hang."""
    tr = CollectiveTracer(n_ranks=2)
    tr.enter("ar_0", 0)
    tr.enter("ar_0", 1)
    tr.exit("ar_0", 0)   # rank 1 stuck in ar_0...
    tr.enter("ar_1", 0)  # ...and never reaches ar_1
    d = tr.diagnose()
    assert d["collective"] == "ar_1" and d["kind"] == "missing_entry"
    assert d["culprit_ranks"] == [1]


def test_collective_tracer_healthy_returns_none():
    tr = CollectiveTracer(n_ranks=2)
    for cid in ("ar_0", "ar_1"):
        for r in range(2):
            tr.enter(cid, r)
            tr.exit(cid, r)
    assert tr.diagnose() is None


def test_monitors_as_obs_metric_sources():
    """Both monitors plug into MetricsRegistry.add_source; their polls
    land under sources.<name> in every snapshot."""
    from repro.obs import MetricsRegistry

    mon = StragglerMonitor(n_nodes=2, threshold=1.5, patience=1)
    mon.observe(0, {0: 1.0, 1: 5.0})
    tr = CollectiveTracer(n_ranks=2)
    tr.enter("ar_0", 0)

    reg = MetricsRegistry()
    reg.add_source("stragglers", mon.as_metric_source())
    reg.add_source("collectives", tr.as_metric_source())

    class _StubSpec:
        n_nodes = 2
        gpus_per_node = 8

    class _StubSim:  # enough surface for a snapshot poll
        spec = _StubSpec()
        _node_state = [0, 0]
        running = {}
        queue = []
        _deferred = []
        _now = 0.0
        horizon_s = 1.0

    reg._sim = _StubSim()
    snap = reg._snapshot(1.0)
    assert snap["sources"]["stragglers"] == {
        "n_flagged": 1, "flagged": [1], "n_striking": 1, "n_steps": 1}
    assert snap["sources"]["collectives"] == {
        "n_collectives": 1, "diagnosis_kind": "missing_entry",
        "culprit_ranks": [1]}


# -- serving ------------------------------------------------------------------
def test_server_retries_through_fault(cfg):
    srv = Server(cfg, ServeConfig(batch=2, prompt_len=16, max_new_tokens=6),
                 FaultInjector(schedule={2: InjectedFault("ib_link_error")}))
    rep = srv.run()
    assert rep.retries == 1
    assert rep.outputs.shape == (2, 6)


def test_server_output_deterministic(cfg):
    r1 = Server(cfg, ServeConfig(batch=2, prompt_len=16, max_new_tokens=6)).run()
    r2 = Server(cfg, ServeConfig(batch=2, prompt_len=16, max_new_tokens=6),
                FaultInjector(schedule={3: InjectedFault("pcie_errors")})).run()
    # a mid-decode fault + full replay must yield identical tokens
    assert np.array_equal(r1.outputs, r2.outputs)


def test_decode_matches_full_forward_past_the_prompt():
    """Decoding beyond the prompt keeps every earlier token in the global
    KV cache: each decode step's logits equal a full forward pass over the
    prompt plus the tokens generated so far (float32, own process)."""
    import textwrap

    from tests.conftest import run_subprocess_py

    code = textwrap.dedent("""
        import os
        os.environ["REPRO_COMPUTE_DTYPE"] = "float32"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_arch, smoke_config
        from repro.models import params as pmod, transformer
        from repro.models.steps import make_decode_step, make_prefill_step
        cfg = smoke_config(get_arch("starcoder2-3b"))
        params = pmod.materialize(transformer.model_defs(cfg), seed=0)
        S, N = 16, 6
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 3,
                                  cfg.vocab_size)
        logits, cache = jax.jit(make_prefill_step(cfg, S + N))(
            params, {"tokens": toks})
        decode = jax.jit(make_decode_step(cfg))
        for i in range(N):
            tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
            toks = jnp.concatenate([toks, tok], axis=1)
            logits, cache = decode(params, cache, tok)
            full, _ = make_prefill_step(cfg)(params, {"tokens": toks})
            np.testing.assert_allclose(logits, full, atol=1e-4, rtol=1e-4)
        print("OK")
    """)
    r = run_subprocess_py(code, timeout=600)
    assert "OK" in r.stdout, r.stderr[-3000:]
