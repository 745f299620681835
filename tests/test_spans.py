"""Runtime spans (repro.obs.spans): the trainer's, checkpoint manager's and
server's own timing, kept only under a profiler capture, on its clock."""
import glob
import threading
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_arch, smoke_config
from repro.obs import spans
from repro.runtime.fault_injection import FaultInjector, InjectedFault
from repro.runtime.serve_loop import ServeConfig, Server
from repro.runtime.train_loop import FaultTolerantTrainer, TrainerConfig


def _own(records):
    return [r for r in records if r.name != spans.COMPILE]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """A 2-step trainer run with a save after each step and a crash before
    the second, and a 2-token server run: once bare, once captured."""
    cfg = smoke_config(get_arch("rsc-llm"))
    tmp = tmp_path_factory.mktemp("spans")

    def trainer(name):
        tcfg = TrainerConfig(total_steps=2, global_batch=2, seq_len=16,
                             ckpt_dir=str(tmp / name), ckpt_every_steps=1,
                             ckpt_async=True, n_nodes=4)
        return FaultTolerantTrainer(cfg, tcfg, FaultInjector(schedule={
            1: InjectedFault("gpu_memory_errors", node_id=0)}))

    srv = Server(cfg, ServeConfig(batch=2, prompt_len=8, max_new_tokens=2))
    spans.clear()
    trainer("bare").run()
    srv.run()
    bare = spans.captured()
    tr = trainer("captured")
    spans.clear()
    with jax.profiler.trace(str(tmp / "trace")):
        report = tr.run()
        served = srv.run()
    records = spans.captured()
    spans.clear()
    xplane, = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                        recursive=True)
    return SimpleNamespace(bare=bare, records=_own(records), trainer=tr,
                           report=report, served=served, xplane=xplane)


def test_trainer_spans_nest_with_their_ids(runs):
    train = [(r.name, r.ids, r.parent) for r in runs.records
             if r.name.startswith(("repro.train.", "repro.ckpt."))
             and r.name != "repro.ckpt.write"]

    def step(attempt, n):
        return [("repro.train.data", {"step": n}, "repro.train.step"),
                ("repro.train.dispatch", {"step": n}, "repro.train.step"),
                ("repro.train.sync", {"step": n}, "repro.train.step"),
                ("repro.train.step", {"attempt": attempt, "step": n}, None)]

    def save(n):
        return [("repro.ckpt.snapshot", {"step": n}, "repro.train.save"),
                ("repro.train.save", {"step": n}, None)]

    assert train == ([("repro.train.restore", {"attempt": 1}, None)]
                     + step(1, 0) + save(1)
                     + [("repro.train.restore", {"attempt": 2}, None)]
                     + step(2, 1) + save(2))
    # the writes run on the manager's own thread, outside any span
    assert [(r.ids, r.parent) for r in runs.records
            if r.name == "repro.ckpt.write"] == [({"step": 1}, None),
                                                 ({"step": 2}, None)]


def test_trainer_report_reads_its_spans(runs):
    def secs(name):
        return [r.seconds for r in runs.records if r.name == name]

    rep = runs.report
    assert [a.outcome for a in rep.attempts] == ["fault:gpu_memory_errors",
                                                 "completed"]
    assert rep.step_wall_s == secs("repro.train.step")
    assert [a.restore_s for a in rep.attempts] == secs("repro.train.restore")
    assert rep.checkpoint_block_s == sum(secs("repro.train.save"))
    assert [w.wall_time_s for w in runs.trainer.manager.write_log] \
        == secs("repro.ckpt.write")
    # a span holds the spans inside it
    by_step = {r.ids["step"]: r for r in runs.records
               if r.name == "repro.train.step"}
    for r in runs.records:
        if r.parent == "repro.train.step":
            outer = by_step[r.ids["step"]]
            assert outer.start_ns <= r.start_ns <= r.end_ns <= outer.end_ns


def test_server_spans_nest_and_feed_the_report(runs):
    serve = [(r.name, r.ids, r.parent) for r in runs.records
             if r.name.startswith("repro.serve.")]
    batch = 1  # the server's second run() call
    # the step that consumes token i is dispatched before token i's copy;
    # whether its input was still being computed (``ahead``) depends on
    # how fast the device ran, so only its type is checked
    ahead = [r.ids.get("ahead") for r in runs.records
             if r.name == "repro.serve.dispatch"]
    assert [type(a) for a in ahead] == [bool, bool]
    tokens = [entry for i in range(2) for entry in (
        ("repro.serve.dispatch", {"batch": batch, "token": i,
                                  "ahead": ahead[i]}, "repro.serve.run"),
        ("repro.serve.copy", {"batch": batch, "token": i}, "repro.serve.run"))]
    assert serve == tokens + [("repro.serve.run", {"batch": batch}, None)]
    whole, = [r for r in runs.records if r.name == "repro.serve.run"]
    assert runs.served.wall_s == whole.seconds
    assert runs.served.outputs.shape == (2, 2)


def test_no_record_outside_a_capture(runs):
    assert runs.bare and _own(runs.bare) == []
    assert all(r.name == spans.COMPILE for r in runs.bare)


def test_spans_reach_the_profilers_host_plane(runs):
    data = jax.profiler.ProfileData.from_file(runs.xplane)
    host, = [p for p in data.planes if p.name == "/host:CPU"]
    names = {ev.name for line in host.lines for ev in line.events}
    assert {"repro.train.step", "repro.serve.dispatch"} <= names


def test_compile_listener_records_a_fresh_jit():
    def tripled_plus_one(x):
        return x * 3 + 1

    spans.clear()
    out = jax.jit(tripled_plus_one)(jnp.arange(7.0))
    assert float(out[2]) == 7.0
    records = spans.captured()
    mine = [r for r in records
            if "tripled_plus_one" in r.ids.get("fun_name", "")]
    assert mine and all(r.name == spans.COMPILE and r.parent is None
                        and r.end_ns >= r.start_ns for r in mine)
    events = {r.ids["event"] for r in mine}
    assert {"/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/backend_compile_duration"} <= events
    assert events <= spans.COMPILE_EVENTS


def test_span_parent_is_the_enclosing_span_on_its_thread(tmp_path):
    def worker():
        with spans.span("side", k=2):
            pass

    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("outer", k=0) as outer:
            with spans.span("inner", k=1) as inner:
                t = threading.Thread(target=worker)
                t.start()
                t.join(timeout=30)
    assert not t.is_alive()
    got = {r.name: (r.ids, r.parent) for r in _own(spans.captured())}
    assert got == {"inner": ({"k": 1}, "outer"), "side": ({"k": 2}, None),
                   "outer": ({"k": 0}, None)}
    assert 0 < inner.seconds <= outer.seconds
    with spans.span("after") as after:
        pass
    assert after.seconds >= 0
    assert "after" not in {r.name for r in spans.captured()}
    spans.clear()


def test_sync_save_blocks_for_its_snapshot_and_write(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    mgr = CheckpointManager(tmp_path / "ckpt", async_mode=False)
    spans.clear()
    with jax.profiler.trace(str(tmp_path / "trace")):
        blocked = mgr.save(3, {"w": jnp.ones((4, 4))})
    got = {r.name: r for r in _own(spans.captured())}
    spans.clear()
    assert set(got) == {"repro.ckpt.snapshot", "repro.ckpt.write"}
    assert all(r.ids == {"step": 3} and r.parent is None
               for r in got.values())
    assert blocked == (got["repro.ckpt.snapshot"].seconds
                       + got["repro.ckpt.write"].seconds)
    assert mgr.write_log[-1].wall_time_s == got["repro.ckpt.write"].seconds
