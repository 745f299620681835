"""The trace reduction on a small trace recorded on a TPU v5e chip.

``data/small.xplane.pb``: inside a host span ``bench.window``, three
rounds of a 2048 x 2048 bf16 matmul module (``jit_mm``, in ``bench.mm``),
the Pallas flash forward (``jit__unknown``, in ``bench.flash``) and a
20 ms host sleep (``bench.sleep``)."""
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(DATA), window="bench.window", prefix="bench.")


@pytest.fixture(scope="module")
def raw():
    import jax

    data = jax.profiler.ProfileData.from_file(str(DATA))
    dev = next(p for p in data.planes if p.name == "/device:TPU:0")
    lines = {ln.name: list(ln.events) for ln in dev.lines}
    host = next(p for p in data.planes if p.name == "/host:CPU")
    spans = [e for ln in host.lines for e in ln.events
             if e.name.startswith("bench.")]
    return lines, spans


def test_union_length_merges_overlaps():
    total, merged = trace.union_length([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert total == 7
    assert merged == [[0, 3], [5, 9]]


def test_window_is_the_host_span(summary, raw):
    _, spans = raw
    win = next(e for e in spans if e.name == "bench.window")
    assert summary.window_s == pytest.approx(win.duration_ns * 1e-9)
    assert summary.n_chips == 1


def test_busy_is_the_union_of_ops(summary, raw):
    lines, _ = raw
    ops = sorted((e.start_ns, e.start_ns + e.duration_ns)
                 for e in lines["XLA Ops"])
    # a sweep over start and end points, independent of union_length
    points = sorted([(s, 1) for s, _ in ops] + [(e, -1) for _, e in ops])
    depth, busy, last = 0, 0.0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert summary.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    assert summary.busy_s <= sum(e - s for s, e in ops) * 1e-9
    assert summary.idle_share == pytest.approx(
        1 - summary.busy_s / summary.window_s)
    assert 0.98 < summary.idle_share < 1.0   # mostly the 60 ms of sleeps


def test_module_time_per_run(summary, raw):
    lines, _ = raw
    assert sorted(summary.modules) == ["jit__unknown", "jit_mm"]
    for name in summary.modules:
        want = sorted(e.duration_ns * 1e-9 for e in lines["XLA Modules"]
                      if trace.module_name(e.name) == name)
        assert sorted(summary.module_runs(name)) == pytest.approx(want)
        assert len(want) == 3


def test_device_clock_lands_modules_in_their_host_spans(summary, raw):
    lines, spans = raw
    for module, span in (("jit_mm", "bench.mm"),
                         ("jit__unknown", "bench.flash")):
        starts = [e.start_ns + summary.shift_ns for e in lines["XLA Modules"]
                  if trace.module_name(e.name) == module]
        inside = [e for e in spans if e.name == span]
        assert len(starts) == len(inside) == 3
        for t, e in zip(sorted(starts), sorted(inside, key=lambda e: e.start_ns)):
            assert e.start_ns <= t <= e.start_ns + e.duration_ns


def test_gaps_charged_to_the_host_span(summary):
    longest = summary.gaps[:3]
    assert [name for _, name in longest] == ["bench.sleep"] * 3
    assert all(0.019 < secs < 0.025 for secs, _ in longest)
    top = summary.top_idle(10)
    assert top[0][0] == "bench.sleep"
    assert sum(v for _, v in top) == pytest.approx(
        summary.window_s - summary.busy_s)


def test_top_ops_names_and_order(summary):
    ops = summary.top_ops(3)
    assert len(ops) == 3
    assert ops[0][1] >= ops[1][1] >= ops[2][1] > 0
    assert "_unknown_.1" in [k for k, _ in summary.top_ops(10)]


def test_missing_window_raises():
    with pytest.raises(ValueError, match="no host span"):
        trace.reduce(str(DATA), window="bench.nothing", prefix="bench.")


@pytest.mark.parametrize("text,code", [
    ("%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %a)",
     "fusion"),
    ("%while.121 = (s32[], bf16[2,4096]{1,0:T(8,128)}) while((s32[], "
     "bf16[2,4096]{1,0}) %t), condition=%c, body=%b", "while"),
    ("%closed_call.28 = f32[4]{0} call(f32[4]{0} %x), to_apply=%f", "call"),
    ("%_unknown_.1 = (bf16[1,4,1024,128]{3,2,1,0:T(8,128)(2,1)S(1)}, "
     "f32[1,4,1024,1]{3,2,1,0:T(8,128)}) custom-call(bf16[1] %q)",
     "custom-call"),
])
def test_op_code_and_loops_left_out_of_top_ops(text, code):
    assert trace.op_code(text) == code
    s = trace.Summary(1.0, 0.5, 1, {}, {text: [0.25, 1]}, {}, {}, [])
    assert s.top_ops() == ([] if code in trace.CONTAINERS
                           else [[trace.op_name(text), 0.25]])
