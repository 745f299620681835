"""The readers of the program's runtime spans, on hand-built records."""
import pathlib
import sys

import pytest

from chipbench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
READERS = ("host_gap_ms.train", "dispatch_ms.train", "compile_s.train",
           "host_gap_ms.serve", "dispatch_ms.serve", "compile_s.serve")
MS, S = 1_000_000, 1_000_000_000
T0 = 10 * S  # the traced window's first span starts here


@pytest.fixture
def spans(monkeypatch):
    from repro.obs import spans

    held = []
    monkeypatch.setattr(spans, "captured", lambda: list(held))
    return spans, held


def _read(name):
    return harness.reader(ROOT, name)(None)


def _compiles(spans):
    """Three set-up compiles, one nested in another, and one in the window."""
    return [spans.Record(spans.COMPILE, a, b, {"fun_name": "f", "event": e},
                         None)
            for a, b, e in [
                (1 * S, 3 * S, "/jax/core/compile/backend_compile_duration"),
                (2 * S, 2 * S + S // 2,
                 "/jax/compilation_cache/cache_retrieval_time_sec"),
                (4 * S, 5 * S, "/jax/core/compile/jaxpr_trace_duration"),
                (T0 + 100 * MS, T0 + 200 * MS,
                 "/jax/core/compile/backend_compile_duration")]]


def _train(spans):
    """Steps 0-2: dispatch 2, 1 and 3 ms; the next dispatch ends 11 and 15
    ms after the previous loss is on the host."""
    out = []
    for n, (d0, d1, s1) in enumerate([(0, 2, 300), (310, 311, 600),
                                      (612, 615, 900)]):
        out += [spans.Record("repro.train.dispatch", T0 + d0 * MS,
                             T0 + d1 * MS, {"step": n}, "repro.train.step"),
                spans.Record("repro.train.sync", T0 + d1 * MS, T0 + s1 * MS,
                             {"step": n}, "repro.train.step")]
    return out


def _serve(spans):
    """Tokens 0-1 of batch 3: dispatches of 0.5 and 1 ms, each starting
    0.5 ms after its token's copy ends; token 2's copy saw a crash and no
    dispatch."""
    out = []
    for i, (c0, c1, d1) in enumerate([(0, 16, 18), (34, 50, 53)]):
        ids = {"batch": 3, "token": i}
        out += [spans.Record("repro.serve.copy", T0 + c0 * MS // 2,
                             T0 + c1 * MS // 2, ids, "repro.serve.run"),
                spans.Record("repro.serve.dispatch", T0 + (c1 + 1) * MS // 2,
                             T0 + d1 * MS // 2, ids, "repro.serve.run")]
    out.append(spans.Record("repro.serve.copy", T0 + 60 * MS, T0 + 61 * MS,
                            {"batch": 3, "token": 2}, "repro.serve.run"))
    return out


def test_train_readers(spans):
    mod, held = spans
    held += _compiles(mod) + _train(mod)
    assert _read("host_gap_ms.train") == pytest.approx(13.0)
    assert _read("dispatch_ms.train") == pytest.approx(2.0)
    assert _read("compile_s.train") == pytest.approx(3.0)


def test_serve_readers(spans):
    mod, held = spans
    held += _compiles(mod) + _serve(mod)
    assert _read("host_gap_ms.serve") == pytest.approx(1.25)
    assert _read("dispatch_ms.serve") == pytest.approx(0.75)
    assert _read("compile_s.serve") == pytest.approx(3.0)


def test_a_cell_reads_only_its_own_spans(spans):
    mod, held = spans
    held += _train(mod)
    assert _read("host_gap_ms.serve") is None
    assert _read("dispatch_ms.serve") is None
    assert _read("compile_s.train") == 0.0  # spans, and no compile


@pytest.mark.parametrize("name", READERS)
def test_no_records_read_none(spans, name):
    assert _read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_only_compiles_read_none(spans, name):
    mod, held = spans
    held += _compiles(mod)
    assert _read(name) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_spans_reads_none(monkeypatch, name):
    import repro.obs

    monkeypatch.delattr(repro.obs, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert _read(name) is None
