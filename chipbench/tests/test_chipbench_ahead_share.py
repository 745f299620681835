"""The ``decode_ahead_share.serve`` reader on hand-built span records, and
the serve readers on the dispatch-first order it measures."""
import sys

import pytest

from chipbench.tests.test_chipbench_spans import (MS, T0, _compiles, _read,
                                                  _train)

NAME = "decode_ahead_share.serve"


@pytest.fixture
def spans(monkeypatch):
    from repro.obs import spans

    held = []
    monkeypatch.setattr(spans, "captured", lambda: list(held))
    return spans, held


def _ahead(spans, flags):
    """Batch 4's decode dispatches, each followed by its token's copy; a
    flag of None leaves the ``ahead`` id out, as the copy-first loop did."""
    out = []
    for i, flag in enumerate(flags):
        ids = {"batch": 4, "token": i}
        at = T0 + 20 * i * MS
        out += [spans.Record("repro.serve.dispatch", at, at + MS,
                             ids if flag is None else dict(ids, ahead=flag),
                             "repro.serve.run"),
                spans.Record("repro.serve.copy", at + MS, at + 16 * MS, ids,
                             "repro.serve.run")]
    return out


@pytest.mark.parametrize("flags, share", [
    ([True] * 5, 100.0),
    ([True, False, True, True], 75.0),
    ([False, False], 0.0),
    ([None] * 3, None),
])
def test_decode_ahead_share_reader(spans, flags, share):
    mod, held = spans
    held += _compiles(mod) + _train(mod) + _ahead(mod, flags)
    got = _read(NAME)
    assert got == (None if share is None else pytest.approx(share))


def test_host_gap_serve_pairs_no_copy_with_a_later_dispatch(spans):
    mod, held = spans
    held += _ahead(mod, [True] * 3)
    assert _read("host_gap_ms.serve") is None
    assert _read("dispatch_ms.serve") == pytest.approx(1.0)


def test_a_train_cell_reads_no_ahead_share(spans):
    mod, held = spans
    held += _train(mod)
    assert _read(NAME) is None


def test_no_records_read_none(spans):
    assert _read(NAME) is None


def test_only_compiles_read_none(spans):
    mod, held = spans
    held += _compiles(mod)
    assert _read(NAME) is None


def test_a_program_without_spans_reads_none(monkeypatch):
    import repro.obs

    monkeypatch.delattr(repro.obs, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    assert _read(NAME) is None
