"""Both drivers end to end on the CPU, at smoke widths.

The cells' files are copied into a scratch root with their widths, depth,
vocabulary and lengths cut; the harness is called as a function with its
look for a chip skipped (``require_tpu=False``), so the jnp paths stand
in for the Pallas kernels.  A configuration, a traffic mix and a
per-layer metric added as files, with entries in BENCHMARK.json and no
other edit, are found by name."""
import json
import pathlib
import shutil
import time

import pytest

from chipbench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**33 + 17
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=512,
            num_hidden_layers=2)
NEW_METRIC = '''"""Steps the traced window held, read from the run's counts."""


def read(rec):
    return float(rec.outcome["counts"]["steps"])
'''


def tiny_root(dest: pathlib.Path) -> pathlib.Path:
    """A scratch root holding the cells' files at smoke widths, plus one
    new configuration, traffic mix, per-layer metric and workload."""
    bench = dest / "chipbench"
    for sub in ("configs", "traffic", "checks", "metrics"):
        shutil.copytree(ROOT / "chipbench" / sub, bench / sub)
    shutil.copy(ROOT / "chipbench" / "peaks.json", bench)

    def edit(rel, **changes):
        with open(bench / rel) as f:
            data = json.load(f)
        data.update(changes)
        with open(bench / rel, "w") as f:
            json.dump(data, f)
        return data

    edit("configs/sc2-3b-4l.json", **TINY)
    edit("configs/granite-20b-13l.json", **dict(TINY, num_key_value_heads=1))
    edit("traffic/train-steady.json", seq_len=64)
    edit("traffic/code-completion.json", batch=2, prompt_len=32,
         new_tokens=6)
    # the new files
    new_cfg = edit("configs/sc2-3b-4l.json", name="sc2-tiny-1l",
                   num_hidden_layers=1)
    with open(bench / "configs" / "sc2-tiny-1l.json", "w") as f:
        json.dump(new_cfg, f)
    edit("configs/sc2-3b-4l.json", name="sc2-3b-4l", num_hidden_layers=2)
    short = edit("traffic/train-steady.json")
    short.update(seq_len=32, trace_steps=3)
    with open(bench / "traffic" / "train-short.json", "w") as f:
        json.dump(short, f)
    shutil.copy(bench / "checks" / "sc2-3b.train-4k.json",
                bench / "checks" / "sc2-tiny.train-short.json")
    (bench / "metrics" / "steps_seen.train.py").write_text(NEW_METRIC)

    with open(ROOT / "BENCHMARK.json") as f:
        b = json.load(f)
    b["configs"].append(dict(b["configs"][0], name="sc2-tiny-1l",
                             file="chipbench/configs/sc2-tiny-1l.json"))
    b["workloads"].append({"name": "sc2-tiny.train-short",
                           "config": "sc2-tiny-1l", "traffic": "train-short",
                           "chips": 1, "why": "a cell added as files"})
    b["per_layer"].append({"name": "steps_seen.train", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "model step",
                           "moves": "train_tokens_per_s",
                           "workloads": ["sc2-tiny.train-short"]})
    for e in b["end_to_end"]:
        if e["name"] == "train_tokens_per_s":
            e["workloads"].append("sc2-tiny.train-short")
    with open(dest / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return dest


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("cells"))


def run(root, workload, trace=False):
    return harness.run_cell(root, workload, SEED, 1.0, trace,
                            time.perf_counter(), require_tpu=False)


def assert_contract_line(line, capsys):
    harness.emit(line)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "checks"
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(last["device"])
    for v in last["metrics"].values():
        assert set(v) == {"value", "unit"}
    for k, v in last["checks"].items():
        assert set(v) == {"value", "limit"}
        assert f"check {k} " in err
    return last


def test_train_driver_end_to_end(root, capsys):
    line = assert_contract_line(run(root, "sc2-3b.train-4k"), capsys)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "update_gap"}


def test_serve_driver_end_to_end(root, capsys):
    line = assert_contract_line(run(root, "granite-20b.serve-code"), capsys)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["attempted"] % 2 == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert set(line["checks"]) == {"logit_gap"}


def test_files_added_alone_are_found_by_name(root, capsys):
    line = assert_contract_line(
        run(root, "sc2-tiny.train-short", trace=True), capsys)
    assert line["correct"] is True
    assert line["attempted"] == 3
    # the new reader reads; the device's metrics stay silent on the CPU
    assert line["metrics"] == {"steps_seen.train": {"value": 3.0,
                                                    "unit": "steps"}}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
