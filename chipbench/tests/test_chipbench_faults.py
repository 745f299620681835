"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference in float8) fails a limit.

Each fault is planted in the program the window drives, at smoke widths
on the CPU, and a whole run is driven through the harness with its look
for a chip skipped: a train step that returns its state unchanged; a
train step that takes half of the batch and the mean over the rest; a
decode step whose token is altered where it is produced.  (The cells run
on one chip: there is no exchange between chips to leave out.)"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, faults, harness, reference, weights
from chipbench.tests.test_chipbench_rehearsal import SEED, tiny_root

TRAIN, SERVE = "sc2-3b.train-4k", "granite-20b.serve-code"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("cells"))


def run(root, workload):
    return harness.run_cell(root, workload, SEED, 0.5, False,
                            time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("workload,fault", [
    (TRAIN, "state_unchanged"), (TRAIN, "half_batch"),
    (SERVE, "token_altered")])
def test_fault_makes_the_run_incorrect(root, workload, fault):
    with faults.planted(fault):
        line = run(root, workload)
    assert line["correct"] is False
    if fault == "state_unchanged":   # nothing moved: every leaf reads 1
        assert line["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_sound_runs_are_correct(root):
    assert run(root, TRAIN)["correct"] is True
    assert run(root, SERVE)["correct"] is True


def limits(root, workload):
    return {k: v["limit"] for k, v in
            harness.load_json(root / "chipbench" / "checks"
                              / f"{workload}.json").items()}


def test_train_control_fails_a_limit(root):
    _, _, model, traffic, _ = harness.cell(root, TRAIN)
    rng = np.random.default_rng(0)
    batches = [rng.integers(3, model["vocab_size"],
                            (traffic["global_batch"], traffic["seq_len"] + 1),
                            dtype=np.int32) for _ in range(3)]
    ref = reference.train_readings(model, SEED, batches,
                                   traffic["total_steps"])
    ctrl = reference.train_readings(model, SEED, batches,
                                    traffic["total_steps"], fp8=True)
    numbers = compare.train_numbers(ctrl, ref)
    assert not compare.judge(numbers, limits(root, TRAIN))


def test_serve_control_fails_the_limit(root):
    _, _, model, traffic, _ = harness.cell(root, SERVE)
    params = weights.make(model, SEED, jnp.bfloat16)
    f32 = reference.make_served_logits(model)
    fp8 = reference.make_served_logits(model, fp8=True)
    rng = np.random.default_rng(1)
    widest = 0.0
    # at smoke widths the gaps are smaller than at the cell's: 256 served
    # positions give the widest room to show
    for _ in range(16):
        prompt = rng.integers(3, model["vocab_size"], traffic["prompt_len"],
                              dtype=np.int32)
        served = rng.integers(3, model["vocab_size"], 16, dtype=np.int32)
        pick = np.asarray(fp8(params, prompt, served)).argmax(-1)
        widest = max(widest, float(reference.served_gaps(
            f32(params, prompt, served), pick).max()))
    assert not compare.judge({"logit_gap": widest}, limits(root, SERVE))
