"""The sparse-expert cell on the CPU at a small size: the program (Server's
prefill and decode steps, the dropless expert layer, YaRN on the full
layers, the sliding layers' rings) against ``chipbench/reference_moe.py``
on the seed's weights, the routing counter against the reference's
routing, and both new cells end to end through the harness."""
import json
import time

import pytest

from chipbench import harness
from chipbench.tests.test_chipbench_rehearsal import SEED, tiny_root

MOE, PREFILL = "mellum2-12b.serve-code-4k", "granite-20b.serve-prefill"
# d 64, 4 heads of 16 over 2 KV heads, 8 experts of width 32, top 2,
# window 16, 4 layers: sliding x 3 and full
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, moe_intermediate_size=32, num_experts=8,
             num_experts_per_tok=2, vocab_size=512, num_hidden_layers=4,
             sliding_window=16,
             layer_types=["sliding_attention"] * 3 + ["full_attention"],
             mlp_layer_types=["sparse"] * 4)


def small_model() -> dict:
    with open(harness.pathlib.Path(__file__).resolve().parents[1]
              / "configs" / "mellum2-12b-8l.json") as f:
        m = json.load(f)
    m.update(SMALL)
    return m


def small_root(dest):
    """The tiny cells' scratch root (``test_chipbench_rehearsal``) with the
    MoE configuration at ``SMALL`` and both new traffic mixes cut."""
    root = tiny_root(dest)
    bench = root / "chipbench"
    (bench / "configs" / "mellum2-12b-8l.json").write_text(
        json.dumps(small_model()))
    for name, changes in (("code-completion-4k", dict(batch=2, prompt_len=40,
                                                      new_tokens=6)),
                          ("prefill-8k", dict(prompt_len=48, new_tokens=4))):
        path = bench / "traffic" / f"{name}.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        **changes)))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("cells"))


def run(root, workload, trace=False, seconds=1.0):
    return harness.run_cell(root, workload, SEED, seconds, trace,
                            time.perf_counter(), require_tpu=False)


# the MoE cell through the harness, in float32: at this size a bfloat16
# forward flips near-tied routes often enough to move a served token's
# reference logit by tenths (0.87 in 40 windows), which would make the
# check's outcome depend on which requests a window samples
HARNESS_RUNS = """
import json, os, pathlib, sys, tempfile, time
os.environ["REPRO_COMPUTE_DTYPE"] = "float32"
sys.path[:0] = [{root!r}, {src!r}]
from chipbench import calibrate_moe, faults, harness
from chipbench.tests.test_chipbench_moe import MOE, SEED, small_root

root = small_root(pathlib.Path(tempfile.mkdtemp()))


def run(trace=False, seconds=1.0):
    return harness.run_cell(root, MOE, SEED, seconds, trace,
                            time.perf_counter(), require_tpu=False)


out = {{"sound": run(), "traced": run(trace=True)}}
with faults.planted("token_altered"):
    out["fault"] = run(seconds=0.5)
_, _, model, traffic, _ = harness.cell(root, MOE)
# a window of one batch, so the sampled requests do not depend on timing
_, outcome, _ = harness.drive(MOE, model, traffic, SEED, 0.0, False,
                              time.perf_counter())
out["control"] = calibrate_moe.control_numbers(model, traffic, SEED, outcome)
print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def moe_lines():
    from tests.conftest import run_subprocess_py

    root = harness.pathlib.Path(__file__).resolve().parents[2]
    r = run_subprocess_py(HARNESS_RUNS.format(root=str(root),
                                              src=str(root / "src")),
                          timeout=900)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")]
    assert line, r.stderr[-3000:]
    return json.loads(line[0][len("RESULT "):])


def test_moe_cell_end_to_end(moe_lines):
    line = moe_lines["sound"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    # token gaps on the host clock, not the (float32: zero) logit gaps
    assert line["metrics"]["itl_p95_ms"]["value"] > 0


def test_traced_moe_cell_reads_its_program_spans(moe_lines):
    """On the CPU the device's metrics stay silent; the serve loop's span
    readers read the window's decode dispatches."""
    line = moe_lines["traced"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {"dispatch_ms.serve",
                                    "decode_ahead_share.serve",
                                    "compile_s.serve"}


def test_token_altered_fault_fails_the_moe_cell(moe_lines):
    assert moe_lines["fault"]["correct"] is False


def test_float8_control_reads_gaps_the_program_does_not(moe_lines):
    """The control's reading, which sets the limit's upper side on the
    chip (``chipbench/checks/``), comes from the same window's samples;
    at this size it is tenths where the float32 program reads none."""
    assert set(moe_lines["sound"]["checks"]) == {"mean_logit_gap"}
    assert moe_lines["sound"]["checks"]["mean_logit_gap"]["value"] < 1e-4
    assert moe_lines["control"]["mean_logit_gap"] > 1e-3


def test_check_number_is_the_mean_gap_over_every_position():
    from chipbench.drivers.serve_moe import check_numbers

    got = check_numbers([[0.0, 0.5, 0.0], [0.0, 0.0, 0.0, 0.1]])
    assert got == {"mean_logit_gap": 0.6 / 7}


def test_prefill_cell_end_to_end(root):
    line = run(root, PREFILL)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


PROGRAM_VS_REFERENCE = """
import json, os, sys
os.environ["REPRO_COMPUTE_DTYPE"] = "float32"
sys.path[:0] = [{root!r}, {src!r}]
import jax.numpy as jnp, numpy as np
from chipbench import reference_moe, weights_moe
from chipbench.drivers.serve import benchmark_weights
from chipbench.drivers.serve_moe import arch_config
from chipbench.tests.test_chipbench_moe import small_model
from repro.runtime.serve_loop import ServeConfig, Server

m = small_model()
cfg = arch_config(m)
params = weights_moe.make(m, 7, jnp.bfloat16)
f32 = reference_moe.make_served_logits(m)
fp8 = reference_moe.make_served_logits(m, fp8=True)
N, out = 12, {{}}
for P in {prompts!r}:
    with benchmark_weights(params):
        srv = Server(cfg, ServeConfig(batch=2, prompt_len=P,
                                      max_new_tokens=N, seed=P))
    rep = srv.run()
    prompts = srv._requests()
    # the logits of Server's own prefill and decode steps, on its tokens
    logits, cache = srv.prefill(srv.params, {{"tokens": jnp.asarray(prompts)}})
    got = [logits[:, -1]]
    for i in range(N - 1):
        logits, cache = srv.decode(srv.params, cache,
                                   jnp.asarray(rep.outputs[:, i:i + 1]))
        got.append(logits[:, -1])
    got = np.stack([np.asarray(g, np.float64) for g in got], 1)
    gap = ctrl = 0.0
    gates = []
    for r in range(2):
        ref = np.asarray(f32(params, prompts[r], rep.outputs[r]), np.float64)
        low = np.asarray(fp8(params, prompts[r], rep.outputs[r]), np.float64)
        scale = np.abs(ref).max()
        gap = max(gap, np.abs(got[r] - ref).max() / scale)
        ctrl = max(ctrl, np.abs(low - ref).max() / scale)
        toks = np.concatenate([prompts[r], rep.outputs[r][:-1]])
        gates.append(np.asarray(reference_moe.forward(params, toks, m)[1]) > 0)
    gates = np.stack(gates)                       # (B, layers, S, E)
    n = len(weights_moe.period(m))
    R = m["num_hidden_layers"] // n
    order = [i * R + r for r in range(R) for i in range(n)]
    routes = rep.routes
    out[P] = {{
        "gap": gap, "ctrl": ctrl,
        "routed": np.array_equal(routes["routed"][order],
                                 gates.sum((0, 2))),
        "touched": np.array_equal(routes["touched"][order],
                                  gates[:, :, P:].any(0).sum((1, 2))),
        "decode_steps": routes["decode_steps"]}}
print("RESULT", json.dumps(out))
"""


# float32 program against the float32 reference: ordering and blocking of
# sums apart, the same computation (observed gaps about 5e-7 of the
# largest logit); the float8 control is off by tenths of it
LOGIT_TOL = 1e-4


def test_prefill_then_decode_matches_the_reference():
    """Server's prefill and decode steps give the reference's logits, in
    float32, where the prompt is a multiple of the sliding window (32),
    is not (20), and is shorter than it (10; its ring wraps during
    decode).  The ``repro.serve.moe`` counters equal the routing of the
    reference's forward: routes per expert over the prompt and every
    decode step before the last, and distinct experts a step."""
    from tests.conftest import run_subprocess_py

    root = harness.pathlib.Path(__file__).resolve().parents[2]
    code = PROGRAM_VS_REFERENCE.format(root=str(root), src=str(root / "src"),
                                       prompts=(32, 20, 10))
    r = run_subprocess_py(code, timeout=900)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")]
    assert line, r.stderr[-3000:]
    for P, got in json.loads(line[0][len("RESULT "):]).items():
        assert got["gap"] < LOGIT_TOL, (P, got)
        assert got["ctrl"] > 10 * LOGIT_TOL, (P, got)
        assert got["routed"] and got["touched"], (P, got)
        assert got["decode_steps"] == 11
