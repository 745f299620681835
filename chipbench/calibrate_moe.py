#!/usr/bin/env python3
"""Readings to set the sparse-expert cell's limits from, as
``chipbench/calibrate.py`` reads them for the dense cells, with the float8
control computed by ``chipbench/reference_moe.py``:

    python3 chipbench/calibrate_moe.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...] [--control-seeds <n> ...] \\
        [--fault-seeds <n> ...]

For each seed a short window of the cell gives the program's numbers
(``drivers/serve_moe.py:check_numbers``); for each control seed, at each
position of the same prompts and served tokens, the float32 reference's
gap of the token float8 puts first gives the control's; for each fault
seed, the ``token_altered`` fault planted in the program gives the
fault's.  Prints one JSON line per reading, with the widest gap and the
per-position gaps beside the numbers compared."""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control_gaps(model: dict, seed: int, outcome: dict) -> list:
    """Per sampled request of the window, the gap (by the float32
    reference) of the token float8 puts first at each served position."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference, reference_moe, weights_moe

    params = weights_moe.make(model, seed, jnp.bfloat16)
    f32 = reference_moe.make_served_logits(model)
    fp8 = reference_moe.make_served_logits(model, fp8=True)
    gaps = []
    for prompt, served in outcome["readings"]["samples"]:
        pick = np.asarray(fp8(params, prompt, served)).argmax(-1)
        gaps.append(reference.served_gaps(f32(params, prompt, served), pick))
    return gaps


def control_numbers(model: dict, traffic: dict, seed: int, outcome: dict):
    from chipbench.drivers.serve_moe import check_numbers

    return check_numbers(control_gaps(model, seed, outcome))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    from chipbench import faults, harness
    from chipbench.drivers.serve_moe import check_numbers
    from repro.launch.compile_cache import configure_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the readings come from the chip only")
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _, _, model, traffic, _ = harness.cell(ROOT, args.workload)

    def emit(seed, side, gaps, t0, **extra):
        g = np.concatenate(gaps)
        row = {"seed": seed, "side": side, **check_numbers(gaps),
               "widest": float(g.max()), **extra,
               "seconds": time.perf_counter() - t0,
               "gaps": [round(float(x), 5) for x in g]}
        print(json.dumps(row), flush=True)

    def drive(seed):
        _, outcome, _ = harness.drive(args.workload, model, traffic, seed,
                                      args.seconds, False,
                                      time.perf_counter())
        return outcome

    for seed in args.fault_seeds:
        t0 = time.perf_counter()
        with faults.planted("token_altered"):
            outcome = drive(seed)
        emit(seed, "token_altered", outcome["readings"]["gaps"], t0)
    for seed in args.seeds:
        t0 = time.perf_counter()
        outcome = drive(seed)
        emit(seed, "program", outcome["readings"]["gaps"], t0,
             attempted=outcome["attempted"])
        if seed in args.control_seeds:
            t1 = time.perf_counter()
            emit(seed, "control", control_gaps(model, seed, outcome), t1)


if __name__ == "__main__":
    main()
