#!/usr/bin/env python3
"""Readings to set the limits of ``chipbench/checks/<workload>.json`` from.

    python3 chipbench/calibrate.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...] [--control-seeds <n> ...] \\
        [--fault-seeds <n> ...]

For each seed, in this one process, a run of the cell as the benchmark
makes it (a short window) gives the numbers the program reads against the
reference: the lower readings.  For each control seed, the reference put
in the program's place and computed in float8 (e4m3, one scale per
tensor; ``chipbench/reference.py``) gives the control's numbers, read the
same way: the upper readings.  Serving's control does not decode: at each
position of the same prompts and served tokens it reads the gap of the
token float8 puts first.  For each fault seed, each fault of
``chipbench/faults.py`` that the cell can have, planted in the program,
gives the fault's numbers.  Prints one JSON line per reading."""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control_numbers(model: dict, traffic: dict, seed: int, outcome: dict):
    import jax.numpy as jnp
    import numpy as np

    from chipbench import compare, reference, weights

    got = outcome["readings"]
    if traffic["driver"] == "train":
        ctrl = reference.train_readings(model, seed, got["batches"],
                                        traffic["total_steps"], fp8=True)
        return compare.train_numbers(ctrl, got["reference"])
    params = weights.make(model, seed, jnp.bfloat16)
    f32 = reference.make_served_logits(model)
    fp8 = reference.make_served_logits(model, fp8=True)
    widest = 0.0
    for prompt, served in got["samples"]:
        pick = np.asarray(fp8(params, prompt, served)).argmax(-1)
        widest = max(widest, float(reference.served_gaps(
            f32(params, prompt, served), pick).max()))
    return {"logit_gap": widest}


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

    from chipbench import faults, harness
    from repro.launch.compile_cache import configure_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the readings come from the chip only")
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _, _, model, traffic, _ = harness.cell(ROOT, args.workload)
    for seed in args.fault_seeds:
        for fault in faults.BY_DRIVER[traffic["driver"]]:
            t0 = time.perf_counter()
            with faults.planted(fault):
                _, outcome, _ = harness.drive(args.workload, model, traffic,
                                              seed, args.seconds, False, t0)
            row = {"seed": seed, "side": fault, **outcome["checks"],
                   "seconds": time.perf_counter() - t0}
            print(json.dumps(row), flush=True)
    for seed in args.seeds:
        t0 = time.perf_counter()
        _, outcome, _ = harness.drive(args.workload, model, traffic, seed,
                                      args.seconds, False, t0)
        row = {"seed": seed, "side": "program", **outcome["checks"],
               "attempted": outcome["attempted"],
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        if seed in args.control_seeds:
            t1 = time.perf_counter()
            row = {"seed": seed, "side": "control",
                   **control_numbers(model, traffic, seed, outcome),
                   "seconds": time.perf_counter() - t1}
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
