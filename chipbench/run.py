#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout.  It needs a TPU and the chips the cell
asks for; without them it exits non-zero and prints no result.  The last
line of standard output is the JSON result; the numbers compared with
the reference, each beside its limit, are the last lines of standard
error.  See ``chipbench/harness.py`` for how a cell is put together.
"""
import pathlib
import sys
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness

    harness.main(t0=T0)
