"""Runs one cell once and builds the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``chipbench/configs/<config>.json`` (the entry's ``file``): the model;
* ``chipbench/traffic/<traffic>.json``: the mix, naming its ``driver``
  (``chipbench/drivers/<driver>.py``) and its parameters;
* ``chipbench/checks/<workload>.json``: the limit of each number compared
  with the reference, and the readings it was set from;
* ``chipbench/metrics/<metric>.py``: a reader ``read(rec)`` of one
  per-layer metric, returning ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import sys
import tempfile
import time


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's files, the run's arguments, and the
    clock that ``open_window`` stops to give the set-up time."""

    workload: str
    model: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    trace_dir: pathlib.Path
    tmp_dir: pathlib.Path
    t0: float
    setup_s: float | None = None

    def open_window(self) -> None:
        self.setup_s = time.perf_counter() - self.t0


@dataclasses.dataclass
class Record:
    """What a per-layer metric's reader gets."""

    summary: object        # chipbench.trace.Summary of the traced window
    model: dict
    traffic: dict
    peaks: dict
    outcome: dict


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(root: pathlib.Path, workload: str):
    """(benchmark, workload entry, model file, traffic file, limits)."""
    bench = load_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    model = load_json(root / conf["file"])
    traffic = load_json(root / "chipbench" / "traffic" / f"{wl['traffic']}.json")
    limits = load_json(root / "chipbench" / "checks" / f"{workload}.json")
    return bench, wl, model, traffic, limits


def peaks_for(root: pathlib.Path, device_kind: str) -> dict:
    table = load_json(root / "chipbench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def reader(root: pathlib.Path, metric: str):
    path = root / "chipbench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def drive(workload: str, model: dict, traffic: dict, seed: int,
          seconds: float, trace: bool, t0: float):
    """Run the traffic's driver once: (context, outcome, trace summary)."""
    driver = importlib.import_module(f"chipbench.drivers.{traffic['driver']}")
    with tempfile.TemporaryDirectory(prefix="chipbench_") as tmp:
        tmp = pathlib.Path(tmp)
        ctx = Context(workload, model, traffic, seed, seconds, trace,
                      tmp / "trace", tmp, t0)
        outcome = driver.run(ctx)
        summary = None
        if trace:
            from chipbench import trace as tr

            summary = tr.reduce(tr.find_xplane(str(ctx.trace_dir)))
    return ctx, outcome, summary


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, t0: float, require_tpu: bool = True) -> dict:
    """One run of ``workload``; returns the result line as a dict."""
    import jax

    bench, wl, model, traffic, limits = cell(root, workload)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform}; the benchmark "
                         f"runs only on the chip")
    if len(devices) < wl["chips"]:
        raise SystemExit(f"{workload} needs {wl['chips']} chips, JAX found "
                         f"{len(devices)}")
    peaks = peaks_for(root, dev.device_kind) if require_tpu else {}
    ctx, outcome, summary = drive(workload, model, traffic, seed, seconds,
                                  trace, t0)

    checks = outcome["checks"]
    correct = all(math.isfinite(v) and v <= limits[k]["limit"]
                  for k, v in checks.items())
    metrics = {}
    if not trace:
        values = dict(outcome["e2e"], setup_s=ctx.setup_s)
        for e in bench["end_to_end"]:
            if applies(e, workload):
                metrics[e["name"]] = {"value": values[e["name"]],
                                      "unit": e["unit"]}
    else:
        rec = Record(summary, model, traffic, peaks, outcome)
        for e in bench["per_layer"]:
            if applies(e, workload):
                value = reader(root, e["name"])(rec)
                if value is not None:
                    metrics[e["name"]] = {"value": value, "unit": e["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": outcome["attempted"],
            "failed": outcome["failed"], "metrics": metrics,
            "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.top_ops(10),
                             "idle_gaps": summary.top_idle(10)}
    line["checks"] = {k: {"value": v, "limit": limits[k]["limit"]}
                      for k, v in checks.items()}
    return line


def main(argv=None, t0: float | None = None) -> None:
    import argparse

    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parents[1]
    import jax

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    # every program into the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    emit(run_cell(root, args.workload, args.seed, args.seconds,
                  bool(args.trace), t0))


def emit(line: dict) -> None:
    """Each compared number beside its limit as the last lines of standard
    error, and the result as the last line of standard output."""
    for k, v in line["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
