"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

Every per-layer metric that reads the device takes it from :class:`Summary`,
so each run computes a number the same way.

* Device planes are ``/device:TPU:<n>``.  Their events carry the device's
  clock, which is offset from the host's; the offset is taken from the
  programs both sides name by ``run_id`` (the host's ``DoEnqueueProgram``
  and the device's ``XLA Modules`` event): a program cannot start before
  the host enqueued it, so the device clock is shifted by the largest
  ``enqueue start - device start``.
* The traced window is the benchmark's host span named ``window`` (default
  ``chipbench.window``).  Busy time is the union of the intervals of the
  ``XLA Ops`` line inside the window, per chip, averaged over chips.
* An idle gap is a stretch of the window in which no op runs on the first
  chip.  It is charged to the benchmark span (``chipbench.*``, or ``prefix``,
  the window itself excluded) that overlaps it most, else to ``outside spans``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OUTSIDE = "outside spans"
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over chips
    n_chips: int
    modules: dict                      # module name -> [seconds of each run]
    ops: dict                          # HLO op text -> [total seconds, runs]
    spans: dict                        # host span name -> [seconds of each]
    idle_by_span: dict                 # host span name -> idle seconds
    gaps: list                         # [(seconds, span name)], longest first
    shift_ns: float = 0.0              # device clock -> host clock, chip 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_runs(self, name: str) -> list:
        return self.modules.get(name, [])

    def top_ops(self, n: int = 10) -> list:
        """[[op name, seconds]] of the ``n`` ops that took most time.  A
        loop or call op spans the ops of its body, so it is left out."""
        by_name = collections.Counter()
        for text, (secs, _) in self.ops.items():
            if op_code(text) not in CONTAINERS:
                by_name[op_name(text)] += secs
        return [[k, v] for k, v in by_name.most_common(n)]

    def top_idle(self, n: int = 10) -> list:
        ranked = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:n]]


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_code(text: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion``."""
    m = re.search(r"\s([a-z][a-z0-9-]*)\(", text.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def module_name(event_name: str) -> str:
    """``jit_train_step(1234)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_length(intervals) -> tuple[float, list]:
    """Total length of the union of ``[(start, end)]`` and the merged list."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def reduce(path: str, window: str = WINDOW_SPAN,
           prefix: str = SPAN_PREFIX) -> Summary:
    """Reduce one ``.xplane.pb`` file; times in the Summary are seconds."""
    import jax  # the reader ships with JAX

    data = jax.profiler.ProfileData.from_file(path)
    devices, host_lines = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append({ln.name: list(ln.events) for ln in plane.lines})
        elif plane.name == "/host:CPU":
            host_lines.extend(list(ln.events) for ln in plane.lines)

    enqueue, spans_raw = {}, []
    for events in host_lines:
        for ev in events:
            if ev.name == "DoEnqueueProgram":
                rid = _stats(ev).get("run_id")
                if rid is not None:
                    enqueue.setdefault(int(rid), ev.start_ns)
            elif ev.name.startswith(prefix):
                spans_raw.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    wins = [(s, e) for n, s, e in spans_raw if n == window]
    if not wins:
        raise ValueError(f"no host span {window!r} in {path}")
    w0, w1 = wins[0]

    modules = collections.defaultdict(list)
    ops = {}
    busy, first_merged, shifts0 = [], None, []
    for lines in devices:
        mods = lines.get("XLA Modules", [])
        shifts = [enqueue[int(_stats(m)["run_id"])] - m.start_ns for m in mods
                  if int(_stats(m).get("run_id", -1)) in enqueue]
        shift = max(shifts) if shifts else 0.0
        shifts0.append(shift)
        for m in mods:
            s = m.start_ns + shift
            if w0 <= s < w1:
                modules[module_name(m.name)].append(m.duration_ns * 1e-9)
        ivals = []
        for op in lines.get("XLA Ops", []):
            s = op.start_ns + shift
            e = s + op.duration_ns
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            ivals.append((s, e))
            if first_merged is None:  # op totals from the first chip only
                ent = ops.setdefault(op.name, [0.0, 0])
                ent[0] += (e - s) * 1e-9
                ent[1] += 1
        length, merged = union_length(ivals)
        busy.append(length * 1e-9)
        if first_merged is None:
            first_merged = merged

    spans = collections.defaultdict(list)
    charged = [(n, s, e) for n, s, e in spans_raw if n != window]
    for n, s, e in charged:
        if s >= w0 and e <= w1:
            spans[n].append((e - s) * 1e-9)

    gaps, idle = [], collections.Counter()
    edges = [w0] + [x for iv in (first_merged or []) for x in iv] + [w1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, best_ov = OUTSIDE, 0.0
        for n, s, e in charged:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = n, ov
        gaps.append(((g1 - g0) * 1e-9, best))
        idle[best] += (g1 - g0) * 1e-9
    gaps.sort(reverse=True)
    n_chips = max(len(devices), 1)
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(busy) / n_chips, n_chips=len(devices),
                   modules=dict(modules), ops=ops, spans=dict(spans),
                   idle_by_span=dict(idle), gaps=gaps,
                   shift_ns=shifts0[0] if shifts0 else 0.0)
