"""One driver per program entry that a measured window drives.

A traffic file names its driver (``"driver": "train"`` loads
``chipbench/drivers/train.py``).  A driver exposes ``run(ctx) -> dict``:
it builds the system under test through the program's public interfaces,
warms up every shape the window uses, calls ``ctx.open_window()`` as the
window opens, measures, reads the device's peak memory, frees the
program's state, and compares what the window produced with the
reference.  The returned dict holds ``attempted``, ``failed``, ``e2e``
(end-to-end metric values), ``checks`` (name -> value),
``memory_peak_bytes``, ``counts`` for the per-layer readers, and the
``readings`` behind the checks (``chipbench/calibrate.py`` reads them).
"""
from __future__ import annotations

import gc
import sys
import time

import jax


def arch_config(m: dict):
    """The program's ArchConfig for the model file ``m``: the registered
    architecture ``m["arch"]`` at the file's widths and depth."""
    from repro.configs.base import get_arch

    base = get_arch(m["arch"])
    cfg = base.replace(
        name=m["name"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_head=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        n_layers=m["num_hidden_layers"],
        block_groups=((("global",), m["num_hidden_layers"]),),
        rope_theta=m["rope_theta"], norm_eps=m["norm_epsilon"])
    # what chipbench/reference.py implements, and so what a cell may run
    plain = (cfg.family == "dense" and cfg.moe is None and not cfg.ffn_gated
             and not cfg.tie_embeddings and not cfg.qk_norm
             and not cfg.enc_dec and not cfg.n_patches
             and cfg.attn_logit_softcap == 0.0)
    if not plain:
        raise ValueError(f"{m['arch']}: not the dense non-gated decoder the "
                         f"reference implements")
    return cfg


def spanned(name: str, fn, stamps: list | None = None):
    """``fn`` inside a profiler span ``name``; ``stamps`` gets the host
    clock at each call's entry."""

    def call(*args, **kwargs):
        if stamps is not None:
            stamps.append(time.perf_counter())
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)

    return call


def peak_bytes() -> int:
    """Peak device memory of the fullest chip this process used."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class GcClock:
    """Counts the interpreter's cyclic collections, and the seconds they
    took, while open: a long one inside a window shows in the window."""

    def __enter__(self):
        self.count, self.seconds, self._t = 0, 0.0, 0.0
        gc.callbacks.append(self._tick)
        return self

    def _tick(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._t

    def __exit__(self, *exc):
        gc.callbacks.remove(self._tick)


def note(what: str, walls, gc_clock: GcClock) -> None:
    """One line on standard error about the window's units of work."""
    walls = sorted(walls)
    if walls:
        print(f"window: {len(walls)} {what}, wall min {walls[0]!r} median "
              f"{walls[len(walls) // 2]!r} max {walls[-1]!r} s; "
              f"{gc_clock.count} gc collections, {gc_clock.seconds!r} s",
              file=sys.stderr)
