#!/usr/bin/env python3
"""Smoke test of the runtime on TPU: its main path once, at real widths.

    python chip_smoke.py             # one chip: train, serve, bands, kernels
    python chip_smoke.py --chips 4   # four chips: the sharded train step only

One chip.  All phases run in this one process (a chip belongs to one
process at a time):

1. train    ``FaultTolerantTrainer`` on starcoder2-3b at its published
            widths, depth cut to 4 layers (what fits one 16 GB chip with
            f32 Adam state), batch 4 x 2048, a checkpoint every 2 steps and
            a scheduled crash at step 3: two attempts, and the step replayed
            after the restore gives the loss it gave before the fault.
2. serve    ``Server`` on the full 30-layer starcoder2-3b in bf16: batch 8,
            prompt 1024, 32 new tokens; a crash mid-decode leaves the
            outputs unchanged.
3. bands    ``batch_bands`` on ``jax_vmap`` for 3 policies x 3 scales x 256
            seeds: one compiled call, closed-form parity with numpy.
4. kernels  each Pallas kernel against its ``kernels/ref.py`` oracle at real
            widths, and the kernels present (``tpu_custom_call``) in the
            compiled train step and prefill.

Four chips: the same 4-layer train step on a (data 2, model 2) mesh against
the step on one chip with the same params and batch; then ``plan_shrink``
-> ``make_elastic_mesh`` -> ``reshard_for`` onto 2 chips and one more step,
again against one chip.

Weights and data are random, made from seed 0.  Printed times are
information (compilation is set-up), not claims.  Any failed check exits
non-zero and prints no result; otherwise the last line of stdout is the JSON
result naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Sizes:
    train_layers: int = 4
    train_batch: int = 4
    train_seq: int = 2048
    train_steps: int = 4        # + 1 replayed: two checkpoints on disk at most
    ckpt_every: int = 2
    fault_step: int = 3
    serve_batch: int = 8
    prompt: int = 1024
    new_tokens: int = 32
    fault_decode_step: int = 16
    band_seeds: int = 256
    wkv: tuple = (2, 512)       # (batch, seq) at rwkv6-7b widths
    rglru: tuple = (2, 2048)    # (batch, seq) at recurrentgemma-9b widths
    # the rehearsal on the CPU (tests/test_chip_smoke.py): smoke widths and
    # interpret-mode kernels; refused on a chip
    tiny: bool = False


FULL = Sizes()
REHEARSAL = Sizes(train_seq=64, serve_batch=2, prompt=32, new_tokens=6,
                  fault_decode_step=3, band_seeds=8, wkv=(1, 16),
                  rglru=(1, 64), tiny=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in float32."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def arch(name: str, sizes: Sizes, layers: int = 0):
    from repro.configs.base import get_arch, smoke_config

    cfg = get_arch(name)
    if sizes.tiny:
        cfg = smoke_config(cfg)
    if layers:
        cfg = cfg.replace(name=f"{cfg.name}-{layers}L", n_layers=layers,
                          block_groups=((("global",), layers),))
    return cfg


def compiled_text(jitted, *args) -> str:
    return jitted.lower(*args).compile().as_text()


def peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def phase_train(sizes: Sizes, ckpt_dir: pathlib.Path) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import params as pmod
    from repro.optim import adamw
    from repro.runtime.fault_injection import FaultInjector, InjectedFault
    from repro.runtime.train_loop import FaultTolerantTrainer, TrainerConfig

    cfg = arch("starcoder2-3b", sizes, sizes.train_layers)
    tcfg = TrainerConfig(
        total_steps=sizes.train_steps, global_batch=sizes.train_batch,
        seq_len=sizes.train_seq, ckpt_dir=str(ckpt_dir), ckpt_async=True,
        ckpt_every_steps=sizes.ckpt_every, n_nodes=4, seed=0)
    injector = FaultInjector(schedule={
        sizes.fault_step: InjectedFault("gpu_memory_errors", node_id=0)})
    trainer = FaultTolerantTrainer(cfg, tcfg, injector)
    state_bytes = 3 * pmod.param_bytes(trainer.defs)   # params + Adam m, v
    free = shutil.disk_usage(ckpt_dir).free
    print(f"train: {cfg.name}, {pmod.count_params(trainer.defs)} params, "
          f"checkpoint {state_bytes / 1e9:.3f} GB, {free / 1e9:.1f} GB free "
          f"under {ckpt_dir.parent}")
    check(free > 2.2 * state_bytes, "too little disk for two checkpoints")
    rep = trainer.run()

    losses = rep.losses
    restored = (sizes.fault_step // sizes.ckpt_every) * sizes.ckpt_every
    n_replay = sizes.fault_step - restored
    print(f"train: attempts {[a.outcome for a in rep.attempts]}, "
          f"final step {rep.final_step}, losses {losses}")
    check(all(np.isfinite(losses)), "non-finite loss")
    check(len(rep.attempts) == 2, f"{len(rep.attempts)} attempts, want 2")
    check(rep.final_step == sizes.train_steps, "final step not reached")
    check(len(losses) == sizes.train_steps + n_replay, "wrong step count")
    before = losses[restored:sizes.fault_step]
    after = losses[sizes.fault_step:sizes.fault_step + n_replay]
    diff = max(abs(a - b) for a, b in zip(before, after))
    print(f"train: step(s) {restored}..{sizes.fault_step - 1} before the "
          f"fault {before}, replayed after the restore {after}, "
          f"max |diff| {diff!r}")
    check(before == after, "replayed loss differs from the loss before "
                           "the fault")

    walls = rep.step_wall_s
    print(f"train: compile + first step {walls[0]:.3f} s (set-up); steady "
          f"step median {statistics.median(walls[1:]):.4f} s over "
          f"{len(walls) - 1} steps (information)")
    saves = len(trainer.manager.write_log)
    print(f"train: checkpoint block {rep.checkpoint_block_s:.3f} s over "
          f"{saves} saves; restore {rep.attempts[1].restore_s:.3f} s "
          f"(information)")

    if not sizes.tiny:
        p_abs = pmod.abstract(trainer.defs)
        tokens = jax.ShapeDtypeStruct(
            (sizes.train_batch, sizes.train_seq + 1), jnp.int32)
        t0 = time.time()
        text = compiled_text(trainer.step_fn, p_abs,
                             jax.eval_shape(adamw.init, p_abs),
                             {"tokens": tokens})
        print(f"kernels: compiled train step has tpu_custom_call: "
              f"{'tpu_custom_call' in text} ({time.time() - t0:.3f} s to "
              f"compile again)")
        check("tpu_custom_call" in text, "no Pallas kernel in the train step")


def phase_serve(sizes: Sizes) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.fault_injection import FaultInjector, InjectedFault
    from repro.runtime.serve_loop import ServeConfig, Server

    cfg = arch("starcoder2-3b", sizes)
    t0 = time.time()
    srv = Server(cfg, ServeConfig(batch=sizes.serve_batch,
                                  prompt_len=sizes.prompt,
                                  max_new_tokens=sizes.new_tokens, seed=0))
    jax.block_until_ready(srv.params)
    print(f"serve: {cfg.name}, {cfg.n_layers} layers bf16, weights made in "
          f"{time.time() - t0:.3f} s (set-up)")
    clean = srv.run()
    srv.injector = FaultInjector(schedule={
        sizes.fault_decode_step: InjectedFault("ib_link_error", node_id=0)})
    faulted = srv.run()
    srv.injector = FaultInjector()
    warm = srv.run()
    print(f"serve: batch {sizes.serve_batch} x prompt {sizes.prompt} + "
          f"{sizes.new_tokens} tokens; wall {clean.wall_s:.3f} s with "
          f"compile (set-up), {warm.wall_s:.3f} s warm, {faulted.wall_s:.3f} "
          f"s with a crash at decode step {sizes.fault_decode_step} "
          f"(information)")
    print(f"serve: retries {faulted.retries}, outputs equal "
          f"{np.array_equal(clean.outputs, faulted.outputs)}")
    check(faulted.retries == 1, "the mid-decode crash did not fire once")
    check(clean.outputs.shape == (sizes.serve_batch, sizes.new_tokens),
          "wrong output shape")
    check(np.array_equal(clean.outputs, faulted.outputs),
          "outputs with a fault differ from outputs without one")
    check(np.array_equal(clean.outputs, warm.outputs), "outputs not stable")

    if not sizes.tiny:
        tokens = jax.ShapeDtypeStruct((sizes.serve_batch, sizes.prompt),
                                      jnp.int32)
        text = compiled_text(srv.prefill, srv.params, {"tokens": tokens})
        print(f"kernels: compiled prefill has tpu_custom_call: "
              f"{'tpu_custom_call' in text}")
        check("tpu_custom_call" in text, "no Pallas kernel in the prefill")


def phase_bands(sizes: Sizes) -> None:
    import numpy as np

    from repro.mitigations.sweep import (DEFAULT_GPUS, DEFAULT_POLICIES,
                                         analytic_policy_bands)

    seeds = range(sizes.band_seeds)
    res = analytic_policy_bands(DEFAULT_POLICIES, DEFAULT_GPUS, seeds,
                                backend="jax_vmap")
    warm = analytic_policy_bands(DEFAULT_POLICIES, DEFAULT_GPUS, seeds,
                                 backend="jax_vmap")
    ref = analytic_policy_bands(DEFAULT_POLICIES, DEFAULT_GPUS, seeds,
                                backend="numpy")
    print(f"bands: {res.grid.n_cells} cells ({len(DEFAULT_POLICIES)} "
          f"policies x {len(DEFAULT_GPUS)} scales x {sizes.band_seeds} "
          f"seeds), {res.n_compiled_calls} compiled call; "
          f"{res.wall_s:.4f} s with compile (set-up), {warm.wall_s:.4f} s "
          f"warm (information)")
    check(res.n_compiled_calls == 1, "the grid took more than one call")
    # closed-form float32 parity (docs/stat_backend.md: ~5e-4 relative)
    for name in ("ettr", "n_failures", "dt_s", "mttf_hours"):
        got, want = getattr(res, name), getattr(ref, name)
        fin = np.isfinite(want)
        check(np.array_equal(fin, np.isfinite(got)),
              f"{name}: finite cells differ from numpy")
        err = float(np.max(np.abs(got[fin] - want[fin])
                           / np.maximum(np.abs(want[fin]), 1e-30)))
        print(f"bands: {name} max relative diff vs numpy {err:.3e}")
        check(err <= 5e-4, f"{name} off numpy by {err:.3e} > 5e-4")


def phase_kernels(sizes: Sizes) -> None:
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rglru_scan import rglru
    from repro.kernels.rwkv6_scan import wkv6

    interpret = sizes.tiny
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def normal(shape, scale=1.0, dtype=jnp.bfloat16):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    # tolerances relative to max |oracle|: bf16 inputs and outputs
    cfg = arch("starcoder2-3b", sizes)
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    flash = jax.jit(functools.partial(flash_attention, causal=True,
                                      interpret=interpret))
    oracle = jax.jit(ref.attention_ref)
    for where, B, S in (("train", sizes.train_batch, sizes.train_seq),
                        ("serve", sizes.serve_batch, sizes.prompt)):
        q, k, v = (normal((B, S, H, D)), normal((B, S, KV, D)),
                   normal((B, S, KV, D)))
        err = rel_err(flash(q, k, v), oracle(q, k, v))
        print(f"kernels: flash {where} shape {(B, S, H, KV, D)} relative "
              f"err {err:.3e}")
        check(err <= 2e-2, f"flash ({where}) off its oracle")

    B, S = sizes.train_batch, sizes.train_seq
    q, k, v = (normal((B, S, H, D)), normal((B, S, KV, D)),
               normal((B, S, KV, D)))
    do = normal((B, S, H, D))

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v).astype(jnp.float32)
                                * do.astype(jnp.float32)).sum()

    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(ref.attention_ref), argnums=(0, 1, 2)))(
        q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        err = rel_err(a, b)
        print(f"kernels: flash vjp {name} relative err {err:.3e}")
        check(err <= 5e-2, f"flash vjp {name} off its oracle")

    rw = arch("rwkv6-7b", sizes)
    Dh = rw.rwkv.head_dim
    Hr = rw.d_model // Dh
    B, S = sizes.wkv
    r, kk, vv = (normal((B, S, Hr, Dh), 0.5) for _ in range(3))
    w = (jax.nn.sigmoid(normal((B, S, Hr, Dh), dtype=jnp.float32)) * 0.5
         + 0.45).astype(jnp.bfloat16)
    u = normal((Hr, Dh), 0.3)
    s0 = normal((B, Hr, Dh, Dh), 0.3, jnp.float32)
    kernel = jax.jit(functools.partial(wkv6, interpret=interpret))
    oracle = jax.jit(ref.wkv6_ref)
    for state in (None, s0):
        (o1, s1), (o2, s2) = kernel(r, kk, vv, w, u, state), \
            oracle(r, kk, vv, w, u, state)
        err = max(rel_err(o1, o2), rel_err(s1, s2))
        print(f"kernels: wkv6 shape {(B, S, Hr, Dh)} "
              f"{'with' if state is not None else 'no'} incoming state "
              f"relative err {err:.3e}")
        check(err <= 2e-2, "wkv6 off its oracle")

    W = arch("recurrentgemma-9b", sizes).rglru.lru_width
    B, S = sizes.rglru
    x = normal((B, S, W), dtype=jnp.float32)
    la = -jax.nn.softplus(normal((B, S, W), dtype=jnp.float32))
    h0 = normal((B, W), dtype=jnp.float32)
    (o1, h1) = jax.jit(functools.partial(rglru, interpret=interpret))(x, la,
                                                                      h0)
    (o2, h2) = jax.jit(ref.rglru_ref)(x, la, h0)
    err = max(rel_err(o1, o2), rel_err(h1, h2))
    print(f"kernels: rglru shape {(B, S, W)} relative err {err:.3e}")
    check(err <= 1e-4, "rglru off its oracle")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def phase_four_chips(sizes: Sizes) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.pipeline import DataConfig, SyntheticLMPipeline
    from repro.models import params as pmod
    from repro.models import transformer
    from repro.models.steps import make_train_step
    from repro.optim import adamw
    from repro.parallel.axes import TRAIN_RULES, mesh_context
    from repro.runtime.elastic import (make_elastic_mesh, plan_shrink,
                                       reshard_for)

    cfg = arch("starcoder2-3b", sizes, sizes.train_layers)
    defs = transformer.model_defs(cfg)
    # the optimizer FaultTolerantTrainer builds for a short run
    step = make_train_step(cfg, adamw.AdamWConfig(lr=1e-3, warmup_steps=5,
                                                  total_steps=10))
    data = SyntheticLMPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=sizes.train_seq,
        global_batch=sizes.train_batch, seed=0))
    params = jax.device_get(pmod.materialize(defs, seed=0))
    zeros = jax.tree_util.tree_map(
        lambda p: np.zeros(p.shape, np.float32), params)
    opt = adamw.AdamWState(np.zeros((), np.int32), zeros,
                           jax.tree_util.tree_map(np.copy, zeros))

    def on_mesh(mesh, params, opt, batch):
        """One step with the state placed by ``reshard_for`` (the
        ``models.params.shardings`` layout); returns (loss, grad_norm) and
        the updated state on the host."""
        rep = NamedSharding(mesh, P())
        placed = (reshard_for(params, mesh, TRAIN_RULES, defs),
                  adamw.AdamWState(
                      jax.device_put(opt.step, rep),
                      reshard_for(opt.m, mesh, TRAIN_RULES, defs),
                      reshard_for(opt.v, mesh, TRAIN_RULES, defs)))
        with mesh_context(mesh, TRAIN_RULES):
            t0 = time.time()
            p, o, m = jax.jit(step, donate_argnums=(0, 1))(
                *placed, jax.device_put(batch, NamedSharding(mesh, P("data"))))
            loss, gnorm = float(m["loss"]), float(m["grad_norm"])
        print(f"four chips: step on {dict(mesh.shape)} loss {loss!r} "
              f"grad norm {gnorm!r}, {time.time() - t0:.3f} s with compile "
              f"(set-up)")
        return (loss, gnorm), jax.device_get(p), jax.device_get(o)

    def on_one_chip(params, opt, batch):
        dev = jax.devices()[0]
        t0 = time.time()
        _, _, m = jax.jit(step, donate_argnums=(0, 1))(
            jax.device_put(params, dev), jax.device_put(opt, dev),
            jax.device_put(batch, dev))
        loss, gnorm = float(m["loss"]), float(m["grad_norm"])
        print(f"four chips: same step on one chip loss {loss!r} grad norm "
              f"{gnorm!r}, {time.time() - t0:.3f} s with compile (set-up)")
        return loss, gnorm

    def compare(where, got, want):
        dl = abs(got[0] - want[0]) / abs(want[0])
        dg = abs(got[1] - want[1]) / abs(want[1])
        print(f"four chips: {where} vs one chip: loss relative diff "
              f"{dl:.3e}, grad norm relative diff {dg:.3e}")
        # bf16 compute; only the reduction order differs
        check(dl <= 1e-2 and dg <= 2e-2, f"{where} step off the one-chip step")

    batch = data.batch_at(0)
    one = on_one_chip(params, opt, batch)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    got, params, opt = on_mesh(mesh, params, opt, batch)
    compare("(data 2, model 2) mesh", got, one)

    plan = plan_shrink(2, model_parallel=2, old_global_batch=sizes.train_batch,
                       old_data=2)
    print(f"four chips: elastic shrink to {plan.n_alive} chips: {plan.note}, "
          f"global batch {plan.global_batch}")
    mesh2 = make_elastic_mesh(plan)
    batch = {k: v[:plan.global_batch] for k, v in data.batch_at(1).items()}
    one = on_one_chip(params, opt, batch)
    got, _, _ = on_mesh(mesh2, params, opt, batch)
    check(np.isfinite(got[0]), "non-finite loss after the shrink")
    compare(f"shrunk {dict(mesh2.shape)} mesh", got, one)


# ---------------------------------------------------------------------------
def main(argv=None, sizes: Sizes = FULL) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step and the "
                         "elastic shrink (needs four chips)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("chip_smoke: no src/repro next to this script; "
                         "run it from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    from importlib.metadata import version

    import jax

    from repro.launch.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devs)}")
    print(f"jax {jax.__version__}, libtpu {version('libtpu')}, compile cache "
          f"{cache_dir}")
    if sizes.tiny:
        check(dev.platform != "tpu", "the rehearsal sizes never run on a chip")
    else:
        check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    check(len(devs) >= args.chips, f"{args.chips} chips asked, "
                                   f"{len(devs)} found")

    t0 = time.time()
    if args.chips == 4:
        phase_four_chips(sizes)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
            phase_train(sizes, pathlib.Path(d))
        gc.collect()
        print(f"train: device peak memory {peak_gb(dev)}")
        phase_serve(sizes)
        gc.collect()
        print(f"serve: device peak memory since the start {peak_gb(dev)}")
        phase_bands(sizes)
        phase_kernels(sizes)
    print(f"all phases passed in {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
