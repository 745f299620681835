#!/usr/bin/env python3
"""Compile each cell's programs for a described TPU v5e chip, at the cell's
own sizes, and print what ``memory_analysis()`` reckons (no chip needed).

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_memory.py [--out FILE]

Programs: the train step of ``sc2-3b-4l`` at 2 x 4096 with its f32 AdamW
state, and the reference's step; prefill and decode of
``granite-20b-13l`` at batch 8, prompt 2048 + 128, and the reference's
forward over one request.  The Pallas path is forced on, as on the chip.
Nothing runs, so nothing here is a time."""
import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def reckon(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes")}
    out["peak_bytes"] = (out["argument_size_in_bytes"]
                         + out["output_size_in_bytes"]
                         - out["alias_size_in_bytes"]
                         + out["temp_size_in_bytes"])
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import reference, weights
    from chipbench.drivers import arch_config
    from repro.kernels import ops
    from repro.models import params as pmod
    from repro.models import transformer
    from repro.models.steps import (make_decode_step, make_prefill_step,
                                    make_train_step)
    from repro.optim import adamw

    jax.config.update("jax_enable_compilation_cache", False)
    ops.use_pallas = lambda: True   # the chip's path, on a described chip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    def load(name):
        with open(ROOT / "chipbench" / name) as f:
            return json.load(f)

    res = {}
    m = load("configs/sc2-3b-4l.json")
    t = load("traffic/train-steady.json")
    cfg = arch_config(m)
    p = shapes(pmod.abstract(transformer.model_defs(cfg)))
    o = shapes(jax.eval_shape(adamw.init, p))
    tok = jax.ShapeDtypeStruct((t["global_batch"], t["seq_len"] + 1),
                               jnp.int32, sharding=one)
    step = make_train_step(cfg, adamw.AdamWConfig(
        lr=m["optimizer"]["lr"], warmup_steps=5, total_steps=t["total_steps"]))
    res["sc2-3b-4l.train_step"] = reckon(jax.jit(
        step, donate_argnums=(0, 1)).lower(p, o, {"tokens": tok}).compile())
    step_ref = reference.make_train_step(m, t["total_steps"])
    pf = shapes(jax.eval_shape(lambda: weights.make(m, 0, jnp.float32)))
    res["sc2-3b-4l.reference_step"] = reckon(step_ref.lower(
        pf, pf, pf, jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
        tok).compile())
    print(json.dumps(res), flush=True)

    m = load("configs/granite-20b-13l.json")
    t = load("traffic/code-completion.json")
    cfg = arch_config(m)
    B, P, T = t["batch"], t["prompt_len"], t["new_tokens"]
    pb = shapes(pmod.abstract(pmod.cast_defs(transformer.model_defs(cfg),
                                             jnp.bfloat16)))
    prompt = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=one)}
    prefill = jax.jit(make_prefill_step(cfg, cache_len=P + T))
    res["granite-20b-13l.prefill"] = reckon(prefill.lower(pb, prompt).compile())
    cache = shapes(jax.eval_shape(prefill, pb, prompt)[1])
    res["granite-20b-13l.decode"] = reckon(jax.jit(make_decode_step(cfg)).lower(
        pb, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32,
                                        sharding=one)).compile())
    served = reference.make_served_logits(m)
    res["granite-20b-13l.reference_forward"] = reckon(served.lower(
        pb, jax.ShapeDtypeStruct((P,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one)).compile())
    print(json.dumps(res))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
