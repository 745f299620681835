#!/usr/bin/env python3
"""Compile the programs of the cells ``rehearse_memory.py`` leaves out for
a described TPU v5e chip, at the cells' own sizes, and print what
``memory_analysis()`` reckons (no chip needed).

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_moe.py [--out FILE]

Programs: prefill and decode of ``mellum2-12b-8l`` at batch 8, prompt
4096 + 128, and the reference's forward over one request; prefill and
decode of ``granite-20b-13l`` at batch 1, prompt 8192 + 16, and the
reference's forward over one request.  The Pallas path is forced on, as
on the chip.  Nothing runs, so nothing here is a time."""
import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


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

    from chipbench import reference, reference_moe
    from chipbench.drivers import arch_config
    from chipbench.drivers import serve_moe
    from chipbench.rehearse_memory import reckon
    from repro.kernels import ops
    from repro.models import params as pmod
    from repro.models import transformer
    from repro.models.steps import make_decode_step, make_prefill_step

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

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    res = {}
    for config, traffic, make_cfg, ref in (
            ("mellum2-12b-8l", "code-completion-4k", serve_moe.arch_config,
             reference_moe),
            ("granite-20b-13l", "prefill-8k", arch_config, reference)):
        m, t = load(f"configs/{config}.json"), load(f"traffic/{traffic}.json")
        cfg = make_cfg(m)
        B, P, T = t["batch"], t["prompt_len"], t["new_tokens"]
        pb = shapes(pmod.abstract(pmod.cast_defs(
            transformer.model_defs(cfg), jnp.bfloat16)))
        prefill = jax.jit(make_prefill_step(cfg, cache_len=P + T))
        res[f"{config}.prefill"] = reckon(
            prefill.lower(pb, {"tokens": ints(B, P)}).compile())
        cache = shapes(jax.eval_shape(prefill, pb, {"tokens": ints(B, P)})[1])
        res[f"{config}.decode"] = reckon(jax.jit(make_decode_step(cfg)).lower(
            pb, cache, ints(B, 1)).compile())
        res[f"{config}.reference_forward"] = reckon(
            ref.make_served_logits(m).lower(pb, ints(P), ints(T)).compile())
        print(json.dumps(res), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
