"""Record the small trace ``data/small.xplane.pb`` on a TPU chip.

    python3 chipbench/tests/record_trace.py OUT_DIR

Inside a host span ``bench.window``: three rounds of a 2048 x 2048 bf16
matmul module (span ``bench.mm``), the Pallas flash forward at batch 1,
1024 positions, 4 query heads and 1 KV head (span ``bench.flash``), and
a 20 ms host sleep (span ``bench.sleep``).  The ``.xplane.pb`` lands
under ``OUT_DIR/plugins/profile/<time>/``."""
import functools
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(out: str) -> None:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention import flash_attention

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: the trace records a chip")

    @jax.jit
    def mm(a, b):
        return jnp.tanh(a @ b) @ b

    flash = jax.jit(functools.partial(flash_attention, causal=True))
    k = jax.random.split(jax.random.key(0), 5)
    a = jax.random.normal(k[0], (2048, 2048), jnp.bfloat16)
    b = jax.random.normal(k[1], (2048, 2048), jnp.bfloat16)
    q = jax.random.normal(k[2], (1, 1024, 4, 128), jnp.bfloat16)
    kk = jax.random.normal(k[3], (1, 1024, 1, 128), jnp.bfloat16)
    v = jax.random.normal(k[4], (1, 1024, 1, 128), jnp.bfloat16)
    mm(a, b).block_until_ready()
    flash(q, kk, v).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.mm"):
                mm(a, b).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.flash"):
                flash(q, kk, v).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
