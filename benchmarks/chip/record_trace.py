"""Record the small chip trace that the trace-reduction test reads.

    python3 benchmarks/chip/record_trace.py <out_dir>

Two jitted programs run in turns inside a ``bench.window`` host span,
with a host sleep between turns, so the trace holds device work, named
programs and idle gaps with known host spans around them.  The test
keeps the resulting ``.xplane.pb`` in ``tests/data/``.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    @jax.jit
    def matmul_step(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def reduce_step(x):
        return x * jnp.sum(x, axis=0, keepdims=True) / x.shape[0]

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    matmul_step(x).block_until_ready()
    reduce_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.step"):
                y = matmul_step(x)
                y = reduce_step(y)
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait_for_arrival"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
