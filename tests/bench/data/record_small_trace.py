"""Record ``v5e_small.xplane.pb`` on a TPU (run from the checkout's root):

    python3 tests/bench/data/record_small_trace.py <out_dir>

Two jitted programs (a 2048^2 bf16 matmul reduced to a scalar, then a tanh)
run three times, with a 2 ms host sleep between, inside the host span
``bench.traced_window``.  ``test_bench_trace.py`` holds the numbers read off
the recorded events by hand.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir):
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    f = jax.jit(lambda x: (x @ x).astype(jnp.float32).sum())
    g = jax.jit(lambda x: jnp.tanh(x) * 2)
    f(a).block_until_ready()
    g(a).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("step"):
                f(a).block_until_ready()
            with jax.profiler.TraceAnnotation("host.sleep"):
                time.sleep(0.002)
            g(a).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
