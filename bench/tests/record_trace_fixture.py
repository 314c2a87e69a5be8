#!/usr/bin/env python3
"""Record the small TPU trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace_fixture.py OUT.xplane.pb

On one TPU: within a ``bench.window`` annotation, two ``bench.join``
spans around a jitted ``prefill`` (a 1024x1024 matmul chain), four
``bench.step`` spans around a jitted ``decode``, and one
``bench.await_arrival`` span of 50 ms with the device idle.  Kept as a
fixture so that the reduction is tested on a real device trace.
"""
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    sys.path[:0] = [BENCH]
    from lib.boot import place_cache, tpu_devices

    place_cache()
    tpu_devices(1, "record_trace_fixture")
    import jax
    import jax.numpy as jnp

    from lib.trace import Tracer

    def prefill(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    def decode(x):
        return jnp.tanh(x @ x[:, :8]).sum()

    prefill, decode = jax.jit(prefill), jax.jit(decode)
    x = jnp.ones((1024, 1024), jnp.float32) / 1024
    prefill(x).block_until_ready()
    decode(x).block_until_ready()
    tracer = Tracer()
    tracer.start()
    for _ in range(2):
        with tracer.annotate("bench.join"):
            prefill(x).block_until_ready()
    for _ in range(4):
        with tracer.annotate("bench.step"):
            decode(x).block_until_ready()
    with tracer.annotate("bench.await_arrival"):
        time.sleep(0.05)
    tracer.stop()
    shutil.copy(tracer.path(), sys.argv[1])
    tracer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
