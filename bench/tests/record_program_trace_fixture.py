#!/usr/bin/env python3
"""Record the TPU trace of the serving engine that
``test_bench_program_trace.py`` reduces.

    python3 bench/tests/record_program_trace_fixture.py OUT_DIR

On one TPU: the smoke-size qwen3-1.7b engine of the
``qwen3-1.7b.decode-batch`` cell, warmed up, then within a
``bench.window`` annotation three requests joined (each in a
``bench.join`` span) and six decode steps (each in a ``bench.step``
span), as the harness drives them.  Writes, gzipped, the trace
(``OUT_DIR/engine.xplane.pb.gz``) and the compiled HLO text of the
decode step (``OUT_DIR/engine.jit_decode.hlo.txt.gz``), which holds the
name scopes of its ops; ``bench/tests/fixtures/program_trace/`` keeps
both.
"""
import gzip
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 4242
PROMPTS = (64, 128, 256)
STEPS = 6


def main() -> int:
    out = sys.argv[1]
    sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
    from lib.boot import place_cache, tpu_devices

    place_cache()
    tpu_devices(1, "record_program_trace_fixture")
    from lib import program_trace as PT
    from lib.harness import Session
    from lib.registry import load_cell
    from lib.traffic import Request

    sess = Session(load_cell("qwen3-1.7b.decode-batch"), SEED, smoke=True)
    sess.build()
    sess.warm_up()
    e = sess.engine
    tracer = PT.ProgramTracer(e)
    tracer.start()
    for i, s in enumerate(PROMPTS):
        e.queue.offer(Request(i, s, 64, i))
    while len(e.queue):
        req = e.queue.take()
        with tracer.annotate("bench.join"):
            e._join(req)
    for _ in range(STEPS):
        with tracer.annotate("bench.step"):
            e._decode_step()
    tracer.stop()
    os.makedirs(out, exist_ok=True)
    with open(tracer.path(), "rb") as src, \
            gzip.open(os.path.join(out, "engine.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    (decode,) = PT.hlo_texts(e, PROMPTS)["jit_decode"]
    with gzip.open(os.path.join(out, "engine.jit_decode.hlo.txt.gz"), "wt") as f:
        f.write(decode)
    tracer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
