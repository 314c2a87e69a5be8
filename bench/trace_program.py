#!/usr/bin/env python3
"""A traced window of one cell, read down to the program's own spans,
name scopes and counters.

    python3 bench/trace_program.py --workload qwen3-1.7b.decode-batch --seed 7 --seconds 30

Set-up and window as ``bench/run.py --trace 1`` makes them, with a
``lib.program_trace.ProgramTracer``; then, before the trace is deleted,
both reductions of it (``lib.trace.reduce`` and
``lib.program_trace.reduce``, the latter joined with the compiled HLO
text of the cell's decode step and prefills).  Prints the counts line
with ``decode_scope_ms``, ``idle_by_program_span_s``, ``occupancy``,
``cache_valid_share`` and ``queue_wait_ms``, then a line with the
cell's per-layer metrics, the four that read the program's spans and
scopes among them, two cross-checks (every decode scope summed against
the device time of ``jit_decode``; the idle inside ``serve.step`` spans
against the idle inside the harness's ``bench.step`` spans) and the
largest ops of ``jit_decode`` in no named scope.  No correctness check:
``bench/run.py`` makes it.
Without a TPU it exits 3.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))


def trace_program(cell, seed: int, seconds: float, *, smoke: bool = False, counter=None):
    """(counts, metrics, cross-checks) of one traced window."""
    import jax

    from lib import program_trace as PT
    from lib import trace as T
    from lib.harness import Session, counts, layer_record
    from lib.peaks import peaks as device_peaks

    sess = Session(cell, seed, smoke=smoke, counter=counter)
    sess.build()
    sess.warm_up()
    tracer = PT.ProgramTracer(sess.engine)
    rec = sess.window(seconds, tracer)
    before = counter.compiles if counter else None
    hlo = PT.hlo_texts(sess.engine, cell.traffic.prompt_lens)
    try:
        reduced = T.reduce(tracer.path())
        program = PT.reduce(tracer.path(), hlo)
    finally:
        tracer.close()
    info = counts(rec)
    info.update(PT.counts(program, tracer.counters), seed=seed, workload=cell.name,
                hlo_compiles=counter.compiles - before if counter else None,
                modules_s=reduced["modules"], idle_s=reduced["idle"])
    record = layer_record(rec, cell.work, sess.dims,
                          device_peaks(jax.devices()[0].device_kind), reduced)
    record["program"] = program
    metrics = {m["name"]: cell.reader(m["name"]).read(record) for m in cell.per_layer}
    metrics.update({name: read(record) for name, read in PT.METRICS.items()})
    decode_s = reduced["modules"].get("jit_decode", 0.0)
    scoped_s = sum(v["self_s"] for v in program["scopes"]["jit_decode"].values())
    other_s = program["scopes"]["jit_decode"].get(PT.OTHER, {}).get("self_s", 0.0)
    step_idle = metrics["step_idle_ms.closed"]
    cross = {
        "decode_scopes_over_module": scoped_s / decode_s if decode_s else None,
        "decode_other_share": other_s / scoped_s if scoped_s else None,
        "step_idle_over_bench_step_idle": (
            1e-3 * step_idle * program["steps"] / reduced["idle"]["step"]
            if step_idle is not None and reduced["idle"].get("step") else None),
        "serve_steps": program["steps"], "bench_steps": info["steps"],
        # what lies in jit_decode's ``other``: its ten largest ops, ms per step
        "decode_other_top_ms": [
            [op, 1e3 * t / program["steps"]] for op, t in list(
                program["scopes"]["jit_decode"].get(PT.OTHER, {}).get("ops", {}).items())[:10]
        ] if program["steps"] else None,
    }
    return info, metrics, cross


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
    from lib.boot import place_cache, tpu_devices

    place_cache()
    from lib.registry import load_cell

    cell = load_cell(args.workload)
    tpu_devices(cell.chips, args.workload)
    from lib.compiles import CompileCounter

    t0 = time.time()
    info, metrics, cross = trace_program(cell, args.seed, args.seconds, counter=CompileCounter())
    print(json.dumps({"counts": info}), flush=True)
    print(json.dumps({"metrics": metrics, "cross_checks": cross,
                      "wall_s": time.time() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
