#!/usr/bin/env python3
"""Bring-up run of the main path on one TPU: serve, train, explore.

    python3 chip_smoke.py        # from the root of a checkout

One process drives the chip through the entry points a user calls:

  serve    qwen3-1.7b at its published width (random f32 weights from a
           fixed seed) behind ``repro.launch.serve``'s continuous-
           batching engine: 8 burst requests, prompts of 128 and 512
           tokens, 32 generated each, max_batch 4, queue_limit 8.  Every
           request must be served and none shed, every token id must lie
           in [0, vocab) and no logits may be NaN/inf.  One prompt's
           ``LM.prefill`` logits on the chip are compared with the same
           call on the host's CPU, both under
           ``default_matmul_precision("highest")``: the max error
           relative to the largest CPU logit must stay within
           PREFILL_MAX_REL_ERR and the per-position top-1 token must
           agree on at least PREFILL_MIN_TOP1 of the positions.
  train    ``repro.launch.train --arch qwen3-1.7b --smoke`` on the host
           mesh for a few steps (full width does not fit: f32 weights
           plus AdamW state exceed 16 GB); the loss must be finite and
           the mesh must sit on the chip.
  explore  a fixed-seed ``Explorer`` run of
           examples/experiments/kernel_tuning.yaml against target
           tpu_v5e on the serial executor, with a cache cleared first:
           no trial may FAIL, at least one kernel-schedule tune must
           have been timed on the chip, and every Pallas call must have
           compiled (never interpreted).

Lines before the last are JSON bring-up facts per phase (wall time,
XLA compiles, persistent-cache hits, the device's peak bytes in use so
far, the prefill error against the CPU): facts about this run, not
benchmark numbers.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Off-TPU, or if any phase fails, the script exits nonzero and prints no
``ok`` line; it never falls back to the CPU.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
# bounds of the highest-precision prefill comparison (f32 on both sides;
# what is left is summation order and transcendental rounding)
PREFILL_MAX_REL_ERR = 2e-3
PREFILL_MIN_TOP1 = 0.95
NOTE = "bring-up fact, not a benchmark"


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# phases: each returns its facts; ``platform`` is where they must have run
# ---------------------------------------------------------------------------

def _prefill_logits(model, params, prompt, max_context, device):
    import jax
    import jax.numpy as jnp
    import numpy as np

    with jax.default_device(device), jax.default_matmul_precision("highest"):
        p = jax.device_put(params, device)
        cache = model.init_cache(p, 1, max_context, dtype=jnp.float32)
        logits, _ = jax.jit(model.prefill)(p, cache, jax.device_put(prompt, device))
        return np.asarray(logits[0], np.float64)


def _compare(got, want) -> dict:
    import numpy as np

    return {
        "max_rel_err": float(np.max(np.abs(got - want)) / np.max(np.abs(want))),
        "top1_agree": float(np.mean(got.argmax(-1) == want.argmax(-1))),
    }


def serve_phase(platform: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve

    args = serve.parse_args([
        "--arch", "qwen3-1.7b", "--requests", "8", "--arrival", "burst",
        "--max-batch", "4", "--queue-limit", "8", "--seed", "0",
        "--prompt-lens", "128,512", "--gen-lens", "32"])
    engine, traffic = serve.build_lm_engine(args)
    requests = traffic.requests()
    summary = engine.run(requests)
    vocab = engine.model.spec.vocab

    check(summary["served"] == len(requests),
          f"served {summary['served']} of {len(requests)} requests")
    check(summary["shed"] == 0, f"shed {summary['shed']} requests")
    check(summary["nonfinite_logits"] == 0,
          f"{summary['nonfinite_logits']} prefills/steps had NaN/inf logits")
    want_len = {r.id: r.gen_len for r in requests}
    for done in engine.completed:
        check(len(done["tokens"]) == want_len[done["id"]],
              f"request {done['id']} got {len(done['tokens'])} tokens")
        check(all(0 <= t < vocab for t in done["tokens"]),
              f"request {done['id']} has a token id outside [0, {vocab})")
    params_platform = jax.tree_util.tree_leaves(engine.params)[0].devices().pop().platform
    check(params_platform == platform, f"weights live on {params_platform}")

    req = min(requests, key=lambda r: (r.prompt_len, r.id))
    prompt = jnp.asarray(req.prompt_tokens(vocab)[None])
    model, params = engine.model, engine.params
    chip = _prefill_logits(model, params, prompt, engine.max_context,
                           jax.devices()[0])
    cpu = _prefill_logits(model, params, prompt, engine.max_context,
                          jax.devices("cpu")[0])
    highest = _compare(chip, cpu)
    # the served path's own prefill (default matmul precision) for scale
    served, _ = engine._prefill_jit(
        params, model.init_cache(params, 1, engine.max_context,
                                 dtype=jnp.float32), prompt)
    served = _compare(np.asarray(served[0], np.float64), cpu)
    check(np.isfinite(chip).all(), "chip prefill logits are not finite")
    check(highest["max_rel_err"] <= PREFILL_MAX_REL_ERR,
          f"prefill max_rel_err {highest['max_rel_err']} > {PREFILL_MAX_REL_ERR}")
    check(highest["top1_agree"] >= PREFILL_MIN_TOP1,
          f"prefill top-1 agreement {highest['top1_agree']} < {PREFILL_MIN_TOP1}")
    return {
        "arch": engine.model.spec.name, "d_model": engine.model.spec.d_model,
        "served": summary["served"], "shed": summary["shed"],
        "tokens_generated": summary["tokens_generated"],
        "prefills": summary["prefills"], "iterations": summary["iterations"],
        "prefill_vs_cpu": {"prompt_len": req.prompt_len,
                           "highest": highest, "served_default": served,
                           "bounds": {"max_rel_err": PREFILL_MAX_REL_ERR,
                                      "top1_agree": PREFILL_MIN_TOP1}},
    }


def train_phase(platform: str) -> dict:
    import math

    from repro.launch import train

    final = train.run(train.parse_args([
        "--arch", "qwen3-1.7b", "--smoke", "--mesh", "host", "--steps", "4",
        "--seq", "64", "--global-batch", "4", "--log-every", "2"]))
    check(math.isfinite(final["final_loss"]), f"loss {final['final_loss']}")
    check(final["platform"] == platform, f"mesh on {final['platform']}")
    return {"final_loss": final["final_loss"], "steps": 4}


def _tune_devices(cache_dir: str) -> list:
    """Platforms the cached kernel-schedule tunes were timed on."""
    from repro.evaluation import DiskEvaluationCache

    found = []
    with open(os.path.join(cache_dir, DiskEvaluationCache.FILENAME)) as f:
        for line in f:
            rec = json.loads(line)
            if json.loads(rec["key"])["key"][0] == "kernel_schedule":
                found.append(rec["value"]["device"]["platform"])
    return found


def explore_phase(platform: str) -> dict:
    import yaml

    from repro import Explorer, ExperimentSpec
    from repro.kernels.schedule import record_kernel_calls

    out = os.path.join(ROOT, "results", "chip_smoke")
    shutil.rmtree(out, ignore_errors=True)
    path = os.path.join(ROOT, "examples", "experiments", "kernel_tuning.yaml")
    with open(path) as f:
        raw = yaml.safe_load(f)
    raw.update(target="tpu_v5e", executor={"backend": "serial"},
               cache=os.path.join(out, "cache"), report_dir=out)
    spec = ExperimentSpec.from_dict(raw, base_dir=os.path.dirname(path))
    calls: dict = {}
    with record_kernel_calls(calls):
        report = Explorer.from_spec(spec).run()

    check(report.states.get("fail", 0) == 0, f"failed trials: {report.states}")
    kt = report.kernel_tuning or {}
    check(kt.get("tunes", 0) >= 1, f"no kernel-schedule tune ran: {kt}")
    timed_on = _tune_devices(spec.cache.dir)
    check(timed_on and all(p == platform for p in timed_on),
          f"tunes timed on {timed_on}, expected {platform}")
    interpreted = sorted({e["effective"].interpret for e in calls.values()})
    check(bool(calls), "no Pallas kernel call was recorded")
    check(interpreted == [platform != "tpu"],
          f"kernel calls ran with interpret={interpreted}")
    return {"trials": report.n_trials, "states": report.states,
            "tunes": kt.get("tunes"), "tunes_timed_on": timed_on,
            "kernel_calls": len(calls), "interpret": interpreted,
            "best_values": (report.best or {}).get("values")}


PHASES = (("serve", serve_phase), ("train", train_phase),
          ("explore", explore_phase))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class CompileCounter:
    """XLA backend compiles and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def _fail(message: str) -> int:
    print(f"chip_smoke: FAIL: {message}", file=sys.stderr, flush=True)
    return 1


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import place_compile_cache
    except ImportError as e:
        return _fail(f"the repository's sources are not next to this script: {e}")
    cache_dir = place_compile_cache()
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        # the prefill check needs the host's CPU beside the chip
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        return _fail(f"JAX found no TPU (default device: {device}); this "
                     f"script never falls back to the CPU")
    print(json.dumps({"device": str(device), "kind": device.device_kind,
                      "count": len(devices), "jax": jax.__version__,
                      "JAX_PLATFORMS": platforms or None,
                      "compile_cache": cache_dir}), flush=True)
    counter = CompileCounter()
    for name, phase in PHASES:
        t0, c0, h0 = time.time(), counter.compiles, counter.cache_hits
        try:
            facts = phase(device.platform)
        except Exception:
            traceback.print_exc()
            return _fail(f"phase {name!r} failed")
        stats = device.memory_stats() or {}
        print(json.dumps({
            "phase": name, "ok": True, "note": NOTE,
            "wall_s": time.time() - t0,
            "xla_compiles": counter.compiles - c0,
            "persistent_cache_hits": counter.cache_hits - h0,
            "peak_bytes_in_use_so_far": stats.get("peak_bytes_in_use"),
            **facts}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
