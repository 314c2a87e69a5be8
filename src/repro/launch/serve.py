"""Serving driver: continuous-batching engine + artifact-store warm boot.

Two modes share one traffic-shaped request loop (bounded admission
queue, continuous batching up to a concurrency limit, graceful shedding
when the queue is full), driven by a deterministic seeded
:class:`~repro.launch.traffic.TrafficSpec`:

LM mode — a real language model with KV/state caches::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --requests 8 --max-batch 4 --queue-limit 8

  Prompts run through :meth:`LM.prefill` (the full-sequence kernel, one
  forward per prompt) and join the running batch mid-flight; decode
  advances every active slot with a per-slot position vector.

Report mode — serve the winning candidate of an exploration::

    PYTHONPATH=src python -m repro.launch.serve \
        --from-report results/experiment.report.json

  Rebuilds the best architecture from the report's recorded trial
  params, then loads its compiled executable from the content-addressed
  artifact store the exploration populated — a warm boot performs
  **zero** XLA compiles (reported as ``compiles`` in the JSON summary,
  enforceable with ``--expect-compiles 0``).

Tracing a live LM server: under ``jax.profiler.start_trace`` the engine
writes ``TraceAnnotation`` spans into the profiler's trace, on the clock
of the device's events; with no profiler active each costs about a
microsecond.  Children nest in their parent on the calling thread.

* ``serve.join`` (``request_id``, ``prompt_len``): one admission, with
  ``serve.join.alloc`` (the batch-1 cache), ``serve.join.prefill`` (the
  prompt to the device and the prefill call), ``serve.join.merge`` (the
  slot merge) and ``serve.join.pick`` (the first token's host fetch),
  each carrying the same ``request_id``;
* ``serve.step`` (``step``, ``active``): one decode step, with
  ``serve.step.inputs`` (host token and position arrays to the device),
  ``serve.step.dispatch`` (the decode call), ``serve.step.pick`` (the
  host waits for the logits) and ``serve.step.bookkeep`` (slot updates
  and completions).

The device ops of ``LM.prefill`` and ``LM.decode`` carry the name scope
of their sub-block kind (``attention``, ``mlp``, ``moe``, ``mamba2``,
...), ``embed`` or ``head`` in their HLO metadata.  Counters, plain
numbers that grow for the engine's life (read differences over a
window): ``queue.waits_s`` (queue wait of each request taken),
``steps``, ``slot_steps`` (active slots summed over steps),
``valid_positions`` (each active slot's valid cache positions summed
over steps), ``attended_positions`` (the K/V positions each slot's
decode attention reads, summed over steps: on a TPU, where the decode
attention kernel runs, the slot's length rounded up to the kernel's
block, an idle slot's one block; else ``max_context``, the whole layer
XLA reads) and ``capacity_positions`` (``max_batch * max_context``),
positions of one K/V cache: every K/V cache of a model holds
``max_context`` positions a slot, be it an attention layer's or, in a
hybrid model, a shared block invocation's.
``decode_kernel_calls``, set once when the engine is built, counts the
distinct kernel calls its decode program runs (0 off a TPU): the
``decode_matmul`` shapes and the ``decode_attention`` call of each
segment whose heads are lane-aligned;
``decode_cache_donated_bytes``, set with it, the bytes of cache that the
decode program takes donated and updates in place (on a TPU the whole
cache, else 0: each step then writes a second one).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np


# ---------------------------------------------------------------------------
# shared request loop
# ---------------------------------------------------------------------------

class RequestQueue:
    """Bounded admission queue: arrivals beyond ``limit`` are shed.

    ``waits_s`` holds, for each request taken, the seconds it spent in
    the queue on the host's monotonic clock."""

    def __init__(self, limit: int):
        self.limit = int(limit)
        self.items: List[Any] = []
        self.offered_at: List[float] = []   # parallel to ``items``
        self.shed: List[Any] = []
        self.waits_s: List[float] = []

    def offer(self, request) -> bool:
        if len(self.items) >= self.limit:
            self.shed.append(request)
            return False
        self.items.append(request)
        self.offered_at.append(time.perf_counter())
        return True

    def take(self):
        if not self.items:
            return None
        self.waits_s.append(time.perf_counter() - self.offered_at.pop(0))
        return self.items.pop(0)

    def __len__(self):
        return len(self.items)


def _admit(queue: RequestQueue, pending: List[Any], upto: float) -> None:
    while pending and pending[0].arrival_s <= upto:
        queue.offer(pending.pop(0))


# ---------------------------------------------------------------------------
# LM mode: continuous batching with per-slot cache depths
# ---------------------------------------------------------------------------

class ServingEngine:
    """Continuous-batching engine for :class:`repro.models.lm.LM`.

    One batched decode cache serves ``max_batch`` slots; joining
    requests prefill at batch 1 through the full-sequence kernel and are
    merged into their slot (every cache leaf's batch axis located via
    :meth:`LM.cache_axes`), so the running batch never stalls for a
    joiner's token-by-token warmup.  Decode advances all active slots in
    one step with a per-slot position vector.  Admission is clocked by a
    simulated tick (``tick_s`` per engine iteration), so a fixed seed
    replays the same admissions, sheds, and outputs on any host.
    """

    def __init__(self, model, params, *, max_batch: int, queue_limit: int,
                 max_context: int, tick_s: float = 0.01):
        import jax
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.model = model
        self.params = params
        self.max_batch = int(max_batch)
        self.max_context = int(max_context)
        self.tick_s = float(tick_s)
        self.queue = RequestQueue(queue_limit)
        self.cache = model.init_cache(params, self.max_batch,
                                      self.max_context, dtype=jnp.float32)
        self._axes_flat = self._batch_axes()
        # on a TPU decode takes the cache donated and updates it in place
        # (``LM.decode``); elsewhere the cache it is given stays valid, for
        # callers that keep it (the benchmark's CPU fault tests)
        self.decode = jax.jit(model.decode,
                              donate_argnums=1 if self._platform() == "tpu" else ())
        self._prefill_jit = jax.jit(model.prefill)
        # a join's merge into its slot, one program (eagerly, an op per
        # cache leaf) that writes the slot in place of the batched cache
        # it is given
        self._merged = jax.jit(self._merge_leaves, donate_argnums=0)
        # each row's greedy id at its last position and a finiteness flag,
        # one program (eagerly, the indexing alone cost milliseconds)
        self._greedy = jax.jit(lambda logits: (jnp.argmax(logits[:, -1], axis=-1),
                                               jnp.isfinite(logits[:, -1]).all()))
        (self.decode_kernel_calls, self.decode_cache_donated_bytes,
         self._attention_block) = self._trace_decode()
        # slot i: None, or dict(req=, pos=, token=, out=[generated tokens])
        self.slots: List[Optional[Dict[str, Any]]] = [None] * self.max_batch
        self.completed: List[Dict[str, Any]] = []
        self.iterations = 0
        self.prefills = 0
        # decode steps; active slots and their valid cache positions
        # (``pos + 1``) summed over steps; the positions decode attention
        # reads, summed over steps; positions the cache reserves
        self.steps = 0
        self.slot_steps = 0
        self.valid_positions = 0
        self.attended_positions = 0
        self.capacity_positions = self.max_batch * self.max_context
        self._span = jax.profiler.TraceAnnotation
        # prefills / decode steps whose logits held a NaN or inf: a
        # token sampled from those is garbage however valid its id looks
        self.nonfinite_logits = 0

    def _platform(self) -> str:
        """The platform the engine's programs run on: its cache's."""
        leaf = self.jax.tree_util.tree_leaves(self.cache)[0]
        return next(iter(leaf.devices())).platform

    def _trace_decode(self):
        """One abstract trace of the decode program, no compile (the
        call reuses it): (distinct Pallas kernel calls in it, by shape,
        bytes of the arguments it takes donated, the block of positions
        the decode attention kernel reads or None where it does not run).
        ``LM.decode`` on a TPU multiplies each stack segment's f32
        projections by ``decode_matmul`` and attends over lane-aligned
        heads with ``decode_attention``; the calls count 0 where every
        projection and attention takes the XLA path, as on any other
        platform (where the kernels' branches are traced but not
        lowered)."""
        from repro.kernels import schedule as ksched

        jax, jnp = self.jax, self.jnp
        tokens = jax.ShapeDtypeStruct((self.max_batch, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((self.max_batch,), jnp.int32)
        with ksched.record_kernel_calls({}) as calls:
            traced = self.decode.trace(self.params, self.cache, tokens, pos)
        donated = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(traced.args_info) if a.donated)
        if self._platform() != "tpu":
            return 0, donated, None
        blocks = {c["effective"].block_kv for c in calls.values()
                  if c["kernel"] == "decode_attention"}
        return len(calls), donated, max(blocks, default=None)

    def _attended(self, pos) -> int:
        """The K/V positions decode attention reads in a step at slot
        positions ``pos`` (0 for an idle slot): each slot's length rounded
        up to the kernel's block where it runs, else the whole layer."""
        if self._attention_block is None:
            return self.max_batch * self.max_context
        block = self._attention_block
        return int(np.minimum(-(-(pos + 1) // block) * block, self.max_context).sum())

    def _pick(self, logits):
        """Greedy token ids at each row's last position of ``logits``
        (batch, seq, vocab), fetched in one transfer with a finiteness
        flag of those logits that feeds ``nonfinite_logits``."""
        ids, finite = self.jax.device_get(self._greedy(logits))
        self.nonfinite_logits += int(not finite)
        return np.asarray(ids)

    def _batch_axes(self) -> List[int]:
        """Per-cache-leaf distance of the batch axis from the right (the
        stacked-segment leading layers axis makes left-indexing wrong)."""
        import jax

        is_axes = lambda t: isinstance(t, tuple)
        axes_leaves = jax.tree_util.tree_flatten(
            self.model.cache_axes(), is_leaf=is_axes)[0]
        return [len(t) - t.index("batch") for t in axes_leaves]

    def _merge_leaves(self, cache, single_cache, slot):
        """``cache`` with a batch-1 prefilled cache written into slot
        ``slot`` (dynamic_update_slice on each leaf's batch axis)."""
        jax = self.jax
        b_leaves, treedef = jax.tree_util.tree_flatten(cache)
        s_leaves = jax.tree_util.tree_flatten(single_cache)[0]
        merged = []
        for b, s, from_right in zip(b_leaves, s_leaves, self._axes_flat):
            starts = [0] * b.ndim
            starts[b.ndim - from_right] = slot
            merged.append(jax.lax.dynamic_update_slice(
                b, s.astype(b.dtype), tuple(starts)))
        return jax.tree_util.tree_unflatten(treedef, merged)

    def _merge_slot(self, single_cache, slot: int) -> None:
        """Write a batch-1 prefilled cache into slot ``slot`` of the
        batched cache."""
        self.cache = self._merged(self.cache, single_cache, self.jnp.int32(slot))

    def _join(self, req) -> None:
        """Prefill one request (full-sequence kernel) into a free slot."""
        jnp, span, rid = self.jnp, self._span, req.id
        with span("serve.join", request_id=rid, prompt_len=req.prompt_len):
            slot = self.slots.index(None)
            prompt = req.prompt_tokens(self.model.spec.vocab)[None]  # (1, S)
            with span("serve.join.alloc", request_id=rid):
                single = self.model.init_cache(self.params, 1, self.max_context,
                                               dtype=jnp.float32)
            with span("serve.join.prefill", request_id=rid):
                logits, single = self._prefill_jit(self.params, single,
                                                   jnp.asarray(prompt))
            with span("serve.join.merge", request_id=rid):
                self._merge_slot(single, slot)
            self.prefills += 1
            with span("serve.join.pick", request_id=rid):
                first = int(self._pick(logits)[0])
            self.slots[slot] = {"req": req, "pos": req.prompt_len,
                                "token": first, "out": [first]}

    def _decode_step(self) -> None:
        """One engine iteration: every active slot decodes one token."""
        jnp, span = self.jnp, self._span
        active = [i for i, s in enumerate(self.slots) if s is not None]
        with span("serve.step", step=self.steps, active=len(active)):
            with span("serve.step.inputs"):
                tokens = np.zeros((self.max_batch, 1), np.int32)
                pos = np.zeros((self.max_batch,), np.int32)
                for i in active:
                    tokens[i, 0] = self.slots[i]["token"]
                    pos[i] = self.slots[i]["pos"]
                tokens_d, pos_d = jnp.asarray(tokens), jnp.asarray(pos)
            with span("serve.step.dispatch"):
                logits, self.cache = self.decode(self.params, self.cache,
                                                 tokens_d, pos_d)
            with span("serve.step.pick"):
                nxt = self._pick(logits)
            with span("serve.step.bookkeep"):
                self.steps += 1
                self.slot_steps += len(active)
                self.valid_positions += int(pos[active].sum()) + len(active)
                self.attended_positions += self._attended(pos)
                for i in active:
                    s = self.slots[i]
                    s["pos"] += 1
                    s["token"] = int(nxt[i])
                    s["out"].append(int(nxt[i]))
                    if len(s["out"]) >= s["req"].gen_len or s["pos"] + 1 >= self.max_context:
                        self.completed.append({
                            "id": s["req"].id,
                            "prompt_len": s["req"].prompt_len,
                            "tokens": s["out"],
                            "finish_iter": self.iterations,
                        })
                        self.slots[i] = None

    def run(self, requests: List[Any]) -> Dict[str, Any]:
        pending = sorted(requests, key=lambda r: (r.arrival_s, r.id))
        now = 0.0
        while pending or len(self.queue) or any(s is not None for s in self.slots):
            _admit(self.queue, pending, now)
            if not len(self.queue) and all(s is None for s in self.slots):
                now = max(now, pending[0].arrival_s)
                _admit(self.queue, pending, now)
            while len(self.queue) and None in self.slots:
                self._join(self.queue.take())
            if any(s is not None for s in self.slots):
                self._decode_step()
            self.iterations += 1
            now += self.tick_s
        self.completed.sort(key=lambda r: r["id"])
        return {
            "served": len(self.completed),
            "shed": len(self.queue.shed),
            "shed_ids": [r.id for r in self.queue.shed],
            "iterations": self.iterations,
            "prefills": self.prefills,
            "tokens_generated": sum(len(r["tokens"]) for r in self.completed),
            "nonfinite_logits": self.nonfinite_logits,
        }


def build_lm_engine(args):
    """(engine, traffic) for LM mode: the named architecture with random
    f32 weights from a fixed seed, sized to the traffic's longest
    request."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models.lm import LM
    from repro.nn.types import split

    arch = get_arch(args.arch)
    spec = arch.smoke_spec_fn() if args.smoke else arch.spec()
    model = LM(spec)
    params, _ = split(model.init(jax.random.PRNGKey(0), dtype=jnp.float32))

    traffic = _traffic_from_args(args)
    engine = ServingEngine(
        model, params, max_batch=args.max_batch,
        queue_limit=args.queue_limit,
        max_context=min(traffic.max_context + 1, spec.max_position),
        tick_s=args.tick_ms / 1e3)
    return engine, traffic


def _serve_lm(args) -> Dict[str, Any]:
    engine, traffic = build_lm_engine(args)
    t0 = time.time()
    summary = engine.run(traffic.requests())
    wall = time.time() - t0
    summary.update({
        "mode": "lm", "arch": engine.model.spec.name,
        "traffic": traffic.to_dict(),
        "max_batch": args.max_batch, "queue_limit": args.queue_limit,
        "wall_s": round(wall, 3),
        "tok_per_s": round(summary["tokens_generated"] / max(wall, 1e-9), 1),
        "sample": engine.completed[0]["tokens"][:8] if engine.completed else [],
    })
    return summary


# ---------------------------------------------------------------------------
# report mode: warm-boot the exploration winner from the artifact store
# ---------------------------------------------------------------------------

def rebuild_best(report: Dict[str, Any]):
    """(candidate, spec) — the report's best architecture, rebuilt from
    its recorded trial params via a fixed (pre-set params) trial."""
    from repro.core.builder import ModelBuilder
    from repro.core.space import parse_search_space
    from repro.core.translate import sample_architecture
    from repro.explorer.experiment import ExperimentSpec
    from repro.search.trial import Trial

    if not report.get("best"):
        raise SystemExit("report has no best trial to serve")
    spec = ExperimentSpec.from_dict(report["spec"])
    space = parse_search_space(dict(spec.search_space))
    trial = Trial(number=report["best"].get("number", 0), study=None)
    trial.params = dict(report["best"]["params"])
    arch = sample_architecture(space, trial)
    recorded = report["best"].get("signature")
    if recorded is not None and arch.signature() != recorded:
        raise SystemExit(
            f"rebuilt architecture signature {arch.signature()!r} does not "
            f"match the report's {recorded!r}; the search space or builder "
            f"changed since the exploration")
    builder = ModelBuilder(space.input_shape, space.output_dim)
    return builder.build(arch), spec


def _serve_report(args) -> Dict[str, Any]:
    import jax.numpy as jnp

    from repro.evaluation.serving import _ServingEstimator
    from repro.hwgen.generator import generate_call_count
    from repro.launch.traffic import ServingCosts, ServingSim

    with open(args.from_report) as f:
        report = json.load(f)
    candidate, spec = rebuild_best(report)
    serving = spec.serving
    if serving is None:
        from repro.explorer.experiment import ServingSpec

        serving = ServingSpec()
    if args.requests:
        serving.traffic.n_requests = args.requests
    if spec.cache.dir is None:
        print("warning: report's experiment had no cache dir; the boot "
              "will compile instead of warm-loading", file=sys.stderr)

    est = _ServingEstimator(target=spec.target, serving=serving,
                            cache=spec.cache.dir)
    before = generate_call_count()
    t0 = time.time()
    plan = est._schedule_plan(candidate)
    artifact, (params, _x0) = est._artifact(candidate, plan)
    boot_s = time.time() - t0
    compiles = generate_call_count() - before

    # the same deterministic admission/shedding/batching model the
    # estimators ranked this candidate by, with the *loaded* executable
    # really running once per joining batch
    requests = serving.traffic.requests()
    queue = RequestQueue(serving.queue_limit)
    pending = sorted(requests, key=lambda r: (r.arrival_s, r.id))
    seq_len = max(1, int(candidate.input_shape[-1]))
    costs = ServingCosts(
        prefill_s_per_token=est._prefill_bound_s(candidate, plan)
        / (serving.max_batch * seq_len),
        decode_step_s=est._decode_step_s(candidate))
    now, served, batches = 0.0, 0, 0
    l, c = int(candidate.input_shape[-1]), int(candidate.input_shape[0])
    t1 = time.time()
    while pending or len(queue):
        _admit(queue, pending, now)
        if not len(queue):
            now = max(now, pending[0].arrival_s)
            _admit(queue, pending, now)
        group = []
        while len(queue) and len(group) < serving.max_batch:
            group.append(queue.take())
        if not group:
            continue
        xb = np.zeros((serving.max_batch, l, c), np.float32)
        for i, req in enumerate(group):
            rng = np.random.default_rng(req.token_seed)
            xb[i] = rng.standard_normal((l, c)).astype(np.float32)
        artifact.compiled(params, jnp.asarray(xb))
        served += len(group)
        batches += 1
        now += sum(r.prompt_len for r in group) * costs.prefill_s_per_token \
            + costs.decode_step_s
    exec_s = time.time() - t1

    sim = ServingSim(max_batch=serving.max_batch,
                     queue_limit=serving.queue_limit).run(requests, costs)
    return {
        "mode": "report",
        "experiment": report.get("experiment"),
        "signature": candidate.arch.signature(),
        "target": spec.target,
        "compiles": compiles,
        "artifact_store": est.artifacts.stats() if est.artifacts else None,
        "boot_s": round(boot_s, 3),
        "served": served,
        "shed": len(queue.shed),
        "batches": batches,
        "exec_s": round(exec_s, 3),
        "traffic": serving.traffic.to_dict(),
        "modelled": {k: sim[k] for k in
                     ("p50_latency_s", "p99_latency_s", "throughput_tok_s",
                      "peak_concurrency")},
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _parse_mix(text: Optional[str]) -> Optional[Dict[int, float]]:
    """``"8,16"`` -> equal weights; ``"8:0.75,16:0.25"`` -> weighted."""
    if not text:
        return None
    mix: Dict[int, float] = {}
    for part in text.split(","):
        if ":" in part:
            k, w = part.split(":", 1)
            mix[int(k)] = float(w)
        else:
            mix[int(part)] = 1.0
    return mix


def _traffic_from_args(args):
    from repro.launch.traffic import TrafficSpec

    raw: Dict[str, Any] = {
        "seed": args.seed, "n_requests": args.requests or 8,
        "arrival": args.arrival, "rate_rps": args.rate_rps,
    }
    if _parse_mix(args.prompt_lens):
        raw["prompt_lens"] = _parse_mix(args.prompt_lens)
    if _parse_mix(args.gen_lens):
        raw["gen_lens"] = _parse_mix(args.gen_lens)
    return TrafficSpec.from_raw(raw)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    mode = p.add_mutually_exclusive_group()
    mode.add_argument("--arch", default=None,
                      help="serve a named LM architecture")
    mode.add_argument("--from-report", default=None,
                      help="serve an exploration report's best candidate, "
                           "warm-loading its executable from the artifact "
                           "store")
    p.add_argument("--smoke", action="store_true",
                   help="reduced same-family config (LM mode)")
    p.add_argument("--requests", type=int, default=0,
                   help="number of requests (0 = traffic default)")
    p.add_argument("--arrival", default="burst",
                   choices=("burst", "uniform", "poisson"))
    p.add_argument("--rate-rps", type=float, default=8.0)
    p.add_argument("--prompt-lens", default="",
                   help="prompt length mix, e.g. '8,16' or '8:0.75,16:0.25'")
    p.add_argument("--gen-lens", default="",
                   help="generation length mix, same syntax")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--queue-limit", type=int, default=8)
    p.add_argument("--tick-ms", type=float, default=10.0,
                   help="simulated admission clock per engine iteration")
    p.add_argument("--expect-compiles", type=int, default=None,
                   help="exit nonzero if the boot performed more XLA "
                        "compiles than this (report mode)")
    args = p.parse_args(argv)
    if args.arch is None and args.from_report is None:
        args.arch = "qwen3-1.7b"
        args.smoke = True
    return args


def main(argv=None) -> int:
    from repro.compile_cache import place_compile_cache

    args = parse_args(argv)
    place_compile_cache()
    if args.from_report:
        result = _serve_report(args)
    else:
        result = _serve_lm(args)
    print(json.dumps(result))
    if args.expect_compiles is not None and args.from_report:
        if result["compiles"] > args.expect_compiles:
            print(f"FAIL: boot performed {result['compiles']} XLA "
                  f"compile(s), expected <= {args.expect_compiles}",
                  file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
