"""Drives ``repro.launch.serve.ServingEngine`` on the wall clock.

The engine's own ``run`` advances a simulated tick; the harness does not
call it.  Its whole surface on the program:

* ``ServingEngine(model, params, max_batch=, queue_limit=, max_context=)``;
* ``engine.queue.offer(request)`` (arrivals beyond the queue limit are
  shed) and ``engine.queue.take()``;
* ``engine._join(request)``: prefill, slot merge, first-token fetch;
* ``engine._decode_step()``: one step of every active slot;
* ``engine.slots``, ``engine.completed`` and ``engine.nonfinite_logits``.

The weights are the benchmark's (``lib.weights``), made on the device
from the seed; the model is ``repro.models.lm.LM`` of the configuration's
``arch``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from lib import weights as W
from lib.registry import Cell
from lib.traffic import Request

# After the window closes, requests due in it are still served until
# each has its first token and some request has finished (the check
# needs one); a request still waiting this long after the close counts
# as missing.
DRAIN_LIMIT_S = 60.0


@dataclasses.dataclass
class Span:
    """A harness span in the window; ``arg`` is the join's prompt length
    or the step's per-slot contexts (valid positions)."""

    name: str
    start: float
    end: float
    arg: Any = None


@dataclasses.dataclass
class WindowRecord:
    """What one measured window saw; times in seconds from its start."""

    loop: str
    seconds: float
    requests: Dict[int, Request]          # every request offered
    due: Dict[int, float]                 # request id -> due time
    tokens: Dict[int, List[float]]        # request id -> emission times
    shed: List[int]
    spans: List[Span]                     # started before the window closed
    max_offer_lag_s: float
    window_compiles: int
    drain_compiles: int
    drain_s: float


def program_shapes(model, dtype) -> Dict[str, Tuple[int, ...]]:
    """``{path: shape}`` of the program's parameter tree."""
    import jax

    from repro.nn.types import split

    tree = jax.eval_shape(lambda k: split(model.init(k, dtype=dtype))[0],
                          jax.random.PRNGKey(0))
    return {p: tuple(v.shape) for p, v in W.flatten_paths(tree).items()}


def config_dims(cell: Cell, smoke: bool) -> Dict[str, Any]:
    """The configuration's numbers; at smoke size, with its ``smoke``
    block laid over them (CPU rehearsals only)."""
    c = dict(cell.config)
    if smoke:
        c.update(c["smoke"])
    return c


class Session:
    """One process's benchmark run of one cell: set-up, window, checks."""

    def __init__(self, cell: Cell, seed: int, *, smoke: bool = False, counter=None):
        self.cell, self.seed, self.smoke = cell, int(seed), smoke
        self.dims = config_dims(cell, smoke)
        self.counter = counter
        self.engine = None
        self.model = None

    # -- set-up ---------------------------------------------------------

    def build(self) -> None:
        """Weights on the device from the seed, in one jitted call, and
        the engine around them."""
        import jax.numpy as jnp

        from repro.configs import get_arch
        from repro.launch.serve import ServingEngine
        from repro.models.lm import LM

        arch = get_arch(self.cell.config["arch"])
        self.model = LM(arch.smoke_spec_fn() if self.smoke else arch.spec())
        dtype = jnp.dtype(self.cell.config["weight_dtype"])
        shapes = program_shapes(self.model, dtype)
        want = self.cell.reference.param_shapes(self.dims)
        if shapes != want:
            diff = sorted(set(shapes.items()) ^ set(want.items()))[:6]
            raise RuntimeError(f"the program's parameter tree is not the reference's: {diff}")
        flat = W.make(self.seed, shapes, dtype)
        params = W.unflatten_paths(flat)
        t = self.cell.traffic
        max_context = int(self.dims["max_context"]) if not self.smoke else \
            t.max_prompt + t.max_gen + 1
        if t.max_prompt + t.max_gen + 1 > max_context:
            raise ValueError(f"traffic needs {t.max_prompt + t.max_gen + 1} positions, "
                             f"the configuration holds {max_context}")
        self.engine = ServingEngine(self.model, params, max_batch=int(self.dims["max_batch"]),
                                    queue_limit=t.queue_limit, max_context=max_context)

    def warm_up(self) -> None:
        """Every shape the cell's traffic uses: a join at each prompt
        length (prefill, batch-1 cache, slot merge, first-token fetch),
        then the decode step.  The engine's state is reset after."""
        e = self.engine
        lens = sorted(self.cell.traffic.prompt_lens)
        for i, s in enumerate(lens):
            e._join(Request(-1 - i, s, 2, i))
            e._decode_step()
            e.slots = [None] * e.max_batch
        e.completed.clear()
        e.nonfinite_logits = 0
        e.prefills = 0
        _block(e.cache)

    # -- the measured window ---------------------------------------------

    def window(self, seconds: float, trace=None) -> WindowRecord:
        """Offer the traffic for ``seconds`` on the wall clock, then serve
        the requests due in the window until each has its first token.
        ``trace`` (a ``lib.trace.Tracer``) records the window alone."""
        e, t = self.engine, self.cell.traffic
        clock = time.perf_counter
        annotate = trace.annotate if trace is not None else _null_annotation
        requests: Dict[int, Request] = {}
        due: Dict[int, float] = {}
        tokens: Dict[int, List[float]] = {}
        spans: List[Span] = []
        lag = 0.0
        if t.loop == "open":
            pending = t.open_requests(self.seed, seconds)
            stream = None
        else:
            pending = []
            stream = t.closed_stream(self.seed)
        done_seen = 0

        def offer(req: Request, now: float):
            nonlocal lag
            requests[req.id] = req
            due[req.id] = req.due_s
            lag = max(lag, now - req.due_s)
            e.queue.offer(req)

        def span(name, fn, arg, in_window):
            a = clock() - t0
            with annotate(f"bench.{name}"):
                fn()
            b = clock() - t0
            if in_window:
                spans.append(Span(name, a, b, arg))
            return b

        c0 = self.counter.compiles if self.counter else 0
        c1 = None
        if trace is not None:
            trace.start()
        t0 = clock()
        if stream is not None:
            for _ in range(t.clients):
                req = next(stream)
                req.due_s = 0.0
                offer(req, 0.0)
        closed_at = None
        while True:
            now = clock() - t0
            if closed_at is None and now >= seconds:
                closed_at, c1 = now, self.counter.compiles if self.counter else 0
                if trace is not None:
                    trace.stop()
            if closed_at is not None:
                shed = set(_shed_ids(e))
                waiting = [i for i in due if i not in tokens and i not in shed]
                if (not waiting and e.completed) or now - closed_at > DRAIN_LIMIT_S:
                    break
            while pending and pending[0].due_s <= now:
                offer(pending.pop(0), now)
            while len(e.queue) and None in e.slots:
                req = e.queue.take()
                end = span("join", lambda: e._join(req), req.prompt_len,
                           closed_at is None)
                tokens[req.id] = [end]
            active = [(s["req"].id, s["pos"] + 1) for s in e.slots if s is not None]
            if active:
                end = span("step", e._decode_step, [ctx for _, ctx in active],
                           closed_at is None)
                for rid, _ in active:
                    tokens[rid].append(end)
                if stream is not None and closed_at is None:
                    for _ in e.completed[done_seen:]:
                        req = next(stream)
                        req.due_s = end
                        offer(req, end)
                done_seen = len(e.completed)
            elif not len(e.queue) and closed_at is None:
                nxt = min(pending[0].due_s if pending else seconds, seconds)
                a = clock() - t0
                with annotate("bench.await_arrival"):
                    time.sleep(max(0.0, nxt - a))
                spans.append(Span("await_arrival", a, clock() - t0))
            elif closed_at is not None and not len(e.queue):
                break
        c2 = self.counter.compiles if self.counter else 0
        return WindowRecord(
            loop=t.loop, seconds=float(seconds), requests=requests, due=due, tokens=tokens,
            shed=_shed_ids(e), spans=spans, max_offer_lag_s=lag,
            window_compiles=c1 - c0 if c1 is not None else c2 - c0,
            drain_compiles=c2 - c1 if c1 is not None else 0,
            drain_s=(clock() - t0) - closed_at)


def _shed_ids(engine) -> List[int]:
    return [r.id for r in engine.queue.shed]


def _block(tree) -> None:
    import jax

    jax.block_until_ready(tree)


class _null_annotation:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def nearest_rank(values: List[float], q: float) -> float:
    """Nearest-rank percentile: no interpolation."""
    v = sorted(values)
    rank = max(1, int(np.ceil(q * len(v))))
    return float(v[min(rank, len(v)) - 1])


def end_to_end(rec: WindowRecord) -> Dict[str, float]:
    """TTFT over every request due in the window (a shed or unserved one
    counts as missing: timed to the end of the drain), gaps between
    consecutive tokens that fall in the window, and every token emitted
    in the window over the window."""
    end = rec.seconds + rec.drain_s
    ttft = []
    for rid, due in rec.due.items():
        if due >= rec.seconds:
            continue
        times = rec.tokens.get(rid)
        ttft.append((times[0] if times else end) - due)
    gaps = [b - a for times in rec.tokens.values()
            for a, b in zip(times, times[1:]) if b <= rec.seconds]
    emitted = sum(1 for times in rec.tokens.values() for x in times if x <= rec.seconds)
    out = {"tokens_per_s": emitted / rec.seconds}
    if ttft:
        out["ttft_p90_ms"] = 1e3 * nearest_rank(ttft, 0.90)
        out["ttft_p50_ms"] = 1e3 * nearest_rank(ttft, 0.50)
    if gaps:
        out["itl_p95_ms"] = 1e3 * nearest_rank(gaps, 0.95)
        out["itl_p50_ms"] = 1e3 * nearest_rank(gaps, 0.50)
    return out


def counts(rec: WindowRecord) -> Dict[str, Any]:
    """Counts of the window, printed before the result line."""
    due_in = [i for i, d in rec.due.items() if d < rec.seconds]
    step_spans = [s for s in rec.spans if s.name == "step"]
    joins_in_gap = 0
    # gaps whose interval holds a join: the population p95 of the gaps can flip into
    join_spans = [s for s in rec.spans if s.name == "join"]
    gaps = [(a, b) for times in rec.tokens.values()
            for a, b in zip(times, times[1:]) if b <= rec.seconds]
    starts = np.array(sorted(s.start for s in join_spans)) if join_spans else np.zeros(0)
    for a, b in gaps:
        i = np.searchsorted(starts, a)
        joins_in_gap += int(i < len(starts) and starts[i] < b)
    return {
        "requests_due": len(due_in),
        "shed": len(rec.shed),
        "unserved": sum(1 for i in due_in if i not in rec.tokens),
        "joins": len(join_spans),
        "steps": len(step_spans),
        "gaps": len(gaps),
        "gaps_with_join_share": joins_in_gap / len(gaps) if gaps else None,
        "max_offer_lag_ms": 1e3 * rec.max_offer_lag_s,
        "window_compiles": rec.window_compiles,
        "drain_compiles": rec.drain_compiles,
        "drain_s": rec.drain_s,
    }


# ---------------------------------------------------------------------------
# per-layer record
# ---------------------------------------------------------------------------

def layer_record(rec: WindowRecord, work_mod, dims: Dict[str, Any],
                 peaks: Dict[str, float], traced: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """What the metric readers read: harness spans, the least work of the
    window's prefills and decode steps, the peaks, the trace reduction."""
    spans: Dict[str, Dict[str, float]] = {}
    for s in rec.spans:
        d = spans.setdefault(s.name, {"count": 0, "total_s": 0.0})
        d["count"] += 1
        d["total_s"] += s.end - s.start
    work: Dict[str, Dict[str, Any]] = {}
    for kind, span in (("prefill", "join"), ("decode", "step")):
        calls = [getattr(work_mod, kind)(dims, s.arg) for s in rec.spans if s.name == span]
        least = [w.least_s(peaks) for w in calls]
        memory = sum(t for w, t in zip(calls, least) if w.bound(peaks) == "memory")
        work[kind] = {"calls": len(calls),
                      "flops": sum(w.flops for w in calls),
                      "bytes": sum(w.bytes for w in calls),
                      "least_s": sum(least),
                      "bound": "memory" if memory * 2 >= sum(least) else "compute"}
    return {"loop": rec.loop,
            "window_s": traced["window_s"] if traced else rec.seconds,
            "spans": spans, "work": work, "peaks": peaks, "trace": traced}
