"""The program's own spans and name scopes in the profiler trace of a
window, and the serving engine's counters over it.

``repro.launch.serve.ServingEngine`` writes ``serve.*`` spans
(``TraceAnnotation``s: ``serve.join`` with ``.alloc``, ``.prefill``,
``.merge``, ``.pick``; ``serve.step`` with ``.inputs``, ``.dispatch``,
``.pick``, ``.bookkeep``) on the clock of the device's events, and
``repro.models.lm.LM`` puts every op of a sub-block under the name scope
of its kind (``attention``, ``mlp``, ...), of ``embed`` or of ``head``.
``reduce`` reads the same ``.xplane.pb`` as ``lib.trace.reduce``, before
``Tracer.close``, and gives over the ``bench.window``:

* ``scopes``: for ``jit_decode`` and ``jit_prefill``, device self time
  per scope, with each scope's ops.  An op's self time is its duration
  less the part of it that op events nested in it on the same line
  cover, so a ``while`` or ``call`` container counts once.  Its scope
  is the last sub-block name in its name-scope path, which the compiled
  module's HLO text gives (``op_name`` metadata, joined by instruction
  name); an op in no named scope is ``other``;
* ``idle``: the first device's idle seconds, each gap split by the
  innermost ``serve.*`` span that covers it, else ``host``;
* ``steps`` and ``joins``: the ``serve.step`` and ``serve.join`` spans
  in the window, so that per-step and per-join numbers take numerator
  and denominator from one clock.

``ProgramTracer`` is a ``lib.trace.Tracer`` that also reads the engine's
counters when the window starts and closes.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

from lib import trace as T
from lib.harness import nearest_rank

SCOPES = frozenset({"attention", "cross_attention", "mlp", "moe", "mamba2", "mlstm",
                    "slstm", "embed", "head"})
OTHER = "other"
PROGRAM_PREFIX = "serve."
STEP = "serve.step"
JOIN = "serve.join"
MODULES = ("jit_decode", "jit_prefill")
COUNTERS = ("steps", "slot_steps", "valid_positions")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"", re.M)
_EVENT_NAME = re.compile(r"^%?([\w.\-]+)")


class ProgramTracer(T.Tracer):
    """A ``Tracer`` that reads the engine's counters as the window starts
    and closes; after ``stop``, ``counters`` holds their differences and
    the queue waits of the requests taken in the window."""

    def __init__(self, engine):
        super().__init__()
        self.engine = engine
        self.counters: Optional[Dict[str, Any]] = None
        self._at_start: Dict[str, int] = {}

    def _read(self) -> Dict[str, int]:
        e = self.engine
        out = {k: getattr(e, k) for k in COUNTERS}
        out["waits"] = len(e.queue.waits_s)
        return out

    def start(self) -> None:
        self._at_start = self._read()
        super().start()

    def stop(self) -> None:
        super().stop()
        end, e = self._read(), self.engine
        self.counters = {k: end[k] - self._at_start[k] for k in COUNTERS}
        self.counters.update(
            waits_s=list(e.queue.waits_s[self._at_start["waits"]:end["waits"]]),
            max_batch=e.max_batch, capacity_positions=e.capacity_positions)


def hlo_texts(engine, prompt_lens: Iterable[int]) -> Dict[str, List[str]]:
    """The compiled HLO text of the engine's decode step and of its
    prefill at each prompt length.  Lowered with arguments of the types
    the engine passes, so each is the executable already compiled."""
    import jax.numpy as jnp
    import numpy as np

    e = engine
    tokens = jnp.asarray(np.zeros((e.max_batch, 1), np.int32))
    pos = jnp.asarray(np.zeros((e.max_batch,), np.int32))
    out = {"jit_decode": [e.decode.lower(e.params, e.cache, tokens, pos).compile().as_text()],
           "jit_prefill": []}
    single = e.model.init_cache(e.params, 1, e.max_context, dtype=jnp.float32)
    for s in sorted(set(prompt_lens)):
        prompt = jnp.asarray(np.zeros((1, s), np.int32))
        out["jit_prefill"].append(e._prefill_jit.lower(e.params, single, prompt).compile().as_text())
    return out


def scope_of(path: str) -> str:
    """The last sub-block name in a name-scope path, else ``other``."""
    for part in reversed(re.split(r"[/;]", path)):
        if part in SCOPES:
            return part
    return OTHER


def scope_map(texts: Iterable[str]) -> Dict[str, str]:
    """Instruction name -> scope, from compiled HLO text.  A name that
    two texts give different scopes maps to ``other``."""
    out: Dict[str, str] = {}
    for text in texts:
        for name, path in _INSTRUCTION.findall(text):
            scope = scope_of(path)
            out[name] = scope if out.get(name, scope) == scope else OTHER
    return out


def _instruction(event_name: str) -> str:
    m = _EVENT_NAME.match(event_name)
    return m.group(1) if m else event_name


def self_times(events: List[Tuple[float, float, str]]) -> List[Tuple[str, float, float, float]]:
    """``(name, start, end, self seconds)`` of one line's op events; an
    event's self time is its duration less what the events nested in it
    cover (their union, which is the union of its direct children)."""
    order = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    covered = [0.0] * len(order)
    stack: List[int] = []
    for i, (a, b, _) in enumerate(order):
        while stack and order[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            parent = order[stack[-1]]
            covered[stack[-1]] += min(b, parent[1]) - a
        stack.append(i)
    return [(n, a, b, (b - a) - c) for (a, b, n), c in zip(order, covered)]


def _innermost(spans: List[Tuple[float, float, str]]) -> List[Tuple[float, float, str]]:
    """Nested spans of one thread flattened to disjoint pieces, each
    named for the innermost span that covers it."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, float, str]] = []
    t = None

    def emit(upto):
        nonlocal t
        if stack and t is not None and upto > t:
            pieces.append((t, upto, stack[-1][2]))
        t = upto

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= a:
            emit(stack[-1][1])
            stack.pop()
        emit(a)
        stack.append((a, min(b, stack[-1][1]) if stack else b, name))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return pieces


def reduce(path: str, hlo: Optional[Dict[str, List[str]]] = None) -> Dict[str, Any]:
    """Reduce one trace file; times in seconds.  ``hlo`` maps a module
    name to the compiled HLO texts of its executables (``hlo_texts``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    threads: List[List[Tuple[float, float, str]]] = []
    devices = []
    for plane in data.planes:
        if T.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                iv = (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                if ev.name == T.WINDOW and window is None:
                    window = iv
                elif ev.name.startswith(PROGRAM_PREFIX):
                    spans.append(iv + (ev.name,))
            if spans:
                threads.append(spans)
    if not devices:
        raise ValueError(f"no device plane in {path}")
    if window is None:
        raise ValueError(f"no {T.WINDOW} annotation in {path}")
    lo, hi = window
    scopes = {m: scope_map((hlo or {}).get(m, ())) for m in MODULES}
    acc = {m: defaultdict(lambda: defaultdict(float)) for m in MODULES}
    busy_first: List[Tuple[float, float]] = []
    for k, plane in enumerate(devices):
        modules: List[Tuple[float, float, str]] = []
        ops: List[Tuple[float, float, str]] = []
        for line in plane.lines:
            if line.name not in (T.OPS_LINE, T.MODULES_LINE):
                continue
            for ev in line.events:
                a, b = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                if b <= lo or a >= hi:
                    continue
                (ops if line.name == T.OPS_LINE else modules).append(
                    (max(a, lo), min(b, hi), ev.name))
        if k == 0:
            busy_first = T._clip(T._union([(a, b) for a, b, _ in ops]), lo, hi)
        modules.sort()
        starts = [a for a, _, _ in modules]
        for name, a, b, self_s in self_times(ops):
            mid = (a + b) / 2
            i = bisect.bisect_right(starts, mid) - 1
            if i < 0 or modules[i][1] <= mid:
                continue
            module = T._module_name(modules[i][2])
            if module not in acc:
                continue
            op = _instruction(name)
            acc[module][scopes[module].get(op, OTHER)][op] += self_s / len(devices)
    out_scopes = {m: {s: {"self_s": sum(ops.values()),
                          "ops": dict(sorted(ops.items(), key=lambda kv: -kv[1]))}
                      for s, ops in sorted(acc[m].items())} for m in MODULES}
    pieces: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for spans in threads:
        for a, b, name in _innermost(spans):
            pieces[name].append((a, b))
    begun = [n for spans in threads for a, _, n in spans if lo <= a < hi]
    return {"window_s": hi - lo, "scopes": out_scopes,
            "steps": begun.count(STEP), "joins": begun.count(JOIN),
            "idle": T._attribute_idle(busy_first, lo, hi, pieces)}


# ---------------------------------------------------------------------------
# what the counts line and the per-layer metrics read
# ---------------------------------------------------------------------------

def scope_ms(record: Dict[str, Any], module: str, scope: str, loop: str) -> Optional[float]:
    """Device self milliseconds of ``module``'s ops in ``scope`` per
    ``serve.step`` span of the window; ``None`` where the trace holds no
    such span or scope (a program without them)."""
    program = record.get("program")
    if record["loop"] != loop or not program or not program["steps"]:
        return None
    s = program["scopes"].get(module, {}).get(scope)
    return None if s is None else 1e3 * s["self_s"] / program["steps"]


def step_idle_ms(record: Dict[str, Any], loop: str) -> Optional[float]:
    """Device idle milliseconds inside ``serve.step`` spans (its children
    included) per ``serve.step`` span of the window."""
    program = record.get("program")
    if record["loop"] != loop or not program or not program["steps"]:
        return None
    idle = sum(v for k, v in program["idle"].items() if k == STEP or k.startswith(STEP + "."))
    return 1e3 * idle / program["steps"]


METRICS = {
    "decode_attention_ms.closed": lambda r: scope_ms(r, "jit_decode", "attention", "closed"),
    "decode_mlp_ms.closed": lambda r: scope_ms(r, "jit_decode", "mlp", "closed"),
    "decode_head_ms.closed": lambda r: scope_ms(r, "jit_decode", "head", "closed"),
    "step_idle_ms.closed": lambda r: step_idle_ms(r, "closed"),
}


def counts(program: Optional[Dict[str, Any]], counters: Optional[Dict[str, Any]],
           top: int = 3) -> Dict[str, Any]:
    """The counts line's keys: each decode scope's milliseconds per step
    with its ``top`` ops, each prefill scope's per join, device idle by
    program span, and from the engine's counters batch occupancy, the
    valid share of the reserved cache positions and the queue wait (p50
    and max)."""
    out: Dict[str, Any] = {}
    if program and program["steps"]:
        n = program["steps"]
        out["decode_scope_ms"] = {
            s: {"ms": 1e3 * v["self_s"] / n,
                "top": [[op, 1e3 * t / n] for op, t in list(v["ops"].items())[:top]]}
            for s, v in sorted(program["scopes"]["jit_decode"].items(),
                               key=lambda kv: -kv[1]["self_s"])}
    if program and program["joins"]:
        out["prefill_scope_ms"] = {s: 1e3 * v["self_s"] / program["joins"]
                                   for s, v in program["scopes"]["jit_prefill"].items()}
    if program:
        out["idle_by_program_span_s"] = program["idle"]
    if counters and counters["steps"]:
        steps = counters["steps"]
        out["occupancy"] = counters["slot_steps"] / (steps * counters["max_batch"])
        out["cache_valid_share"] = counters["valid_positions"] / (
            steps * counters["capacity_positions"])
    if counters and counters["waits_s"]:
        waits = counters["waits_s"]
        out["queue_wait_ms"] = {"p50": 1e3 * nearest_rank(waits, 0.5),
                                "max": 1e3 * max(waits), "n": len(waits)}
    return out
