"""Seeded serving traffic for the benchmark: one generator, driven by a
traffic file (``bench/traffic/<mix>.json``).

Copied from ``repro.launch.traffic`` (``TrafficSpec``, ``Request``,
``_length_mix``) and extended:

* closed loops: ``clients`` sessions, each sending its next request when
  the previous one completes;
* stratified draws: every seed gets the same multiset of prompt and
  generation lengths (and, in an open loop, the same multiset of
  inter-arrival gaps), in another order.  The seed changes the order and
  the prompt tokens, not the amount of work, so runs on different seeds
  spread no wider than runs on one seed.

A traffic file is a JSON object::

    {"loop": "open", "rate_rps": 4.0, "queue_limit": 64,
     "prompt_lens": {"32": 0.1, "64": 0.9}, "gen_lens": {"16": 1}}

or, closed: ``{"loop": "closed", "clients": 8, ...}``.  Lengths are
``{length: weight}``; weights are normalised.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Mapping

import numpy as np

LOOPS = ("open", "closed")
KEYS = ("loop", "rate_rps", "clients", "queue_limit", "prompt_lens",
        "gen_lens", "why")
# requests per stratified block of a closed loop (lengths repeat their
# mix exactly within each block)
CLOSED_BLOCK = 100


class TrafficError(ValueError):
    pass


def _length_mix(raw: Any, where: str) -> Dict[int, float]:
    if not isinstance(raw, Mapping) or not raw:
        raise TrafficError(f"{where}: expected a non-empty {{length: weight}} mapping")
    mix: Dict[int, float] = {}
    for k, w in raw.items():
        length, weight = int(k), float(w)
        if length < 1 or weight <= 0:
            raise TrafficError(f"{where}: bad entry {k!r}: {w!r}")
        mix[length] = weight
    total = sum(mix.values())
    return {k: v / total for k, v in sorted(mix.items())}


def stratified(mix: Dict[int, float], n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths whose counts follow ``mix`` by largest remainder,
    in an order drawn from ``rng``."""
    keys = list(mix)
    exact = np.array([mix[k] * n for k in keys])
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    values = np.repeat(np.array(keys, np.int64), counts)
    return rng.permutation(values)


@dataclasses.dataclass
class Request:
    """One request; ``due_s`` is when it is due, from the window's start
    (set at send time in a closed loop)."""

    id: int
    prompt_len: int
    gen_len: int
    token_seed: int
    due_s: float = 0.0

    def prompt_tokens(self, vocab: int) -> np.ndarray:
        rng = np.random.default_rng(self.token_seed)
        return rng.integers(0, vocab, self.prompt_len).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class Traffic:
    loop: str
    prompt_lens: Dict[int, float]
    gen_lens: Dict[int, float]
    queue_limit: int
    rate_rps: float = 0.0
    clients: int = 0

    @classmethod
    def from_dict(cls, raw: Mapping[str, Any], where: str = "traffic") -> "Traffic":
        unknown = set(raw) - set(KEYS)
        if unknown:
            raise TrafficError(f"{where}: unknown keys {sorted(unknown)}")
        loop = raw.get("loop")
        if loop not in LOOPS:
            raise TrafficError(f"{where}: loop must be one of {LOOPS}, got {loop!r}")
        t = cls(loop=loop,
                prompt_lens=_length_mix(raw.get("prompt_lens"), f"{where}.prompt_lens"),
                gen_lens=_length_mix(raw.get("gen_lens"), f"{where}.gen_lens"),
                queue_limit=int(raw.get("queue_limit", 64)),
                rate_rps=float(raw.get("rate_rps", 0.0)),
                clients=int(raw.get("clients", 0)))
        if loop == "open" and t.rate_rps <= 0:
            raise TrafficError(f"{where}: an open loop needs rate_rps > 0")
        if loop == "closed" and not 1 <= t.clients <= t.queue_limit:
            raise TrafficError(f"{where}: a closed loop needs 1 <= clients <= queue_limit")
        return t

    @property
    def max_prompt(self) -> int:
        return max(self.prompt_lens)

    @property
    def max_gen(self) -> int:
        return max(self.gen_lens)

    def open_requests(self, seed: int, seconds: float) -> List[Request]:
        """The requests due in ``[0, seconds)``, by due time: exactly
        ``round(rate * seconds)`` of them on every seed."""
        rng = np.random.default_rng(seed)
        n = max(1, int(round(self.rate_rps * seconds)))
        # exponential quantiles at the midpoints of n equal strata,
        # scaled to mean 1/rate and shuffled: a Poisson-like stream with
        # the same gaps on every seed
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = rng.permutation(gaps / gaps.mean() / self.rate_rps)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        prompt = stratified(self.prompt_lens, n, rng)
        gen = stratified(self.gen_lens, n, rng)
        seeds = rng.integers(0, 2**31 - 1, n)
        return [Request(i, int(prompt[i]), int(gen[i]), int(seeds[i]), float(due[i]))
                for i in range(n) if due[i] < seconds]

    def closed_stream(self, seed: int):
        """An endless iterator of requests for a closed loop, their lengths
        stratified in blocks of ``CLOSED_BLOCK``."""
        rng = np.random.default_rng(seed)
        i = 0
        while True:
            prompt = stratified(self.prompt_lens, CLOSED_BLOCK, rng)
            gen = stratified(self.gen_lens, CLOSED_BLOCK, rng)
            seeds = rng.integers(0, 2**31 - 1, CLOSED_BLOCK)
            for p, g, s in zip(prompt, gen, seeds):
                yield Request(i, int(p), int(g), int(s))
                i += 1
