"""Whether what the timed path served is correct.

After the window closes, a sample drawn from the seed of the requests
the engine finished, the longest among them, is run through the plain
reference (``bench/reference/<name>.py``) once per request: prompt plus
served tokens, teacher-forced, in float32 at ``HIGHEST`` matmul
precision, with weights the reference makes again from the seed.  Each
served token was the engine's greedy pick, so the number compared is
the widest gap by which a served token's reference logit lies below the
reference's best at that position.  This covers the prefill (first
token), the slot merge and the batched decode through the cache at
per-slot depths (every later token), for requests that joined beside
others.

The control puts the reference in the program's place, computed in
bfloat16: at the same positions of the same sequences, the gap of the
token that the bfloat16 reference puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from lib import refmath as R
from lib import weights as W

# a sample holds the longest finished request and then others, drawn
# from the seed, until it holds this many served tokens
SAMPLE_TOKENS = 400
SAMPLE_MAX_REQUESTS = 16


def sample(completed: List[Dict[str, Any]], seed: int) -> List[Dict[str, Any]]:
    if not completed:
        return []
    longest = max(completed, key=lambda r: (len(r["tokens"]), -r["id"]))
    rest = [r for r in completed if r is not longest]
    order = np.random.default_rng([int(seed), 1]).permutation(len(rest))
    out, n = [longest], len(longest["tokens"])
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i]["tokens"])
    return out


def sequences(picked, prompts: Dict[int, np.ndarray], pad_len: int, max_gen: int):
    """Per request: the padded teacher-forced input (prompt + served
    tokens but the last), the positions whose logits chose each served
    token, and the served tokens."""
    out = []
    for r in picked:
        prompt, served = prompts[r["id"]], np.asarray(r["tokens"], np.int32)
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        s, g = len(prompt), len(served)
        x = np.zeros(pad_len, np.int32)
        x[: len(toks)] = toks
        pos = np.zeros(max_gen, np.int32)
        pos[:g] = np.arange(s - 1, s - 1 + g)
        tok = np.zeros(max_gen, np.int32)
        tok[:g] = served
        out.append((x, pos, tok, g))
    return out


class Reference:
    """The configuration's reference on the default device, with its
    weights made again from the seed."""

    def __init__(self, module, dims: Dict[str, Any], seed: int):
        import jax
        import jax.numpy as jnp

        self.jax = jax
        self.w = W.make(seed, module.param_shapes(dims), jnp.float32)

        def gaps(w, x, pos, tok):
            logits = module.forward(w, x, dims, jnp.float32, R.HIGHEST)[pos]
            return logits.max(-1) - jnp.take_along_axis(logits, tok[:, None], 1)[:, 0]

        def control(w, x, pos):
            ref = module.forward(w, x, dims, jnp.float32, R.HIGHEST)[pos]
            low = module.forward(w, x, dims, jnp.bfloat16, R.DEFAULT)[pos]
            pick = jnp.argmax(low, -1)
            return ref.max(-1) - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]

        self._gaps = jax.jit(gaps)
        self._control = jax.jit(control)

    def gaps(self, seqs) -> np.ndarray:
        """Every served token's gap below the reference's best."""
        with self.jax.default_matmul_precision("highest"):
            return np.concatenate([np.asarray(self._gaps(self.w, x, pos, tok))[:g]
                                   for x, pos, tok, g in seqs])

    def control_gaps(self, seqs) -> np.ndarray:
        """The gap of the token the control puts first, at the same
        positions."""
        return np.concatenate([np.asarray(self._control(self.w, x, pos))[:g]
                               for x, pos, tok, g in seqs])


def gap_numbers(gaps: np.ndarray) -> Dict[str, float]:
    """The widest gap, the mean gap over the served tokens, and the share
    of served tokens that are not the reference's best (inf where a gap
    is not finite)."""
    if not np.isfinite(gaps).all():
        return dict.fromkeys(("widest_gap", "mean_gap", "mismatch_share"), float("inf"))
    return {"widest_gap": float(gaps.max()), "mean_gap": float(gaps.mean()),
            "mismatch_share": float((gaps > 0).mean())}


def token_faults(completed, due_gen: Dict[int, int], vocab: int) -> int:
    """Finished requests whose tokens are out of range or whose count is
    not the requested generation length."""
    bad = 0
    for r in completed:
        toks = r["tokens"]
        bad += int(len(toks) != due_gen[r["id"]] or min(toks) < 0 or max(toks) >= vocab)
    return bad


def verdict(numbers: Dict[str, Sequence[float]]) -> bool:
    """``{name: (value, limit)}``: correct when every value is within its
    limit."""
    return all(v is not None and v <= lim for v, lim in numbers.values())
