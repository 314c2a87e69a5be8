"""Each plain reference against the program's ``LM.prefill`` followed by
``LM.decode`` through the cache, at the smoke size on the CPU, both at
``HIGHEST`` matmul precision.

Tolerance: max |program - reference| <= 1e-4 x max |reference logit|.
Both sides compute in float32 at full precision; what is left is
summation order (fused vs. separate matmuls) and transcendental
rounding, measured here at about
1e-6 of the logit scale.  A wrong mask, position, norm or layer order
moves the logits by a sizeable share of that scale.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import refmath as R
from lib import weights as W
from lib.harness import config_dims, program_shapes
from lib.registry import load_cell

TOL = 1e-4


@pytest.mark.parametrize("workload", ["qwen3-1.7b.decode-batch"])
def test_reference_matches_prefill_then_decode(workload):
    from repro.configs import get_arch
    from repro.models.lm import LM

    cell = load_cell(workload)
    dims = config_dims(cell, smoke=True)
    model = LM(get_arch(cell.config["arch"]).smoke_spec_fn())
    shapes = program_shapes(model, jnp.float32)
    assert shapes == cell.reference.param_shapes(dims)
    flat = W.make(2**31 + 5, shapes, jnp.float32)
    params = W.unflatten_paths(flat)
    prompt, steps = 12, 6
    tokens = np.random.default_rng(3).integers(0, dims["vocab_size"], prompt + steps).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prefill, decode = jax.jit(model.prefill), jax.jit(model.decode)
        cache = model.init_cache(params, 1, 32, dtype=jnp.float32)
        logits, cache = prefill(params, cache, jnp.asarray(tokens[None, :prompt]))
        got = [np.asarray(logits[0])]
        for i in range(steps - 1):
            step, cache = decode(params, cache, jnp.asarray(tokens[None, prompt + i:prompt + i + 1]),
                                       jnp.asarray([prompt + i], jnp.int32))
            got.append(np.asarray(step[0]))
        want = np.asarray(cell.reference.forward(flat, jnp.asarray(tokens[:-1]), dims,
                                                 jnp.float32, R.HIGHEST))
    got = np.concatenate(got)
    scale = np.abs(want).max()
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= TOL * scale


@pytest.mark.parametrize("workload", ["qwen3-1.7b.decode-batch"])
def test_reference_sees_a_changed_layer(workload):
    """The comparison above is not blind: zeroing one layer's output
    projection moves the reference's logits far beyond the tolerance."""
    cell = load_cell(workload)
    dims = config_dims(cell, smoke=True)
    shapes = cell.reference.param_shapes(dims)
    flat = W.make(11, shapes, jnp.float32)
    tokens = jnp.asarray(np.arange(10, dtype=np.int32) % dims["vocab_size"])
    base = np.asarray(cell.reference.forward(flat, tokens, dims, jnp.float32, R.HIGHEST))
    key = next(k for k in shapes if k.endswith(("inner/wo", "inner/out_proj")))
    broken = dict(flat, **{key: flat[key].at[0].set(0.0)})
    moved = np.asarray(cell.reference.forward(broken, tokens, dims, jnp.float32, R.HIGHEST))
    assert np.abs(moved - base).max() > 100 * TOL * np.abs(base).max()
