"""Name scopes of the LM's sub-blocks: every kind a spec has, ``embed``
and ``head`` appear in the HLO metadata of ``LM.decode`` and
``LM.prefill``, and nothing but the metadata changes."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.models.lm import LM
from repro.nn.types import split

ARCHS = ["qwen3-1.7b", "zamba2-2.7b", "xlstm-1.3b"]
BATCH, CONTEXT, PROMPT = 2, 16, 8


def _lowered(spec, entry):
    model = LM(spec)
    params = jax.eval_shape(lambda k: split(model.init(k, dtype=jnp.float32))[0],
                            jax.random.PRNGKey(0))
    batch = 1 if entry == "prefill" else BATCH
    cache = jax.eval_shape(lambda p: model.init_cache(p, batch, CONTEXT, dtype=jnp.float32),
                           params)
    if entry == "prefill":
        tokens = jax.ShapeDtypeStruct((1, PROMPT), jnp.int32)
        return jax.jit(model.prefill).lower(params, cache, tokens)
    tokens = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    return jax.jit(model.decode).lower(params, cache, tokens, pos)


def _scopes(lowered):
    """Every name-scope component in the HLO's ``op_name`` metadata."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return {part for path in re.findall(r'op_name="([^"]*)"', text)
            for part in re.split(r"[/;]", path)}


@pytest.mark.parametrize("entry", ["decode", "prefill"])
@pytest.mark.parametrize("arch", ARCHS)
def test_hlo_metadata_names_every_sub_block_kind(arch, entry):
    spec = get_arch(arch).smoke_spec_fn()
    kinds = {sub.kind for layer in spec.layers for sub in layer.subs}
    assert kinds | {"embed", "head"} <= _scopes(_lowered(spec, entry))


@pytest.mark.parametrize("entry", ["decode", "prefill"])
@pytest.mark.parametrize("arch", ARCHS)
def test_scopes_change_nothing_but_metadata(arch, entry, monkeypatch):
    spec = get_arch(arch).smoke_spec_fn()
    scoped = _lowered(spec, entry)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _lowered(spec, entry)
    assert {"embed", "head"} <= _scopes(scoped)
    assert _scopes(plain).isdisjoint({"embed", "head"})
    assert scoped.as_text() == plain.as_text()
