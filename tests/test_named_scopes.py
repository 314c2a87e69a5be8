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


# what the compiled program carries outside every scope, apart from its
# inputs: the scans' bookkeeping (slicing and updating the stacked
# params and caches, the loop counter), which qwen3 has too
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([\w\-]+)\(.*?op_name=\"([^\"]*)\"", re.M)
_INPUTS = {"parameter", "bitcast", "get-tuple-element", "constant"}
_SCOPES = {"attention", "cross_attention", "mlp", "moe", "mamba2", "mlstm", "slstm",
           "embed", "head"}


def _unscoped(arch, entry):
    """``{(opcode, op_name)}`` of the compiled program's ops in no scope
    (the attribution ``bench/lib/program_trace.py`` makes), digits
    dropped."""
    text = _lowered(get_arch(arch).smoke_spec_fn(), entry).compile().as_text()
    return {(op, re.sub(r"\d+", "", name)) for op, name in _INSTRUCTION.findall(text)
            if op not in _INPUTS and not set(re.split(r"[/;]", name)) & _SCOPES}


@pytest.mark.parametrize("entry", ["decode", "prefill"])
def test_no_op_of_a_hybrid_layer_lands_in_other(entry):
    """zamba2's hybrid layers (the concat, the shared block's norms,
    attention, MLP and adapter, the invocation's linear, the Mamba2
    layer with its input) leave nothing outside the sub-block scopes
    that qwen3's program does not leave there too, apart from more of
    the same bookkeeping: the stacked output buffers of more layer scans
    (``broadcast_in_dim`` at the top), and the layout copies the
    compiler makes of a weight in a layer's body (``closed_call``) or as
    a one-layer segment's weight is taken off its stack (``squeeze``)."""
    top = f"jit({entry})"
    layout = {(op, f"{top}/{where}") for op in ("copy", "transpose", "fusion")
              for where in ("while/body/closed_call", "squeeze")}
    bookkeeping = layout | {("broadcast", f"{top}/broadcast_in_dim"),
                            ("fusion", f"{top}/broadcast_in_dim"),
                            # the adders of a reduction and of a cumsum
                            ("add", "reduce_sum"), ("add", "reduce_window_sum")}
    extra = _unscoped("zamba2-2.7b", entry) - _unscoped("qwen3-1.7b", entry)
    assert extra <= bookkeeping, extra - bookkeeping
