"""Random weights from the seed, made by the benchmark and not by the
program, so that the reference can make the same ones again.

Each leaf is named by its path in the parameter tree (``seg_0/sub_1/
inner/w_up``) and drawn from its own key, ``fold_in(key(seed), crc32
(path))``, with the distribution ``LM.init`` gives a leaf of that name:
normal(0.02) tables, truncated normals scaled by 1/sqrt(fan-in) for
matrices (fan-in is the second-to-last axis, also for a stack of
layers), ones for norm scales, zeros for biases.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import numpy as np

ONES = ("scale", "norm_scale", "q_norm", "k_norm")
ZEROS = ("bias", "bq", "bk", "bv", "b_up", "b_down")
TABLES = ("embed", "head", "pos_embed")


def seed32(seed: int) -> int:
    """A 31-bit key seed from any whole number (the benchmark's seeds
    exceed what a signed 32-bit key takes)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) & 0x7FFFFFFF


def leaf(key, path: str, shape: Tuple[int, ...], dtype):
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if name in ONES:
        return jnp.ones(shape, dtype)
    if name in ZEROS:
        return jnp.zeros(shape, dtype)
    if name in TABLES:
        return (jax.random.normal(k, shape) * 0.02).astype(dtype)
    fan_in = shape[-2]
    return (jax.random.truncated_normal(k, -2.0, 2.0, shape)
            * (1.0 / fan_in) ** 0.5).astype(dtype)


def make(seed: int, shapes: Dict[str, Tuple[int, ...]], dtype):
    """``{path: array}`` on the default device, in one jitted call."""
    import jax

    def build(key):
        return {p: leaf(key, p, s, dtype) for p, s in shapes.items()}

    return jax.jit(build)(jax.random.PRNGKey(seed32(seed)))


def flatten_paths(tree) -> Dict[str, object]:
    """``{path: leaf}`` of a nested dict, paths joined with ``/``."""
    out: Dict[str, object] = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, f"{prefix}/{k}" if prefix else k)
        else:
            out[prefix] = node

    walk(tree, "")
    return out


def unflatten_paths(flat: Dict[str, object]) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return tree
