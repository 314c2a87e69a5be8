"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see Mosaic's tiling and
VMEM rules; the TPU compiler can, for a chip that is only described
(``v5e:2x2``), with nothing attached.  Each case lowers the kernel's
jitted wrapper with ``interpret=False`` for one described chip and
checks that the program holds a TPU custom call.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and test
collection runs in every worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels import schedule as ksched

F32, BF16 = jnp.float32, jnp.bfloat16

# (kernel, widths, dtype): qwen3-1.7b attention (16 q / 8 kv heads,
# d=128), zamba2-2.7b's shared attention (32 heads of 160 from its 5120
# input) and Mamba2 (H=80, P=N=64, one group), xlstm-1.3b mLSTM (H=4,
# P=1024), at a 2048-token sequence
CASES = [
    ("flash", dict(b=1, s=2048, h=32, kh=32, d=160, window=None), BF16),
    ("flash", dict(b=1, s=2048, h=16, kh=8, d=128, window=None), F32),
    ("flash", dict(b=1, s=2048, h=16, kh=8, d=128, window=None), BF16),
    ("flash", dict(b=1, s=2048, h=16, kh=8, d=128, window=512), BF16),
    ("ssm", dict(b=1, l=2048, h=80, p=64, n=64, g=1, chunk=128), F32),
    ("ssm", dict(b=1, l=2048, h=80, p=64, n=64, g=1, chunk=32), BF16),
    ("mlstm", dict(b=1, l=2048, h=4, p=1024, chunk=128), F32),
    ("mlstm", dict(b=1, l=2048, h=4, p=1024, chunk=512), BF16),
    # needs the kernel's raised scoped-VMEM limit
    ("mlstm", dict(b=1, l=2048, h=4, p=1024, chunk=512), F32),
    # qwen3-1.7b's decode projection shapes: q and o, k and v, gate and
    # up, down; 8 slots, its 28 stacked f32 layers
    ("decode", dict(m=8, k=2048, n=2048), F32),
    ("decode", dict(m=8, k=2048, n=1024), F32),
    ("decode", dict(m=8, k=2048, n=6144), F32),
    ("decode", dict(m=8, k=6144, n=2048), F32),
    # zamba2-2.7b's Mamba2 in_proj, K-minor: its first 10240 of 10448
    # columns
    ("decode", dict(m=8, k=2560, n=10448, cols=10240), F32),
    # zamba2-2.7b's MLP gate and its in_proj at its spec's precision:
    # three bf16 passes
    ("decode", dict(m=8, k=2560, n=10240, passes=3), F32),
    ("decode", dict(m=8, k=2560, n=10448, cols=10240, passes=3), F32),
    # qwen3-1.7b's decode attention: 8 slots, 16 query heads over 8 K/V
    # heads of 128, its 28 stacked layers of 1281 positions, whose last
    # block runs past the stack
    ("attend", dict(b=8, h=16, kh=8, d=128, t=1281), F32),
    ("attend", dict(b=8, h=16, kh=8, d=128, t=1281), BF16),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep such entries out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _lower(kernel, w, dtype, chip):
    def arg(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    if kernel == "flash":
        b, s, h, kh, d = w["b"], w["s"], w["h"], w["kh"], w["d"]
        fn = jax.jit(lambda q, k, v: ops._flash_attention_impl(
            q, k, v, causal=True, window=w["window"], scale=None,
            block_q=128, block_kv=128, interpret=False))
        return fn.lower(arg((b, s, h, d)), arg((b, s, kh, d)), arg((b, s, kh, d)))
    if kernel == "decode":
        sched = ksched.default_schedule("decode_matmul")
        k_minor = "cols" in w
        fn = jax.jit(lambda x, stack, layer: ops.decode_matmul_stacked(
            x, stack, layer, block_k=sched.block_k, block_n=sched.block_n,
            n=w.get("cols"), k_minor=k_minor, passes=w.get("passes", 1)))
        stack = (28, w["n"], w["k"]) if k_minor else (28, w["k"], w["n"])
        return fn.lower(arg((w["m"], w["k"])), arg(stack), arg((), jnp.int32))
    if kernel == "attend":
        block = ksched.default_schedule("decode_attention").block_kv
        fn = jax.jit(lambda q, k, v, layer, lengths: ops.decode_attention_stacked(
            q, k, v, layer, lengths, scale=w["d"] ** -0.5, block_t=block))
        stack = arg((28, w["b"], w["t"], w["kh"], w["d"]))
        return fn.lower(arg((w["b"], w["h"], w["d"]), F32), stack, stack,
                        arg((), jnp.int32), arg((w["b"],), jnp.int32))
    if kernel == "ssm":
        b, l, h, p, n, g = w["b"], w["l"], w["h"], w["p"], w["n"], w["g"]
        fn = jax.jit(lambda *a: ops._ssm_scan_impl(
            *a, chunk=w["chunk"], interpret=False))
        return fn.lower(arg((b, l, h, p)), arg((b, l, h), F32), arg((h,), F32),
                        arg((b, l, g, n)), arg((b, l, g, n)))
    b, l, h, p = w["b"], w["l"], w["h"], w["p"]
    fn = jax.jit(lambda *a: ops._mlstm_scan_impl(
        *a, chunk=w["chunk"], interpret=False))
    return fn.lower(arg((b, l, h, p)), arg((b, l, h, p)), arg((b, l, h, p)),
                    arg((b, l, h), F32), arg((b, l, h), F32))


@pytest.mark.parametrize("kernel,widths,dtype", CASES,
                         ids=[f"{k}-{jnp.dtype(d).name}-{i}"
                              for i, (k, _, d) in enumerate(CASES)])
def test_kernel_compiles_for_v5e(kernel, widths, dtype, one_chip,
                                 no_persistent_cache):
    compiled = _lower(kernel, widths, dtype, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


# the decode step's temp bytes when the layer scan sliced the f32
# stacks itself: the seven bf16 stacks of qwen3-1.7b's projections
HOISTED_TEMP_BYTES = 2_819_152_896


def test_qwen3_decode_reads_f32_stacks_without_whole_stack_converts(
        one_chip, no_persistent_cache):
    """``LM.decode`` of qwen3-1.7b at its published widths (f32 weights
    and cache, 8 slots of 1281) compiled for a v5e as the serving engine
    compiles it, the cache donated: no weight stack is rounded to bf16
    outside the layer loop, and the temp bytes that rounding needed are
    gone; the cache is updated in place (aliased whole, and no K/V stack
    copied); attention reads the stack through its kernel, with no layer's
    whole K/V sliced or copied out of it."""
    import re

    from repro.configs import get_arch
    from repro.models.lm import LM
    from repro.nn.types import split

    model = LM(get_arch("qwen3-1.7b").spec_fn())
    params = jax.eval_shape(lambda: split(model.init(jax.random.PRNGKey(0)))[0])
    cache = jax.eval_shape(
        lambda p: model.init_cache(p, 8, 1281, dtype=F32), params)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    compiled = jax.jit(model.decode, donate_argnums=1).lower(
        place(params), place(cache),
        jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert not re.findall(r"= bf16\[28,[^\]]*\]\S* convert\(", text)
    assert text.count("tpu_custom_call") >= 8
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= HOISTED_TEMP_BYTES - 2_500_000_000, memory
    assert memory.alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(cache))
    assert not re.findall(r"= f32\[28,8,1281,8,128\]\S* copy\(", text)
    assert not re.findall(r"= \w+\[(1,)?8,1281,8,128\]\S* (dynamic-slice|copy|fusion)\(", text)


def test_zamba2_decode_fits_one_chip_without_whole_stack_converts(
        one_chip, no_persistent_cache):
    """``LM.decode`` of zamba2-2.7b at its published widths (f32 weights
    and cache, 8 slots of 513) compiled for a v5e: no weight stack is
    rounded to bf16 outside the layer loops (every Mamba2 stack, hybrid
    layer and shared block reads its f32 projections through the
    kernel), the kernel reads in_proj's stacks K-minor as a view (no
    f32 transpose or copy of them), and the program with its arguments
    and outputs fits the chip's 16 GiB.  Compiled as the serving engine
    compiles it, the cache donated: each invocation's K/V, which the TPU
    lays out with the positions minor, is updated in place (no copy of
    it), and the cache is held once."""
    import re

    from repro.configs import get_arch
    from repro.models.lm import LM
    from repro.nn.types import split

    model = LM(get_arch("zamba2-2.7b").spec_fn())
    params = jax.eval_shape(lambda: split(model.init(jax.random.PRNGKey(0)))[0])
    cache = jax.eval_shape(
        lambda p: model.init_cache(p, 8, 513, dtype=F32), params)
    place = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    compiled = jax.jit(model.decode, donate_argnums=1).lower(
        place(params), place(cache),
        jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert not re.findall(r"= bf16\[\d+,(2560,10448|5120,2560)\][^ ]* convert\(", text)
    assert not re.findall(r"= f32\[\d+,(2560,10448|10448,2560)\][^ ]* (transpose|copy)\(", text)
    assert text.count("tpu_custom_call") >= 2 * 10 + 9 * (5 + 2 + 3)
    assert not re.findall(r"= f32\[1,8,513,32,160\]\S* copy\(", text)
    memory = compiled.memory_analysis()
    assert memory.peak_memory_in_bytes <= 16 * 2**30
    # of the outputs only the logits (1 MB) are not the donated cache,
    # which the device pads (2.82 GB of 2.11): the cache is held once
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes < 2**21, memory
    assert memory.peak_memory_in_bytes < memory.argument_size_in_bytes + 2**30, memory
