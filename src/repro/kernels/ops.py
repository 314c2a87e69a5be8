"""Jit-ready wrappers around the Pallas kernels.

Handle layout transposes (model layout (B, L, H, ·) to the kernels'
head-major layout), sequence padding to block multiples, and
interpret-mode selection.  On a TPU the kernels always compile: neither
``REPRO_PALLAS_INTERPRET`` nor a schedule's ``interpret`` field can put
them in the interpreter there.  Elsewhere the interpreter is the only
way to run them (how CPU hosts validate the kernels), and those two
knobs may override it.

Each public op is a plain-Python *resolver* over an inner jitted impl:
schedule resolution, shape clamping, and call recording all happen
outside jit, at trace time, so an active :func:`~repro.kernels.schedule
.use_schedules` context is read fresh on every trace (a contextvar read
inside a jitted body would be baked into the first trace and silently
reused) and the *effective* — clamped — block sizes are observable by
callers that key caches on them.  Resolution precedence:

  explicit ``schedule=``  >  active ``use_schedules`` context
      >  legacy block/chunk kwargs  >  the named ``default`` schedule.

Legacy kwargs stay deliberately unvalidated: call sites derive them from
shapes (e.g. a decrement-clamped chunk) and predate the legal-range
rules.  The context outranks them so a generator can retarget kernels
that a model's layers configured with their own constants.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.envvars import read_env
from repro.kernels import schedule as ksched
from repro.kernels.decode_attention import decode_attention_stacked
from repro.kernels.decode_matmul import decode_matmul_stacked
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.mlstm_scan import mlstm_scan_bhlp
from repro.kernels.schedule import KernelSchedule
from repro.kernels.ssm_scan import ssm_scan_bhlp


def _platform() -> str:
    """Platform the call being traced will run on: a ``jax.default_device``
    in force (a device or a platform name) wins over the default backend."""
    device = jax.config.jax_default_device
    if device is None:
        return jax.default_backend()
    return device if isinstance(device, str) else device.platform


def _interpret(requested, platform=None) -> bool:
    if (platform or _platform()) == "tpu":
        return False
    if requested is not None:
        return bool(requested)
    # REPRO_PALLAS_INTERPRET is declared in repro.envvars (the shared
    # REPRO_* registry)
    return read_env("REPRO_PALLAS_INTERPRET", True)


def _pad_seq(x, block, axis):
    s = x.shape[axis]
    pad = (-s) % block
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def _resolve(kernel, schedule, legacy):
    """Apply the precedence in the module docstring; returns a fully
    populated (every size field set) KernelSchedule."""
    if schedule is not None:
        return ksched.as_schedule(kernel, schedule)
    active = ksched.active_schedule(kernel)
    if active is not None:
        return active
    legacy = {k: v for k, v in legacy.items() if v is not None}
    if legacy:
        # call-site kwargs: unvalidated by design (shape-derived values)
        return KernelSchedule(**legacy).merged_over(
            ksched.default_schedule(kernel))
    return ksched.default_schedule(kernel)


def _finish(requested, effective, platform=None):
    """Pin the interpret decision into the effective schedule so the
    recorded metadata says how the kernel actually ran."""
    return dataclasses.replace(
        effective, interpret=_interpret(requested.interpret, platform))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_kv", "interpret"))
def _flash_attention_impl(q, k, v, *, causal, window, scale,
                          block_q, block_kv, interpret):
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    s0 = qT.shape[2]
    qT, _ = _pad_seq(qT, block_q, 2)
    kT, _ = _pad_seq(kT, block_kv, 2)
    vT, _ = _pad_seq(vT, block_kv, 2)
    # padded kv columns must be masked: rely on causal/window for tail; for
    # non-causal pads, mask via window=None + explicit kv validity
    out = flash_attention_bhsd(
        qT, kT, vT, causal=causal, window=window, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
    return out[:, :, :s0].transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q=None, block_kv=None, schedule=None):
    """q: (B, S, H, D); k/v: (B, T, KH, D)  [model layout] -> (B, S, H, D)."""
    requested = _resolve("flash_attention", schedule,
                         {"block_q": block_q, "block_kv": block_kv})
    s0, t0 = q.shape[1], k.shape[1]
    eff = _finish(requested, ksched.effective_schedule(
        "flash_attention", requested, seq_len=s0, kv_len=t0))
    ksched.note_kernel_call(
        "flash_attention", requested, eff,
        shapes={"q": q.shape, "k": k.shape, "v": v.shape},
        meta={"causal": causal, "window": window, "scale": scale,
              "dtype": str(q.dtype)})
    return _flash_attention_impl(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=eff.block_q, block_kv=eff.block_kv, interpret=eff.interpret)


# ---------------------------------------------------------------------------
# scan kernels
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssm_scan_impl(x, dt, a, b_grouped, c_grouped, *, chunk, interpret):
    y, state = ssm_scan_bhlp(
        x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), a,
        b_grouped.transpose(0, 2, 1, 3), c_grouped.transpose(0, 2, 1, 3),
        chunk=chunk, interpret=interpret)
    return y.transpose(0, 2, 1, 3), state


def ssm_scan(x, dt, a, b_grouped, c_grouped, *, chunk=None, schedule=None):
    """Mamba2 SSD scan.  x: (B,L,H,P); dt: (B,L,H); a: (H,);
    b/c: (B,L,G,N) group layout (the kernel indexes each head's group).
    Returns (y, state)."""
    requested = _resolve("ssm_scan", schedule, {"chunk": chunk})
    eff = _finish(requested, ksched.effective_schedule(
        "ssm_scan", requested, seq_len=x.shape[1]))
    ksched.note_kernel_call(
        "ssm_scan", requested, eff,
        shapes={"x": x.shape, "dt": dt.shape, "a": a.shape,
                "b": b_grouped.shape, "c": c_grouped.shape},
        meta={"dtype": str(x.dtype)})
    return _ssm_scan_impl(x, dt, a, b_grouped, c_grouped,
                          chunk=eff.chunk, interpret=eff.interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _mlstm_scan_impl(q, k, v, i_log, f_log, *, chunk, interpret):
    seq = [t.transpose(0, 2, 1, 3) for t in (q, k, v)]
    gates = [g.transpose(0, 2, 1) for g in (i_log, f_log)]
    h = mlstm_scan_bhlp(*seq, *gates, chunk=chunk, interpret=interpret)
    return h.transpose(0, 2, 1, 3)


def mlstm_scan(q, k, v, i_log, f_log, *, chunk=None, schedule=None):
    """Chunkwise mLSTM.  All (B,L,H,P) / (B,L,H).  Returns (h, None)."""
    requested = _resolve("mlstm_scan", schedule, {"chunk": chunk})
    eff = _finish(requested, ksched.effective_schedule(
        "mlstm_scan", requested, seq_len=q.shape[1]))
    ksched.note_kernel_call(
        "mlstm_scan", requested, eff,
        shapes={"q": q.shape, "k": k.shape, "v": v.shape,
                "i_log": i_log.shape, "f_log": f_log.shape},
        meta={"dtype": str(q.dtype)})
    h = _mlstm_scan_impl(q, k, v, i_log, f_log,
                         chunk=eff.chunk, interpret=eff.interpret)
    return h, None


# ---------------------------------------------------------------------------
# decode matmul
# ---------------------------------------------------------------------------

def decode_matmul(x, w, layer, *, n=None, k_minor=False, passes=1, schedule=None,
                  platform=None):
    """``x @ bf16(w[layer][:, :n])``, or ``x @ bf16(w[layer][:n]).T`` with
    ``k_minor``; ``passes=3`` gives the bf16_3x product (XLA's ``HIGH``).

    x: (M, K); w: (L, K, N) float32, or (L, N, K) with ``k_minor``; K
    and ``n`` (all of N by default) multiples of 128; layer: int32
    scalar.  Returns (M, n) float32.  ``platform`` names the platform
    the call is lowered for where the caller knows it (a branch of
    ``lax.platform_dependent``); by default, the platform being traced
    for."""
    kdim, ndim = (w.shape[2], w.shape[1]) if k_minor else (w.shape[1], w.shape[2])
    n = ndim if n is None else n
    requested = _resolve("decode_matmul", schedule, {})
    eff = _finish(requested, ksched.effective_schedule(
        "decode_matmul", requested, seq_len=kdim, kv_len=n),
        platform)
    shapes, meta = {"x": x.shape, "w": w.shape}, {"dtype": str(w.dtype)}
    if n != ndim or k_minor:
        shapes["out"] = (x.shape[0], n)
        meta["k_minor"] = k_minor
    if passes != 1:
        meta["passes"] = passes
    ksched.note_kernel_call("decode_matmul", requested, eff, shapes=shapes, meta=meta)
    return decode_matmul_stacked(x, w, layer, block_k=eff.block_k, block_n=eff.block_n,
                                 n=n, k_minor=k_minor, passes=passes,
                                 interpret=eff.interpret)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, layer, lengths, *, scale, schedule=None, platform=None):
    """One token's attention over positions ``[0, lengths[b])`` of layer
    ``layer`` of a stacked K/V cache, reading only the blocks of
    ``block_kv`` positions that hold them.

    q: (B, H, Dh); k, v: (L, B, T, K, Dh), K dividing H; layer: int32
    scalar; lengths: (B,) int32 in ``[1, T]``.  Returns (B, H, Dh)
    float32, from bf16 operands.  ``platform`` as for
    :func:`decode_matmul`."""
    requested = _resolve("decode_attention", schedule, {})
    eff = _finish(requested, ksched.effective_schedule(
        "decode_attention", requested, seq_len=k.shape[2]), platform)
    ksched.note_kernel_call("decode_attention", requested, eff,
                            shapes={"q": q.shape, "k": k.shape},
                            meta={"dtype": str(k.dtype)})
    return decode_attention_stacked(q, k, v, layer, lengths, scale=scale,
                                    block_t=eff.block_kv, interpret=eff.interpret)
