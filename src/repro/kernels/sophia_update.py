"""Fused optimizer-step Pallas TPU kernels (flat-shard granularity).

Why kernels: the optimizer update is element-wise over every parameter —
pure HBM-bandwidth work.  Unfused, XLA materializes m', raw-update, clipped
update, decayed params as separate buffers: ~6 reads + ~4 writes per element.
Each fused kernel reads its operands once and writes its outputs once — the
bandwidth floor — and streams VMEM blocks of 128k elements (512 KiB fp32
per operand; 4 in + 2 out = 3 MiB live, well under the ~16 MiB v5e VMEM
budget).  Blocks are 1-D and lane-aligned (128k = 1024 x 128).

The engine (core/engine.py) calls these on whole dtype-homogeneous flat
shards whose length is a multiple of ``block`` (tail-padded once at init),
so one ``pallas_call`` grid sweep covers the entire parameter set.  All
kernels compute in fp32 and preserve input dtypes on write, so bf16
optimizer state (``state_dtype="bfloat16"`` at 400B scale) streams half the
bytes without a separate cast pass.

TPU layout rules the specs follow: scalar operands (lr, refresh flag, GNB
scale, bias corrections) ride in SMEM as one small fp32 vector, and the
per-block clip count is emitted as a lane-dense ``(1, 1, 128)`` int32 block
(a rank-1 block of size 1 is not a legal TPU tiling); the wrappers return it
as ``(grid,)``.  ``interpret=None`` resolves from the backend (interpret off
a TPU, native compilation on one).

Validated under ``interpret=True`` on CPU against kernels/ref.py across a
shape x dtype sweep (tests/test_kernels.py, tests/test_engine.py) and
compiled for a described v5e chip in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_ce import _interpret_default

BLOCK = 128 * 1024  # elements per VMEM block (fp32: 512 KiB per operand)

_f32 = jnp.float32

#: the sweep kernels' names in a compiled program (the Mosaic module's
#: ``sym_name``, which a profile reader matches); pinned here so that
#: renaming a kernel function does not rename its ops
KERNEL_NAMES = (
    "_sophia_kernel", "_hess_ema_kernel", "_sophia_refresh_kernel",
    "_adahessian_refresh_kernel", "_adamw_kernel", "_adahessian_kernel",
    "_lion_kernel", "_signgd_kernel", "_sgd_kernel")


def _grid_spec(block):
    return pl.BlockSpec((block,), lambda i: (i,))


# clip counts: one lane-dense (1, 1, 128) int32 block per grid step, the
# count broadcast across its lanes (lane 0 is read back)
_NCLIP_LANES = 128


def _write_nclip(nclip_out, raw, clip_threshold):
    n = jnp.sum((jnp.abs(raw) >= clip_threshold).astype(jnp.int32))
    nclip_out[...] = jnp.full(nclip_out.shape, n, jnp.int32)


def _sweep(kern, name, scalars, vecs, out_dtypes, *, block, nclip=False,
           interpret=None):
    """One grid sweep over flat shards (length % block == 0) of the kernel
    ``kern``, named ``name`` (one of ``KERNEL_NAMES``): ``scalars`` (fp32,
    SMEM) first, then the streamed ``vecs``.  Returns flat outputs of
    ``out_dtypes`` and, with ``nclip``, the per-block clip counts
    ``(grid,)`` int32."""
    assert name in KERNEL_NAMES, name
    n = vecs[0].shape[0]
    grid = n // block
    spec = _grid_spec(block)
    out_specs = [spec] * len(out_dtypes)
    out_shape = [jax.ShapeDtypeStruct((n,), dt) for dt in out_dtypes]
    if nclip:
        out_specs.append(pl.BlockSpec((1, 1, _NCLIP_LANES),
                                      lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((grid, 1, _NCLIP_LANES),
                                              jnp.int32))
    outs = pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [spec] * len(vecs),
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret_default() if interpret is None else interpret,
        name=name,
    )(jnp.stack([jnp.asarray(x, _f32) for x in scalars]), *vecs)
    if nclip:
        outs = list(outs[:-1]) + [outs[-1][:, 0, 0]]
    return outs


def _bias_corrections(beta1, beta2, step):
    step = jnp.asarray(step, _f32)
    return 1.0 - beta1 ** step, 1.0 - beta2 ** step


# ---------------------------------------------------------------------------
# Sophia (Algorithm 3 lines 6, 12, 13) + Hessian EMA (line 9)


def _sophia_kernel(sc_ref, p_ref, m_ref, h_ref, g_ref,
                   p_out, m_out, nclip_out, *,
                   beta1, gamma, eps, weight_decay, clip_threshold):
    lr = sc_ref[0]
    m = beta1 * m_ref[...].astype(_f32) + (1.0 - beta1) * g_ref[...].astype(_f32)
    raw = m / jnp.maximum(gamma * h_ref[...].astype(_f32), eps)
    u = jnp.clip(raw, -clip_threshold, clip_threshold)
    p_out[...] = (p_ref[...].astype(_f32) * (1.0 - lr * weight_decay)
                  - lr * u).astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    _write_nclip(nclip_out, raw, clip_threshold)


def sophia_fused_block(p, m, h, g, lr, *, beta1, gamma, eps, weight_decay,
                       clip_threshold=1.0, block=BLOCK, interpret=None):
    """Run the fused step on flat arrays (length % block == 0).

    Dtypes are preserved: p' matches p, m' matches m (compute is fp32).
    Returns (p', m', nclip per block)."""
    kern = functools.partial(
        _sophia_kernel, beta1=beta1, gamma=gamma, eps=eps,
        weight_decay=weight_decay, clip_threshold=clip_threshold)
    return _sweep(kern, "_sophia_kernel", (lr,), (p, m, h, g),
                  (p.dtype, m.dtype), block=block, nclip=True,
                  interpret=interpret)


def _hess_ema_kernel(sc_ref, h_ref, e_ref, h_out, *, beta2, square):
    e = sc_ref[0] * e_ref[...].astype(_f32)
    if square:
        e = e * e
    h_out[...] = (beta2 * h_ref[...].astype(_f32)
                  + (1.0 - beta2) * e).astype(h_out.dtype)


def hessian_ema_block(h, est, *, beta2, scale=1.0, square=False, block=BLOCK,
                      interpret=None):
    """h' = beta2 h + (1-beta2) * scale * est on a flat array.

    ``scale`` folds the GNB batch factor B in (Algorithm 2 line 6) so the
    squared-gradient estimate never materializes separately; it is a traced
    scalar (B depends on the step's valid-token mask).  ``square=True`` is
    the AdaHessian refresh: h' = b2 h + (1-b2) (scale * est)^2.
    """
    kern = functools.partial(_hess_ema_kernel, beta2=beta2, square=square)
    (h2,) = _sweep(kern, "_hess_ema_kernel", (scale,), (h, est), (h.dtype,),
                   block=block, interpret=interpret)
    return h2


def _sophia_refresh_kernel(sc_ref, p_ref, m_ref, h_ref, g_ref, e_ref,
                           p_out, m_out, h_out, nclip_out, *,
                           beta1, beta2, gamma, eps, weight_decay,
                           clip_threshold):
    lr, flag, scale = sc_ref[0], sc_ref[1], sc_ref[2]
    h32 = h_ref[...].astype(_f32)
    h_upd = beta2 * h32 + (1.0 - beta2) * (scale * e_ref[...].astype(_f32))
    # storage-dtype roundtrip before the update reads h: the two-pass path
    # (hessian_ema_block writes h, sophia_fused_block re-reads it) rounds
    # through h's dtype, and the fused sweep must be bit-compatible with it
    h_new = jnp.where(flag > 0.5, h_upd, h32).astype(h_out.dtype)
    h_out[...] = h_new
    m = beta1 * m_ref[...].astype(_f32) + (1.0 - beta1) * g_ref[...].astype(_f32)
    raw = m / jnp.maximum(gamma * h_new.astype(_f32), eps)
    u = jnp.clip(raw, -clip_threshold, clip_threshold)
    p_out[...] = (p_ref[...].astype(_f32) * (1.0 - lr * weight_decay)
                  - lr * u).astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    _write_nclip(nclip_out, raw, clip_threshold)


def sophia_refresh_fused_block(p, m, h, g, e, lr, flag, scale, *,
                               beta1, beta2, gamma, eps, weight_decay,
                               clip_threshold=1.0, block=BLOCK,
                               interpret=None):
    """One grid sweep fusing the Hessian-EMA refresh into the Sophia step.

    ``flag`` (traced 0/1) selects whether h absorbs ``scale * e`` before the
    update reads it — h streams through VMEM exactly once either way, which
    is what makes the unified train step's refresh branch free of a second
    h read/write pass.  ``scale`` is the GNB batch factor B (traced).

    Returns (p', m', h', nclip per block)."""
    kern = functools.partial(
        _sophia_refresh_kernel, beta1=beta1, beta2=beta2, gamma=gamma,
        eps=eps, weight_decay=weight_decay, clip_threshold=clip_threshold)
    return _sweep(kern, "_sophia_refresh_kernel", (lr, flag, scale),
                  (p, m, h, g, e), (p.dtype, m.dtype, h.dtype), block=block,
                  nclip=True, interpret=interpret)


def _adahessian_refresh_kernel(sc_ref, p_ref, m_ref, v_ref, g_ref, e_ref,
                               p_out, m_out, v_out, *,
                               beta1, beta2, eps, weight_decay):
    lr, flag, scale = sc_ref[0], sc_ref[1], sc_ref[2]
    bc1, bc2 = sc_ref[3], sc_ref[4]
    v32 = v_ref[...].astype(_f32)
    es = scale * e_ref[...].astype(_f32)
    v_upd = beta2 * v32 + (1.0 - beta2) * es * es
    v_new = jnp.where(flag > 0.5, v_upd, v32).astype(v_out.dtype)
    v_out[...] = v_new
    m = beta1 * m_ref[...].astype(_f32) + (1.0 - beta1) * g_ref[...].astype(_f32)
    u = (m / bc1) / (jnp.sqrt(v_new.astype(_f32) / bc2) + eps)
    p_out[...] = (p_ref[...].astype(_f32) * (1.0 - lr * weight_decay)
                  - lr * u).astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)


def adahessian_refresh_fused_block(p, m, v, g, e, lr, flag, scale, step, *,
                                   beta1, beta2, eps, weight_decay,
                                   block=BLOCK, interpret=None):
    """AdaHessian step with the squared-estimate EMA fused in (flag-gated),
    the refresh analogue of :func:`sophia_refresh_fused_block`."""
    bc1, bc2 = _bias_corrections(beta1, beta2, step)
    kern = functools.partial(_adahessian_refresh_kernel, beta1=beta1,
                             beta2=beta2, eps=eps, weight_decay=weight_decay)
    return _sweep(kern, "_adahessian_refresh_kernel",
                  (lr, flag, scale, bc1, bc2), (p, m, v, g, e),
                  (p.dtype, m.dtype, v.dtype), block=block,
                  interpret=interpret)


# ---------------------------------------------------------------------------
# Baselines (the paper's Table 1 comparison runs through identical machinery)


def _adamw_kernel(sc_ref, p_ref, m_ref, v_ref, g_ref, p_out, m_out, v_out, *,
                  beta1, beta2, eps, weight_decay):
    lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2]
    g = g_ref[...].astype(_f32)
    m = beta1 * m_ref[...].astype(_f32) + (1.0 - beta1) * g
    v = beta2 * v_ref[...].astype(_f32) + (1.0 - beta2) * g * g
    u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    p_out[...] = (p_ref[...].astype(_f32) * (1.0 - lr * weight_decay)
                  - lr * u).astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)


def adamw_fused_block(p, m, v, g, lr, step, *, beta1, beta2, eps,
                      weight_decay, block=BLOCK, interpret=None):
    """Fused AdamW on flat arrays (baseline parity for Table 1)."""
    bc1, bc2 = _bias_corrections(beta1, beta2, step)
    kern = functools.partial(_adamw_kernel, beta1=beta1, beta2=beta2,
                             eps=eps, weight_decay=weight_decay)
    return _sweep(kern, "_adamw_kernel", (lr, bc1, bc2), (p, m, v, g),
                  (p.dtype, m.dtype, v.dtype), block=block,
                  interpret=interpret)


def _adahessian_kernel(sc_ref, p_ref, m_ref, v_ref, g_ref, p_out, m_out, *,
                       beta1, beta2, eps, weight_decay):
    lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2]
    m = beta1 * m_ref[...].astype(_f32) + (1.0 - beta1) * g_ref[...].astype(_f32)
    u = (m / bc1) / (jnp.sqrt(v_ref[...].astype(_f32) / bc2) + eps)
    p_out[...] = (p_ref[...].astype(_f32) * (1.0 - lr * weight_decay)
                  - lr * u).astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)


def adahessian_fused_block(p, m, v, g, lr, step, *, beta1, beta2, eps,
                           weight_decay, block=BLOCK, interpret=None):
    """AdaHessian step: Adam-shaped, v read-only (refreshed out-of-band)."""
    bc1, bc2 = _bias_corrections(beta1, beta2, step)
    kern = functools.partial(_adahessian_kernel, beta1=beta1, beta2=beta2,
                             eps=eps, weight_decay=weight_decay)
    return _sweep(kern, "_adahessian_kernel", (lr, bc1, bc2), (p, m, v, g),
                  (p.dtype, m.dtype), block=block, interpret=interpret)


def _lion_kernel(sc_ref, p_ref, m_ref, g_ref, p_out, m_out, *,
                 beta1, beta2, weight_decay):
    lr = sc_ref[0]
    g = g_ref[...].astype(_f32)
    m = m_ref[...].astype(_f32)
    u = jnp.sign(beta1 * m + (1.0 - beta1) * g)
    p_out[...] = (p_ref[...].astype(_f32) * (1.0 - lr * weight_decay)
                  - lr * u).astype(p_out.dtype)
    m_out[...] = (beta2 * m + (1.0 - beta2) * g).astype(m_out.dtype)


def lion_fused_block(p, m, g, lr, *, beta1, beta2, weight_decay, block=BLOCK,
                     interpret=None):
    kern = functools.partial(_lion_kernel, beta1=beta1, beta2=beta2,
                             weight_decay=weight_decay)
    return _sweep(kern, "_lion_kernel", (lr,), (p, m, g), (p.dtype, m.dtype),
                  block=block, interpret=interpret)


def _signgd_kernel(sc_ref, p_ref, m_ref, g_ref, p_out, m_out, *,
                   beta1, weight_decay):
    lr = sc_ref[0]
    m = beta1 * m_ref[...].astype(_f32) + (1.0 - beta1) * g_ref[...].astype(_f32)
    p_out[...] = (p_ref[...].astype(_f32) * (1.0 - lr * weight_decay)
                  - lr * jnp.sign(m)).astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)


def signgd_fused_block(p, m, g, lr, *, beta1, weight_decay, block=BLOCK,
                       interpret=None):
    kern = functools.partial(_signgd_kernel, beta1=beta1,
                             weight_decay=weight_decay)
    return _sweep(kern, "_signgd_kernel", (lr,), (p, m, g), (p.dtype, m.dtype),
                  block=block, interpret=interpret)


def _sgd_kernel(sc_ref, p_ref, m_ref, g_ref, p_out, m_out, *, momentum):
    lr = sc_ref[0]
    m = momentum * m_ref[...].astype(_f32) + g_ref[...].astype(_f32)
    p_out[...] = (p_ref[...].astype(_f32) - lr * m).astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)


def sgd_fused_block(p, m, g, lr, *, momentum, block=BLOCK, interpret=None):
    kern = functools.partial(_sgd_kernel, momentum=momentum)
    return _sweep(kern, "_sgd_kernel", (lr,), (p, m, g), (p.dtype, m.dtype),
                  block=block, interpret=interpret)
