"""Training-path flash attention: fused forward + custom_vjp backward.

The training hot path used to run ``models/layers.py:full_attention``,
which materializes an fp32 ``(B, Hkv, G, S, S)`` score tensor every layer.
This module replaces it with a production Pallas kernel family that keeps
the online softmax entirely in VMEM:

* **forward** — grid ``(B, H, Sq/BQ, Sk/BK)`` with the KV axis innermost
  ("arbitrary"): VMEM scratch carries the running max/denominator and an
  fp32 accumulator, initialized at ``j == 0`` and flushed at the last KV
  block.  Emits the logsumexp ``(B, H, Sq, 1)`` as a second output — the
  backward residual, carried with a trailing unit axis so its
  ``(block_q, 1)`` blocks meet the TPU tiling rule (last two block dims a
  multiple of (8, 128) or equal to the array's).  GQA maps
  ``h -> h // G`` in the KV BlockSpecs.
* **backward** (``custom_vjp``) — two kernels with the delta/lse recompute
  trick (``delta = rowsum(dO * O)`` precomputed outside): dQ iterates KV
  innermost with an fp32 ``(BQ, hd)`` accumulator; dK/dV iterate
  ``(group, q-block)`` pairs innermost over ``(B, Hkv, Sk/BK)`` so the
  GQA group-sum lands in one fp32 ``(BK, hd)`` scratch — no
  ``(B, H, Sk, hd)`` intermediate.
* **custom_jvp twin** (``use_jvp=True``) — same Pallas forward for the
  primal; the tangent is a chunked fp32 jnp sweep, *linear* in the input
  tangents, so JAX can both push Hutchinson's forward-over-reverse HVP
  through it and transpose it for reverse mode.

Masking covers causal, sliding-window and the gemma2 logit softcap.  The
window rides in as a scalar-prefetch operand (sentinel ``1 << 30`` = no
window) so the *traced* per-layer windows from ``transformer.layer_windows``
work, and — because ``PrefetchScalarGridSpec`` index maps receive the
scalar ref — the ``schedule="skip"`` variant clamps the streamed block
index into the live band: fully-masked ``j > i`` (causal) and
out-of-window grid cells neither DMA fresh tiles nor compute.
``schedule="dense"`` streams every block and relies on masking alone.

Block sizes and the schedule come from ``kernels/autotune.py``
(``get_tuned_attn``) unless given explicitly; ``interpret=None`` resolves
to "not on a real TPU" (the repo convention, ``fused_ce._interpret_default``)
and interpret-mode grids are auto-clamped to <= ``INTERPRET_CELL_CAP``
cells so CPU CI never unrolls huge grids.

Parity: ``kernels/ref.py`` closed-form oracles mirror every fp32 rounding
point (<= 3e-6, tests/test_flash_attention.py); ``KERNEL_CALLS`` counts
``attn_fwd`` / ``attn_bwd_dq`` / ``attn_bwd_dkv`` / ``attn_jvp_rule`` at
trace time so tests can assert nothing silently fell back.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import per_device
from .fused_ce import KERNEL_CALLS, _interpret_default

NEG_INF = -1e30
WINDOW_NONE = 1 << 30          # sentinel window: larger than any context
INTERPRET_CELL_CAP = 64        # max unrolled grid cells under interpret


# ---------------------------------------------------------------------------
# block bands (shared by index maps and kernels; jnp int arithmetic so the
# window may be a traced scalar from the prefetch ref)


#: the kernels' names in a compiled program (the Mosaic module's
#: ``sym_name``, which a profile reader matches); pinned here so that
#: renaming a kernel function does not rename its ops
KERNEL_NAMES = FWD_NAME, DQ_NAME, DKV_NAME = (
    "_fwd_kernel", "_dq_kernel", "_dkv_kernel")


def _kv_band(i, win, *, causal, q_offset, block_q, block_k, n_k):
    """Inclusive [lo, hi] range of KV blocks attended by q-block ``i``."""
    if causal:
        hi = jnp.minimum(n_k - 1,
                         ((i + 1) * block_q - 1 + q_offset) // block_k)
    else:
        hi = n_k - 1
    lo = jnp.maximum(0, (i * block_q + q_offset - win + 1) // block_k)
    return lo, hi


def _q_band(j, win, *, causal, q_offset, block_q, block_k, n_q):
    """Inclusive [lo, hi] range of q blocks attending KV block ``j``."""
    if causal:
        lo = jnp.maximum(0, (j * block_k - q_offset) // block_q)
    else:
        lo = 0
    hi = jnp.minimum(n_q - 1,
                     ((j + 1) * block_k - 2 + win - q_offset) // block_q)
    return lo, hi


def _tile_mask(i, j, win, *, causal, q_offset, block_q, block_k):
    """(BQ, BK) bool attend-mask for grid cell (i, j), global positions."""
    qpos = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    m = kpos > qpos - win
    if causal:
        m = m & (kpos <= qpos)
    return m


def _dotT(a, b):
    """a (M, D) x b (N, D) -> (M, N) fp32 contraction over the last axis."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel


def _fwd_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale, causal, softcap, q_offset, block_q,
                block_k, n_k, schedule):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = _dotT(q, k) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = _tile_mask(i, j, win_ref[0], causal=causal, q_offset=q_offset,
                          block_q=block_q, block_k=block_k)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # where-guard: a fully-masked tile has m_new == NEG_INF and
        # exp(s - m_new) == 1 — the mask zeroes it instead
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    if schedule == "skip":
        lo, hi = _kv_band(i, win_ref[0], causal=causal, q_offset=q_offset,
                          block_q=block_q, block_k=block_k, n_k=n_k)
        pl.when((j >= lo) & (j <= hi))(_step)
    else:
        _step()

    @pl.when(j == n_k - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _forward(q, k, v, win, *, causal, scale, softcap, q_offset, block_q,
             block_k, schedule, interpret):
    """Raw fwd pallas_call -> (o, lse); no autodiff wiring."""
    B, H, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    group = H // Hkv
    n_q, n_k = Sq // block_q, Sk // block_k
    skip = schedule == "skip"

    def kv_index(b, h, i, j, w):
        if skip:
            lo, hi = _kv_band(i, w[0], causal=causal, q_offset=q_offset,
                              block_q=block_q, block_k=block_k, n_k=n_k)
            j = jnp.clip(j, lo, hi)
        return (b, h // group, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, i, j, w: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, i, j, w: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i, j, w: (b, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max
            pltpu.VMEM((block_q, 1), jnp.float32),     # running denominator
            pltpu.VMEM((block_q, hd), jnp.float32),    # output accumulator
        ],
    )
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_k=block_k, n_k=n_k,
        schedule=schedule)
    KERNEL_CALLS["attn_fwd"] += 1
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        interpret=interpret,
        name=FWD_NAME,
    )(win, q, k, v)


# ---------------------------------------------------------------------------
# backward kernels (delta/lse recompute: p = exp(z - lse) per tile, no
# stored probabilities)


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, i, j, win, *,
              scale, causal, softcap, q_offset, block_q, block_k):
    """Shared per-tile recompute: (p, ds, do32) with ds already
    softcap-chained; all fp32."""
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                               # (block_q, 1)
    delta = dl_ref[0, 0]
    s = _dotT(q, k) * scale
    if softcap is not None:
        t = jnp.tanh(s / softcap)
        z, dcap = softcap * t, 1.0 - t * t
    else:
        z, dcap = s, None
    mask = _tile_mask(i, j, win, causal=causal, q_offset=q_offset,
                      block_q=block_q, block_k=block_k)
    p = jnp.where(mask, jnp.exp(z - lse), 0.0)
    ds = p * (_dotT(do, v) - delta)
    if dcap is not None:
        ds = ds * dcap
    return q, k, do, p, ds


def _dq_kernel(win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dq_ref, dq_scr, *, scale, causal, softcap, q_offset, block_q,
               block_k, n_k, schedule):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])

    def _step():
        _, k, _, _, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, i, j, win_ref[0],
            scale=scale, causal=causal, softcap=softcap, q_offset=q_offset,
            block_q=block_q, block_k=block_k)
        dq_scr[...] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    if schedule == "skip":
        lo, hi = _kv_band(i, win_ref[0], causal=causal, q_offset=q_offset,
                          block_q=block_q, block_k=block_k, n_k=n_k)
        pl.when((j >= lo) & (j <= hi))(_step)
    else:
        _step()

    @pl.when(j == n_k - 1)
    def _flush():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, softcap,
                q_offset, block_q, block_k, n_q, n_inner, schedule):
    j, t = pl.program_id(2), pl.program_id(3)
    i = t % n_q                         # q-block; t // n_q is the GQA group

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    def _step():
        q, _, do, p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, i, j, win_ref[0],
            scale=scale, causal=causal, softcap=softcap, q_offset=q_offset,
            block_q=block_q, block_k=block_k)
        dv_scr[...] += _dotT(p.T, do.T)
        dk_scr[...] += _dotT(ds.T, q.T) * scale

    if schedule == "skip":
        lo, hi = _q_band(j, win_ref[0], causal=causal, q_offset=q_offset,
                         block_q=block_q, block_k=block_k, n_q=n_q)
        pl.when((i >= lo) & (i <= hi))(_step)
    else:
        _step()

    @pl.when(t == n_inner - 1)
    def _flush():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _backward(q, k, v, win, do, lse, delta, *, causal, scale, softcap,
              q_offset, block_q, block_k, schedule, interpret):
    B, H, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    group = H // Hkv
    n_q, n_k = Sq // block_q, Sk // block_k
    skip = schedule == "skip"
    band = dict(causal=causal, q_offset=q_offset, block_q=block_q,
                block_k=block_k)

    # --- dQ: grid (B, H, n_q, n_k), KV innermost --------------------------
    def kv_index(b, h, i, j, w):
        if skip:
            lo, hi = _kv_band(i, w[0], n_k=n_k, **band)
            j = jnp.clip(j, lo, hi)
        return (b, h // group, j, 0)

    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda b, h, i, j, w: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, i, j, w: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd), kv_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
    )
    kern = functools.partial(
        _dq_kernel, scale=scale, causal=causal, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_k=block_k, n_k=n_k,
        schedule=schedule)
    KERNEL_CALLS["attn_bwd_dq"] += 1
    dq = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=DQ_NAME,
    )(win, q, k, v, do, lse, delta)

    # --- dK/dV: grid (B, Hkv, n_k, G * n_q), (group, q-block) innermost ---
    n_inner = group * n_q

    def q_index(b, kv, j, t, w):
        i = t % n_q
        if skip:
            lo, hi = _q_band(j, w[0], n_q=n_q, **band)
            i = jnp.clip(i, lo, hi)
        return (b, kv * group + t // n_q, i, 0)

    def row_index(b, kv, j, t, w):
        i = t % n_q
        if skip:
            lo, hi = _q_band(j, w[0], n_q=n_q, **band)
            i = jnp.clip(i, lo, hi)
        return (b, kv * group + t // n_q, i, 0)

    qg_spec = pl.BlockSpec((1, 1, block_q, hd), q_index)
    rowg_spec = pl.BlockSpec((1, 1, block_q, 1), row_index)
    kvb_spec = pl.BlockSpec((1, 1, block_k, hd),
                            lambda b, kv, j, t, w: (b, kv, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, n_k, n_inner),
        in_specs=[qg_spec, kvb_spec, kvb_spec, qg_spec, rowg_spec,
                  rowg_spec],
        out_specs=[kvb_spec, kvb_spec],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
    )
    kern = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_k=block_k, n_q=n_q,
        n_inner=n_inner, schedule=schedule)
    KERNEL_CALLS["attn_bwd_dkv"] += 1
    dk, dv = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
        name=DKV_NAME,
    )(win, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring


def _float0(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


_NONDIFF = (4, 5, 6, 7, 8, 9, 10, 11)
#           causal, scale, softcap, q_offset, block_q, block_k, schedule,
#           interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=_NONDIFF)
def _flash(q, k, v, win, causal, scale, softcap, q_offset, block_q,
           block_k, schedule, interpret):
    o, _ = _forward(q, k, v, win, causal=causal, scale=scale,
                    softcap=softcap, q_offset=q_offset, block_q=block_q,
                    block_k=block_k, schedule=schedule, interpret=interpret)
    return o


def _flash_fwd(q, k, v, win, causal, scale, softcap, q_offset, block_q,
               block_k, schedule, interpret):
    o, lse = _forward(q, k, v, win, causal=causal, scale=scale,
                      softcap=softcap, q_offset=q_offset, block_q=block_q,
                      block_k=block_k, schedule=schedule,
                      interpret=interpret)
    return o, (q, k, v, win, o, lse)


def _flash_bwd(causal, scale, softcap, q_offset, block_q, block_k, schedule,
               interpret, res, g):
    q, k, v, win, o, lse = res
    delta = (g.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        -1, keepdims=True)
    dq, dk, dv = _backward(
        q, k, v, win, g, lse, delta, causal=causal, scale=scale,
        softcap=softcap, q_offset=q_offset, block_q=block_q,
        block_k=block_k, schedule=schedule, interpret=interpret)
    return dq, dk, dv, _float0(win)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# custom_jvp twin (Hutchinson's forward-over-reverse HVP route)


def _chunk_len(S: int, cap: int = 512) -> int:
    c = min(S, cap)
    while S % c:
        c -= 1
    return c


@functools.partial(jax.custom_jvp, nondiff_argnums=_NONDIFF)
def _flash_jvp(q, k, v, win, causal, scale, softcap, q_offset, block_q,
               block_k, schedule, interpret):
    o, _ = _forward(q, k, v, win, causal=causal, scale=scale,
                    softcap=softcap, q_offset=q_offset, block_q=block_q,
                    block_k=block_k, schedule=schedule, interpret=interpret)
    return o


@_flash_jvp.defjvp
def _flash_jvp_rule(causal, scale, softcap, q_offset, block_q, block_k,
                    schedule, interpret, primals, tangents):
    """o-tangent of attention, linear in (dq, dk, dv) so JAX can transpose
    it: with row-normalized p and z the (softcapped, scaled) logits,
    ``do = (p * dz) @ v - rowsum(p * dz) * o + p @ dv``."""
    q, k, v, win = primals
    dq, dk, dv, _ = tangents
    KERNEL_CALLS["attn_jvp_rule"] += 1
    # The primal is recomputed below by the checkpointed jnp scan, NOT by
    # re-entering the Pallas forward: inside ``lax.scan`` (the layer loop)
    # linearization inlines the known side of a staged custom_jvp call, so
    # a Pallas primal here would surface as a bare pallas_call to the
    # OUTER jvp of Hutchinson's forward-over-reverse HVP and die in
    # ``_pallas_call_jvp_rule``.  An all-jnp rule stays differentiable at
    # every order; the Pallas forward still serves the undifferentiated
    # ``use_jvp=True`` call (the twin's own body).

    B, H, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    G = H // Hkv
    f32 = jnp.float32
    q32 = q.astype(f32).reshape(B, Hkv, G, Sq, hd)
    dq32 = dq.astype(f32).reshape(B, Hkv, G, Sq, hd)
    k32, v32 = k.astype(f32), v.astype(f32)
    dk32, dv32 = dk.astype(f32), dv.astype(f32)
    win32 = win[0]
    c = _chunk_len(Sk)
    n_c = Sk // c
    qpos = q_offset + jnp.arange(Sq)[:, None]

    def _z(kc, kpos):
        s = jnp.einsum("bkgsh,bkth->bkgst", q32, kc,
                       preferred_element_type=f32) * scale
        if softcap is not None:
            t = jnp.tanh(s / softcap)
            z, dcap = softcap * t, 1.0 - t * t
        else:
            z, dcap = s, None
        mask = kpos[None, :] > qpos - win32
        if causal:
            mask = mask & (kpos[None, :] <= qpos)
        return jnp.where(mask[None, None, None], z, NEG_INF), dcap, mask

    # primal-only online (m, l, acc) over KV chunks — checkpointed scan so
    # the HVP's reverse sweep re-derives rather than stores the chunks
    def body(carry, ci):
        m, l, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k32, ci * c, c, 2)
        vc = jax.lax.dynamic_slice_in_dim(v32, ci * c, c, 2)
        kpos = ci * c + jnp.arange(c)
        z, _, mask = _z(kc, kpos)
        m_new = jnp.maximum(m, z.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask[None, None, None],
                      jnp.exp(z - m_new[..., None]), 0.0)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgst,bkth->bkgsh", p, vc, preferred_element_type=f32)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((B, Hkv, G, Sq), NEG_INF, f32),
            jnp.zeros((B, Hkv, G, Sq), f32),
            jnp.zeros((B, Hkv, G, Sq, hd), f32))
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), init,
                                  jnp.arange(n_c))
    l = jnp.maximum(l, 1e-30)
    lse = m + jnp.log(l)
    o32 = acc / l[..., None]

    # tangent accumulation: an unrolled Python loop (a scan closing over
    # tangents is untransposable), each term linear in (dq, dk, dv)
    u = jnp.zeros((B, Hkv, G, Sq), f32)
    t_pv = jnp.zeros((B, Hkv, G, Sq, hd), f32)
    for ci in range(n_c):
        kc = k32[:, :, ci * c:(ci + 1) * c]
        vc = v32[:, :, ci * c:(ci + 1) * c]
        dkc = dk32[:, :, ci * c:(ci + 1) * c]
        dvc = dv32[:, :, ci * c:(ci + 1) * c]
        kpos = ci * c + jnp.arange(c)
        z, dcap, mask = _z(kc, kpos)
        # where-guard, not bare exp: a fully-masked row has lse == NEG_INF
        p = jnp.where(mask[None, None, None], jnp.exp(z - lse[..., None]),
                      0.0)
        dz = (jnp.einsum("bkgsh,bkth->bkgst", dq32, kc,
                         preferred_element_type=f32)
              + jnp.einsum("bkgsh,bkth->bkgst", q32, dkc,
                           preferred_element_type=f32)) * scale
        if dcap is not None:
            dz = dz * dcap
        pdz = p * dz
        u = u + pdz.sum(-1)
        t_pv = t_pv + jnp.einsum("bkgst,bkth->bkgsh", pdz, vc,
                                 preferred_element_type=f32) \
            + jnp.einsum("bkgst,bkth->bkgsh", p, dvc,
                         preferred_element_type=f32)
    do32 = t_pv - u[..., None] * o32
    o = o32.reshape(B, H, Sq, hd).astype(q.dtype)
    do = do32.reshape(B, H, Sq, hd).astype(q.dtype)
    return o, do


# ---------------------------------------------------------------------------
# public entry


def _fit_block(n: int, want: int) -> int:
    b = int(max(1, min(n, want)))
    while n % b:
        b -= 1
    return b


def _clamp_interpret_grid(Sq, Sk, bq, bk, outer, cap=INTERPRET_CELL_CAP):
    """Grow blocks until the unrolled grid has <= cap cells (best effort:
    the B*H outer product alone may exceed the cap)."""
    def _grow(S, b):
        nb = b + 1
        while nb <= S and S % nb:
            nb += 1
        return min(nb, S)

    while outer * (Sq // bq) * (Sk // bk) > cap and (bq < Sq or bk < Sk):
        if (Sk // bk) >= (Sq // bq) and bk < Sk:
            bk = _grow(Sk, bk)
        else:
            bq = _grow(Sq, bq)
    return bq, bk


def flash_attention(q, k, v, *, causal=True, scale=None, window=None,
                    softcap=None, q_offset=0, block_q=None, block_k=None,
                    schedule=None, interpret=None, use_jvp=False):
    """Fused attention: q (B, H, Sq, hd), k/v (B, Hkv, Sk, hd) -> o like q.

    ``window`` may be None, a static int, or a traced int32 scalar (the
    per-layer windows from ``transformer.layer_windows``); ``scale``
    defaults to 1/sqrt(hd); ``q_offset`` shifts the query positions for
    chunked-prefill-style calls.  ``use_jvp=True`` selects the custom_jvp
    twin (forward-mode capable, jnp tangent); the default custom_vjp path
    runs the Pallas dQ / dKV kernels in reverse mode.  Unset blocks /
    schedule come from ``kernels/autotune.get_tuned_attn``.
    """
    B, H, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    assert H % Hkv == 0, (H, Hkv)
    assert k.shape == v.shape, (k.shape, v.shape)
    assert q_offset >= 0, q_offset
    if interpret is None:
        interpret = _interpret_default()
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    scale = float(scale)
    if softcap is not None:
        softcap = float(softcap)

    if block_q is None or block_k is None or schedule is None:
        from .autotune import get_tuned_attn
        t = get_tuned_attn(B, H, Hkv, Sq, Sk, hd, dtype=q.dtype,
                           causal=causal, softcap=softcap,
                           interpret=interpret)
        block_q = block_q or t.bq
        block_k = block_k or t.bk
        schedule = schedule or t.schedule
    block_q = _fit_block(Sq, block_q)
    block_k = _fit_block(Sk, block_k)
    if interpret:
        block_q, block_k = _clamp_interpret_grid(Sq, Sk, block_q, block_k,
                                                 B * H)

    win = jnp.reshape(
        jnp.asarray(WINDOW_NONE if window is None else window, jnp.int32),
        (1,))
    fn = _flash_jvp if use_jvp else _flash

    def local(_, q, k, v, win):
        return fn(q, k, v, win, causal, scale, softcap, int(q_offset),
                  block_q, block_k, schedule, bool(interpret))

    # under an activation mesh each device attends over its batch shard
    spec = P("batch", None, None, None)
    return per_device(local, (spec, spec, spec, P()), spec, B)(q, k, v, win)


# ---------------------------------------------------------------------------
# analytic HBM byte models (roofline overlays, launch/roofline.py)


def attention_hbm_bytes_flash(B, H, Hkv, S, hd, bytes_per_el=2) -> int:
    """HBM floor of the fused forward: Q + O per head, K + V per KV head
    (the VMEM online softmax adds no score traffic)."""
    q_o = 2 * B * H * S * hd * bytes_per_el
    kv = 2 * B * Hkv * S * hd * bytes_per_el
    return q_o + kv


def attention_hbm_bytes_train_flash(B, H, Hkv, S, hd,
                                    bytes_per_el=2) -> int:
    """Fused fwd + bwd traffic floor: forward (Q, K, V reads; O, lse
    writes) plus dQ (re-reads + dO, writes dQ) plus dK/dV (re-reads,
    writes dK/dV).  KV tile re-streaming across q blocks is a block-size
    term deliberately excluded from the floor."""
    q_like = B * H * S * hd * bytes_per_el          # one (B, H, S, hd) plane
    kv_like = B * Hkv * S * hd * bytes_per_el
    lse = 4 * B * H * S
    fwd = 2 * q_like + 2 * kv_like + lse
    d_q = 3 * q_like + 2 * kv_like + 2 * lse
    d_kv = 2 * q_like + 4 * kv_like + 2 * lse
    return fwd + d_q + d_kv


def attention_hbm_bytes_unfused(B, H, S, hd, block_k=1024, passes=5,
                                bytes_per_el=4) -> int:
    """XLA materialized-scores traffic model: each (S, block_k) fp32 score
    tile makes ~``passes`` HBM round-trips (scores, mask, softmax
    normalize, weight, matmul operand re-reads)."""
    tiles = max(S // block_k, 1)
    return B * H * S * block_k * tiles * passes * bytes_per_el
