"""Decode-attention Pallas TPU kernel: one query token per slot against its
ring/paged KV cache.

The serve engine's hot path is token-at-a-time attention: for every slot a
single query attends to that slot's valid cache entries.  XLA lowers the
jnp path as score-materialize / mask / softmax / AV — each an HBM round
trip over the (N, C) score plane.  This kernel keeps the online softmax in
VMEM scratch and streams the cache one page at a time, so HBM traffic is
one read of the slot's K/V pages plus one write of the output.

Layout (matches ``models/transformer.init_slots``):
    q          (N, H, hd)       one query token per slot
    k_cache/v  (N, C, Hkv, hd)  slot-major ring cache, C = n_pages * page_len
    positions  (N,) int32       per-slot write position (the query's position)

Ring semantics: cache index ``s`` holds absolute position
``pos - ((pos - s) mod C)``; entries are valid when that is >= 0 (and
inside the sliding window when one is set).  When C covers the whole
request the ring degenerates to a linear cache and the mask to the causal
prefix — this is the layout ``ring_mask`` in models/layers.py defines, and
the kernel reproduces it page by page.

Grid: (N, C / page_len) with the page axis innermost ("arbitrary"): one
grid step streams one page of every KV head of a slot, and the kernel
loops over the KV heads, each serving its G = H / Hkv query heads, with the
same m/l/acc VMEM scratch pattern as kernels/flash_attention.py (one row
per query head).  Per-slot positions arrive through
``PrefetchScalarGridSpec`` so the mask offsets are known before the body
runs.

TPU tiling: blocks take whole trailing ``(H, hd)`` / ``(Hkv, hd)`` planes
(a single head's ``(1, hd)`` row is not a legal block), the int8 scale
planes travel as ``(N, 1, C)`` so a page's scales are a lane-dense
``(1, page_len)`` row, and ``page_len`` is a multiple of 128 dividing C,
or C itself.

Validated under ``interpret=True`` against ``kernels/ref.decode_attention_ref``
(<= 3e-6 fp32) in tests/test_decode_attention.py and compiled for a
described v5e chip in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_ce import _interpret_default

DEFAULT_PAGE = 128
NEG_INF = -1e30


def _decode_kernel(pos_ref, win_ref, q_ref, k_ref, v_ref, *rest, scale,
                   page_len, cache_len, n_pages, n_kv, group, softcap,
                   kv_cast=None):
    if kv_cast is None:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    n = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    pos = pos_ref[n]
    win = win_ref[0]
    # ring validity: index s holds absolute position pos - ((pos - s) mod C)
    idx = j * page_len + jax.lax.broadcasted_iota(jnp.int32, (1, page_len), 1)
    abs_pos = pos - jnp.mod(pos - idx, cache_len)
    valid = (abs_pos >= 0) & (abs_pos > pos - win)        # (1, page_len)
    if ks_ref is not None:
        # per-token scales as (page_len, 1) columns: the lane-dense row,
        # broadcast to a full sublane tile and transposed
        def _column(ref):
            tile = jnp.broadcast_to(ref[0], (8, page_len))
            return jnp.transpose(tile)[:, :1]
        k_sc, v_sc = _column(ks_ref), _column(vs_ref)

    for g in range(n_kv):
        rows = slice(g * group, (g + 1) * group)
        q = q_ref[0, rows, :].astype(jnp.float32)           # (G, hd)
        k = k_ref[0, :, g, :]                               # (page_len, hd)
        v = v_ref[0, :, g, :]
        if ks_ref is not None:
            # int8 page dequant: fp32 payload * per-token scale, rounded
            # once into the compute dtype — bit-identical to the XLA read
            # path (models/layers._dequant_cache), so kernel on/off never
            # changes sampled tokens
            k = (k.astype(jnp.float32) * k_sc).astype(kv_cast)
            v = (v.astype(jnp.float32) * v_sc).astype(kv_cast)
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (G, page_len)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[rows, :]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[rows, :] = l_scr[rows, :] * alpha + p.sum(-1, keepdims=True)
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + jnp.dot(
            p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
        m_scr[rows, :] = m_new

    @pl.when(j == n_pages - 1)
    def _flush():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def _page_len(C: int, want: int) -> int:
    """Largest multiple of 128 <= ``want`` dividing C, else C (one page):
    the lane-dense scale row of a page must be a multiple of 128 wide or
    span the whole ring."""
    p = (min(want, C) // 128) * 128
    while p >= 128:
        if C % p == 0:
            return p
        p -= 128
    return C


def decode_attention_pallas(q, k_cache, v_cache, positions, *, scale=None,
                            window=None, softcap=None,
                            page_len=DEFAULT_PAGE, interpret=None,
                            k_scale=None, v_scale=None):
    """q (N, H, hd); k/v (N, C, Hkv, hd); positions (N,) -> (N, H, hd).

    One grid step per (slot, page); HBM traffic = K + V pages once plus Q
    and O.  The page is the largest multiple of 128 <= ``page_len``
    dividing C, else the whole ring.  ``window`` may be a traced scalar (it
    rides in as a scalar-prefetch operand, so per-layer sliding windows
    scan cleanly); None means global attention.  ``interpret`` defaults to
    interpreter mode off-TPU, native compilation on TPU.

    int8 caches pass ``k_scale``/``v_scale`` (N, C) fp32 per-token scales:
    each page's scale slice streams into VMEM alongside its K/V page
    (same index map on the ring axis) and the page is dequantized in
    registers — HBM reads the 1-byte payloads, halving cache traffic and
    doubling the slots a fixed HBM budget sustains.
    """
    interpret = _interpret_default() if interpret is None else interpret
    N, H, hd = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    page_len = _page_len(C, page_len)
    n_pages = C // page_len
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    win = jnp.reshape(jnp.asarray(
        (1 << 30) if window is None else window, jnp.int32), (1,))
    quant = k_scale is not None

    kern = functools.partial(
        _decode_kernel, scale=scale, page_len=page_len, cache_len=C,
        n_pages=n_pages, n_kv=Hkv, group=H // Hkv, softcap=softcap,
        kv_cast=q.dtype if quant else None)
    q_spec = pl.BlockSpec((1, H, hd), lambda n, j, pos, w: (n, 0, 0))
    kv_spec = pl.BlockSpec((1, page_len, Hkv, hd),
                           lambda n, j, pos, w: (n, j, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k_cache, v_cache]
    if quant:
        scale_spec = pl.BlockSpec((1, 1, page_len),
                                  lambda n, j, pos, w: (n, 0, j))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale.reshape(N, 1, C), v_scale.reshape(N, 1, C)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N, n_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),    # running max
            pltpu.VMEM((H, 1), jnp.float32),    # running sum
            pltpu.VMEM((H, hd), jnp.float32),   # accumulator
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(positions.astype(jnp.int32), win, *operands)


def decode_attention_hbm_bytes(N, H, Hkv, C, hd, bytes_per_el=2,
                               kv_dtype="bf16") -> int:
    """Analytic HBM floor of the fused decode step (roofline overlay).

    ``kv_dtype="int8"`` charges 1 byte/element for the cache payload plus
    one fp32 per-token scale per K/V plane; Q and O stay in the compute
    dtype either way."""
    q_o = 2 * N * H * hd * bytes_per_el
    if kv_dtype == "int8":
        kv = 2 * N * C * (Hkv * hd + 4)
    else:
        kv = 2 * N * C * Hkv * hd * bytes_per_el
    return q_o + kv
