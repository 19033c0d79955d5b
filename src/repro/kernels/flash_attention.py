"""Training-path flash attention: fused forward + custom_vjp backward.

The training hot path used to run ``models/layers.py:full_attention``,
which materializes an fp32 ``(B, Hkv, G, S, S)`` score tensor every layer.
This module replaces it with a production Pallas kernel family that keeps
the online softmax entirely in VMEM and walks the causal band *inside* the
kernel, so an out-of-band tile costs neither a grid step nor a DMA:

* **forward** — grid ``(B, H, Sq/BQ, n_c)``.  The K and V of the query
  head's KV head are one VMEM-resident block of ``BKM`` rows, fetched once
  per ``(b, h)``: the whole head (``n_c == 1``) unless double-buffered,
  lane-padded K and V would pass ``RESIDENT_VMEM_BYTES``, in which case
  ``BKM`` is the largest divisor of Sk that fits and the ``n_c`` chunks stay
  on the grid.  Each grid step walks its q-block's band of ``BK``-row KV
  sub-tiles with ``lax.fori_loop``: the sub-tiles that straddle the diagonal
  or the window edge build the iota mask, the rest run unmasked.  VMEM
  scratch carries the running max/denominator and an fp32 accumulator
  across chunks.  Emits the logsumexp as a lane-dense ``(B, H, 1, Sq)``
  row, the backward residual (a ``(B, H, Sq, 1)`` column pads its unit
  lane axis to 128 in HBM).  GQA maps ``h -> h // G`` in the KV BlockSpecs.
* **backward** (``custom_vjp``) — two kernels with the delta/lse recompute
  trick (``delta = rowsum(dO * O)`` precomputed outside, a lane-dense row
  like lse): dQ has the forward's grid and band walk with an fp32
  ``(BQ, hd)`` accumulator; dK/dV run over ``(B, Hkv, Sk/BK, n_c)`` with
  the G query heads' Q, dO, lse and delta of the KV head resident (chunked
  like K/V in the forward), walking ``(group, q-block)`` pairs of the band
  into one fp32 ``(BK, hd)`` scratch — no ``(B, H, Sk, hd)`` intermediate.
  Its scores are computed transposed (``K Q^T``), so the lse/delta rows
  broadcast as they are.
* **custom_jvp twin** (``use_jvp=True``) — same Pallas forward for the
  primal; the tangent is a chunked fp32 jnp sweep, *linear* in the input
  tangents, so JAX can both push Hutchinson's forward-over-reverse HVP
  through it and transpose it for reverse mode.

Masking covers causal, sliding-window and the gemma2 logit softcap.  The
window rides in as a scalar-prefetch operand (sentinel ``1 << 30`` = no
window) so the *traced* per-layer windows from ``transformer.layer_windows``
work: the band walk reads it, and where ``n_c > 1`` so do the chunk index
maps, which clamp into the band so that out-of-band chunks are not fetched.
``schedule="skip"`` walks the band; ``schedule="dense"`` walks every
sub-tile and masks each (the reference the parity tests hold "skip" to).
``attn_grid_plan`` gives each kernel call's grid, resident rows and full /
masked sub-tile counts: the wrappers build their grids from it and add it
to ``KERNEL_CALLS`` (``attn_grid_steps``, ``attn_tiles_full``,
``attn_tiles_masked``), and the autotuner prices candidates with it.

Block sizes and the schedule come from ``kernels/autotune.py``
(``get_tuned_attn``) unless given explicitly.  On a TPU a q-block is a
multiple of 128 or the whole sequence (``block_quantum``: it offsets the
lse/delta rows along lanes); an explicit one is rounded down to such a
divisor, or up to the smallest.  ``interpret=None`` resolves to "not on a
real TPU" (the repo convention, ``fused_ce._interpret_default``) and
interpret-mode grids are auto-clamped to <= ``INTERPRET_CELL_CAP`` cells so
CPU CI never unrolls huge grids.

Parity: ``kernels/ref.py`` closed-form oracles mirror every fp32 rounding
point (<= 3e-6, tests/test_flash_attention.py); ``KERNEL_CALLS`` counts
``attn_fwd`` / ``attn_bwd_dq`` / ``attn_bwd_dkv`` / ``attn_jvp_rule`` at
trace time so tests can assert nothing silently fell back.
"""
from __future__ import annotations

import dataclasses
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
LANES = 128                    # a TPU vreg's lane count: minor dims pad to it
#: VMEM for a kernel's resident operand, double-buffered and lane-padded:
#: K and V in the forward and dq, the KV head's Q, dO, lse and delta in dkv
RESIDENT_VMEM_BYTES = 8 << 20


#: the kernels' names in a compiled program (the Mosaic module's
#: ``sym_name``, which a profile reader matches); pinned here so that
#: renaming a kernel function does not rename its ops
KERNEL_NAMES = FWD_NAME, DQ_NAME, DKV_NAME = (
    "_fwd_kernel", "_dq_kernel", "_dkv_kernel")


# ---------------------------------------------------------------------------
# block bands (shared by index maps, kernels and the grid plan; integer
# arithmetic in ``xp`` — jnp so the window may be a traced scalar from the
# prefetch ref, numpy for the host-side plan)


def _kv_band(i, win, *, causal, q_offset, block_q, block_k, n_k, xp=jnp):
    """Inclusive [lo, hi] range of KV blocks attended by q-block ``i``."""
    if causal:
        hi = xp.minimum(n_k - 1,
                        ((i + 1) * block_q - 1 + q_offset) // block_k)
    else:
        hi = n_k - 1
    lo = xp.maximum(0, (i * block_q + q_offset - win + 1) // block_k)
    return lo, hi


def _q_band(j, win, *, causal, q_offset, block_q, block_k, n_q, xp=jnp):
    """Inclusive [lo, hi] range of q blocks attending KV block ``j``."""
    if causal:
        lo = xp.maximum(0, (j * block_k - q_offset) // block_q)
    else:
        lo = 0
    hi = xp.minimum(n_q - 1,
                    ((j + 1) * block_k - 2 + win - q_offset) // block_q)
    return lo, hi


def _kv_full(i, win, *, causal, q_offset, block_q, block_k, n_k):
    """Inclusive range of KV blocks that every row of q-block ``i`` attends
    in full (empty when lo > hi): past the window edge, up to the diagonal."""
    lo = -((win - q_offset - (i + 1) * block_q) // block_k)
    hi = (q_offset + i * block_q + 1) // block_k - 1 if causal else n_k - 1
    return lo, hi


def _q_full(j, win, *, causal, q_offset, block_q, block_k, n_q):
    """Inclusive range of q blocks whose every row attends all of KV block
    ``j`` (empty when lo > hi)."""
    lo = -((q_offset + 1 - (j + 1) * block_k) // block_q) if causal else 0
    hi = (j * block_k + win - q_offset) // block_q - 1
    return lo, hi


def _walk(band, full, c, per_chunk, schedule, xp=jnp):
    """Chunk ``c``'s sub-tiles ``[c * per_chunk, (c + 1) * per_chunk)`` as
    ``(start, stop, masked)`` ranges: the in-band ones below the full range
    masked, those inside it unmasked, those above it masked.  "dense" walks
    the whole chunk, masked."""
    c0 = c * per_chunk
    c1 = c0 + per_chunk
    if schedule != "skip":
        return ((c0, c1, True),)
    lo = xp.maximum(band[0], c0)
    hi = xp.maximum(xp.minimum(band[1] + 1, c1), lo)
    a = xp.clip(full[0], lo, hi)
    f = xp.clip(full[1] + 1, a, hi)
    return ((lo, a, True), (a, f, False), (f, hi, True))


def _run(ranges, tile):
    """``tile(t, masked)`` for every sub-tile ``t`` of the walk, in order."""
    for start, stop, masked in ranges:
        def body(t, carry, masked=masked):
            tile(t, masked)
            return carry
        jax.lax.fori_loop(start, stop, body, 0)


def _clamp_chunk(c, band, per_chunk, n_c):
    """Chunk index clamped into the band's chunks (and the grid): a chunk
    outside the band re-uses the resident block instead of a fresh DMA."""
    c = jnp.clip(c, band[0] // per_chunk, band[1] // per_chunk)
    return jnp.clip(c, 0, n_c - 1)


def _kv_spec(plan, hd, group, n_k, band, skip):
    """The fwd and dq kernels' K/V BlockSpec: the KV head's resident rows,
    with a chunked head's chunk index clamped into q-block ``i``'s band."""
    n_c, per_chunk = plan.grid[3], plan.block_major // band["block_k"]

    def index(b, h, i, c, w):
        if skip and n_c > 1:
            c = _clamp_chunk(c, _kv_band(i, w[0], n_k=n_k, **band),
                             per_chunk, n_c)
        return (b, h // group, c, 0)

    return pl.BlockSpec((1, 1, plan.block_major, hd), index)


def _sub(t, block, n):
    """The ``t``-th of ``n`` ``block``-long slices of a resident axis."""
    if n == 1:
        return pl.ds(0, block)
    return pl.ds(pl.multiple_of(t * block, block), block)


def _tile_mask(i, j, win, *, causal, q_offset, block_q, block_k,
               transpose=False):
    """(BQ, BK) bool attend-mask for tile (i, j), global positions;
    ``transpose`` gives it (BK, BQ), keys along the rows."""
    shape, qd = ((block_k, block_q), 1) if transpose else \
        ((block_q, block_k), 0)
    qpos = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, qd)
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - qd)
    m = kpos > qpos - win
    if causal:
        m = m & (kpos <= qpos)
    return m


def _dotT(a, b):
    """a (M, D) x b (N, D) -> (M, N) fp32 contraction over the last axis."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _col_to_row(x):
    """(n, 1) -> (1, n), through a lane-wide transpose."""
    return jnp.broadcast_to(x, (x.shape[0], LANES)).T[:1]


def _row_to_col(x):
    """(1, n) -> (n, 1), through a lane-wide transpose."""
    return jnp.broadcast_to(x, (LANES, x.shape[1])).T[:, :1]


# ---------------------------------------------------------------------------
# grid plan (host-side, static shapes)


def _lane_pad(n: int) -> int:
    return -(-n // LANES) * LANES


def block_quantum(S: int, interpret: bool, *, q: bool) -> int:
    """Blocks of a length-S axis are multiples of this.  On a TPU a q-block
    offsets the lse/delta rows along lanes, so it is a multiple of 128 or
    all of S; a KV block offsets rows only."""
    if not interpret and S % LANES == 0:
        return LANES
    if not interpret and q:
        return S
    return 8 if S % 8 == 0 else 1


def _resident_rows(S: int, block: int, row_bytes: int) -> int:
    """The largest multiple of ``block`` dividing S whose rows, at
    ``row_bytes`` each and double-buffered, fit ``RESIDENT_VMEM_BYTES``;
    ``block`` itself when none does."""
    best = block
    for m in range(block, S + 1, block):
        if S % m == 0 and 2 * m * row_bytes <= RESIDENT_VMEM_BYTES:
            best = m
    return best


@dataclasses.dataclass(frozen=True)
class GridPlan:
    """One flash kernel call: its Pallas ``grid``, the rows of the resident
    operand per chunk (``block_major``, a multiple of the walked sub-tile),
    and the sub-tiles of the causal band it computes unmasked
    (``tiles_full``) and masked (``tiles_masked``), over all heads."""
    grid: tuple
    block_major: int
    tiles_full: int
    tiles_masked: int

    @property
    def steps(self) -> int:
        return math.prod(self.grid)


def attn_grid_plan(kernel, B, H, Hkv, Sq, Sk, hd, *, block_q, block_k,
                   itemsize, causal=True, q_offset=0,
                   schedule="skip") -> GridPlan:
    """The grid of one ``kernel`` ("fwd", "dq" or "dkv") call.

    fwd and dq keep K and V resident (``Sk`` rows), dkv the KV head's G
    query heads of Q and dO plus their lse and delta rows (``Sq`` rows).
    Tile counts are per call, and the same for the three kernels; they
    count the causal band, which a sliding window (traced, unknown here)
    narrows further at run time."""
    group = H // Hkv
    n_q, n_k = Sq // block_q, Sk // block_k
    pair = 2 * _lane_pad(hd) * itemsize       # a row of two operands
    if kernel == "dkv":
        # + lse and delta rows, a (1, n) f32 block padding 1 -> 8 sublanes
        bm = _resident_rows(Sq, block_q, group * (pair + 2 * 8 * 4))
        grid = (B, Hkv, n_k, Sq // bm)
    else:
        bm = _resident_rows(Sk, block_k, pair)
        grid = (B, H, n_q, Sk // bm)
    i = np.arange(n_q)
    band = dict(causal=causal, q_offset=q_offset, block_q=block_q,
                block_k=block_k)
    ranges = _walk(_kv_band(i, WINDOW_NONE, n_k=n_k, xp=np, **band),
                   _kv_full(i, WINDOW_NONE, n_k=n_k, **band), 0, n_k,
                   schedule, xp=np)
    tiles = {False: 0, True: 0}                # unmasked, masked
    for start, stop, masked in ranges:
        tiles[masked] += int(np.sum(np.broadcast_to(stop - start, i.shape)))
    return GridPlan(grid, bm, B * H * tiles[False], B * H * tiles[True])


def _plan(kernel, q, k, block_q, block_k, causal, q_offset, schedule):
    """The call's plan, added to the trace-time counters."""
    plan = attn_grid_plan(kernel, *q.shape[:2], k.shape[1], q.shape[2],
                          k.shape[2], q.shape[3], block_q=block_q,
                          block_k=block_k, itemsize=q.dtype.itemsize,
                          causal=causal, q_offset=q_offset,
                          schedule=schedule)
    KERNEL_CALLS["attn_grid_steps"] += plan.steps
    KERNEL_CALLS["attn_tiles_full"] += plan.tiles_full
    KERNEL_CALLS["attn_tiles_masked"] += plan.tiles_masked
    return plan


# ---------------------------------------------------------------------------
# forward kernel


def _fwd_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale, causal, softcap, q_offset, block_q,
                block_k, n_q, n_k, n_c, schedule):
    i, c = pl.program_id(2), pl.program_id(3)
    per_chunk = k_ref.shape[2] // block_k
    win = win_ref[0]
    band = dict(causal=causal, q_offset=q_offset, block_q=block_q,
                block_k=block_k)

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    def tile(j, masked):
        rows = _sub(j - c * per_chunk, block_k, per_chunk)
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0, rows, :].astype(jnp.float32)
        v = v_ref[0, 0, rows, :].astype(jnp.float32)
        s = _dotT(q, k) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if masked:
            mask = _tile_mask(i, j, win, **band)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if masked:
            # a fully-masked row has m_new == NEG_INF and exp(s - m_new)
            # == 1 — the mask zeroes it instead
            p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    _run(_walk(_kv_band(i, win, n_k=n_k, **band),
               _kv_full(i, win, n_k=n_k, **band), c, per_chunk, schedule),
         tile)

    @pl.when(c == n_c - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, _sub(i, block_q, n_q)] = _col_to_row(
            m_scr[...] + jnp.log(l))


def _forward(q, k, v, win, *, causal, scale, softcap, q_offset, block_q,
             block_k, schedule, interpret):
    """Raw fwd pallas_call -> (o, lse (B, H, 1, Sq)); no autodiff wiring."""
    B, H, Sq, hd = q.shape
    group = H // k.shape[1]
    n_q, n_k = Sq // block_q, k.shape[2] // block_k
    plan = _plan("fwd", q, k, block_q, block_k, causal, q_offset, schedule)
    n_c = plan.grid[3]
    band = dict(causal=causal, q_offset=q_offset, block_q=block_q,
                block_k=block_k)
    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda b, h, i, c, w: (b, h, i, 0))
    kv_spec = _kv_spec(plan, hd, group, n_k, band, schedule == "skip")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=plan.grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((1, 1, 1, Sq),
                                lambda b, h, i, c, w: (b, h, 0, 0))],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max
            pltpu.VMEM((block_q, 1), jnp.float32),     # running denominator
            pltpu.VMEM((block_q, hd), jnp.float32),    # output accumulator
        ],
    )
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, softcap=softcap,
        q_offset=q_offset, block_q=block_q, block_k=block_k, n_q=n_q,
        n_k=n_k, n_c=n_c, schedule=schedule)
    KERNEL_CALLS["attn_fwd"] += 1
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32),
        ],
        interpret=interpret,
        name=FWD_NAME,
    )(win, q, k, v)


# ---------------------------------------------------------------------------
# backward kernels (delta/lse recompute: p = exp(z - lse) per tile, no
# stored probabilities)


def _bwd_p_ds(s, dp, lse, delta, mask, softcap):
    """Shared per-tile recompute from raw scores ``s`` and ``dp = dO V^T``
    (either orientation; lse/delta broadcast against them): (p, ds) with ds
    already softcap-chained; all fp32."""
    if softcap is not None:
        t = jnp.tanh(s / softcap)
        z, dcap = softcap * t, 1.0 - t * t
    else:
        z, dcap = s, None
    p = jnp.exp(z - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    ds = p * (dp - delta)
    if dcap is not None:
        ds = ds * dcap
    return p, ds


def _dq_kernel(win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dq_ref, dq_scr, lse_scr, dl_scr, *, scale, causal, softcap,
               q_offset, block_q, block_k, n_q, n_k, n_c, schedule):
    i, c = pl.program_id(2), pl.program_id(3)
    per_chunk = k_ref.shape[2] // block_k
    win = win_ref[0]
    band = dict(causal=causal, q_offset=q_offset, block_q=block_q,
                block_k=block_k)

    @pl.when(c == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr[...])
        rows = _sub(i, block_q, n_q)
        lse_scr[...] = _row_to_col(lse_ref[0, 0, :, rows])
        dl_scr[...] = _row_to_col(dl_ref[0, 0, :, rows])

    def tile(j, masked):
        rows = _sub(j - c * per_chunk, block_k, per_chunk)
        q = q_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0, rows, :].astype(jnp.float32)
        v = v_ref[0, 0, rows, :].astype(jnp.float32)
        mask = _tile_mask(i, j, win, **band) if masked else None
        _, ds = _bwd_p_ds(_dotT(q, k) * scale, _dotT(do, v), lse_scr[...],
                          dl_scr[...], mask, softcap)
        dq_scr[...] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    _run(_walk(_kv_band(i, win, n_k=n_k, **band),
               _kv_full(i, win, n_k=n_k, **band), c, per_chunk, schedule),
         tile)

    @pl.when(c == n_c - 1)
    def _flush():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, softcap,
                q_offset, block_q, block_k, n_q, n_c, schedule):
    j, c = pl.program_id(2), pl.program_id(3)
    group, per_chunk = q_ref.shape[1], q_ref.shape[2] // block_q
    win = win_ref[0]
    band = dict(causal=causal, q_offset=q_offset, block_q=block_q,
                block_k=block_k)

    @pl.when(c == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr[...])
        dv_scr[...] = jnp.zeros_like(dv_scr[...])

    def tile(g, i, masked):
        rows = _sub(i - c * per_chunk, block_q, per_chunk)
        q = q_ref[0, g, rows, :].astype(jnp.float32)
        do = do_ref[0, g, rows, :].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(i, j, win, transpose=True, **band) if masked \
            else None
        # (BK, BQ): scores transposed, so lse/delta rows broadcast as read
        p, ds = _bwd_p_ds(_dotT(k, q) * scale, _dotT(v, do),
                          lse_ref[0, g, :, rows], dl_ref[0, g, :, rows],
                          mask, softcap)
        dv_scr[...] += jax.lax.dot(p, do, preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot(
            ds, q, preferred_element_type=jnp.float32) * scale

    ranges = _walk(_q_band(j, win, n_q=n_q, **band),
                   _q_full(j, win, n_q=n_q, **band), c, per_chunk, schedule)

    def per_group(g, carry):
        _run(ranges, functools.partial(tile, g))
        return carry

    jax.lax.fori_loop(0, group, per_group, 0)

    @pl.when(c == n_c - 1)
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
    static = dict(scale=scale, causal=causal, softcap=softcap,
                  q_offset=q_offset, block_q=block_q, block_k=block_k,
                  n_q=n_q, schedule=schedule)

    # --- dQ: the forward's grid and band walk -----------------------------
    plan = _plan("dq", q, k, block_q, block_k, causal, q_offset, schedule)
    n_c = plan.grid[3]
    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda b, h, i, c, w: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, 1, Sq), lambda b, h, i, c, w: (b, h, 0, 0))
    kv_spec = _kv_spec(plan, hd, group, n_k, band, skip)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=plan.grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
    )
    kern = functools.partial(_dq_kernel, n_k=n_k, n_c=n_c, **static)
    KERNEL_CALLS["attn_bwd_dq"] += 1
    dq = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=DQ_NAME,
    )(win, q, k, v, do, lse, delta)

    # --- dK/dV: grid (B, Hkv, n_k, n_c), the group's Q rows resident -------
    plan = _plan("dkv", q, k, block_q, block_k, causal, q_offset, schedule)
    n_c, bm = plan.grid[3], plan.block_major
    per_chunk = bm // block_q

    def chunk(j, c, w):
        if skip and n_c > 1:
            c = _clamp_chunk(c, _q_band(j, w[0], n_q=n_q, **band),
                             per_chunk, n_c)
        return c

    qg_spec = pl.BlockSpec((1, group, bm, hd),
                           lambda b, kv, j, c, w: (b, kv, chunk(j, c, w), 0))
    rowg_spec = pl.BlockSpec((1, group, 1, bm),
                             lambda b, kv, j, c, w: (b, kv, 0, chunk(j, c, w)))
    kvb_spec = pl.BlockSpec((1, 1, block_k, hd),
                            lambda b, kv, j, c, w: (b, kv, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=plan.grid,
        in_specs=[qg_spec, kvb_spec, kvb_spec, qg_spec, rowg_spec,
                  rowg_spec],
        out_specs=[kvb_spec, kvb_spec],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
    )
    kern = functools.partial(_dkv_kernel, n_c=n_c, **static)
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
    delta = (g.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    delta = delta[:, :, None, :]                    # lane-dense, like lse
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
    quantum = block_quantum(Sq, interpret, q=True)
    if not interpret and block_q % quantum:
        block_q = max([m for m in range(quantum, block_q + 1, quantum)
                       if Sq % m == 0] or [quantum])
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
