"""Logits-free fused LM cross-entropy Pallas kernels (chunked vocab sweep).

The training hot path used to materialize the full ``[B*T, V]`` logits
tensor in HBM — and the GNB Hessian refresh (Algorithm 2) materialized it
twice (Gumbel-max sampling + an fp32 ``log_softmax`` copy).  At GPT-2-and-up
vocab sizes that buffer dominates the step's memory peak and its ~5 HBM
crossings dominate loss-stage bandwidth.  These kernels stream ``lm_head``
weight *tiles* through VMEM instead, fusing the final projection with an
online-softmax cross-entropy:

  forward   one (rows, vocab-chunks) grid sweep; per row tile the kernel
            keeps running (max, sum-exp, label-logit) in VMEM scratch and
            emits only ``lse`` and ``label_logit`` vectors — ``(N,)`` each.
            The final-*norm* producer can be fused in: the kernel reads
            PRE-norm hidden tiles and applies rms/layer norm in VMEM, so
            the normed (N, D) activation never round-trips HBM.
  backward  ``custom_vjp``: vocab sweeps recompute each logits tile and
            emit ``d_hidden`` and ``d_W`` directly from
            ``softmax - onehot``.  Two schedules:
              * ``split``  — two sweeps (d_hidden chunks-inner with a VMEM
                accumulator; d_W rows-inner with the chunk block resident);
              * ``fused``  — ONE combined sweep computing both, legal
                whenever one grid axis is 1 (the autotuner only emits such
                tilings for it): every output block is then either written
                once or accumulated over *consecutive* grid steps, so no
                block is ever revisited non-consecutively (which Pallas TPU
                pipelining does not guarantee to re-fetch).  Saves one full
                logits recompute (backward 6 -> 4 matmul-sweeps).
            The ``[N, V]`` logits (and the fp32 log-probs copy) never touch
            HBM either way.
  sampling  the same forward sweep optionally draws ``yhat ~
            softmax(logits)`` by online chunked Gumbel-argmax (counter-based
            hash noise, pure function of ``(seed, row, col)``) and records
            the chosen column's raw logit, so the Algorithm-2 GNB refresh
            goes logits-free too: ``nll = lse - logit[yhat]`` with the
            identical backward.
  hvp       ``fused_lm_loss_jvp`` is a ``custom_jvp`` twin of the labeled
            NLL: the primal runs the same Pallas forward, the tangent is a
            checkpointed chunked jnp sweep (linear in the input tangents,
            so JAX's transpose gives a chunked backward for free).  The
            Hutchinson estimator's forward-over-reverse HVP composes with
            it — it cannot cross the ``custom_vjp`` path, which previously
            forced a silent fall back to the chunked loss.

Block sizes: ``block_n``/``block_v`` default to ``None``, which resolves
through the shape-keyed autotuner (``kernels/autotune.py`` — roofline-model
search with optional measured refinement and a persistent cache; the old
hardcoded ``DEFAULT_BN``/``DEFAULT_BV`` survive only as cache-miss seeds).
Explicit block sizes bypass the tuner (kernel unit tests, measured
tuning).  A vocabulary chunk is any whole number of 128-lane groups: the
grid sweeps ``n_v = cdiv(Vp, bv)`` chunks, and where ``bv`` does not
divide Vp the last W block runs past the array.  Only chunks that hold a
column at or past ``vocab`` take the masked path (``_by_chunk``): W's tail
rows / columns are zeroed before any product (a partial block's tail is
whatever lies past the array, NaN in interpret mode) and the tail's
logits are forced to NEG_INF; the write-back drops a dW block's rows past
Vp.  Full chunks pay nothing.  Each ``pallas_call`` requests
``VMEM_LIMIT_BYTES`` of scoped VMEM, the budget the tuner fits the
working set (``vmem_bytes``) to.  Trace-time counters in ``KERNEL_CALLS``:
calls per kernel, ``ce_grid_steps`` (summed over calls),
``ce_ragged_calls``, and one ``ce_tiling <N>x<D>x<Vp> bn.. bv.. <schedule>
steps..`` key per resolved call shape, its grid steps per kernel call.

Compute convention (matches ``models.layers.unembed``): W is cast to the
hidden dtype, the projection accumulates in fp32
(``preferred_element_type``), softcap (gemma2) applies in fp32, and
``padded_vocab`` columns are masked to ``NEG_INF`` — they contribute
nothing to the CE denominator, are never sampled, and receive exactly zero
gradient.  Tied embeddings pass W as ``(Vp, D)`` (``transpose_w=False``);
untied as ``(D, Vp)`` (``transpose_w=True``) — the BlockSpecs stream the
right tile either way, no host-side transpose.  The fused norm replicates
``models.layers.rms_norm`` / ``layer_norm`` bit-for-bit (fp32 statistics,
cast back to the hidden dtype before the projection).

Validated under ``interpret=True`` against the kernels/ref.py closed-form
oracles (``lm_loss_grads_ref`` / ``lm_loss_sampled_ref``) to <=3e-6 in
tests/test_fused_ce.py; on a real TPU the same pallas_call compiles
natively.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import per_device

DEFAULT_BN = 256    # rows (B*T positions) per tile (autotuner seed)
DEFAULT_BV = 1024   # vocab columns per chunk (multiple of 128; seed)
NEG_INF = -1e30
#: scoped VMEM each CE kernel may use (``vmem_limit_bytes``; a v5e core
#: has 128 MiB, the compiler's default scope is 16 MiB), and the
#: autotuner's budget for ``vmem_bytes``
VMEM_LIMIT_BYTES = 64 << 20
LANES = 128

_f32 = jnp.float32
_u32 = jnp.uint32

# Trace-time kernel invocation counters (per pallas_call wrapper).  Tests
# use these to assert a path really went through the fused kernels — e.g.
# that the Hutchinson HVP's primal ran the Pallas forward instead of
# silently falling back to the chunked jnp loss.
KERNEL_CALLS = collections.Counter()


def kernel_calls() -> dict:
    return dict(KERNEL_CALLS)


def reset_kernel_calls() -> None:
    KERNEL_CALLS.clear()


# ---------------------------------------------------------------------------
# counter-based Gumbel noise (shared by the kernel and the ref.py oracle)


def _mix32(x):
    """lowbias32-style finalizer: uint32 -> well-mixed uint32."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> np.uint32(16))
    return x


def hash_gumbel(seed, rows, cols):
    """Gumbel(0, 1) noise as a pure function of ``(seed, row, col)``.

    ``seed``: (2,) uint32 (derived from a PRNG key); ``rows``/``cols``:
    broadcastable int32 global indices.  Chunk-shape independent by
    construction, so any vocab chunking of the sweep draws the *same*
    perturbation per (row, column) — online chunked Gumbel-argmax over this
    noise equals the monolithic argmax, hence a categorical draw.
    """
    r = _mix32(rows.astype(_u32) ^ seed[0])
    x = _mix32(r ^ (cols.astype(_u32) * np.uint32(0x9E3779B9)) ^ seed[1])
    # 24-bit draws fit int32, whose float cast Mosaic lowers (uint32's not)
    u = (jax.lax.bitcast_convert_type(x >> np.uint32(8), jnp.int32)
         .astype(_f32) * np.float32(1.0 / (1 << 24)))
    u = jnp.clip(u, 1e-7, 1.0 - 1e-7)
    return -jnp.log(-jnp.log(u))


def seed_from_key(rng) -> jnp.ndarray:
    """(2,) uint32 noise seed derived from a JAX PRNG key."""
    return jax.random.bits(rng, (2,), _u32)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# the online-reduction rules, shared verbatim by the Pallas kernels, the
# chunked jnp loss (models/loss.py) and the chunked GNB reference
# (core/estimators.chunked_sampled_stats) — ONE copy of the trickiest
# numerics (running-max rescale, masked-chunk guard, strict-> tie handling)


def online_lse_step(m, l, s, valid=None):
    """One vocab chunk of a running log-sum-exp.

    m, l: (rows,) running max / rescaled sum; s: (rows, chunk) fp32 logits;
    ``valid`` masks columns (without it an all-masked chunk would add
    exp(0)=1 per column while m sits at the -inf sentinel).  Returns
    (m_new, l_new); the final lse is ``m + log(l)``."""
    m_new = jnp.maximum(m, s.max(-1))
    e = jnp.exp(s - m_new[:, None])
    if valid is not None:
        e = jnp.where(valid, e, 0.0)
    return m_new, l * jnp.exp(m - m_new) + e.sum(-1)


def online_argmax_step(best, s, z, c0):
    """One vocab chunk of a running Gumbel-argmax.

    best = (zm, zi, zl): running perturbed max, its global column index,
    and the RAW logit at that column; s/z: (rows, chunk) raw / perturbed
    logits; c0: the chunk's first global column.  Strict ``>`` keeps the
    earliest index on ties and argmax picks the first within the chunk, so
    any chunking reproduces the monolithic first-argmax exactly."""
    zm, zi, zl = best
    zmax = z.max(-1)
    zarg = jnp.argmax(z, axis=-1)
    hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1) \
        == zarg[..., None]
    chunk_logit = jnp.where(hit, s, 0.0).sum(-1)
    upd = zmax > zm
    return (jnp.where(upd, zmax, zm),
            jnp.where(upd, c0 + zarg, zi),
            jnp.where(upd, chunk_logit, zl))


def vocab_chunk(v: int, want: int, quantum: int = 1) -> int:
    """Largest multiple of ``quantum`` <= want dividing ``v`` (static)."""
    b = max(quantum, min(want, v))
    b -= b % quantum
    while b >= quantum:
        if v % b == 0:
            return b
        b -= quantum
    return quantum


def rowscale(n_rows: int, mask):
    """(per-row scale, n_valid): the masked-mean weights ``mask/Σmask``
    flattened to (n_rows,), or uniform 1/N when unmasked.  ``n_valid`` is
    the GNB batch factor B."""
    if mask is None:
        return jnp.full((n_rows,), 1.0 / n_rows, _f32), \
            jnp.asarray(float(n_rows), _f32)
    m = mask.reshape(-1).astype(_f32)
    n_valid = jnp.maximum(m.sum(), 1.0)
    return m / n_valid, n_valid


# ---------------------------------------------------------------------------
# shared tile math


def apply_norm(x, normp, norm, eps):
    """The fused final-norm producer, bit-for-bit the models.layers
    convention: fp32 statistics over the last axis, cast back to ``x``'s
    dtype.  ``normp`` is the packed (2, D) fp32 [scale; bias] pair; rms
    ignores the bias row and uses the (1 + scale) parameterization, ln uses
    ``scale * xhat + bias``.  Plain jnp so the SAME function runs inside
    the Pallas kernels (on VMEM tiles) and as the differentiable host-side
    twin whose ``jax.vjp`` produces the d_x / d_scale / d_bias cotangents
    in the custom_vjp backward."""
    if norm is None:
        return x
    x32 = x.astype(_f32)
    scale = normp[0]
    if norm == "ln":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale + normp[1]
    else:
        assert norm == "rms", norm
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale)
    return out.astype(x.dtype)


def _tile_logits(h, w, transpose_w, softcap):
    """One logits tile in the unembed convention: W cast to the hidden
    dtype, fp32 accumulation, softcap in fp32.  Returns (z, dcap) with
    ``dcap`` the softcap derivative factor (None when uncapped)."""
    wc = w.astype(h.dtype)
    if transpose_w:                       # w tile (D, bv)
        raw = jnp.dot(h, wc, preferred_element_type=_f32)
    else:                                 # w tile (bv, D)
        raw = jnp.dot(h, wc.T, preferred_element_type=_f32)
    if softcap is not None:
        t = jnp.tanh(raw / softcap)
        return softcap * t, 1.0 - t * t
    return raw, None


def _tile_cols(j, bn, bv):
    return j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)


def _label_logit_tile(s, lab, j, bn, bv, interpret):
    """Per-row logit at the label column, 0 for rows whose label falls
    outside this chunk.  Interpret mode uses a (bn,)-sized gather — on the
    CPU interpreter the (bn, bv) iota/compare/where dance costs real time
    at large tiles; TPU keeps the vectorized compare (lane-crossing
    gathers don't lower well in Mosaic)."""
    if interpret:
        idx = lab - j * bv
        ok = (idx >= 0) & (idx < bv)
        got = jnp.take_along_axis(s, jnp.clip(idx, 0, bv - 1)[:, None],
                                  axis=1)[:, 0]
        return jnp.where(ok, got, 0.0)
    hit = _tile_cols(j, bn, bv) == lab[:, None]
    return jnp.where(hit, s, 0.0).sum(-1)


def _by_chunk(j, bv, vocab, n_v, body):
    """Run ``body(masked)`` for vocabulary chunk ``j``: ``masked=False``
    on chunks wholly inside the vocabulary, ``True`` on those that hold a
    column at or past ``vocab`` — padded-vocab columns, and the columns a
    ragged last chunk (``n_v * bv > Vp``) reads past the array.  With
    chunks of whole 128-lane groups only the last chunk can hold such a
    column.  The split is static where the grid allows, else a
    ``pl.when`` pair, so full chunks pay nothing for the mask."""
    first = vocab // bv
    if first >= n_v:
        body(False)
    elif first == 0:
        body(True)
    else:
        pl.when(j < first)(lambda: body(False))
        pl.when(j >= first)(lambda: body(True))


def _chunk(hn, w_ref, j, *, bn, bv, vocab, transpose_w, softcap, masked):
    """One vocabulary chunk: ``(w, s, dcap, valid)``, the W tile and its
    logits ``s`` in the unembed convention (``_tile_logits``).  On a
    masked chunk W's rows (tied) or columns (untied) at or past ``vocab``
    are zeroed BEFORE any product — a ragged last block reads whatever
    lies past the array (NaN in interpret mode), and ``d`` = 0 there
    would still make 0 * NaN = NaN in d_hidden — and those columns'
    logits are forced to NEG_INF (``valid`` marks the rest; None on a
    full chunk)."""
    w = w_ref[...]
    if masked:
        axis = 1 if transpose_w else 0
        rows = j * bv + jax.lax.broadcasted_iota(jnp.int32, w.shape, axis)
        w = jnp.where(rows < vocab, w, jnp.zeros_like(w))
    z, dcap = _tile_logits(hn, w, transpose_w, softcap)
    if not masked:
        return w, z, dcap, None
    valid = _tile_cols(j, bn, bv) < vocab
    return w, jnp.where(valid, z, NEG_INF), dcap, valid


# ---------------------------------------------------------------------------
# forward kernels


def _ce_fwd_kernel(np_ref, lab_ref, h_ref, w_ref, lse_out, ll_out,
                   m_scr, l_scr, ll_scr, *,
                   bn, bv, vocab, n_v, transpose_w, softcap, norm, eps,
                   interpret):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        ll_scr[...] = jnp.zeros_like(ll_scr[...])

    hn = apply_norm(h_ref[...], np_ref[...], norm, eps)

    def chunk(masked):
        _, s, _, valid = _chunk(hn, w_ref, j, bn=bn, bv=bv, vocab=vocab,
                                transpose_w=transpose_w, softcap=softcap,
                                masked=masked)
        m_new, l_new = online_lse_step(m_scr[...][:, 0], l_scr[...][:, 0],
                                       s, valid)
        m_scr[...] = m_new[:, None]
        l_scr[...] = l_new[:, None]
        ll_scr[...] += _label_logit_tile(s, lab_ref[...][:, 0], j, bn, bv,
                                         interpret)[:, None]

    _by_chunk(j, bv, vocab, n_v, chunk)

    @pl.when(j == n_v - 1)
    def _flush():
        lse_out[...] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-37))
        ll_out[...] = ll_scr[...]


def _ce_fwd_sample_kernel(np_ref, seed_ref, h_ref, w_ref, lse_out, ll_out,
                          yhat_out, m_scr, l_scr, zm_scr, zi_scr, zl_scr, *,
                          bn, bv, vocab, n_v, transpose_w, softcap, norm,
                          eps):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        zm_scr[...] = jnp.full_like(zm_scr[...], NEG_INF)
        zi_scr[...] = jnp.zeros_like(zi_scr[...])
        zl_scr[...] = jnp.zeros_like(zl_scr[...])

    hn = apply_norm(h_ref[...], np_ref[...], norm, eps)

    def chunk(masked):
        _, s, _, valid = _chunk(hn, w_ref, j, bn=bn, bv=bv, vocab=vocab,
                                transpose_w=transpose_w, softcap=softcap,
                                masked=masked)
        m_new, l_new = online_lse_step(m_scr[...][:, 0], l_scr[...][:, 0],
                                       s, valid)
        m_scr[...] = m_new[:, None]
        l_scr[...] = l_new[:, None]

        # online Gumbel-argmax: perturb this chunk, keep the running best,
        # remembering the winning column's RAW logit so the sampled-label
        # NLL needs no second pass; the noise is keyed on global columns
        # and rows (the seed operand's third word is the first row's), so
        # any chunking draws the same samples
        rows = (i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 0)
                ).astype(_u32) + seed_ref[2]
        g = hash_gumbel((seed_ref[0], seed_ref[1]), rows,
                        _tile_cols(j, bn, bv))
        zp = s + g if valid is None else jnp.where(valid, s + g, NEG_INF)
        zm, zi, zl = online_argmax_step(
            (zm_scr[...][:, 0], zi_scr[...][:, 0], zl_scr[...][:, 0]),
            s, zp, j * bv)
        zm_scr[...] = zm[:, None]
        zi_scr[...] = zi[:, None]
        zl_scr[...] = zl[:, None]

    _by_chunk(j, bv, vocab, n_v, chunk)

    @pl.when(j == n_v - 1)
    def _flush():
        lse_out[...] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-37))
        ll_out[...] = zl_scr[...]
        yhat_out[...] = zi_scr[...]


# ---------------------------------------------------------------------------
# backward kernels (shared by the labeled and sampled paths)


def _dlogits_tile(s, dcap, lab, rs, lse, j, *, bn, bv, interpret):
    """d_logits_raw (bn, bv) fp32 of one chunk from its logits ``s``
    (``_chunk``): ``(softmax - onehot(lab)) * rowscale``, softcap chain
    rule applied, exactly zero on columns past the vocabulary (p = 0 and
    onehot = 0 there).  Interpret mode subtracts the onehot term with a
    (bn,)-sized scatter-add instead of materializing the (bn, bv) compare
    (cheap on CPU, not Mosaic-lowerable on TPU)."""
    p = jnp.exp(s - lse[:, None])
    if interpret:
        d = p * rs[:, None]
        idx = lab - j * bv
        ok = (idx >= 0) & (idx < bv)
        d = d.at[jnp.arange(bn), jnp.clip(idx, 0, bv - 1)].add(
            jnp.where(ok, -rs, 0.0))
    else:
        onehot = (_tile_cols(j, bn, bv) == lab[:, None]).astype(_f32)
        d = (p - onehot) * rs[:, None]
    if dcap is not None:
        d = d * dcap
    return d


def _chunk_dlogits(hn, w_ref, lab_ref, rs_ref, lse_ref, j, *, bn, bv, vocab,
                   transpose_w, softcap, interpret, masked):
    """(w, d): one chunk's W tile (tail zeroed on a masked chunk) and its
    recomputed d_logits, shared by every backward kernel."""
    w, s, dcap, _ = _chunk(hn, w_ref, j, bn=bn, bv=bv, vocab=vocab,
                           transpose_w=transpose_w, softcap=softcap,
                           masked=masked)
    return w, _dlogits_tile(s, dcap, lab_ref[...][:, 0], rs_ref[...][:, 0],
                            lse_ref[...][:, 0], j, bn=bn, bv=bv,
                            interpret=interpret)


def _dh_update(acc, d, w, transpose_w):
    """acc += d @ W^T for one chunk, in fp32."""
    w32 = w.astype(_f32)
    if transpose_w:                       # w tile (D, bv): dh = d @ w^T
        acc[...] += jnp.dot(d, w32.T, preferred_element_type=_f32)
    else:                                 # w tile (bv, D): dh = d @ w
        acc[...] += jnp.dot(d, w32, preferred_element_type=_f32)


def _dw_update(acc, d, hn, transpose_w):
    """acc += d^T @ h for one chunk's dW tile, in fp32."""
    h32 = hn.astype(_f32)
    if transpose_w:                       # dW tile (D, bv) = h^T @ d
        acc[...] += jnp.dot(h32.T, d, preferred_element_type=_f32)
    else:                                 # dW tile (bv, D) = d^T @ h
        acc[...] += jnp.dot(d.T, h32, preferred_element_type=_f32)


def _ce_bwd_dh_kernel(np_ref, lab_ref, rs_ref, lse_ref, h_ref, w_ref, dh_out,
                      acc_scr, *, bn, bv, vocab, n_v, transpose_w,
                      softcap, norm, eps, interpret):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    hn = apply_norm(h_ref[...], np_ref[...], norm, eps)

    def chunk(masked):
        w, d = _chunk_dlogits(hn, w_ref, lab_ref, rs_ref, lse_ref, j, bn=bn,
                              bv=bv, vocab=vocab, transpose_w=transpose_w,
                              softcap=softcap, interpret=interpret,
                              masked=masked)
        _dh_update(acc_scr, d, w, transpose_w)

    _by_chunk(j, bv, vocab, n_v, chunk)

    @pl.when(j == n_v - 1)
    def _flush():
        dh_out[...] = acc_scr[...].astype(dh_out.dtype)


def _ce_bwd_dw_kernel(np_ref, lab_ref, rs_ref, lse_ref, h_ref, w_ref, dw_out,
                      acc_scr, *, bn, bv, vocab, n_v, n_r, transpose_w,
                      softcap, norm, eps, interpret):
    # grid (chunks, rows): the dW block for chunk j accumulates across the
    # inner row sweep in an fp32 VMEM scratch (accumulating in the output
    # dtype would round the partial sum per row tile — per-mille error for
    # bf16 weights at real tile counts) and rounds ONCE at the flush; a
    # ragged last block's rows past Vp are dropped by the write-back.
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    hn = apply_norm(h_ref[...], np_ref[...], norm, eps)

    def chunk(masked):
        _, d = _chunk_dlogits(hn, w_ref, lab_ref, rs_ref, lse_ref, j, bn=bn,
                              bv=bv, vocab=vocab, transpose_w=transpose_w,
                              softcap=softcap, interpret=interpret,
                              masked=masked)
        _dw_update(acc_scr, d, hn, transpose_w)

    _by_chunk(j, bv, vocab, n_v, chunk)

    @pl.when(i == n_r - 1)
    def _flush():
        dw_out[...] = acc_scr[...].astype(dw_out.dtype)


def _ce_bwd_fused_kernel(np_ref, lab_ref, rs_ref, lse_ref, h_ref, w_ref,
                         dh_out, dw_out, dh_scr, dw_scr, *,
                         bn, bv, vocab, n_r, n_v, transpose_w, softcap,
                         norm, eps, interpret):
    """Combined d_hidden + d_W in ONE sweep: the logits tile is recomputed
    once per grid step and feeds both products (the split schedule
    recomputes it twice).  Requires min(n_r, n_v) == 1 — then the dh scratch
    is either flushed per step (n_v == 1: each row block's sweep is a single
    step) or accumulated over the whole inner-j sweep of the only row block
    (n_r == 1), and symmetrically for dW, so neither output block is ever
    revisited after a different block was written (Pallas TPU pipelining
    does not re-fetch non-consecutively revisited output blocks)."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_dh():
        dh_scr[...] = jnp.zeros_like(dh_scr[...])

    @pl.when(i == 0)
    def _init_dw():
        dw_scr[...] = jnp.zeros_like(dw_scr[...])

    hn = apply_norm(h_ref[...], np_ref[...], norm, eps)

    def chunk(masked):
        w, d = _chunk_dlogits(hn, w_ref, lab_ref, rs_ref, lse_ref, j, bn=bn,
                              bv=bv, vocab=vocab, transpose_w=transpose_w,
                              softcap=softcap, interpret=interpret,
                              masked=masked)
        _dh_update(dh_scr, d, w, transpose_w)
        _dw_update(dw_scr, d, hn, transpose_w)

    _by_chunk(j, bv, vocab, n_v, chunk)

    @pl.when(j == n_v - 1)
    def _flush_dh():
        dh_out[...] = dh_scr[...].astype(dh_out.dtype)

    @pl.when(i == n_r - 1)
    def _flush_dw():
        dw_out[...] = dw_scr[...].astype(dw_out.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers


#: the kernels' names in a compiled program (the Mosaic module's
#: ``sym_name``, which a profile reader matches); pinned here so that
#: renaming a kernel function does not rename its ops
KERNEL_NAMES = (FWD_NAME, FWD_SAMPLE_NAME, BWD_FUSED_NAME, BWD_DH_NAME,
                BWD_DW_NAME) = ("_ce_fwd_kernel", "_ce_fwd_sample_kernel",
                                "_ce_bwd_fused_kernel", "_ce_bwd_dh_kernel",
                                "_ce_bwd_dw_kernel")


def _specs(bn, bv, D, transpose_w):
    """Grid (rows, chunks) BlockSpecs.  Per-row vectors (labels, row
    scales, lse, label logits, samples) travel as ``(N, 1)`` columns: a
    ``(bn, 1)`` block meets the TPU tiling rule, where a rank-1 ``(bn,)``
    block would have to match XLA's 1-D layout tile."""
    h_spec = pl.BlockSpec((bn, D), lambda i, j: (i, 0))
    w_spec = (pl.BlockSpec((D, bv), lambda i, j: (0, j)) if transpose_w
              else pl.BlockSpec((bv, D), lambda i, j: (j, 0)))
    vec_spec = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    np_spec = pl.BlockSpec((2, D), lambda i, j: (0, 0))
    return h_spec, w_spec, vec_spec, np_spec


def _col(x):
    return x.reshape(-1, 1)


def _vp_of(w, transpose_w):
    return w.shape[1] if transpose_w else w.shape[0]


def _call(kernel, *, grid, ragged, name, **kw):
    """``pl.pallas_call`` with the CE kernels' scoped VMEM limit.  The
    call's grid steps go to the trace-time counters (``ce_grid_steps``),
    and so does a call whose last vocabulary chunk runs past Vp
    (``ce_ragged_calls``)."""
    KERNEL_CALLS["ce_grid_steps"] += grid[0] * grid[1]
    KERNEL_CALLS["ce_ragged_calls"] += int(ragged)
    return pl.pallas_call(
        kernel, grid=grid, name=name,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES), **kw)


def _no_normp(D, normp=None):
    return jnp.zeros((2, D), _f32) if normp is None else normp


def _ce_forward(h2, w, normp, labels, *, vocab, transpose_w, softcap, norm,
                eps, bn, bv, interpret):
    KERNEL_CALLS["fwd"] += 1
    N, D = h2.shape
    Vp = _vp_of(w, transpose_w)
    n_r, n_v = N // bn, pl.cdiv(Vp, bv)
    h_spec, w_spec, vec_spec, np_spec = _specs(bn, bv, D, transpose_w)
    kern = functools.partial(_ce_fwd_kernel, bn=bn, bv=bv, vocab=vocab,
                             n_v=n_v, transpose_w=transpose_w,
                             softcap=softcap, norm=norm, eps=eps,
                             interpret=interpret)
    lse, ll = _call(
        kern,
        grid=(n_r, n_v),
        ragged=n_v * bv != Vp,
        in_specs=[np_spec, vec_spec, h_spec, w_spec],
        out_specs=[vec_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct((N, 1), _f32),
                   jax.ShapeDtypeStruct((N, 1), _f32)],
        scratch_shapes=[pltpu.VMEM((bn, 1), _f32)] * 3,
        interpret=interpret,
        name=FWD_NAME,
    )(_no_normp(D, normp), _col(labels), h2, w)
    return lse[:, 0], ll[:, 0]


def _ce_forward_sampled(h2, w, normp, seed, *, vocab, transpose_w, softcap,
                        norm, eps, bn, bv, interpret):
    KERNEL_CALLS["fwd_sample"] += 1
    N, D = h2.shape
    Vp = _vp_of(w, transpose_w)
    n_r, n_v = N // bn, pl.cdiv(Vp, bv)
    h_spec, w_spec, vec_spec, np_spec = _specs(bn, bv, D, transpose_w)
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(_ce_fwd_sample_kernel, bn=bn, bv=bv,
                             vocab=vocab, n_v=n_v, transpose_w=transpose_w,
                             softcap=softcap, norm=norm, eps=eps)
    outs = _call(
        kern,
        grid=(n_r, n_v),
        ragged=n_v * bv != Vp,
        in_specs=[np_spec, seed_spec, h_spec, w_spec],
        out_specs=[vec_spec, vec_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct((N, 1), _f32),
                   jax.ShapeDtypeStruct((N, 1), _f32),
                   jax.ShapeDtypeStruct((N, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((bn, 1), _f32),
                        pltpu.VMEM((bn, 1), _f32),
                        pltpu.VMEM((bn, 1), _f32),
                        pltpu.VMEM((bn, 1), jnp.int32),
                        pltpu.VMEM((bn, 1), _f32)],
        interpret=interpret,
        name=FWD_SAMPLE_NAME,
    )(_no_normp(D, normp), seed, h2, w)
    return tuple(o[:, 0] for o in outs)


def _ce_backward(h2, w, normp, labels, rs, lse, *, vocab, transpose_w,
                 softcap, norm, eps, bn, bv, schedule, interpret):
    """(d_hidden_normed, d_W) via vocab re-sweeps (no [N, V] buffer).

    With a fused norm the returned d_hidden is the cotangent w.r.t. the
    NORMED hidden (fp32); the caller pulls it back through the norm with
    ``jax.vjp(apply_norm, ...)``."""
    N, D = h2.shape
    Vp = _vp_of(w, transpose_w)
    n_r, n_v = N // bn, pl.cdiv(Vp, bv)
    dh_dtype = _f32 if norm is not None else h2.dtype
    h_spec, w_spec, vec_spec, np_spec = _specs(bn, bv, D, transpose_w)
    normp = _no_normp(D, normp)
    labels, rs, lse = _col(labels), _col(rs), _col(lse)
    dw_scr = pltpu.VMEM((D, bv) if transpose_w else (bv, D), _f32)

    if schedule == "fused":
        assert n_r == 1 or n_v == 1, (n_r, n_v)
        KERNEL_CALLS["bwd_fused"] += 1
        kern = functools.partial(
            _ce_bwd_fused_kernel, bn=bn, bv=bv, vocab=vocab, n_r=n_r,
            n_v=n_v, transpose_w=transpose_w, softcap=softcap, norm=norm,
            eps=eps, interpret=interpret)
        dh, dw = _call(
            kern,
            grid=(n_r, n_v),
            ragged=n_v * bv != Vp,
            in_specs=[np_spec, vec_spec, vec_spec, vec_spec, h_spec, w_spec],
            out_specs=[pl.BlockSpec((bn, D), lambda i, j: (i, 0)), w_spec],
            out_shape=[jax.ShapeDtypeStruct((N, D), dh_dtype),
                       jax.ShapeDtypeStruct(w.shape, w.dtype)],
            scratch_shapes=[pltpu.VMEM((bn, D), _f32), dw_scr],
            interpret=interpret,
            name=BWD_FUSED_NAME,
        )(normp, labels, rs, lse, h2, w)
        return dh, dw

    assert schedule == "split", schedule
    KERNEL_CALLS["bwd_split"] += 1
    kern_h = functools.partial(_ce_bwd_dh_kernel, bn=bn, bv=bv, vocab=vocab,
                               n_v=n_v, transpose_w=transpose_w,
                               softcap=softcap, norm=norm, eps=eps,
                               interpret=interpret)
    dh = _call(
        kern_h,
        grid=(n_r, n_v),
        ragged=n_v * bv != Vp,
        in_specs=[np_spec, vec_spec, vec_spec, vec_spec, h_spec, w_spec],
        out_specs=pl.BlockSpec((bn, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), dh_dtype),
        scratch_shapes=[pltpu.VMEM((bn, D), _f32)],
        interpret=interpret,
        name=BWD_DH_NAME,
    )(normp, labels, rs, lse, h2, w)

    # rows innermost so each dW chunk block accumulates while resident
    hT_spec = pl.BlockSpec((bn, D), lambda j, i: (i, 0))
    wT_spec = (pl.BlockSpec((D, bv), lambda j, i: (0, j)) if transpose_w
               else pl.BlockSpec((bv, D), lambda j, i: (j, 0)))
    vT_spec = pl.BlockSpec((bn, 1), lambda j, i: (i, 0))
    npT_spec = pl.BlockSpec((2, D), lambda j, i: (0, 0))
    kern_w = functools.partial(_ce_bwd_dw_kernel, bn=bn, bv=bv, vocab=vocab,
                               n_v=n_v, n_r=n_r, transpose_w=transpose_w,
                               softcap=softcap, norm=norm, eps=eps,
                               interpret=interpret)
    dw = _call(
        kern_w,
        grid=(n_v, n_r),
        ragged=n_v * bv != Vp,
        in_specs=[npT_spec, vT_spec, vT_spec, vT_spec, hT_spec, wT_spec],
        out_specs=wT_spec,
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        scratch_shapes=[dw_scr],
        interpret=interpret,
        name=BWD_DW_NAME,
    )(normp, labels, rs, lse, h2, w)
    return dh, dw


# ---------------------------------------------------------------------------
# custom_vjp plumbing


def _float0(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


_NONDIFF = (5, 6, 7, 8, 9, 10, 11, 12, 13)
#           vocab, transpose_w, softcap, norm, eps, bn, bv, schedule,
#           interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=_NONDIFF)
def _fused_nll(h2, w, normp, labels, rowscale, vocab, transpose_w, softcap,
               norm, eps, bn, bv, schedule, interpret):
    """sum(rowscale * nll) with labels fixed; logits never materialize."""
    loss, _ = _fused_nll_fwd(h2, w, normp, labels, rowscale, vocab,
                             transpose_w, softcap, norm, eps, bn, bv,
                             schedule, interpret)
    return loss


def _fused_nll_fwd(h2, w, normp, labels, rowscale, vocab, transpose_w,
                   softcap, norm, eps, bn, bv, schedule, interpret):
    lse, ll = _ce_forward(h2, w, normp, labels, vocab=vocab,
                          transpose_w=transpose_w, softcap=softcap,
                          norm=norm, eps=eps, bn=bn, bv=bv,
                          interpret=interpret)
    loss = jnp.sum(rowscale * (lse - ll))
    return loss, (h2, w, normp, labels, rowscale, lse, ll)


def _norm_pullback(h2, normp, norm, eps, dhn):
    """Pull the kernel's d(normed hidden) back through the norm producer
    with the differentiable twin of the in-kernel math (exact: same fp32
    statistics, same cast)."""
    if norm is None:
        return dhn.astype(h2.dtype), jnp.zeros_like(normp)
    _, pull = jax.vjp(lambda x, p: apply_norm(x, p, norm, eps).astype(_f32),
                      h2, normp)
    return pull(dhn)


def _fused_nll_bwd(vocab, transpose_w, softcap, norm, eps, bn, bv, schedule,
                   interpret, res, g):
    h2, w, normp, labels, rowscale, lse, ll = res
    rs = (rowscale * g).astype(_f32)
    dhn, dw = _ce_backward(h2, w, normp, labels, rs, lse, vocab=vocab,
                           transpose_w=transpose_w, softcap=softcap,
                           norm=norm, eps=eps, bn=bn, bv=bv,
                           schedule=schedule, interpret=interpret)
    dh, dnormp = _norm_pullback(h2, normp, norm, eps, dhn)
    return dh, dw, dnormp, _float0(labels), (lse - ll) * g


_fused_nll.defvjp(_fused_nll_fwd, _fused_nll_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=_NONDIFF)
def _fused_sampled_nll(h2, w, normp, seed, rowscale, vocab, transpose_w,
                       softcap, norm, eps, bn, bv, schedule, interpret):
    """sum(rowscale * nll) against in-sweep sampled labels (GNB path)."""
    loss, _ = _fused_sampled_nll_fwd(h2, w, normp, seed, rowscale, vocab,
                                     transpose_w, softcap, norm, eps, bn, bv,
                                     schedule, interpret)
    return loss


def _fused_sampled_nll_fwd(h2, w, normp, seed, rowscale, vocab, transpose_w,
                           softcap, norm, eps, bn, bv, schedule, interpret):
    lse, ll, yhat = _ce_forward_sampled(
        h2, w, normp, seed, vocab=vocab, transpose_w=transpose_w,
        softcap=softcap, norm=norm, eps=eps, bn=bn, bv=bv,
        interpret=interpret)
    loss = jnp.sum(rowscale * (lse - ll))
    return loss, (h2, w, normp, seed, yhat, rowscale, lse, ll)


def _fused_sampled_nll_bwd(vocab, transpose_w, softcap, norm, eps, bn, bv,
                           schedule, interpret, res, g):
    h2, w, normp, seed, yhat, rowscale, lse, ll = res
    rs = (rowscale * g).astype(_f32)
    dhn, dw = _ce_backward(h2, w, normp, yhat, rs, lse, vocab=vocab,
                           transpose_w=transpose_w, softcap=softcap,
                           norm=norm, eps=eps, bn=bn, bv=bv,
                           schedule=schedule, interpret=interpret)
    dh, dnormp = _norm_pullback(h2, normp, norm, eps, dhn)
    return dh, dw, dnormp, _float0(seed), (lse - ll) * g


_fused_sampled_nll.defvjp(_fused_sampled_nll_fwd, _fused_sampled_nll_bwd)


# ---------------------------------------------------------------------------
# custom_jvp twin: the Hutchinson HVP path
#
# ``jax.jvp(jax.grad(f))`` cannot cross a custom_vjp (no JVP rule for the
# residual application), and the Pallas backward kernels can never sit
# inside an HVP anyway (forward-mode would have to differentiate them).
# This twin keeps the Pallas forward as the primal and defines the tangent
# as ONE checkpointed chunked jnp sweep that also recomputes the
# (lse, label-logit) coefficients online — linear in (dh, dw, drs), so
# JAX's transpose machinery derives a chunked jnp backward, and because the
# rule is built from differentiable jnp (plus a recursive primal self-call
# that re-enters this boundary), it composes to arbitrary order.


@functools.partial(jax.custom_jvp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _fused_nll_jvp(h2, w, labels, rowscale, vocab, transpose_w, softcap,
                   bn, bv, interpret):
    lse, ll = _ce_forward(h2, w, None, labels, vocab=vocab,
                          transpose_w=transpose_w, softcap=softcap,
                          norm=None, eps=0.0, bn=bn, bv=bv,
                          interpret=interpret)
    return jnp.sum(rowscale * (lse - ll))


def _chunk_z(h2, w, dh2, dw, c, bvt, *, transpose_w, softcap, vocab):
    """One vocab chunk's masked logits ``s`` and (optionally) their
    tangent ``ds`` in the unembed convention.  Pass dh2=dw=None for the
    primal-only variant."""
    cdt = h2.dtype
    axis = 1 if transpose_w else 0
    wc = jax.lax.dynamic_slice_in_dim(w, c * bvt, bvt, axis=axis)
    wc = wc.astype(cdt) if transpose_w else wc.astype(cdt).T
    raw = jnp.dot(h2, wc, preferred_element_type=_f32)
    draw = None
    if dh2 is not None:
        dwc = jax.lax.dynamic_slice_in_dim(dw, c * bvt, bvt, axis=axis)
        dwc = dwc.astype(cdt) if transpose_w else dwc.astype(cdt).T
        draw = (jnp.dot(dh2, wc, preferred_element_type=_f32)
                + jnp.dot(h2, dwc, preferred_element_type=_f32))
    if softcap is not None:
        t = jnp.tanh(raw / softcap)
        z = softcap * t
        dz = None if draw is None else (1.0 - t * t) * draw
    else:
        z, dz = raw, draw
    cols = c * bvt + jnp.arange(bvt, dtype=jnp.int32)[None, :]
    valid = cols < vocab
    s = jnp.where(valid, z, NEG_INF)
    return s, dz, cols


@_fused_nll_jvp.defjvp
def _fused_nll_jvp_rule(vocab, transpose_w, softcap, bn, bv, interpret,
                        primals, tangents):
    h2, w, labels, rowscale = primals
    dh2, dw, _dlab, drs = tangents
    KERNEL_CALLS["jvp_rule"] += 1
    # primal through the custom_jvp boundary itself: at higher orders the
    # rule re-enters here instead of hitting a bare (non-differentiable)
    # pallas_call
    loss = _fused_nll_jvp(h2, w, labels, rowscale, vocab, transpose_w,
                          softcap, bn, bv, interpret)

    # Two chunked jnp sweeps.  Sweep A (a checkpointed scan, primal-only)
    # recomputes the online (lse, label-logit) coefficients; sweep B is
    # LINEAR in (dh2, dw) and accumulates the tangent reductions
    # dlse = sum_c p_c . dz_c (p = exp(s - lse)) and d(label-logit).
    # Splitting matters: mixing primal and tangent work in one scan leaves
    # the scan untransposable (lax.scan partial-eval cannot separate the
    # linear part when tangents enter as body constants — the transpose
    # asserts on undefined-primal residuals), while this layout transposes
    # into the standard chunked CE backward and stays jvp-able for higher
    # orders.  Sweep B must also be an UNROLLED Python loop rather than a
    # scan, for the same constants reason; its chunk count is small
    # (Vp / 2048) and under jax.grad its per-chunk softmax residuals live
    # only on the Hutchinson sub-batch (hess_subbatch rows), never on the
    # training batch, which keeps the custom_vjp kernels.
    N, D = h2.shape
    Vp = _vp_of(w, transpose_w)
    bvt = vocab_chunk(Vp, 2048, 128)
    n_c = Vp // bvt
    lab = labels.reshape(-1)
    dh2 = dh2.astype(h2.dtype)

    def body_primal(carry, c):
        m, l, ll = carry
        s, _, cols = _chunk_z(h2, w, None, None, c, bvt,
                              transpose_w=transpose_w, softcap=softcap,
                              vocab=vocab)
        m_new, l_new = online_lse_step(m, l, s, cols < vocab)
        ll = ll + jnp.where(cols == lab[:, None], s, 0.0).sum(-1)
        return (m_new, l_new, ll), None

    init = (jnp.full((N,), NEG_INF, _f32), jnp.zeros((N,), _f32),
            jnp.zeros((N,), _f32))
    (m, l, ll), _ = jax.lax.scan(jax.checkpoint(body_primal), init,
                                 jnp.arange(n_c))
    lse = m + jnp.log(jnp.maximum(l, 1e-37))

    u = jnp.zeros((N,), _f32)
    dll = jnp.zeros((N,), _f32)
    for c in range(n_c):
        s, dz, cols = _chunk_z(h2, w, dh2, dw, c, bvt,
                               transpose_w=transpose_w, softcap=softcap,
                               vocab=vocab)
        p = jnp.exp(s - lse[:, None])        # 0 on padded cols (s=NEG_INF)
        u = u + (p * dz).sum(-1)
        dll = dll + jnp.where(cols == lab[:, None], dz, 0.0).sum(-1)
    dloss = jnp.sum(rowscale * (u - dll)) \
        + jnp.sum(jnp.asarray(drs, _f32) * (lse - ll))
    return loss, dloss


# ---------------------------------------------------------------------------
# public entry points


def vmem_bytes(bn, bv, D, *, bytes_h, bytes_w=4) -> int:
    """The largest VMEM working set of the CE kernels at a (bn, bv)
    tiling, the autotuner's fit test against ``VMEM_LIMIT_BYTES``: the
    double-buffered h and W tiles, W cast to the hidden dtype, the normed
    hidden tile in fp32, the fp32 logits tile with three temporaries of its
    size (p, d and d's transpose), the fp32 dh or dW accumulator with its
    double-buffered output block, and nine ``(bn, 1)`` row columns (blocks
    and scratch), each padded to 128 lanes."""
    return (2 * (bn * D * bytes_h + bv * D * bytes_w)
            + bv * D * bytes_h + bn * D * 4 + 4 * bn * bv * 4
            + 3 * max(bn, bv) * D * 4 + 9 * bn * LANES * 4)


def _pick_block(n, want, quantum):
    """Largest multiple of ``quantum`` <= want dividing n, else (quantum,
    pad) where pad rounds n up to a quantum multiple."""
    want = max(quantum, min(want, n))
    b = (want // quantum) * quantum
    while b >= quantum:
        if n % b == 0:
            return b, 0
        b -= quantum
    return quantum, (-n) % quantum


def _pad_rows(h2, block_n, *rows):
    """Pad the flattened rows to a block multiple (padded rows carry
    rowscale 0, so they contribute nothing to loss or gradients).  Returns
    (h2, bn, *rows) with each ``rows`` vector padded alike."""
    bn, pad = _pick_block(h2.shape[0], block_n, 8)
    if pad:
        h2 = jnp.pad(h2, ((0, pad), (0, 0)))
        rows = tuple(jnp.pad(r, (0, pad)) for r in rows)
    return (h2, bn) + tuple(rows)


def _row_mapped(local, hidden, w, normp, aux, rs, *, aux_rows):
    """Sum of ``local(row0, hidden_rows, w, normp, aux, rs_rows)`` over the
    batch.  Under an activation mesh each device runs ``local`` on its own
    batch shard (XLA cannot partition the kernels), ``row0`` being the
    global index of the shard's first row; without one it is one call
    with ``row0 = 0``.  ``rs`` is the global row scale, flattened;
    ``aux`` is per row (labels, ``aux_rows``) or whole (a seed)."""
    lead = hidden.shape[:-1]
    rows_spec = P("batch", *([None] * (len(lead) - 1)))
    h_spec = P("batch", *([None] * len(lead)))
    aux_spec = rows_spec if aux_rows else P()

    def body(idx, h, w_, normp_, aux_, rs_):
        n_rows = rs_.size
        return local(idx * n_rows, h.reshape(n_rows, h.shape[-1]), w_,
                     normp_, aux_, rs_.reshape(-1)).reshape(1)

    fn = per_device(body, (h_spec, P(), P(), aux_spec, rows_spec),
                    P("batch"), lead[0] if lead else 1)
    return fn(hidden, w, normp, aux, rs.reshape(lead)).sum()


def _pick_bv(Vp, block_v, interpret=False):
    """Vocab chunk for an *explicit* request: ``block_v`` in whole 128-lane
    groups, within [128, Vp]; where it does not divide Vp the last chunk is
    ragged.  Interpret mode (CPU CI) widens it so that an over-chunked
    request cannot unroll more than 64 interpreter grid cells per row."""
    assert Vp % LANES == 0, f"padded vocab {Vp} not a multiple of 128"
    bv = min(max(LANES, block_v - block_v % LANES), Vp)
    if interpret and pl.cdiv(Vp, bv) > 64:
        bv = pl.cdiv(Vp, 64 * LANES) * LANES
    return bv


def _resolve_blocks(hidden, Vp, *, transpose_w, softcap, norm, block_n,
                    block_v, schedule, interpret):
    """(bn, bv, schedule): explicit blocks pass through (legacy/unit-test
    path, DEFAULT_BN/BV filling the unset one); both-None routes through
    the shape-keyed autotuner."""
    D = hidden.shape[-1]
    N = 1
    for s in hidden.shape[:-1]:
        N *= s
    n_pad = N + ((-N) % 8)
    if block_n is None and block_v is None:
        from .autotune import get_tuned
        t = get_tuned(n_pad, D, Vp, dtype=hidden.dtype,
                      transpose_w=transpose_w, softcap=softcap, norm=norm,
                      interpret=interpret)
        bn, bv = t.bn, t.bv
        schedule = schedule or t.schedule
    else:
        bn, _ = _pick_block(N, block_n or DEFAULT_BN, 8)
        bv = _pick_bv(Vp, block_v or DEFAULT_BV, interpret)
    n_r, n_v = n_pad // bn, pl.cdiv(Vp, bv)
    if schedule is None:
        schedule = "fused" if (n_r == 1 or n_v == 1) else "split"
    if schedule == "fused" and not (n_r == 1 or n_v == 1):
        schedule = "split"
    # the tiling of this call shape, and each of its kernels' grid steps
    KERNEL_CALLS[f"ce_tiling {n_pad}x{D}x{Vp} bn{bn} bv{bv} {schedule} "
                 f"steps{n_r * n_v}"] += 1
    return bn, bv, schedule


def _pack_norm(norm_kind, norm_scale, norm_bias, D):
    if norm_kind is None:
        return None, None
    assert norm_kind in ("rms", "ln"), norm_kind
    scale = jnp.asarray(norm_scale, _f32)
    bias = (jnp.zeros((D,), _f32) if norm_bias is None
            else jnp.asarray(norm_bias, _f32))
    return norm_kind, jnp.stack([scale, bias])


def fused_lm_loss(hidden, w, labels, mask=None, *, vocab_size,
                  transpose_w=False, softcap=None, block_n=None,
                  block_v=None, schedule=None, norm_kind=None,
                  norm_scale=None, norm_bias=None, norm_eps=1e-6,
                  interpret=None):
    """Masked-mean LM cross-entropy without materializing logits.

    hidden (..., D); w (Vp, D) tied or (D, Vp) untied (``transpose_w``);
    labels (...) int; mask (...) optional.  Returns ``(loss, n_valid)`` —
    the batch factor the GNB refresh folds into the Hessian-EMA.
    Differentiable in ``hidden``, ``w`` and the norm parameters via the
    fused backward sweeps.  With ``norm_kind`` ("rms"/"ln") ``hidden`` is
    PRE-final-norm and the norm applies inside the kernel (producer
    fusion); block sizes default to the autotuner's pick for this shape.
    Under an activation mesh the sweep runs per batch shard.
    """
    softcap = float(softcap) if softcap else None
    interpret = bool(_interpret_default() if interpret is None
                     else interpret)
    norm, normp = _pack_norm(norm_kind, norm_scale, norm_bias,
                             hidden.shape[-1])
    rs, n_valid = rowscale(labels.size, mask)
    Vp = _vp_of(w, transpose_w)

    def local(row0, h2, w_, normp_, lab, rs_):
        del row0
        bn, bv, sched = _resolve_blocks(
            h2, Vp, transpose_w=bool(transpose_w), softcap=softcap,
            norm=norm, block_n=block_n, block_v=block_v, schedule=schedule,
            interpret=interpret)
        h2, bn, lab, rs_ = _pad_rows(h2, bn, lab.reshape(-1), rs_)
        return _fused_nll(h2, w_, normp_, lab, rs_, int(vocab_size),
                          bool(transpose_w), softcap, norm, float(norm_eps),
                          bn, bv, sched, interpret)

    loss = _row_mapped(local, hidden, w, _no_normp(hidden.shape[-1], normp),
                       labels.astype(jnp.int32), rs, aux_rows=True)
    return loss, n_valid


def fused_lm_loss_sampled(hidden, w, rng, mask=None, *, vocab_size,
                          transpose_w=False, softcap=None, block_n=None,
                          block_v=None, schedule=None, norm_kind=None,
                          norm_scale=None, norm_bias=None, norm_eps=1e-6,
                          interpret=None):
    """GNB sampled-label CE in one sweep: draws ``yhat ~ softmax(logits)``
    by online chunked Gumbel-argmax *inside* the forward kernel and returns
    the masked-mean NLL against it (``(loss, n_valid)``).  The gradient of
    ``loss`` is Algorithm 2's ``ghat`` contribution through this stage —
    logits-free in both directions.  The noise is keyed on global row
    indices, so a batch-sharded run draws the same samples."""
    softcap = float(softcap) if softcap else None
    interpret = bool(_interpret_default() if interpret is None
                     else interpret)
    norm, normp = _pack_norm(norm_kind, norm_scale, norm_bias,
                             hidden.shape[-1])
    rs, n_valid = rowscale(hidden.size // hidden.shape[-1], mask)
    Vp = _vp_of(w, transpose_w)

    def local(row0, h2, w_, normp_, seed, rs_):
        bn, bv, sched = _resolve_blocks(
            h2, Vp, transpose_w=bool(transpose_w), softcap=softcap,
            norm=norm, block_n=block_n, block_v=block_v, schedule=schedule,
            interpret=interpret)
        h2, bn, rs_ = _pad_rows(h2, bn, rs_)
        seed = jnp.concatenate([seed, jnp.asarray(row0, _u32).reshape(1)])
        return _fused_sampled_nll(h2, w_, normp_, seed, rs_,
                                  int(vocab_size), bool(transpose_w),
                                  softcap, norm, float(norm_eps), bn, bv,
                                  sched, interpret)

    loss = _row_mapped(local, hidden, w, _no_normp(hidden.shape[-1], normp),
                       seed_from_key(rng), rs, aux_rows=False)
    return loss, n_valid


def fused_lm_loss_jvp(hidden, w, labels, mask=None, *, vocab_size,
                      transpose_w=False, softcap=None, block_n=None,
                      block_v=None, interpret=None):
    """The labeled NLL through the custom_jvp twin: Pallas forward primal,
    chunked-jnp linear tangent (transposable -> chunked backward), composes
    under ``jax.jvp(jax.grad(.))`` — the Hutchinson estimator's path.  No
    kernel-fused norm here (apply it in jnp first: the tangent must flow
    through the norm, which the chunked rule handles for free)."""
    softcap = float(softcap) if softcap else None
    interpret = bool(_interpret_default() if interpret is None
                     else interpret)
    rs, n_valid = rowscale(labels.size, mask)
    Vp = _vp_of(w, transpose_w)

    def local(row0, h2, w_, normp_, lab, rs_):
        del row0, normp_
        bn, bv, _ = _resolve_blocks(
            h2, Vp, transpose_w=bool(transpose_w), softcap=softcap,
            norm=None, block_n=block_n, block_v=block_v, schedule="split",
            interpret=interpret)
        h2, bn, lab, rs_ = _pad_rows(h2, bn, lab.reshape(-1), rs_)
        return _fused_nll_jvp(h2, w_, lab, rs_, int(vocab_size),
                              bool(transpose_w), softcap, bn, bv, interpret)

    loss = _row_mapped(local, hidden, w, _no_normp(hidden.shape[-1]),
                       labels.astype(jnp.int32), rs, aux_rows=True)
    return loss, n_valid


def fused_lm_sample(hidden, w, rng, *, vocab_size, transpose_w=False,
                    softcap=None, block_n=None, block_v=None,
                    interpret=None):
    """The sampled labels alone (tests / diagnostics): yhat shaped like
    ``hidden[..., 0]``."""
    softcap = float(softcap) if softcap else None
    interpret = _interpret_default() if interpret is None else interpret
    shp = hidden.shape[:-1]
    bn, bv, _ = _resolve_blocks(
        hidden, _vp_of(w, transpose_w), transpose_w=bool(transpose_w),
        softcap=softcap, norm=None, block_n=block_n, block_v=block_v,
        schedule="split", interpret=bool(interpret))
    h2, bn = _pad_rows(hidden.reshape(-1, hidden.shape[-1]), bn)
    seed = jnp.concatenate([seed_from_key(rng), jnp.zeros((1,), _u32)])
    _, _, yhat = _ce_forward_sampled(
        h2, w, None, seed, vocab=int(vocab_size),
        transpose_w=bool(transpose_w), softcap=softcap, norm=None, eps=0.0,
        bn=bn, bv=bv, interpret=bool(interpret))
    n = 1
    for s in shp:
        n *= s
    return yhat[:n].reshape(shp)


# ---------------------------------------------------------------------------
# analytic HBM traffic (roofline overlay, analogous to
# flash_attention.attention_hbm_bytes_flash)


def lm_loss_hbm_bytes_fused(N, D, V, *, bytes_h=2, bytes_w=4,
                            norm_fused=False) -> int:
    """Fused path: hidden and W stream once per sweep (1 forward + 2
    backward), outputs are d_hidden + d_W + four (N,) vectors.  No term
    scales with N*V.  ``norm_fused`` removes the separate final-norm pass's
    (N, D) write + read — the kernel consumes pre-norm tiles and norms in
    VMEM."""
    h = N * D * bytes_h
    wb = V * D * bytes_w
    vecs = 4 * N * 4
    total = 3 * (h + wb) + h + wb + vecs
    if not norm_fused:
        total += 2 * h  # standalone norm: write normed (N, D), re-read it
    return total


def lm_loss_hbm_bytes_unfused(N, D, V, *, bytes_h=2, bytes_w=4,
                              passes=5) -> int:
    """Unfused XLA path: the fp32 [N, V] logits cross HBM ~``passes``
    times (projection write, log_softmax read/write, NLL gather read,
    backward softmax read) on top of the projection operands."""
    return N * V * 4 * passes + 2 * (N * D * bytes_h + V * D * bytes_w)
