"""One LM-loss entry point for every model family (logits-free by default).

All four families' ``loss_fn`` route their final-norm hidden states through
:func:`lm_loss` (and the GNB refresh through :func:`lm_loss_sampled`), which
honors ``padded_vocab`` masking, tied/untied embeddings and the gemma2
final-logit softcap in every implementation:

  fused     Pallas chunked-vocab kernel (kernels/fused_ce.py): lm_head
            weight tiles stream through VMEM, the [B*T, V] logits never
            touch HBM, and the sampled-label GNB draw happens inside the
            same sweep (online chunked Gumbel-argmax).  Block sizes come
            from the shape-keyed autotuner (kernels/autotune.py).  With
            ``pre_norm`` the final-norm producer fuses into the sweep too
            (the kernel reads pre-norm tiles, norms in VMEM — one less
            (N, D) HBM round-trip).  The default.
  fused_jvp the fused kernel's ``custom_jvp`` twin (Pallas primal, linear
            chunked-jnp tangent): the ONLY fused path that composes under
            ``jax.jvp(jax.grad(.))`` — the Hutchinson estimator's HVP —
            because a custom_vjp cannot be forward-differentiated.
  chunked   pure-jnp vocab-chunk scan with a checkpointed body — the
            compiled logits-free reference (backward recomputes each chunk
            instead of saving [N, V] residuals).
  unfused   the legacy materialized-logits path (unembed + cross_entropy /
            jax.random.categorical) — the memory-hungry oracle the
            benchmarks compare against.

All three share one compute convention (see ``layers.unembed``): W cast to
the hidden dtype, fp32 accumulation, softcap then padded-column masking in
fp32 — so swapping implementations moves bytes, not math.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.fused_ce import (fused_lm_loss, fused_lm_loss_jvp,
                                fused_lm_loss_sampled, online_argmax_step,
                                online_lse_step, rowscale, vocab_chunk)
from .common import ModelConfig
from .layers import (NEG_INF_LOGIT, cross_entropy, layer_norm, rms_norm,
                     unembed)

_LM_LOSS_IMPL = {"impl": "chunked"}
_IMPLS = ("fused", "fused_jvp", "chunked", "unfused")
_CHUNK = 2048  # vocab columns per jnp chunk (multiple of 128)


def set_lm_loss_impl(impl: str) -> None:
    """Select the process-wide default loss implementation."""
    assert impl in _IMPLS, impl
    _LM_LOSS_IMPL["impl"] = impl


def get_lm_loss_impl() -> str:
    return _LM_LOSS_IMPL["impl"]


def unembed_weights(cfg: ModelConfig, params):
    """(w, transpose_w): the unembedding matrix in its stored layout —
    (Vp, D) for tied embeddings, (D, Vp) untied — no host-side transpose."""
    emb = params["embed"]
    if cfg.tie_embeddings:
        return emb["tok"], False
    return emb["unembed"], True


def _norm_args(cfg: ModelConfig, params, pre_norm):
    """Kernel kwargs for the fused final-norm producer: the family's
    ``params["final_norm"]`` in the packed scale/bias convention."""
    p = params["final_norm"]
    return dict(norm_kind=pre_norm, norm_scale=p["scale"],
                norm_bias=p.get("bias"), norm_eps=cfg.norm_eps)


def _apply_final_norm(cfg: ModelConfig, params, hidden, pre_norm):
    """The jnp final norm for the non-kernel impls (identical math to the
    in-kernel producer — models.layers formulas)."""
    if pre_norm is None:
        return hidden
    p = params["final_norm"]
    if pre_norm == "ln":
        return layer_norm(hidden, p["scale"], p["bias"], cfg.norm_eps)
    assert pre_norm == "rms", pre_norm
    return rms_norm(hidden, p["scale"], cfg.norm_eps)


def _rowscale(hidden, mask):
    n = 1
    for s in hidden.shape[:-1]:
        n *= s
    return rowscale(n, mask)


def _chunked_sweep(cfg: ModelConfig, hidden, w, transpose_w, labels=None,
                   rng=None):
    """One checkpointed vocab-chunk scan: (lse, label_or_sampled_logit,
    yhat) per position.  With ``labels`` the gathered logit is the label's;
    with ``rng`` the sweep draws yhat ~ softmax(logits) by online chunked
    Gumbel-argmax (per-chunk ``fold_in`` noise) and gathers the winner's
    raw logit — one pass serves both sampling and logp (no fp32 [N, V]
    log_softmax copy).  The online reductions are the shared
    ``kernels.fused_ce.online_lse_step`` / ``online_argmax_step`` rules."""
    D = hidden.shape[-1]
    h2 = hidden.reshape(-1, D)
    N = h2.shape[0]
    vp = cfg.padded_vocab
    bv = vocab_chunk(vp, _CHUNK, 128)
    n_c = vp // bv
    vocab = cfg.vocab_size
    softcap = cfg.final_logit_softcap
    wdt = w.astype(hidden.dtype)
    sample = rng is not None
    lab = None if sample else labels.reshape(-1)

    def body(carry, c):
        m, l, ll, zm, zi = carry
        if transpose_w:
            wc = jax.lax.dynamic_slice_in_dim(wdt, c * bv, bv, axis=1)
            raw = jnp.dot(h2, wc, preferred_element_type=jnp.float32)
        else:
            wc = jax.lax.dynamic_slice_in_dim(wdt, c * bv, bv, axis=0)
            raw = jnp.dot(h2, wc.T, preferred_element_type=jnp.float32)
        if softcap:
            raw = softcap * jnp.tanh(raw / softcap)
        cols = c * bv + jnp.arange(bv, dtype=jnp.int32)[None, :]
        valid = cols < vocab
        s = jnp.where(valid, raw, NEG_INF_LOGIT)
        m, l = online_lse_step(m, l, s, valid)
        if sample:
            g = jax.random.gumbel(jax.random.fold_in(rng, c), s.shape,
                                  jnp.float32)
            z = jnp.where(valid, s + g, NEG_INF_LOGIT)
            zm, zi, ll = online_argmax_step((zm, zi, ll), s, z, c * bv)
        else:
            ll = ll + jnp.where(cols == lab[:, None], s, 0.0).sum(-1)
        return (m, l, ll, zm, zi), None

    init = (jnp.full((N,), NEG_INF_LOGIT, jnp.float32),
            jnp.zeros((N,), jnp.float32),
            jnp.zeros((N,), jnp.float32),
            jnp.full((N,), NEG_INF_LOGIT, jnp.float32),
            jnp.zeros((N,), jnp.int32))
    (m, l, ll, _, zi), _ = jax.lax.scan(
        jax.checkpoint(body), init, jnp.arange(n_c))
    return m + jnp.log(jnp.maximum(l, 1e-37)), ll, zi


@jax.named_scope("loss.lm")
def lm_loss(cfg: ModelConfig, params, hidden, labels, mask=None, *,
            impl=None, pre_norm=None):
    """Masked-mean LM cross-entropy from final hidden states.

    Returns ``(ce, n_valid)``; ``n_valid`` is the valid-position count (the
    GNB batch factor B).  ``impl`` overrides the module default.  With
    ``pre_norm`` ("rms" | "ln"), ``hidden`` is PRE-final-norm and the norm
    (``params["final_norm"]``) is applied here — fused into the kernel
    sweep for the fused impl, in jnp for the rest."""
    impl = impl or _LM_LOSS_IMPL["impl"]
    assert impl in _IMPLS, impl
    if impl == "fused":
        w, tw = unembed_weights(cfg, params)
        kw = _norm_args(cfg, params, pre_norm) if pre_norm else {}
        return fused_lm_loss(hidden, w, labels, mask,
                             vocab_size=cfg.vocab_size, transpose_w=tw,
                             softcap=cfg.final_logit_softcap, **kw)
    hidden = _apply_final_norm(cfg, params, hidden, pre_norm)
    if impl == "unfused":
        logits = unembed(params["embed"], hidden, cfg)
        _, n_valid = _rowscale(hidden, mask)
        return cross_entropy(logits, labels, mask), n_valid
    w, tw = unembed_weights(cfg, params)
    if impl == "fused_jvp":
        return fused_lm_loss_jvp(hidden, w, labels, mask,
                                 vocab_size=cfg.vocab_size, transpose_w=tw,
                                 softcap=cfg.final_logit_softcap)
    lse, ll, _ = _chunked_sweep(cfg, hidden, w, tw, labels=labels)
    rs, n_valid = _rowscale(hidden, mask)
    return jnp.sum(rs * (lse - ll)), n_valid


@jax.named_scope("loss.lm")
def lm_loss_sampled(cfg: ModelConfig, params, hidden, rng, mask=None, *,
                    impl=None, pre_norm=None):
    """GNB sampled-label CE (Algorithm 2 lines 3-5) from hidden states:
    draws ``yhat ~ softmax(logits)`` and returns the masked-mean NLL
    against it as ``(nll, n_valid)`` — differentiate this for ``ghat``.

    fused: sampling happens inside the kernel's vocab sweep; chunked: one
    jnp sweep serves sampling and logp; unfused: the legacy two-pass
    (categorical + log_softmax) path, kept as the oracle."""
    impl = impl or _LM_LOSS_IMPL["impl"]
    assert impl in _IMPLS, impl
    if impl == "fused_jvp":     # sampling has no HVP path; same kernels
        impl = "fused"
    w, tw = unembed_weights(cfg, params)
    if impl == "fused":
        kw = _norm_args(cfg, params, pre_norm) if pre_norm else {}
        return fused_lm_loss_sampled(hidden, w, rng, mask,
                                     vocab_size=cfg.vocab_size,
                                     transpose_w=tw,
                                     softcap=cfg.final_logit_softcap, **kw)
    hidden = _apply_final_norm(cfg, params, hidden, pre_norm)
    if impl == "unfused":
        logits = unembed(params["embed"], hidden, cfg)
        yhat = jax.random.categorical(rng, jax.lax.stop_gradient(logits),
                                      axis=-1)
        _, n_valid = _rowscale(hidden, mask)
        return cross_entropy(logits, yhat, mask), n_valid
    lse, ll, _ = _chunked_sweep(cfg, hidden, w, tw, rng=rng)
    rs, n_valid = _rowscale(hidden, mask)
    return jnp.sum(rs * (lse - ll)), n_valid
