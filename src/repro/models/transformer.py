"""Decoder-only transformer LM (dense + MoE families), scan-over-layers.

Covers: qwen1.5-110b, yi-6b, gemma2-9b, stablelm-1.6b, qwen2-vl-7b (backbone),
llama4-maverick (interleaved MoE), deepseek-moe-16b, and the paper's GPT-2
family.  One stacked-parameter scan keeps 80-layer configs compiling fast and
makes remat policies per-layer.

Entry points:
    init_params(cfg, key)                       -> params
    forward(cfg, params, tokens, ...)           -> logits, aux
    prefill(cfg, params, tokens, ...)           -> logits, kv_cache
    init_cache(cfg, batch, max_len)             -> kv_cache
    decode_step(cfg, params, cache, tok, pos)   -> logits, kv_cache
    loss_fn / logits_fn                         -> CE loss plumbing (GNB-ready)
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .common import ModelConfig
from .layers import (apply_rope, chunked_attention,
                     decode_attention, decode_attention_slots, dense_init,
                     embed, embed_init, full_attention, init_attention,
                     init_embedding, init_mlp, layer_norm, mlp,
                     prefill_chunk_attention, rms_norm, train_attention,
                     unembed)
from .moe import init_moe, moe_ffn

# ---------------------------------------------------------------------------
# params


def _init_norm(cfg: ModelConfig):
    if cfg.norm_type == "ln":
        return {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)}
    return {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}


def _norm(p, x, cfg: ModelConfig):
    if cfg.norm_type == "ln":
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rms_norm(x, p["scale"], cfg.norm_eps)


def _init_dense_layer(cfg: ModelConfig, key, d_ff=None):
    ks = jax.random.split(key, 2)
    p = {"ln1": _init_norm(cfg), "attn": init_attention(ks[0], cfg),
         "ln2": _init_norm(cfg), "mlp": init_mlp(ks[1], cfg, d_ff=d_ff)}
    if cfg.post_norms:
        p["ln1_post"] = _init_norm(cfg)
        p["ln2_post"] = _init_norm(cfg)
    return p


def _init_moe_layer(cfg: ModelConfig, key):
    ks = jax.random.split(key, 2)
    p = {"ln1": _init_norm(cfg), "attn": init_attention(ks[0], cfg),
         "ln2": _init_norm(cfg), "moe": init_moe(ks[1], cfg)}
    if cfg.post_norms:
        p["ln1_post"] = _init_norm(cfg)
        p["ln2_post"] = _init_norm(cfg)
    return p


def n_scan_groups(cfg: ModelConfig) -> int:
    if cfg.family == "moe" and cfg.moe_every > 1:
        return cfg.n_layers // cfg.moe_every
    return cfg.n_layers


def init_params(cfg: ModelConfig, key) -> dict:
    kemb, klay, kfin = jax.random.split(key, 3)
    params = {"embed": init_embedding(kemb, cfg),
              "final_norm": _init_norm(cfg)}
    if cfg.family == "moe" and cfg.moe_every > 1:
        ngroups = cfg.n_layers // cfg.moe_every
        keys = jax.random.split(klay, ngroups)

        def one_group(k):
            k1, k2 = jax.random.split(k)
            return {"dense": _init_dense_layer(cfg, k1, d_ff=cfg.dense_d_ff),
                    "moe": _init_moe_layer(cfg, k2)}

        params["layers"] = jax.vmap(one_group)(keys)
    elif cfg.family == "moe":
        keys = jax.random.split(klay, cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: _init_moe_layer(cfg, k))(keys)
    else:
        keys = jax.random.split(klay, cfg.n_layers)
        params["layers"] = jax.vmap(lambda k: _init_dense_layer(cfg, k))(keys)
    if cfg.patch_embed_input:
        params["patch_proj"] = dense_init(kfin, (cfg.d_model, cfg.d_model))
    return params


# ---------------------------------------------------------------------------
# per-layer flags (sliding-window pattern, attention temperature)


def layer_windows(cfg: ModelConfig, seq_len: int) -> jnp.ndarray:
    """Per-layer effective window (traced into masks; > seq = global)."""
    big = jnp.asarray(1 << 30, jnp.int32)
    n = n_scan_groups(cfg)
    if cfg.local_global_pattern == "alternating" and cfg.local_window:
        idx = jnp.arange(n)
        return jnp.where(idx % 2 == 0, cfg.local_window, big)
    if cfg.local_window:  # all-local
        return jnp.full((n,), cfg.local_window, jnp.int32)
    return jnp.full((n,), big, jnp.int32)


def layer_scales(cfg: ModelConfig) -> jnp.ndarray:
    n = n_scan_groups(cfg)
    if cfg.attn_temperature_by_layer:
        return 1.0 / (1.0 + jnp.arange(n, dtype=jnp.float32))
    return jnp.ones((n,), jnp.float32)


# ---------------------------------------------------------------------------
# forward


# Each layer of the train step sits in a ``jax.named_scope`` (``model.*``
# here, ``loss.lm``, ``train.grad_clip``, ``optimizer.*`` and
# ``sophia.estimate`` around it), which XLA keeps in the ``op_name`` of every
# instruction traced under it, backward and recomputed copies included: a
# profile attributes each device op to its layer by these names.


@jax.named_scope("model.attn")
def _attn_dispatch(p, x, cfg, positions, window, scale, attn_impl):
    return train_attention(p, x, cfg, positions, window=window,
                           layer_scale=scale, impl=attn_impl)


@jax.named_scope("model.block")
def _dense_block(p, x, cfg, positions, window, scale, attn_impl):
    h = _norm(p["ln1"], x, cfg)
    a = _attn_dispatch(p["attn"], h, cfg, positions, window, scale, attn_impl)
    if cfg.post_norms:
        a = _norm(p["ln1_post"], a, cfg)
    x = x + a
    h = _norm(p["ln2"], x, cfg)
    with jax.named_scope("model.mlp"):
        f = mlp(p["mlp"], h, cfg)
    if cfg.post_norms:
        f = _norm(p["ln2_post"], f, cfg)
    from ..distributed.sharding import constrain, residual_axes
    return constrain(x + f, *residual_axes())


@jax.named_scope("model.block")
def _moe_block(p, x, cfg, positions, window, scale, attn_impl):
    h = _norm(p["ln1"], x, cfg)
    a = _attn_dispatch(p["attn"], h, cfg, positions, window, scale, attn_impl)
    if cfg.post_norms:
        a = _norm(p["ln1_post"], a, cfg)
    x = x + a
    h = _norm(p["ln2"], x, cfg)
    with jax.named_scope("model.mlp"):
        f, aux = moe_ffn(p["moe"], h, cfg)
    if cfg.post_norms:
        f = _norm(p["ln2_post"], f, cfg)
    from ..distributed.sharding import constrain, residual_axes
    return constrain(x + f, *residual_axes()), aux


@jax.named_scope("model.embed")
def _embed_inputs(cfg, params, tokens, positions, patch_embeds):
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[:, None], (B, 3, S))
    rope_pos = positions
    emb_pos = positions if positions.ndim == 2 else positions[:, 0]
    x = embed(params["embed"], tokens, cfg, emb_pos)
    if cfg.patch_embed_input and patch_embeds is not None:
        # stub modality frontend: first P positions are image patches
        P = patch_embeds.shape[1]
        pe = patch_embeds.astype(x.dtype) @ params["patch_proj"].astype(x.dtype)
        x = jnp.concatenate([pe, x[:, P:]], axis=1)
    return x, rope_pos


def forward_hidden(cfg: ModelConfig, params, tokens, *, positions=None,
                   patch_embeds=None, attn_impl: str = "auto",
                   remat: str = "none", final_norm: bool = True):
    """tokens (B, S) -> (final-norm hidden (B, S, D), aux).  The trunk
    shared by :func:`forward` and the logits-free loss paths — the
    unembedding projection happens inside ``models.loss.lm_loss`` (or not
    at all, for the fused kernel).  ``final_norm=False`` returns the
    PRE-norm hidden so ``lm_loss(..., pre_norm=cfg.norm_type)`` can fuse
    the norm producer into the loss sweep (one less (B, S, D) HBM
    round-trip)."""
    x, positions = _embed_inputs(cfg, params, tokens, positions, patch_embeds)
    windows = layer_windows(cfg, tokens.shape[1])
    scales = layer_scales(cfg)

    if cfg.family == "moe" and cfg.moe_every > 1:
        def body(carry, layer):
            x, aux = carry
            p, w, s = layer
            x = _dense_block(p["dense"], x, cfg, positions, w, s, attn_impl)
            x, a = _moe_block(p["moe"], x, cfg, positions, w, s, attn_impl)
            return (x, aux + a), None
    elif cfg.family == "moe":
        def body(carry, layer):
            x, aux = carry
            p, w, s = layer
            x, a = _moe_block(p, x, cfg, positions, w, s, attn_impl)
            return (x, aux + a), None
    else:
        def body(carry, layer):
            x, aux = carry
            p, w, s = layer
            return (_dense_block(p, x, cfg, positions, w, s, attn_impl),
                    aux), None

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    n = n_scan_groups(cfg)
    with jax.named_scope("model.layers"):
        x, aux = _layer_scan(body, x, params, windows, scales, n, remat)
    if final_norm:
        x = _norm(params["final_norm"], x, cfg)
    return x, aux


def _layer_scan(body, x, params, windows, scales, n, remat):
    """The scan of ``body`` over the stacked layers: -> (x, aux)."""
    if remat == "scan2" and n >= 4:
        # nested-scan remat: the OUTER scan checkpoints every g-th carry
        # (long-lived residuals shrink g x); the INNER body is checkpointed
        # too, so the group recompute during backward saves only g layer
        # inputs transiently — never a full layer's intermediates x g.
        g = next(d for d in (8, 5, 4, 2) if n % d == 0)
        xs = jax.tree.map(
            lambda a: a.reshape((n // g, g) + a.shape[1:]),
            (params["layers"], windows, scales))
        inner_body = jax.checkpoint(body)

        def outer(carry, group):
            return jax.lax.scan(inner_body, carry, group)

        (x, aux), _ = jax.lax.scan(jax.checkpoint(outer),
                                   (x, jnp.zeros((), jnp.float32)), xs)
    else:
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (params["layers"], windows, scales))
    return x, aux


def forward(cfg: ModelConfig, params, tokens, *, positions=None,
            patch_embeds=None, attn_impl: str = "auto",
            remat: str = "none"):
    """tokens (B, S) -> logits (B, S, V) fp32, aux (MoE load-balance loss)."""
    x, aux = forward_hidden(cfg, params, tokens, positions=positions,
                            patch_embeds=patch_embeds, attn_impl=attn_impl,
                            remat=remat)
    return unembed(params["embed"], x, cfg), aux


def loss_fn(cfg: ModelConfig, params, batch, *, attn_impl="auto",
            remat="none", loss_impl=None):
    """batch: {tokens, labels, [mask], [patch_embeds]} -> (loss, metrics).

    The CE runs through ``models.loss.lm_loss`` (fused / chunked /
    unfused per ``loss_impl``) — the default never materializes the
    [B, S, V] logits."""
    from .loss import lm_loss
    hidden, aux = forward_hidden(cfg, params, batch["tokens"],
                                 patch_embeds=batch.get("patch_embeds"),
                                 positions=batch.get("positions"),
                                 attn_impl=attn_impl, remat=remat,
                                 final_norm=False)
    ce, _ = lm_loss(cfg, params, hidden, batch["labels"],
                    batch.get("mask"), impl=loss_impl,
                    pre_norm=cfg.norm_type)
    return ce + aux, {"ce": ce, "aux": aux}


def sampled_loss_fn(cfg: ModelConfig, params, batch, rng, *,
                    attn_impl="auto", remat="none", loss_impl=None):
    """GNB sampled-label NLL (Algorithm 2): ``(nll, n_valid)`` with labels
    drawn from the model's own softmax inside the loss sweep."""
    from .loss import lm_loss_sampled
    hidden, _ = forward_hidden(cfg, params, batch["tokens"],
                               patch_embeds=batch.get("patch_embeds"),
                               positions=batch.get("positions"),
                               attn_impl=attn_impl, remat=remat,
                               final_norm=False)
    return lm_loss_sampled(cfg, params, hidden, rng, batch.get("mask"),
                           impl=loss_impl, pre_norm=cfg.norm_type)


def logits_fn(cfg: ModelConfig, params, batch, **kw):
    """Logits view for the GNB estimator (Algorithm 2 line 3)."""
    kw.pop("loss_impl", None)
    logits, _ = forward(cfg, params, batch["tokens"],
                        patch_embeds=batch.get("patch_embeds"),
                        positions=batch.get("positions"), **kw)
    return logits


# ---------------------------------------------------------------------------
# serving: prefill + KV-cache decode


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    L = cfg.n_layers  # caches are per *attention* layer (flat, not grouped)
    shape = (L, batch_size, max_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, cfg.compute_dtype),
            "v": jnp.zeros(shape, cfg.compute_dtype)}


def _flat_layer_params(cfg: ModelConfig, params):
    """Interleaved MoE groups -> flat per-attention-layer view for decode."""
    if cfg.family == "moe" and cfg.moe_every > 1:
        return params["layers"]  # handled group-wise in decode scan
    return params["layers"]


def decode_step(cfg: ModelConfig, params, cache, tokens, position):
    """One decode step.  tokens (B, 1) int32; position: scalar int32.

    Returns (logits (B, 1, V), new_cache).  Static cache length; the causal
    mask hides positions > ``position``.
    """
    B = tokens.shape[0]
    x = embed(params["embed"], tokens, cfg,
              jnp.full((B, 1), position, jnp.int32))
    windows = layer_windows(cfg, cache["k"].shape[2])
    scales = layer_scales(cfg)

    grouped = cfg.family == "moe" and cfg.moe_every > 1
    if grouped:
        ng = n_scan_groups(cfg)
        kc = cache["k"].reshape((ng, cfg.moe_every) + cache["k"].shape[1:])
        vc = cache["v"].reshape((ng, cfg.moe_every) + cache["v"].shape[1:])
    else:
        kc, vc = cache["k"], cache["v"]

    def attn_sub(p, x, k_l, v_l, w, s):
        h = _norm(p["ln1"], x, cfg)
        a, k_l, v_l = decode_attention(p["attn"], h, cfg, k_l, v_l, position,
                                       window=w, layer_scale=s)
        if cfg.post_norms:
            a = _norm(p["ln1_post"], a, cfg)
        return x + a, k_l, v_l

    def ffn_sub(p, x):
        h = _norm(p["ln2"], x, cfg)
        if "moe" in p:
            f, _ = moe_ffn(p["moe"], h, cfg)
        else:
            f = mlp(p["mlp"], h, cfg)
        if cfg.post_norms:
            f = _norm(p["ln2_post"], f, cfg)
        return x + f

    if grouped:
        def body(x, layer):
            p, k_g, v_g, w, s = layer
            x, k0, v0 = attn_sub(p["dense"], x, k_g[0], v_g[0], w, s)
            x = ffn_sub(p["dense"], x)
            x, k1, v1 = attn_sub(p["moe"], x, k_g[1], v_g[1], w, s)
            x = ffn_sub(p["moe"], x)
            return x, (jnp.stack([k0, k1]), jnp.stack([v0, v1]))

        x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], kc, vc,
                                             windows, scales))
        new_cache = {"k": nk.reshape(cache["k"].shape),
                     "v": nv.reshape(cache["v"].shape)}
    else:
        def body(x, layer):
            p, k_l, v_l, w, s = layer
            x, k_l, v_l = attn_sub(p, x, k_l, v_l, w, s)
            x = ffn_sub(p, x)
            return x, (k_l, v_l)

        x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], kc, vc,
                                             windows, scales))
        new_cache = {"k": nk, "v": nv}

    x = _norm(params["final_norm"], x, cfg)
    return unembed(params["embed"], x, cfg), new_cache


# ---------------------------------------------------------------------------
# slot protocol (continuous-batching serve engine; see serve/engine.py)
#
# Slot-major ring KV cache: (L, N, C, Hkv, hd) with C = n_pages * page_len.
# Ring index s of a slot at position p holds absolute position
# p - ((p - s) mod C); the mask (layers.ring_mask) hides unwritten, stale
# and out-of-window entries, so reusing a slot needs no cache reset and a
# prefill chunk may write its padded tail unmasked — those indices stay
# invisible until a later decode overwrites them with real tokens.


def init_slots(cfg: ModelConfig, n_slots: int, cache_len: int) -> dict:
    L = cfg.n_layers
    shape = (L, n_slots, cache_len, cfg.n_kv_heads, cfg.hd)
    if cfg.kv_dtype == "int8":
        # int8 payloads + one fp32 scale per written token per K/V plane
        # (repro.quant.quantize_kv): ~2x slots per HBM byte vs bf16
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros((L, n_slots, cache_len), jnp.float32),
                "v_scale": jnp.zeros((L, n_slots, cache_len), jnp.float32)}
    return {"k": jnp.zeros(shape, cfg.compute_dtype),
            "v": jnp.zeros(shape, cfg.compute_dtype)}


def reset_slot(cfg: ModelConfig, cache, slot):
    """Ring masking hides stale entries — nothing to clear for attention."""
    return cache


def _slot_layer_sweep(cfg: ModelConfig, params, cache, x, attn_fn):
    """Layer sweep shared by :func:`decode_slots` and
    :func:`prefill_into_slot` — the grouped-MoE reshape, attention/FFN
    residual plumbing and both scan bodies live once, parameterized by the
    inner attention call ``attn_fn(p_attn, h, kv_l, window, scale) ->
    (a, kv_l)``.  The per-layer ``kv_l`` dict carries whatever leaves the
    cache holds ({"k", "v"} [+ the int8 path's scale planes]) — the sweep
    never enumerates them, so new cache layouts thread through without
    touching the scan.  Returns (hidden, new_cache)."""
    windows = layer_windows(cfg, cache["k"].shape[2])
    scales = layer_scales(cfg)

    grouped = cfg.family == "moe" and cfg.moe_every > 1
    if grouped:
        ng = n_scan_groups(cfg)
        kvs = {name: leaf.reshape((ng, cfg.moe_every) + leaf.shape[1:])
               for name, leaf in cache.items()}
    else:
        kvs = dict(cache)

    def attn_sub(p, x, kv_l, w, s):
        h = _norm(p["ln1"], x, cfg)
        a, kv_l = attn_fn(p["attn"], h, kv_l, w, s)
        if cfg.post_norms:
            a = _norm(p["ln1_post"], a, cfg)
        return x + a, kv_l

    def ffn_sub(p, x):
        h = _norm(p["ln2"], x, cfg)
        if "moe" in p:
            f, _ = moe_ffn(p["moe"], h, cfg)
        else:
            f = mlp(p["mlp"], h, cfg)
        if cfg.post_norms:
            f = _norm(p["ln2_post"], f, cfg)
        return x + f

    if grouped:
        def body(x, layer):
            p, kv_g, w, s = layer
            x, kv0 = attn_sub(p["dense"], x,
                              jax.tree.map(lambda l: l[0], kv_g), w, s)
            x = ffn_sub(p["dense"], x)
            x, kv1 = attn_sub(p["moe"], x,
                              jax.tree.map(lambda l: l[1], kv_g), w, s)
            x = ffn_sub(p["moe"], x)
            return x, jax.tree.map(lambda a, b: jnp.stack([a, b]), kv0, kv1)

        x, nkv = jax.lax.scan(body, x, (params["layers"], kvs,
                                        windows, scales))
        return x, {name: leaf.reshape(cache[name].shape)
                   for name, leaf in nkv.items()}

    def body(x, layer):
        p, kv_l, w, s = layer
        x, kv_l = attn_sub(p, x, kv_l, w, s)
        x = ffn_sub(p, x)
        return x, kv_l

    x, nkv = jax.lax.scan(body, x, (params["layers"], kvs, windows, scales))
    return x, nkv


def decode_slots(cfg: ModelConfig, params, cache, tokens, positions):
    """One decode step across all slots.  tokens (N, 1); positions (N,).

    Returns (logits (N, 1, V), new_cache).  Identical math to
    :func:`decode_step` when every slot sits at the same position, but each
    slot carries its own position so a continuous batch mixes requests at
    arbitrary depths in one compiled program.
    """
    positions = positions.astype(jnp.int32)
    x = embed(params["embed"], tokens, cfg, positions[:, None])

    def attn_fn(p, h, kv_l, w, s):
        return decode_attention_slots(p, h, cfg, kv_l, positions,
                                      window=w, layer_scale=s)

    x, new_cache = _slot_layer_sweep(cfg, params, cache, x, attn_fn)
    x = _norm(params["final_norm"], x, cfg)
    return unembed(params["embed"], x, cfg), new_cache


def prefill_into_slot(cfg: ModelConfig, params, cache, slot, tokens, start,
                      n_valid):
    """Chunk-prefill one slot.  tokens (1, P) int32; ``slot``, ``start``
    and ``n_valid`` are traced scalars, so one compiled program serves every
    chunk of every request.

    Writes K/V for the chunk's positions into the slot's cache row and
    returns (new_cache, logits (V,) fp32 of the last *valid* token — the
    next-token distribution once the final chunk lands).  Entries past
    ``n_valid`` are written but stay ring-masked until decode overwrites
    them; queries past ``n_valid`` compute garbage that nothing reads.
    """
    P = tokens.shape[1]
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    qpos = start + jnp.arange(P, dtype=jnp.int32)       # (P,)
    x = embed(params["embed"], tokens, cfg, qpos[None])

    def attn_fn(p, h, kv_l, w, s):
        return prefill_chunk_attention(p, h, cfg, kv_l, slot, start,
                                       qpos, window=w, layer_scale=s)

    x, new_cache = _slot_layer_sweep(cfg, params, cache, x, attn_fn)
    # only the last valid token's logits matter (next-token distribution)
    last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
    last = _norm(params["final_norm"], last, cfg)
    return new_cache, unembed(params["embed"], last, cfg)[0, 0]


def prefill(cfg: ModelConfig, params, tokens, *, attn_impl="auto",
            patch_embeds=None):
    """Forward pass that also fills a KV cache (prefill_32k serve path)."""
    x, positions = _embed_inputs(cfg, params, tokens, None, patch_embeds)
    windows = layer_windows(cfg, tokens.shape[1])
    scales = layer_scales(cfg)
    grouped = cfg.family == "moe" and cfg.moe_every > 1

    def kv_of(p, h):
        dt = h.dtype
        B, S, _ = h.shape
        k = (h @ p["wk"].astype(dt))
        v = (h @ p["wv"].astype(dt))
        if cfg.qkv_bias:
            k = k + p["bk"].astype(dt)
            v = v + p["bv"].astype(dt)
        k = k.reshape(B, S, cfg.n_kv_heads, cfg.hd)
        if cfg.rope:
            k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        return k, v.reshape(B, S, cfg.n_kv_heads, cfg.hd)

    def dense_with_kv(p, x, w, s):
        h = _norm(p["ln1"], x, cfg)
        kv = kv_of(p["attn"], h)
        x = _dense_block(p, x, cfg, positions, w, s, attn_impl)
        return x, kv

    def moe_with_kv(p, x, w, s):
        h = _norm(p["ln1"], x, cfg)
        kv = kv_of(p["attn"], h)
        x, _ = _moe_block(p, x, cfg, positions, w, s, attn_impl)
        return x, kv

    if grouped:
        def body(x, layer):
            p, w, s = layer
            x, kv0 = dense_with_kv(p["dense"], x, w, s)
            x, kv1 = moe_with_kv(p["moe"], x, w, s)
            return x, (jnp.stack([kv0[0], kv1[0]]), jnp.stack([kv0[1], kv1[1]]))
    elif cfg.family == "moe":
        def body(x, layer):
            p, w, s = layer
            x, kv = moe_with_kv(p, x, w, s)
            return x, kv
    else:
        def body(x, layer):
            p, w, s = layer
            x, kv = dense_with_kv(p, x, w, s)
            return x, kv

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], windows, scales))
    if grouped:
        L = cfg.n_layers
        ks = ks.reshape((L,) + ks.shape[2:])
        vs = vs.reshape((L,) + vs.shape[2:])
    x = _norm(params["final_norm"], x[:, -1:], cfg)
    # serving prefill only needs the LAST token's logits (the next-token
    # distribution); unembedding all S positions would build a (B,S,V)
    # buffer that cannot exist at 32k x 152k vocab.
    return unembed(params["embed"], x, cfg), {"k": ks, "v": vs}
