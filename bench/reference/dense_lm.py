"""Plain reference for training a dense decoder LM with Sophia-G or AdamW.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no kernels, no flat optimizer state, no sharding.
It imports nothing of the program under test.  It follows the published
GPT-2 / GPT-NeoX block (pre-LayerNorm, causal softmax attention, GELU MLP,
tied or untied unembedding) and the Sophia paper's Algorithms 2 and 3:

* loss: mean next-token cross-entropy over every position;
* gradient clip to global norm ``grad_clip`` before the optimizer;
* Sophia-G: ``h <- b2 h + (1-b2) B ghat^2`` on refresh steps, where
  ``ghat`` is the gradient of the NLL of labels sampled from the model's own
  softmax on the first ``hess_subbatch`` sequences and ``B`` the number of
  sampled positions; then ``m <- b1 m + (1-b1) g``,
  ``p <- p (1 - lr wd) - lr clip(m / max(gamma h, eps), 1)``;
* AdamW with bias correction (b1 0.9, b2 0.95, eps 1e-8);
* learning rate: linear warm-up from 0, then cosine to 0.05 x peak.

The weights are drawn from the seed with the same draws the program's
initializer makes (LeCun-normal projections, N(0, 0.02) embeddings, zero
biases, unit LayerNorm scales), so both start from the same point without
the reference taking anything the program computed.  The sampled labels use
the same counter hash of (seed, row, column) as the program's sweep: a
Gumbel-argmax over the logits, so a draw differs only where rounding moves
the winner.

``quant`` puts every matrix product's operands (and, in the backward, its
cotangent) through a rounding function: identity for the reference, a
scaled float8 cast for the lower-precision control.  Memory is kept to one
layer's attention scores and one row block of logits at a time: the scan
over layers recomputes each layer in the backward, and the cross-entropy
runs over blocks of rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
CE_ROW_BLOCK = 1024


# ---------------------------------------------------------------------------
# matrix products, exact or through a rounding function


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def make_einsum(quant=None):
    """``einsum(spec, a, b)`` in float32; with ``quant``, both operands of
    the forward product and the cotangent of the backward products are
    rounded by ``quant`` first (a low-precision training path)."""
    if quant is None:
        return _einsum

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def qeinsum(spec, a, b):
        return _einsum(spec, quant(a), quant(b))

    def fwd(spec, a, b):
        qa, qb = quant(a), quant(b)
        return _einsum(spec, qa, qb), (qa, qb)

    def bwd(spec, res, g):
        qa, qb = res
        _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), qa, qb)
        return vjp(quant(g))

    qeinsum.defvjp(fwd, bwd)
    return qeinsum


def fp8_quant(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448)."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


# ---------------------------------------------------------------------------
# weights


def padded_vocab(m):
    return -(-m["vocab_size"] // 128) * 128


def _lecun(key, shape, fan_in):
    return jax.random.normal(key, shape) / math.sqrt(fan_in)


def init_params(m, key):
    """Weights from ``key`` with the same draws as the program's dense
    initializer; returns the same nested dict of leaves."""
    D, H, F, L = m["d_model"], m["n_heads"], m["d_ff"], m["n_layers"]
    hd = m.get("head_dim") or D // H
    Hkv = m["n_kv_heads"]
    Vp = padded_vocab(m)
    kemb, klay, _ = jax.random.split(key, 3)
    ke = jax.random.split(kemb, 2)
    embed = {"tok": jax.random.normal(ke[0], (Vp, D)) * 0.02}
    if not m["tie_embeddings"]:
        embed["unembed"] = _lecun(ke[1], (D, Vp), D)
    if m["learned_pos"]:
        embed["pos"] = jax.random.normal(
            ke[1], (m["max_position_embeddings"], D)) * 0.02

    def norm():
        return {"scale": jnp.ones((D,), jnp.float32),
                "bias": jnp.zeros((D,), jnp.float32)}

    def layer(k):
        ka, km = jax.random.split(k, 2)
        a = jax.random.split(ka, 4)
        f = jax.random.split(km, 3)
        return {
            "ln1": norm(),
            "attn": {"wq": _lecun(a[0], (D, H * hd), D),
                     "wk": _lecun(a[1], (D, Hkv * hd), D),
                     "wv": _lecun(a[2], (D, Hkv * hd), D),
                     "wo": _lecun(a[3], (H * hd, D), H * hd)},
            "ln2": norm(),
            "mlp": {"w_up": _lecun(f[0], (D, F), D),
                    "b_up": jnp.zeros((F,), jnp.float32),
                    "w_down": _lecun(f[1], (F, D), F),
                    "b_down": jnp.zeros((D,), jnp.float32)},
        }

    layers = jax.vmap(layer)(jax.random.split(klay, L))
    return {"embed": embed, "final_norm": norm(), "layers": layers}


# ---------------------------------------------------------------------------
# forward


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta):
    """Rotary embedding on (B, S, H, hd), halves rotated (NeoX layout)."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(m, ein, x, p):
    B, S, D = x.shape
    H, Hkv = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or D // H
    eps = m["norm_eps"]
    h = _layer_norm(x, p["ln1"], eps)
    q = ein("bsd,de->bse", h, p["attn"]["wq"]).reshape(B, S, H, hd)
    k = ein("bsd,de->bse", h, p["attn"]["wk"]).reshape(B, S, Hkv, hd)
    v = ein("bsd,de->bse", h, p["attn"]["wv"]).reshape(B, S, Hkv, hd)
    if m.get("rope"):
        q = _rope(q, m.get("rope_theta", 10000.0))
        k = _rope(k, m.get("rope_theta", 10000.0))
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    a = ein("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * hd)
    x = x + ein("bse,ed->bsd", a, p["attn"]["wo"])
    h = _layer_norm(x, p["ln2"], eps)
    u = jax.nn.gelu(ein("bsd,df->bsf", h, p["mlp"]["w_up"])
                    + p["mlp"]["b_up"])
    return x + ein("bsf,fd->bsd", u, p["mlp"]["w_down"]) + p["mlp"]["b_down"]


def hidden(m, ein, params, tokens):
    """tokens (B, S) -> final-norm hidden states (B, S, D), float32."""
    S = tokens.shape[1]
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if m["learned_pos"]:
        x = x + params["embed"]["pos"][:S][None]
    body = jax.checkpoint(lambda c, p: (_block(m, ein, c, p), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    return _layer_norm(x, params["final_norm"], m["norm_eps"])


def _unembed_w(m, params):
    if m["tie_embeddings"]:
        return params["embed"]["tok"].T
    return params["embed"]["unembed"]


def _row_logits(m, ein, h, w):
    z = ein("nd,dv->nv", h, w)
    cols = jnp.arange(z.shape[-1])
    return jnp.where(cols < m["vocab_size"], z, NEG_INF)


def _mix32(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def gumbel(seed, rows, cols):
    """Gumbel(0, 1) noise as a counter hash of (seed, row, column)."""
    u32 = jnp.uint32
    r = _mix32(rows.astype(u32) ^ seed[0])
    x = _mix32(r ^ (cols.astype(u32) * np.uint32(0x9E3779B9)) ^ seed[1])
    u = jax.lax.bitcast_convert_type(x >> np.uint32(8), jnp.int32) \
        .astype(jnp.float32) * np.float32(1.0 / (1 << 24))
    u = jnp.clip(u, 1e-7, 1.0 - 1e-7)
    return -jnp.log(-jnp.log(u))


def mean_nll(m, ein, params, tokens, labels=None, seed=None):
    """Mean NLL over all positions, in blocks of rows.  With ``seed`` the
    labels are drawn from the model's softmax (Gumbel-argmax), held fixed
    under differentiation."""
    h = hidden(m, ein, params, tokens)
    N, D = tokens.size, h.shape[-1]
    h = h.reshape(N, D)
    w = _unembed_w(m, params)
    rb = math.gcd(N, CE_ROW_BLOCK)
    lab = (labels.reshape(N // rb, rb) if labels is not None
           else jnp.zeros((N // rb, rb), jnp.int32))

    @jax.checkpoint
    def block(i, hb, lb):
        z = _row_logits(m, ein, hb, w)
        if seed is not None:
            rows = (i * rb + jnp.arange(rb))[:, None]
            cols = jnp.arange(z.shape[-1])[None, :]
            noisy = jnp.where(cols < m["vocab_size"],
                              jax.lax.stop_gradient(z) + gumbel(seed, rows,
                                                                cols),
                              NEG_INF)
            lb = jnp.argmax(noisy, axis=-1).astype(jnp.int32)
        lse = jax.nn.logsumexp(z, axis=-1)
        ll = jnp.take_along_axis(z, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - ll)

    def body(acc, xs):
        i, hb, lb = xs
        return acc + block(i, hb, lb), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                          (jnp.arange(N // rb), h.reshape(N // rb, rb, D),
                           lab))
    return tot / N


# ---------------------------------------------------------------------------
# one training step


def lr_at(t, opt):
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    final = 0.05 * peak
    if t < warm:
        return peak * t / max(warm, 1)
    frac = min(max((t - warm) / max(total - warm, 1), 0.0), 1.0)
    return final + 0.5 * (peak - final) * (1 + math.cos(math.pi * frac))


def _global_clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.where(norm > max_norm, max_norm / (norm + 1e-16), 1.0)
    return jax.tree.map(lambda g: g * scale, grads)


def make_step(m, opt, quant=None, half_batch=False):
    """Jitted ``step(params, mom, h, tokens, labels, lr, count, seed,
    refresh)`` -> (params, mom, h, loss, grads as the optimizer sees them).

    ``half_batch`` drops the second half of the rows and takes the mean
    over the rest: a planted fault, for the check's upper readings."""
    ein = make_einsum(quant)
    name = opt["optimizer"]

    def loss_of(p, tokens, labels):
        if half_batch:
            n = tokens.shape[0] // 2
            tokens, labels = tokens[:n], labels[:n]
        return mean_nll(m, ein, p, tokens, labels)

    @functools.partial(jax.jit, static_argnames=("refresh",),
                       donate_argnums=(0, 1, 2))
    def step(params, mom, h, tokens, labels, lr, count, seed, refresh):
        loss, grads = jax.value_and_grad(loss_of)(params, tokens, labels)
        grads = _global_clip(grads, opt["grad_clip"])
        if name == "sophia_g":
            b1, b2 = opt["beta1"], opt["beta2"]
            if refresh:
                sub = tokens[:opt["hess_subbatch"]]
                ghat = jax.grad(lambda p: mean_nll(m, ein, p, sub,
                                                   seed=seed))(params)
                n_valid = float(sub.size)
                h = jax.tree.map(
                    lambda hh, gh: b2 * hh + (1 - b2) * n_valid * gh * gh,
                    h, ghat)
            mom = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g,
                               mom, grads)
            upd = jax.tree.map(
                lambda mm, hh: jnp.clip(
                    mm / jnp.maximum(opt["gamma"] * hh, opt["eps"]),
                    -opt["clip_threshold"], opt["clip_threshold"]),
                mom, h)
        elif name == "adamw":
            b1, b2, eps = 0.9, 0.95, 1e-8
            mom = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g,
                               mom, grads)
            h = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                             h, grads)
            c = count.astype(jnp.float32)
            bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
            upd = jax.tree.map(
                lambda mm, v: (mm / bc1) / (jnp.sqrt(v / bc2) + eps), mom, h)
        else:
            raise ValueError(f"reference has no optimizer {name!r}")
        wd = opt["weight_decay"]
        params = jax.tree.map(lambda p, u: p * (1 - lr * wd) - lr * u,
                              params, upd)
        return params, mom, h, loss, grads

    return step
