"""Self-check of the work functions in bench/work/ against XLA's
``cost_analysis()`` of a plain jnp equivalent at a small shape, on the CPU.

Where they differ, and why:

* attention: the plain jnp attention computes every (query, key) pair; the
  work function counts the causal half, so XLA reads twice its count;
* the backward of a plain jnp program recomputes nothing here, so the
  products agree; XLA also counts the softmax, norms and elementwise
  operations, a few percent at these shapes, which the work functions
  leave out;
* bytes: the plain jnp program's bytes accessed includes its intermediates
  (the scores, the logits), which the kernels never store; the work
  functions count each operand and result once, which is checked here
  against the sizes of the plain program's own operands and results.
"""
import math

import jax
import jax.numpy as jnp
import pytest

from bench import common

B, H, S, HD = 2, 4, 256, 64
N, D, V = 512, 128, 1024


def _cost(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    return ca if isinstance(ca, dict) else ca[0]


def _nbytes(*xs):
    return sum(math.prod(x.shape) * x.dtype.itemsize for x in xs)


def _attn(q, k, v):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def test_flash_attention_forward():
    w = common.work("flash_attention")
    q = jnp.ones((B, H, S, HD), jnp.bfloat16)
    flops, nbytes = w.forward(B, H, H, S, HD)
    xla = _cost(_attn, q, q, q)["flops"]
    # full (non-causal) products = twice the causal count; softmax extra
    assert 1.0 <= xla / (2 * flops) < 1.05
    lse = jnp.ones((B, H, S), jnp.float32)
    assert nbytes == _nbytes(q, q, q, q, lse)


def test_flash_attention_backward():
    w = common.work("flash_attention")
    q = jnp.ones((B, H, S, HD), jnp.bfloat16)
    f_fwd, _ = w.forward(B, H, H, S, HD)
    f_bwd, nbytes = w.backward(B, H, H, S, HD)

    def loss(q, k, v):
        o = _attn(q, k, v)
        return jnp.sum(o * o)

    xla = _cost(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)["flops"]
    # the plain backward keeps the forward's scores: forward + backward
    # products, full (non-causal)
    assert 1.0 <= xla / (2 * (f_fwd + f_bwd)) < 1.05
    row = jnp.ones((B, H, S), jnp.float32)
    assert nbytes == _nbytes(q, q, q, q, row, row, q, q, q)


def _ce(h, w, lab):
    z = jnp.einsum("nd,vd->nv", h, w.astype(h.dtype),
                   preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(z, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(z, lab[:, None], 1)[:, 0])


def test_fused_ce_forward_and_backward():
    w = common.work("fused_ce")
    h = jnp.ones((N, D), jnp.bfloat16)
    W = jnp.ones((V, D), jnp.float32)
    lab = jnp.zeros((N,), jnp.int32)
    f_fwd, b_fwd = w.forward(N, D, V)
    f_bwd, b_bwd = w.backward(N, D, V)
    assert 1.0 <= _cost(_ce, h, W, lab)["flops"] / f_fwd < 1.05
    xla = _cost(jax.grad(_ce, argnums=(0, 1)), h, W, lab)["flops"]
    assert 1.0 <= xla / (f_fwd + f_bwd) < 1.05
    row = jnp.ones((N,), jnp.float32)
    assert b_fwd == _nbytes(h, W, lab, row, row)
    assert b_bwd == _nbytes(h, W, lab, row, row, h, W)
    assert w.forward_sample(N, D, V) == (f_fwd, b_fwd)


@pytest.mark.parametrize("optimizer,refresh", [("sophia_g", False),
                                               ("sophia_g", True),
                                               ("adamw", False)])
def test_optimizer_update_bytes(optimizer, refresh):
    w = common.work("optimizer_update")
    n = 1 << 16
    x = jnp.ones((n,), jnp.float32)

    def sophia(p, m, h, g, e=None):
        if e is not None:
            h = 0.99 * h + 0.01 * 8.0 * e
        m = 0.96 * m + 0.04 * g
        u = jnp.clip(m / jnp.maximum(0.05 * h, 1e-12), -1.0, 1.0)
        out = (p * (1 - 1e-4) - 1e-3 * u, m)
        return out + ((h,) if e is not None else ())

    def adamw(p, m, v, g):
        m = 0.9 * m + 0.1 * g
        v = 0.95 * v + 0.05 * g * g
        return p * (1 - 1e-4) - 1e-3 * m / (jnp.sqrt(v) + 1e-8), m, v

    fn = adamw if optimizer == "adamw" else sophia
    args = (x,) * (5 if refresh else 4)
    outs = jax.eval_shape(fn, *args)
    _, nbytes = w.update(n, optimizer, refresh)
    assert nbytes == _nbytes(*args, *outs)


def test_model_flops_per_token():
    """Forward and backward of a plain two-layer dense LM: XLA's count of
    a full-attention program is the work function's products plus the
    causal half of attention it leaves out, within the norms, softmax and
    elementwise operations it does not count."""
    wm = common.work("model")
    m = dict(d_model=64, n_heads=4, n_kv_heads=4, d_ff=256, n_layers=2,
             vocab_size=512, activation="gelu", tie_embeddings=True,
             learned_pos=True, max_position_embeddings=S)
    ref = common.reference("dense_lm")
    params = ref.init_params(dict(m, norm_eps=1e-6, rope=False),
                             jax.random.PRNGKey(0))

    mm = dict(m, norm_eps=1e-6, rope=False)
    ein = ref.make_einsum()

    def loss(p, tokens):
        # the layers unrolled: XLA's count covers a loop body only once
        x = jnp.take(p["embed"]["tok"], tokens, axis=0) + p["embed"]["pos"]
        for i in range(m["n_layers"]):
            x = ref._block(mm, ein, x, jax.tree.map(lambda a: a[i],
                                                    p["layers"]))
        z = jnp.einsum("bsd,vd->bsv", x, p["embed"]["tok"])
        return jnp.mean(jax.nn.logsumexp(z, -1))

    tokens = jnp.zeros((B, S), jnp.int32)
    xla = _cost(jax.grad(loss), params, tokens)["flops"]
    fpt = wm.flops_per_token(m, S)
    full_attn = 6.0 * m["n_layers"] * S * m["d_model"]  # the other half
    ratio = xla / ((fpt + full_attn) * B * S)
    assert 1.0 <= ratio < 1.1, ratio
