"""Pallas decode-attention kernel vs its pure-jnp oracle, and the kernel
wired through the model decode path.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.ref import decode_attention_ref
from repro.models import get_model
from repro.models.layers import set_decode_attn_impl

pytestmark = pytest.mark.serve

TOL = 3e-6


def _rand(N, H, Hkv, C, hd, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (N, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (N, C, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (N, C, Hkv, hd), jnp.float32)
    return q, k, v


# pages are multiples of 128 (the int8 scale row is a lane-dense block)
@pytest.mark.parametrize("N,H,Hkv,C,hd,page", [
    (3, 4, 2, 256, 16, 128),  # GQA
    (2, 2, 1, 512, 8, 256),   # MQA
    (4, 8, 8, 128, 32, 128),  # MHA, single page
    (1, 4, 4, 384, 64, 128),  # non-power-of-two page count
])
def test_kernel_matches_oracle(N, H, Hkv, C, hd, page):
    q, k, v = _rand(N, H, Hkv, C, hd)
    pos = (jnp.arange(N, dtype=jnp.int32) * 97 + C // 2 + 3) % C
    got = decode_attention_pallas(q, k, v, pos, page_len=page)
    want = decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_kernel_ring_wraparound():
    """Positions beyond C: the ring has wrapped; stale entries must mask."""
    N, H, Hkv, C, hd = 2, 4, 2, 256, 16
    q, k, v = _rand(N, H, Hkv, C, hd, seed=1)
    pos = jnp.array([C + 3, 5 * C + 11], jnp.int32)
    got = decode_attention_pallas(q, k, v, pos, page_len=128)
    want = decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


@pytest.mark.parametrize("window", [4, 12])
def test_kernel_sliding_window(window):
    N, H, Hkv, C, hd = 2, 4, 1, 256, 16
    q, k, v = _rand(N, H, Hkv, C, hd, seed=2)
    pos = jnp.array([133, 250], jnp.int32)   # window 12 at 133 spans pages
    got = decode_attention_pallas(q, k, v, pos, page_len=128, window=window)
    want = decode_attention_ref(q, k, v, pos, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_kernel_softcap_and_traced_window():
    N, H, Hkv, C, hd = 2, 4, 2, 256, 16
    q, k, v = _rand(N, H, Hkv, C, hd, seed=3)
    pos = jnp.array([6, 135], jnp.int32)
    got = jax.jit(lambda *a: decode_attention_pallas(
        *a[:-1], window=a[-1], page_len=128, softcap=50.0))(q, k, v, pos,
                                                            jnp.int32(10))
    want = decode_attention_ref(q, k, v, pos, window=10, softcap=50.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_unwritten_slots_fully_masked():
    """A slot at position 0 attends only to its own just-written token even
    when the rest of the ring holds garbage."""
    N, H, Hkv, C, hd = 2, 2, 2, 256, 8
    q, k, v = _rand(N, H, Hkv, C, hd, seed=4)
    pos = jnp.array([0, 0], jnp.int32)
    got = decode_attention_pallas(q, k, v, pos, page_len=128)
    # only index 0 is valid -> output is exactly v[:, 0]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(v[:, 0].astype(got.dtype)),
                               atol=TOL)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_kernel_int8_matches_dequantized_ref(dt):
    """Kernel vs oracle on the SAME int8 cache + scales: both dequantize
    page-by-page with one rounding into the compute dtype, so the bound
    stays as tight as the bf16 case.  Positions cross >= 2 page
    boundaries (page_len=128, pos up to 330)."""
    from repro.quant import quantize_kv

    N, H, Hkv, C, hd = 3, 4, 2, 384, 16
    q, k, v = _rand(N, H, Hkv, C, hd, seed=5)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    pos = jnp.array([170, 330, 250], jnp.int32)
    got = decode_attention_pallas(q.astype(dt), kq, vq, pos, page_len=128,
                                  k_scale=ks, v_scale=vs)
    want = decode_attention_ref(q.astype(dt), kq, vq, pos, k_scale=ks,
                                v_scale=vs)
    atol = TOL if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_kernel_int8_parity_vs_unquantized_oracle(dt):
    """End-to-end quantization error: int8 kernel vs the bf16-oracle on
    the ORIGINAL unquantized cache stays within 1e-2 (the serve-tier
    acceptance bound), again crossing multiple page boundaries."""
    from repro.quant import quantize_kv

    N, H, Hkv, C, hd = 2, 4, 2, 256, 32
    q, k, v = _rand(N, H, Hkv, C, hd, seed=6)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    pos = jnp.array([255, 150], jnp.int32)
    got = decode_attention_pallas(q.astype(dt), kq, vq, pos, page_len=128,
                                  k_scale=ks, v_scale=vs)
    want = decode_attention_ref(q.astype(dt), k.astype(dt), v.astype(dt),
                                pos)
    assert float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32)))) <= 1e-2


def test_quantize_kv_roundtrip_within_half_bin():
    """Deterministic round-to-nearest: |deq - x| <= scale/2 per token."""
    from repro.quant import quantize_kv

    k = jax.random.normal(jax.random.PRNGKey(9), (2, 32, 2, 16)) * 4.0
    kq, ks = quantize_kv(k)
    deq = np.asarray(kq, np.float32) * np.asarray(ks)[..., None, None]
    err = np.abs(deq - np.asarray(k, np.float32))
    assert np.all(err <= np.asarray(ks)[..., None, None] / 2 + 1e-6)


@pytest.mark.parametrize("arch", ["yi-6b", "gemma2-9b",
                                  "recurrentgemma-2b"])
def test_decode_slots_pallas_matches_xla(arch):
    """The kernel wired through decode_slots reproduces the jnp path
    (dense RoPE/GQA, gemma2 softcap + alternating windows, griffin ring)."""
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    N, C = 3, 32
    state = model.init_slots(cfg, N, C)
    toks = jax.random.randint(jax.random.PRNGKey(1), (N, 1), 0,
                              cfg.vocab_size)
    pos = jnp.array([0, 3, 17], jnp.int32)
    lg_x, st_x = model.decode_slots(cfg, params, state, toks, pos)
    set_decode_attn_impl("pallas")
    try:
        lg_p, st_p = model.decode_slots(cfg, params, state, toks, pos)
    finally:
        set_decode_attn_impl("xla")
    np.testing.assert_allclose(np.asarray(lg_x), np.asarray(lg_p),
                               rtol=1e-5, atol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5), st_x, st_p)


def test_decode_slots_pallas_matches_xla_int8():
    """Same wiring check with an int8 KV cache: the kernel's in-register
    dequant (scale planes streamed per page) reproduces the XLA read
    path's full-cache dequant.  State compares with the same tolerance as
    the bf16 case — the write path is shared code, but XLA may fuse the
    K/V projection differently per consumer (an ulp in a scale)."""
    cfg = dataclasses.replace(get_config("yi-6b", smoke=True),
                              dtype="float32", kv_dtype="int8")
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    N, C = 3, 32
    state = model.init_slots(cfg, N, C)
    toks = jax.random.randint(jax.random.PRNGKey(1), (N, 1), 0,
                              cfg.vocab_size)
    pos = jnp.array([0, 3, 17], jnp.int32)
    lg_x, st_x = model.decode_slots(cfg, params, state, toks, pos)
    set_decode_attn_impl("pallas")
    try:
        lg_p, st_p = model.decode_slots(cfg, params, state, toks, pos)
    finally:
        set_decode_attn_impl("xla")
    np.testing.assert_allclose(np.asarray(lg_x), np.asarray(lg_p),
                               rtol=1e-5, atol=1e-5)

    def cmp(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.int8:   # one quantization step of slack
            assert np.max(np.abs(a.astype(np.int32)
                                 - b.astype(np.int32))) <= 1
        else:
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32),
                                       rtol=1e-5, atol=1e-5)
    jax.tree.map(cmp, st_x, st_p)
