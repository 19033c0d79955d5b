"""Training-path flash attention vs the closed-form oracles.

Parity contracts (interpret mode on CPU):

  * forward, backward (custom_vjp) and jvp (custom_jvp twin) match
    ``kernels/ref.py``'s oracles to <= 3e-6 in fp32 across
    causal x window x softcap x q_offset x GQA, including cases whose
    grids cross >= 2 block boundaries in BOTH axes and both schedules;
  * bf16 sits at ~1 ulp (accumulation-order straddling);
  * the Hutchinson route (jvp-of-grad) crosses the custom_jvp rule —
    asserted through the trace-time KERNEL_CALLS counters;
  * the model-level routes agree: flash == full == chunked through the
    same projection weights, including window + softcap + q_offset.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import (INTERPRET_CELL_CAP,
                                           _clamp_interpret_grid, _fit_block,
                                           attention_hbm_bytes_train_flash,
                                           attention_hbm_bytes_unfused,
                                           flash_attention)
from repro.kernels.fused_ce import KERNEL_CALLS
from repro.kernels.ref import (flash_attention_grads_ref,
                               flash_attention_jvp_ref, flash_attention_ref)

F32_TOL = 3e-6
# bf16 mantissa is 8 bits: one output-rounding ulp is a 2**-8 relative
# flip wherever accumulation order straddles a rounding boundary
BF16_RTOL = 2.0 / 256
BF16_ATOL = 2e-5


def _qkv(key, B, H, Hkv, Sq, Sk, hd, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), dtype) * 0.5
    k = jax.random.normal(ks[1], (B, Hkv, Sk, hd), dtype) * 0.5
    v = jax.random.normal(ks[2], (B, Hkv, Sk, hd), dtype) * 0.5
    return q, k, v


# case matrix: every entry runs forward, backward AND jvp parity.
# (1,2,1,192,192) with bq=bk=64 crosses two block boundaries in both grid
# axes (3x3 blocks); the q_offset case has Sq != Sk on uneven blocks.
CASES = [
    # B, H, Hkv, Sq, Sk, hd, bq, bk, causal, window, softcap, qoff, sched
    (1, 2, 1, 192, 192, 32, 64, 64, True, None, None, 0, None),
    (1, 2, 1, 192, 192, 32, 64, 64, True, 48, None, 0, "skip"),
    (1, 2, 1, 192, 192, 32, 64, 64, True, None, 20.0, 0, None),
    (1, 2, 2, 128, 192, 32, 32, 64, True, 80, 8.0, 64, "skip"),
    (1, 4, 1, 96, 160, 32, 32, 32, False, None, None, 0, "dense"),
    (2, 2, 1, 128, 128, 64, 64, 64, True, None, None, 0, "dense"),
]


def _run_parity(B, H, Hkv, Sq, Sk, hd, bq, bk, causal, window, softcap,
                qoff, sched, dtype=jnp.float32, use_jvp=False,
                atol=F32_TOL, rtol=0.0):
    q, k, v = _qkv(jax.random.PRNGKey(Sq + Sk + hd), B, H, Hkv, Sq, Sk, hd,
                   dtype)
    kw = dict(causal=causal, window=window, softcap=softcap, q_offset=qoff)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk, schedule=sched,
                          use_jvp=use_jvp, **kw)
    ref, _ = flash_attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol,
                               rtol=rtol)

    g = jax.random.normal(jax.random.PRNGKey(7), out.shape, dtype) * 0.5

    def f(q, k, v):
        o = flash_attention(q, k, v, block_q=bq, block_k=bk, schedule=sched,
                            use_jvp=use_jvp, **kw)
        return (o.astype(jnp.float32) * g.astype(jnp.float32)).sum()

    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = flash_attention_grads_ref(q, k, v, g, **kw)
    for got, want, name in ((dq, rq, "dq"), (dk, rk, "dk"), (dv, rv, "dv")):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=atol, rtol=rtol, err_msg=name)

    tq, tk, tv = _qkv(jax.random.PRNGKey(11), B, H, Hkv, Sq, Sk, hd, dtype)
    _, do = jax.jvp(
        lambda q, k, v: flash_attention(q, k, v, block_q=bq, block_k=bk,
                                        schedule=sched, use_jvp=True, **kw),
        (q, k, v), (tq, tk, tv))
    do_ref = flash_attention_jvp_ref(q, k, v, tq, tk, tv, **kw)
    np.testing.assert_allclose(np.asarray(do, np.float32),
                               np.asarray(do_ref, np.float32),
                               atol=atol, rtol=rtol)


@pytest.mark.parametrize(
    "B,H,Hkv,Sq,Sk,hd,bq,bk,causal,window,softcap,qoff,sched", CASES)
def test_flash_fwd_bwd_jvp_match_oracle(B, H, Hkv, Sq, Sk, hd, bq, bk,
                                        causal, window, softcap, qoff,
                                        sched):
    _run_parity(B, H, Hkv, Sq, Sk, hd, bq, bk, causal, window, softcap,
                qoff, sched)


def test_flash_bf16_parity():
    """bf16 fwd stays at fp32-level error (fp32 accumulators); grads sit
    ~1 ulp out where accumulation order straddles a rounding boundary."""
    _run_parity(1, 2, 1, 192, 192, 32, 64, 64, True, 48, 20.0, 0, None,
                dtype=jnp.bfloat16, atol=BF16_ATOL, rtol=BF16_RTOL)


def test_flash_traced_window():
    """A traced window (per-layer windows ride through lax.scan) takes the
    scalar-prefetch path and matches the static-window result exactly."""
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 2, 1, 192, 192, 32)
    static = flash_attention(q, k, v, window=48, block_q=64, block_k=64)
    traced = jax.jit(
        lambda w: flash_attention(q, k, v, window=w, block_q=64,
                                  block_k=64))(jnp.asarray(48, jnp.int32))
    np.testing.assert_allclose(np.asarray(traced), np.asarray(static),
                               atol=F32_TOL)


def test_flash_schedules_agree():
    """"skip" (clamped index maps + band guard) == "dense" (mask only)."""
    q, k, v = _qkv(jax.random.PRNGKey(5), 1, 2, 1, 192, 192, 32)
    for window in (None, 48):
        a = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                            schedule="skip")
        b = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                            schedule="dense")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=F32_TOL)


def test_kernel_calls_counters():
    """Trace-time counters: fwd / bwd kernels fire under grad, the
    custom_jvp rule fires under jvp-of-grad (the Hutchinson route) —
    the no-silent-fallback assertion the trainer tests reuse."""
    q, k, v = _qkv(jax.random.PRNGKey(0), 1, 2, 1, 64, 64, 32)
    before = {k_: KERNEL_CALLS.get(k_, 0) for k_ in
              ("attn_fwd", "attn_bwd_dq", "attn_bwd_dkv", "attn_jvp_rule")}
    jax.grad(lambda q: flash_attention(q, k, v).astype(jnp.float32).sum())(q)
    assert KERNEL_CALLS["attn_fwd"] > before["attn_fwd"]
    assert KERNEL_CALLS["attn_bwd_dq"] > before["attn_bwd_dq"]
    assert KERNEL_CALLS["attn_bwd_dkv"] > before["attn_bwd_dkv"]

    def f(q):
        return flash_attention(q, k, v, use_jvp=True).astype(
            jnp.float32).sum()

    u = jnp.ones_like(q)
    jax.jvp(jax.grad(f), (q,), (u,))
    assert KERNEL_CALLS["attn_jvp_rule"] > before["attn_jvp_rule"]


def test_jvp_crosses_layer_scan():
    """Forward-over-reverse through ``lax.scan`` (the transformer layer
    loop): linearization inlines the known side of a staged custom_jvp
    call, so the rule must be Pallas-free — this is the exact composition
    Hutchinson's HVP runs."""
    B, H, Hkv, S, hd = 1, 2, 1, 64, 16
    q0, k, v = _qkv(jax.random.PRNGKey(0), B, H, Hkv, S, S, hd)
    u = jnp.ones_like(q0)

    def f(q):
        def body(x, w):
            return flash_attention(x, k, v, window=w, use_jvp=True), None
        x, _ = jax.lax.scan(body, q, jnp.array([48, 64], jnp.int32))
        return x.astype(jnp.float32).sum()

    def f_ref(q):
        def body(x, w):
            return flash_attention_ref(x, k, v, window=w)[0], None
        x, _ = jax.lax.scan(body, q, jnp.array([48, 64], jnp.int32))
        return x.astype(jnp.float32).sum()

    _, hvp = jax.jvp(jax.grad(f), (q0,), (u,))
    _, hvp_ref = jax.jvp(jax.grad(f_ref), (q0,), (u,))
    np.testing.assert_allclose(np.asarray(hvp), np.asarray(hvp_ref),
                               atol=1e-5)


def test_interpret_grid_clamp():
    """Interpret grids are clamped to <= INTERPRET_CELL_CAP cells (the
    unrolled emulation is ~ms per cell) by growing blocks, preferring the
    axis with more blocks; the B*H outer product alone may exceed the cap
    (best effort)."""
    bq, bk = _clamp_interpret_grid(512, 512, 64, 64, outer=1)
    assert (512 // bq) * (512 // bk) <= INTERPRET_CELL_CAP
    # already small grids are untouched
    assert _clamp_interpret_grid(128, 128, 64, 64, outer=1) == (64, 64)
    # huge outer product: blocks max out at the axis length
    bq, bk = _clamp_interpret_grid(256, 256, 64, 64, outer=1024)
    assert bq == 256 and bk == 256
    assert _fit_block(192, 128) == 96  # largest divisor <= want
    # and a clamped end-to-end call still matches the oracle
    q, k, v = _qkv(jax.random.PRNGKey(9), 1, 1, 1, 512, 512, 16)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref, _ = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=F32_TOL)


def test_byte_models_ordering():
    """The train-path analytic floor: flash < unfused at any real length,
    and the unfused term grows ~quadratically."""
    B, H, Hkv, hd = 8, 12, 4, 128
    for S in (2048, 8192):
        assert attention_hbm_bytes_train_flash(B, H, Hkv, S, hd) < \
            attention_hbm_bytes_unfused(B, H, S, hd)
    r = (attention_hbm_bytes_unfused(B, H, 8192, hd)
         / attention_hbm_bytes_unfused(B, H, 2048, hd))
    assert 8 < r <= 16


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**30), s_blocks=st.integers(1, 4),
       causal=st.booleans(), windowed=st.booleans())
def test_flash_property(seed, s_blocks, causal, windowed):
    S = 64 * s_blocks
    window = 40 if (windowed and causal) else None
    q, k, v = _qkv(jax.random.PRNGKey(seed), 1, 2, 1, S, S, 64)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64)
    ref, _ = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-6)
    # rows are convex combinations of v rows: output bounded by v range
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-4


# ---------------------------------------------------------------------------
# model-level routes (models/layers.py dispatch)


def _layer_cfg(**kw):
    from repro.models.common import ModelConfig
    base = dict(name="t", family="dense", n_layers=1, d_model=64, n_heads=4,
                n_kv_heads=2, d_ff=128, vocab_size=128, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def test_train_attention_routes_agree():
    """flash == full == chunked through the same projections, including
    sliding window + softcap + non-zero q_offset (the chunked-prefill
    continuation case)."""
    from repro.models.layers import init_attention, train_attention
    cfg = _layer_cfg(attn_logit_softcap=8.0)
    p = init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    # window >= qoff + 1 in the offset cases keeps every query row's
    # in-window key set non-empty against the S-long KV chunk: on a fully
    # masked row flash (like the oracle) outputs 0 while full/chunked's
    # all -1e30 softmax degenerates to uniform — a row real prefill
    # continuations never produce (their KV always covers the window)
    for window, qoff in ((None, 0), (32, 0), (96, 64), (None, 64)):
        pos_o = pos + qoff
        outs = {impl: train_attention(p, x, cfg, pos_o, window=window,
                                      q_offset=qoff, impl=impl)
                for impl in ("full", "chunked", "flash")}
        np.testing.assert_allclose(
            np.asarray(outs["chunked"]), np.asarray(outs["full"]),
            rtol=1e-5, atol=1e-5, err_msg=f"chunked w={window} q0={qoff}")
        np.testing.assert_allclose(
            np.asarray(outs["flash"]), np.asarray(outs["full"]),
            rtol=1e-5, atol=1e-5, err_msg=f"flash w={window} q0={qoff}")


def test_train_attention_grads_agree():
    from repro.models.layers import init_attention, train_attention
    cfg = _layer_cfg()
    p = init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 64))
    pos = jnp.broadcast_to(jnp.arange(128)[None], (1, 128))

    def loss(p, impl):
        return (train_attention(p, x, cfg, pos, window=48,
                                impl=impl) ** 2).sum()

    g_full = jax.grad(loss)(p, "full")
    g_flash = jax.grad(loss)(p, "flash")
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_flash)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-5)


def test_set_train_attn_impl_round_trip():
    from repro.models.layers import get_train_attn_impl, set_train_attn_impl
    prev = get_train_attn_impl()
    try:
        set_train_attn_impl("flash")
        assert get_train_attn_impl() == "flash"
        with pytest.raises(AssertionError):
            set_train_attn_impl("nope")
    finally:
        set_train_attn_impl(prev)


def test_train_attention_cross_kv_override():
    """Cross-attention (kv_override) reaches the flash kernel bidirectional
    (no rope on q, raw kv) and matches the full path."""
    from repro.models.layers import _qkv as qkv_proj
    from repro.models.layers import init_attention, train_attention
    cfg = _layer_cfg()
    p = init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64))
    mem = jax.random.normal(jax.random.PRNGKey(2), (1, 96, 64))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (1, 64))
    _, mk, mv = qkv_proj(p, mem, cfg)
    kv = (mk, mv)
    a = train_attention(p, x, cfg, pos, causal=False, kv_override=kv,
                        impl="full")
    b = train_attention(p, x, cfg, pos, causal=False, kv_override=kv,
                        impl="flash")
    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the band walk inside the kernel (grid plan, tile classes, chunks)


def _grads(q, k, v, g, window, **kw):
    """(o, dq, dk, dv) through the custom_vjp path, the window traced."""
    def f(q, k, v, w):
        o = flash_attention(q, k, v, window=w, **kw)
        return (o.astype(jnp.float32) * g).sum(), o
    (_, o), grads = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))(
            q, k, v, jnp.asarray(window or (1 << 30), jnp.int32))
    return (o, *grads)


def _oracle(q, k, v, g, window, *, causal, q_offset, softcap=None):
    kw = dict(causal=causal, window=window, q_offset=q_offset,
              softcap=softcap)
    o, _ = flash_attention_ref(q, k, v, **kw)
    return (o, *flash_attention_grads_ref(q, k, v, g, **kw))


BAND_CASES = {
    # B, H, Hkv, Sq, Sk, hd, bq, bk, causal, window, q_offset
    "window_mid_sequence": (1, 2, 1, 256, 256, 32, 32, 32, True, 70, 0),
    "cross_sq_ne_sk_q_offset": (1, 2, 2, 64, 192, 32, 32, 64, True, None,
                                128),
    "bidirectional_sq_ne_sk_q_offset": (1, 2, 1, 96, 160, 32, 32, 32, False,
                                        None, 32),
    "gqa_group2": (1, 4, 2, 128, 128, 32, 32, 32, True, 48, 0),
    "gqa_group4": (1, 8, 2, 128, 128, 32, 32, 64, True, None, 0),
}


@pytest.mark.parametrize("case", BAND_CASES.values(), ids=BAND_CASES.keys())
def test_band_walk_parity(case):
    """"skip" walks the band inside the kernel: a band that starts
    mid-sequence (traced window, lo > 0), Sq != Sk with q_offset > 0, and
    GQA groups through dkv's (group, q-block) loop all match the oracle,
    and match "dense", which masks every tile."""
    B, H, Hkv, Sq, Sk, hd, bq, bk, causal, window, qoff = case
    q, k, v = _qkv(jax.random.PRNGKey(Sq + 3 * Sk), B, H, Hkv, Sq, Sk, hd)
    g = jax.random.normal(jax.random.PRNGKey(1), q.shape) * 0.5
    kw = dict(causal=causal, q_offset=qoff, block_q=bq, block_k=bk)
    skip = _grads(q, k, v, g, window, schedule="skip", **kw)
    dense = _grads(q, k, v, g, window, schedule="dense", **kw)
    want = _oracle(q, k, v, g, window, causal=causal, q_offset=qoff)
    for name, s, d, w in zip(("o", "dq", "dk", "dv"), skip, dense, want):
        np.testing.assert_allclose(np.asarray(s), np.asarray(w),
                                   atol=F32_TOL, err_msg=name)
        np.testing.assert_allclose(np.asarray(s), np.asarray(d),
                                   atol=F32_TOL, err_msg=name)


def test_major_chunk_fallback(monkeypatch):
    """When the resident head would not fit the VMEM budget, K/V (fwd, dq)
    and Q/dO/lse/delta (dkv) are cut into major chunks that stay on the
    grid; a traced window exercises the chunk index maps' band clamp."""
    from repro.kernels import flash_attention as fa
    B, H, Hkv, S, hd, bq, bk = 1, 4, 2, 256, 32, 32, 128
    # fp32 rows of two operands, lane-padded: 1 KiB; two 128-row buffers
    monkeypatch.setattr(fa, "RESIDENT_VMEM_BYTES", 2 * 128 * 1024)
    plans = {kern: fa.attn_grid_plan(kern, B, H, Hkv, S, S, hd, block_q=bq,
                                      block_k=bk, itemsize=4)
             for kern in ("fwd", "dkv")}
    assert plans["fwd"].grid[3] == 2 and plans["fwd"].block_major == 128
    assert plans["dkv"].grid[3] == S // bq
    q, k, v = _qkv(jax.random.PRNGKey(4), B, H, Hkv, S, S, hd)
    g = jax.random.normal(jax.random.PRNGKey(2), q.shape) * 0.5
    for window in (None, 100):
        got = _grads(q, k, v, g, window, block_q=bq, block_k=bk,
                     schedule="skip")
        want = _oracle(q, k, v, g, window, causal=True, q_offset=0)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=F32_TOL,
                                       err_msg=f"{name} w={window}")


def test_grid_plan_at_gpt2_medium():
    """gpt2m's (8, 16, 1024, 64) at 256 x 256: the parent's grid had
    8 * 16 * 4 * 4 = 2048 steps per kernel call; the band walk leaves 512,
    walking 1280 in-band tiles per call of which the 512 diagonal ones are
    masked."""
    from repro.kernels.flash_attention import attn_grid_plan
    for kern in ("fwd", "dq", "dkv"):
        plan = attn_grid_plan(kern, 8, 16, 16, 1024, 1024, 64, block_q=256,
                              block_k=256, itemsize=2)
        assert plan.steps == 512 < 2048, (kern, plan)
        assert plan.block_major == 1024
        assert plan.tiles_full + plan.tiles_masked == 1280
        assert plan.tiles_masked == 512
    dense = attn_grid_plan("fwd", 8, 16, 16, 1024, 1024, 64, block_q=256,
                           block_k=256, itemsize=2, schedule="dense")
    assert (dense.tiles_full, dense.tiles_masked) == (0, 2048)


def test_grid_plan_counters():
    """The wrappers add their plans to the trace-time counters."""
    q, k, v = _qkv(jax.random.PRNGKey(0), 1, 2, 1, 128, 128, 32)
    before = {k_: KERNEL_CALLS.get(k_, 0) for k_ in
              ("attn_grid_steps", "attn_tiles_full", "attn_tiles_masked")}
    jax.grad(lambda q: flash_attention(
        q, k, v, block_q=32, block_k=32,
        schedule="skip").astype(jnp.float32).sum())(q)
    # three calls (fwd, dq, dkv) of 4 q-blocks x 2 heads, or 4 k-blocks
    # x 1 KV head; 10 in-band tiles per head, 4 of them on the diagonal
    assert KERNEL_CALLS["attn_grid_steps"] - before["attn_grid_steps"] \
        == 8 + 8 + 4
    assert KERNEL_CALLS["attn_tiles_full"] - before["attn_tiles_full"] \
        == 3 * 2 * 6
    assert KERNEL_CALLS["attn_tiles_masked"] - before["attn_tiles_masked"] \
        == 3 * 2 * 4


@settings(max_examples=40, deadline=None)
@given(bq=st.sampled_from([8, 16, 24]), bk=st.sampled_from([8, 16, 24]),
       n=st.integers(1, 4), m=st.integers(1, 4), causal=st.booleans(),
       window=st.one_of(st.none(), st.integers(1, 80)),
       q_offset=st.integers(0, 40))
def test_walk_classifies_tiles(bq, bk, n, m, causal, window, q_offset):
    """Against the mask itself: both walks (fwd/dq over KV tiles of a
    q-block, dkv over q-tiles of a KV block) visit every tile holding an
    attended pair, and call a tile unmasked iff all its pairs attend."""
    from repro.kernels import flash_attention as fa
    Sq, Sk = n * bq, m * bk
    win = window or fa.WINDOW_NONE
    band = dict(causal=causal, q_offset=q_offset, block_q=bq, block_k=bk)
    qpos = q_offset + np.arange(Sq)[:, None]
    kpos = np.arange(Sk)[None, :]
    att = kpos > qpos - win
    if causal:
        att &= kpos <= qpos
    tiles = att.reshape(n, bq, m, bk)
    any_, all_ = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))

    def classes(ranges, size):
        got = np.zeros(size, int)          # 0 skipped, 1 masked, 2 full
        for start, stop, masked in ranges:
            got[int(start):int(stop)] = 1 if masked else 2
        return got

    for i in range(n):
        got = classes(fa._walk(fa._kv_band(i, win, n_k=m, xp=np, **band),
                               fa._kv_full(i, win, n_k=m, **band), 0, m,
                               "skip", xp=np), m)
        assert np.all(got[any_[i]] > 0) and np.all((got == 2) == all_[i])
    for j in range(m):
        got = classes(fa._walk(fa._q_band(j, win, n_q=n, xp=np, **band),
                               fa._q_full(j, win, n_q=n, **band), 0, n,
                               "skip", xp=np), n)
        assert np.all(got[any_[:, j]] > 0) and \
            np.all((got == 2) == all_[:, j])
