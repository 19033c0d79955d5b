"""Logits-free fused LM loss: fused-vs-reference parity for loss /
d_hidden / d_W across {fp32, bf16} x {tied, untied} embeddings, exact-zero
gradients on padded vocab columns, in-sweep GNB sampling parity and chunk
invariance, online-chunked-Gumbel-argmax == jax.random.categorical, and
the model/trainer wiring (all three impls of models.loss.lm_loss)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import HAS_HYPOTHESIS, given, settings, st  # noqa: F401

from repro.core.estimators import chunked_sampled_stats
from repro.kernels.fused_ce import (fused_lm_loss, fused_lm_loss_sampled,
                                    fused_lm_sample, hash_gumbel,
                                    lm_loss_hbm_bytes_fused,
                                    lm_loss_hbm_bytes_unfused, seed_from_key)
from repro.kernels.ref import (lm_loss_grads_ref, lm_loss_ref,
                               lm_loss_sampled_ref)

TOL = 3e-6


VOCAB = 200   # padded to 256 -> two 128-wide chunks: every kernel test
#               exercises the cross-chunk online carries (lse rescale,
#               running argmax, scratch init/flush gating), not just n_v=1


def _setup(dtype, tied, *, B=4, T=12, D=32, V=VOCAB, Vp=256, seed=0,
           w_dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    hidden = jax.random.normal(ks[0], (B, T, D), dtype)
    w_shape = (Vp, D) if tied else (D, Vp)
    w = (jax.random.normal(ks[1], w_shape, jnp.float32) * 0.2) \
        .astype(w_dtype)
    labels = jax.random.randint(ks[2], (B, T), 0, V)
    mask = (jax.random.uniform(ks[3], (B, T)) > 0.3).astype(jnp.float32)
    return hidden, w, labels, mask


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_fused_matches_ref_loss_and_grads(dtype, tied, softcap):
    hidden, w, labels, mask = _setup(dtype, tied)
    tw = not tied

    def f(h, w_):
        return fused_lm_loss(h, w_, labels, mask, vocab_size=VOCAB,
                             transpose_w=tw, softcap=softcap,
                             block_n=16, block_v=64)[0]

    loss, (dh, dw) = jax.value_and_grad(f, argnums=(0, 1))(hidden, w)
    loss_r, dh_r, dw_r = lm_loss_grads_ref(
        hidden, w, labels, mask, vocab_size=VOCAB, transpose_w=tw,
        softcap=softcap)
    np.testing.assert_allclose(float(loss), float(loss_r), atol=TOL)
    assert dh.dtype == hidden.dtype and dw.dtype == w.dtype
    dh32, dh_r32 = np.asarray(dh, np.float32), np.asarray(dh_r, np.float32)
    if dtype == jnp.bfloat16:
        # Kernel and oracle each round an fp32 d_hidden into bf16 once, but
        # sum it in a different order; fp32 values a few 1e-7 apart can
        # straddle a bf16 rounding boundary and land one bf16 ulp apart
        # (1.5e-5 at |dh| ~ 2e-3, above TOL).  So bf16 outputs are held to
        # one bf16 ulp of the reference, never looser than TOL.
        tol = np.maximum(TOL, _bf16_ulp(dh_r32))
        assert np.all(np.abs(dh32 - dh_r32) <= tol), \
            np.max(np.abs(dh32 - dh_r32) - tol)
    else:
        np.testing.assert_allclose(dh32, dh_r32, atol=TOL)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_r), atol=TOL)


def _bf16_ulp(x):
    """One bf16 ulp (8-bit significand) at each element of fp32 ``x``."""
    a = np.abs(x)
    e = np.floor(np.log2(np.where(a > 0, a, 1.0)))
    return np.where(a > 0, 2.0 ** (e - 7), 0.0)


def test_bf16_weights_accumulate_dw_in_fp32():
    """With bf16 weights d_W must accumulate across row tiles in fp32 and
    round ONCE at the flush — per-tile rounding in the output dtype drifts
    per-mille at real tile counts.  Many row tiles (block_n=8 over N=96)
    against the closed-form oracle, which also rounds once."""
    hidden, w, labels, mask = _setup(jnp.bfloat16, True, T=24,
                                     w_dtype=jnp.bfloat16)

    def f(h, w_):
        return fused_lm_loss(h, w_, labels, mask, vocab_size=VOCAB,
                             block_n=8, block_v=128)[0]

    _, dw = jax.value_and_grad(f, argnums=1)(hidden, w)
    _, _, dw_r = lm_loss_grads_ref(hidden, w, labels, mask,
                                   vocab_size=VOCAB)
    assert dw.dtype == jnp.bfloat16
    # both sides round the same fp32 value to bf16: agreement to ~1 ulp
    np.testing.assert_allclose(np.asarray(dw, np.float32),
                               np.asarray(dw_r, np.float32), atol=2e-5)


def test_closed_form_oracle_matches_autodiff_fp32():
    """lm_loss_grads_ref (the kernel-parity oracle) == jax.grad of the
    differentiable materialized-logits oracle in fp32."""
    hidden, w, labels, mask = _setup(jnp.float32, True)

    def f(h, w_):
        return lm_loss_ref(h, w_, labels, mask, vocab_size=VOCAB)

    loss, (dh, dw) = jax.value_and_grad(f, argnums=(0, 1))(hidden, w)
    loss_r, dh_r, dw_r = lm_loss_grads_ref(hidden, w, labels, mask,
                                           vocab_size=VOCAB)
    np.testing.assert_allclose(float(loss), float(loss_r), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(dh_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_r), atol=1e-5)


@pytest.mark.parametrize("tied", [True, False])
def test_padded_vocab_columns_get_exactly_zero_grad(tied):
    """Padded columns (vocab_size <= col < padded_vocab) must receive
    bitwise-zero d_W in the fused kernel AND the reference oracle."""
    hidden, w, labels, mask = _setup(jnp.float32, tied)
    tw = not tied

    def fused(h, w_):
        return fused_lm_loss(h, w_, labels, mask, vocab_size=VOCAB,
                             transpose_w=tw, block_n=16, block_v=64)[0]

    def ref(h, w_):
        return lm_loss_ref(h, w_, labels, mask, vocab_size=VOCAB,
                           transpose_w=tw)

    for f in (fused, ref):
        dw = jax.grad(f, argnums=1)(hidden, w)
        pad = dw[:, VOCAB:] if tw else dw[VOCAB:, :]
        np.testing.assert_array_equal(np.asarray(pad), 0.0)
        live = dw[:, :VOCAB] if tw else dw[:VOCAB, :]
        assert float(jnp.max(jnp.abs(live))) > 0.0


def test_unfused_model_path_masks_padding():
    """The materialized-logits path (unembed + cross_entropy) must not
    leak padding into the CE denominator or its gradient either."""
    from repro.models import get_model, lm_loss
    from repro.models.common import ModelConfig

    cfg = ModelConfig(name="padded", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=VOCAB,
                      tie_embeddings=True, dtype="float32")
    assert cfg.padded_vocab == 256
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.ones((2, 8), jnp.int32)}

    losses = {}
    for impl in ("unfused", "chunked", "fused"):
        loss, _ = model.loss_fn(cfg, params, batch, loss_impl=impl)
        losses[impl] = float(loss)
        dtok = jax.grad(
            lambda p: model.loss_fn(cfg, p, batch, loss_impl=impl)[0]
        )(params)["embed"]["tok"]
        # tied embeddings: rows >= vocab_size exist only as logits columns,
        # so their gradient must be exactly zero
        np.testing.assert_array_equal(np.asarray(dtok[VOCAB:]), 0.0, impl)
    assert abs(losses["unfused"] - losses["chunked"]) < 1e-5
    assert abs(losses["unfused"] - losses["fused"]) < 1e-5
    # denominator excludes padding: at init (logits ~ uniform) the CE must
    # sit near log(vocab_size), not log(padded_vocab)
    assert abs(losses["unfused"] - np.log(VOCAB)) < 0.5


def test_sampled_fused_matches_ref_and_is_chunk_invariant():
    hidden, w, _, mask = _setup(jnp.float32, True)
    rng = jax.random.PRNGKey(9)

    def f(h, w_):
        return fused_lm_loss_sampled(h, w_, rng, mask, vocab_size=VOCAB,
                                     block_n=16, block_v=64)[0]

    loss, (dh, dw) = jax.value_and_grad(f, argnums=(0, 1))(hidden, w)
    loss_r, yhat_r, dh_r, dw_r = lm_loss_sampled_ref(
        hidden, w, rng, mask, vocab_size=VOCAB)
    np.testing.assert_allclose(float(loss), float(loss_r), atol=TOL)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(dh_r), atol=TOL)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_r), atol=TOL)
    np.testing.assert_array_equal(np.asarray(dw_r[VOCAB:]), 0.0)

    # the draw is a pure function of (seed, row, col): any (block_n,
    # block_v) tiling yields bit-identical labels
    yh = fused_lm_sample(hidden, w, rng, vocab_size=VOCAB, block_n=16,
                         block_v=128)
    np.testing.assert_array_equal(np.asarray(yh), np.asarray(yhat_r))
    for bn, bv in [(48, 256), (8, 128)]:
        yh2 = fused_lm_sample(hidden, w, rng, vocab_size=VOCAB, block_n=bn,
                              block_v=bv)
        np.testing.assert_array_equal(np.asarray(yh), np.asarray(yh2))
    # never samples a padded column
    assert int(jnp.max(yh)) < VOCAB


def test_hash_gumbel_is_gumbel_distributed():
    """Counter-based noise matches Gumbel(0,1) moments (mean ~ gamma,
    var ~ pi^2/6)."""
    rows = jnp.arange(512, dtype=jnp.int32)[:, None]
    cols = jnp.arange(256, dtype=jnp.int32)[None, :]
    g = np.asarray(hash_gumbel(seed_from_key(jax.random.PRNGKey(3)),
                               rows, cols))
    assert abs(g.mean() - 0.5772) < 0.02
    assert abs(g.var() - np.pi ** 2 / 6) < 0.05


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 33), st.integers(1, 6),
       st.integers(1, 8))
def test_chunked_gumbel_argmax_identical_to_categorical(seed, v, n, chunk):
    """Online chunked Gumbel-argmax over noise from a fixed key is
    DISTRIBUTION-IDENTICAL to jax.random.categorical — bit-for-bit, since
    categorical(key, logits) == argmax(logits + gumbel(key), -1) and the
    online reduction is exact for any chunking."""
    key = jax.random.PRNGKey(seed)
    logits = jax.random.normal(jax.random.fold_in(key, 1), (n, v),
                               jnp.float32) * 3.0
    noise = jax.random.gumbel(key, logits.shape, jnp.float32)
    _, _, yhat = chunked_sampled_stats(logits, noise=noise, chunk=chunk)
    expect = jax.random.categorical(key, logits, axis=-1)
    np.testing.assert_array_equal(np.asarray(yhat), np.asarray(expect))


def test_chunked_sampled_stats_lse_and_grad():
    """The single-sweep stats reproduce log-sum-exp exactly and
    grad(lse - ll) == softmax - onehot(yhat)."""
    key = jax.random.PRNGKey(4)
    logits = jax.random.normal(key, (6, 37), jnp.float32) * 2.0
    lse, ll, yhat = chunked_sampled_stats(logits, jax.random.PRNGKey(5),
                                          chunk=7)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(jax.nn.logsumexp(logits, -1)),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ll),
        np.asarray(jnp.take_along_axis(logits, yhat[:, None], 1)[:, 0]),
        rtol=1e-6)

    def nll(lg):
        lse_, ll_, _ = chunked_sampled_stats(lg, jax.random.PRNGKey(5),
                                             chunk=7)
        return (lse_ - ll_).sum()

    d = jax.grad(nll)(logits)
    p = jax.nn.softmax(logits, -1)
    onehot = jax.nn.one_hot(yhat, 37)
    np.testing.assert_allclose(np.asarray(d), np.asarray(p - onehot),
                               atol=1e-6)


@pytest.mark.parametrize("norm", ["rms", "ln"])
@pytest.mark.parametrize("tied", [True, False])
def test_norm_producer_fusion_matches_jnp_norm(norm, tied):
    """The in-kernel final-norm producer == jnp norm then kernel, for loss
    AND grads (hidden, W, norm scale/bias) — the (N, D) round-trip the
    fusion eliminates must not change a single ulp beyond fp tolerance."""
    from repro.models.layers import layer_norm, rms_norm

    hidden, w, labels, mask = _setup(jnp.float32, tied)
    tw = not tied
    D = hidden.shape[-1]
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    scale = jax.random.normal(ks[0], (D,), jnp.float32) * 0.1
    bias = jax.random.normal(ks[1], (D,), jnp.float32) * 0.1

    def fused_norm(h, w_, sc, bi):
        return fused_lm_loss(h, w_, labels, mask, vocab_size=VOCAB,
                             transpose_w=tw, block_n=16, block_v=64,
                             norm_kind=norm, norm_scale=sc,
                             norm_bias=bi if norm == "ln" else None)[0]

    def jnp_then_kernel(h, w_, sc, bi):
        hn = (layer_norm(h, sc, bi, 1e-6) if norm == "ln"
              else rms_norm(h, sc, 1e-6))
        return fused_lm_loss(hn, w_, labels, mask, vocab_size=VOCAB,
                             transpose_w=tw, block_n=16, block_v=64)[0]

    args = (hidden, w, scale, bias)
    la, ga = jax.value_and_grad(fused_norm, argnums=(0, 1, 2, 3))(*args)
    lb, gb = jax.value_and_grad(jnp_then_kernel, argnums=(0, 1, 2, 3))(*args)
    np.testing.assert_allclose(float(la), float(lb), atol=TOL)
    for x, y, name in zip(ga, gb, ("dh", "dw", "dscale", "dbias")):
        if norm == "rms" and name == "dbias":
            continue  # rms has no bias; the fused arg gets zero cotangent
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5,
                                   err_msg=name)


@pytest.mark.parametrize("tied", [True, False])
def test_backward_schedules_agree(tied):
    """The combined revisit-free backward ("fused" schedule, legal at
    single-axis grids) == the two-sweep "split" backward at the same
    tiling."""
    hidden, w, labels, mask = _setup(jnp.float32, tied)
    tw = not tied

    def f(sched):
        def loss(h, w_):
            return fused_lm_loss(h, w_, labels, mask, vocab_size=VOCAB,
                                 transpose_w=tw, block_n=16, block_v=256,
                                 schedule=sched)[0]
        return jax.value_and_grad(loss, argnums=(0, 1))(hidden, w)

    (lf, (dhf, dwf)), (ls, (dhs, dws)) = f("fused"), f("split")
    np.testing.assert_allclose(float(lf), float(ls), atol=TOL)
    np.testing.assert_allclose(np.asarray(dhf), np.asarray(dhs), atol=TOL)
    np.testing.assert_allclose(np.asarray(dwf), np.asarray(dws), atol=TOL)


@pytest.mark.parametrize("family", ["dense", "rwkv"])
def test_model_loss_impls_agree(family):
    """fused == chunked == unfused (to fp tolerance) through a real model
    trunk, including the masked mean."""
    from repro.models import get_model
    from repro.models.common import ModelConfig

    d_model = 64 if family == "rwkv" else 32  # rwkv decay heads are 64-wide
    cfg = ModelConfig(name="t", family=family, n_layers=2, d_model=d_model,
                      n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=96,
                      tie_embeddings=False, dtype="float32", rope=True)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    S = 64  # rwkv time-mix needs a 64-multiple sequence
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    batch = {"tokens": jax.random.randint(ks[0], (2, S), 0, 96),
             "labels": jax.random.randint(ks[1], (2, S), 0, 96),
             "mask": (jax.random.uniform(ks[2], (2, S)) > 0.25)
             .astype(jnp.float32)}

    vals, grads = {}, {}
    for impl in ("unfused", "chunked", "fused"):
        loss, g = jax.value_and_grad(
            lambda p: model.loss_fn(cfg, p, batch, loss_impl=impl)[0]
        )(params)
        vals[impl] = float(loss)
        grads[impl] = g
    assert abs(vals["chunked"] - vals["unfused"]) < 1e-5
    assert abs(vals["fused"] - vals["unfused"]) < 1e-5
    for impl in ("chunked", "fused"):
        for a, b in zip(jax.tree.leaves(grads[impl]),
                        jax.tree.leaves(grads["unfused"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)


def test_trainer_fused_loss_end_to_end():
    """Sophia-G + fused loss + in-kernel GNB refresh: losses finite and
    the non-refresh hot path matches the chunked run step-for-step until
    the first refresh diverges the h state (different sampling streams)."""
    from repro.configs.gpt2 import GPT2_TINY
    from repro.data import DataConfig, make_source
    from repro.train import TrainerConfig, train_loop

    src = make_source(DataConfig(seq_len=32, global_batch=4, vocab_size=512,
                                 seed=0))
    hists = {}
    for fused in (False, True):
        tc = TrainerConfig(optimizer="sophia_g", peak_lr=3e-4,
                           total_steps=10, hess_interval=4, hess_subbatch=2,
                           seed=0, fused_loss=fused)
        _, hist = train_loop(GPT2_TINY, tc, src, num_steps=6)
        hists[fused] = hist
        assert all(np.isfinite(h["loss"]) for h in hist)
    # identical grads until the first refresh's h takes effect (step 1)
    assert abs(hists[True][0]["loss"] - hists[False][0]["loss"]) < 1e-5
    assert abs(hists[True][1]["loss"] - hists[False][1]["loss"]) < 1e-4


def test_hbm_bytes_model_v_independence():
    """The analytic fused-loss traffic has no N*V term: growing V only
    adds W-stream bytes, while the unfused model blows up linearly in
    N*V."""
    N, D = 4096, 1024
    f1 = lm_loss_hbm_bytes_fused(N, D, 32_000)
    f2 = lm_loss_hbm_bytes_fused(N, D, 256_000)
    u1 = lm_loss_hbm_bytes_unfused(N, D, 32_000)
    u2 = lm_loss_hbm_bytes_unfused(N, D, 256_000)
    w_delta = 4 * (256_000 - 32_000) * D * 4  # 3 reads + 1 write of dW
    assert f2 - f1 == w_delta
    assert u2 - u1 > 5 * N * (256_000 - 32_000) * 4 * 0.99
    assert f1 < u1 and f2 < u2


def test_per_device_kernels_match_unsharded():
    """Under an activation mesh every kernel entry point runs per device on
    its batch shard (XLA cannot partition a Mosaic kernel).  The sharded
    loss, its gradients and the in-sweep GNB samples (noise keyed on
    *global* row indices) must match the unsharded call; so must flash
    attention and its gradients.  Runs in a subprocess with 4 host
    devices on a 2x2 (data, model) mesh."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, {json.dumps(src)})
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.sharding import set_activation_mesh
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.fused_ce import (fused_lm_loss,
                                            fused_lm_loss_sampled)
        from repro.launch.mesh import make_mesh

        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        h = jax.random.normal(ks[0], (4, 12, 32))
        w = jax.random.normal(ks[1], (256, 32)) * 0.2
        lab = jax.random.randint(ks[2], (4, 12), 0, 200)
        mask = (jax.random.uniform(ks[3], (4, 12)) > 0.3).astype(jnp.float32)
        q = jax.random.normal(ks[4], (4, 2, 32, 16))
        kw = dict(vocab_size=200, block_n=16, block_v=128)

        def losses(h, w, q):
            a = fused_lm_loss(h, w, lab, mask, **kw)[0]
            b = fused_lm_loss_sampled(h, w, jax.random.PRNGKey(7), mask,
                                      **kw)[0]
            c = flash_attention(q, q, q).sum()
            return a + 2.0 * b + 3.0 * c, (a, b, c)

        f = jax.jit(jax.value_and_grad(losses, argnums=(0, 1, 2),
                                       has_aux=True))
        (_, ref), gref = f(h, w, q)
        set_activation_mesh(make_mesh((2, 2), ("data", "model")))
        f = jax.jit(jax.value_and_grad(losses, argnums=(0, 1, 2),
                                       has_aux=True))
        (_, got), g = f(h, w, q)
        for x, y in zip(got + g, ref + gref):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=3e-6, rtol=3e-6)
        print("PER_DEVICE_OK")
    """)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=600, env=env)
    assert "PER_DEVICE_OK" in r.stdout, (r.stdout[-800:], r.stderr[-3000:])


# ---------------------------------------------------------------------------
# ragged vocabulary chunks: block_v need not divide the padded vocab; the
# last chunk runs past Vp and its tail is masked inside the kernels

RAGGED_VP = 384           # three 128-lane groups: block_v 256 leaves 128
#                           columns of the second chunk past the array


def _norm_ref(norm, h, scale, bias):
    from repro.models.layers import layer_norm, rms_norm
    if norm == "ln":
        return layer_norm(h, scale, bias, 1e-6)
    if norm == "rms":
        return rms_norm(h, scale, 1e-6)
    return h


@pytest.mark.parametrize("vocab", [RAGGED_VP, 300], ids=["full", "padded"])
@pytest.mark.parametrize("norm", [None, "ln", "rms"])
@pytest.mark.parametrize("tied", [True, False])
def test_ragged_chunks_match_dividing_chunks_and_oracle(tied, norm, vocab):
    """block_v 256 at Vp 384 (a ragged last chunk, whose tail interpret
    mode reads as NaN) == block_v 128 (chunks that divide Vp) == the
    kernels/ref.py oracles, for the loss and the gradients of hidden, W and
    the fused norm's scale and bias; W's columns past the vocabulary get
    exactly zero gradient."""
    hidden, w, labels, mask = _setup(jnp.float32, tied, V=vocab,
                                     Vp=RAGGED_VP)
    tw = not tied
    D = hidden.shape[-1]
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    scale = jax.random.normal(ks[0], (D,), jnp.float32) * 0.1
    bias = jax.random.normal(ks[1], (D,), jnp.float32) * 0.1

    def fused(bv):
        def loss(h, w_, sc, bi):
            return fused_lm_loss(h, w_, labels, mask, vocab_size=vocab,
                                 transpose_w=tw, block_n=16, block_v=bv,
                                 norm_kind=norm, norm_scale=sc,
                                 norm_bias=bi if norm == "ln" else None)[0]
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
            hidden, w, scale, bias)

    (lr_, gr), (ld, gd) = fused(256), fused(128)
    np.testing.assert_allclose(float(lr_), float(ld), atol=TOL)
    for x, y, name in zip(gr, gd, ("dh", "dw", "dscale", "dbias")):
        assert np.all(np.isfinite(np.asarray(x))), name
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=TOL,
                                   err_msg=name)
    dw = gr[1]
    np.testing.assert_array_equal(
        np.asarray(dw[:, vocab:] if tw else dw[vocab:]), 0.0)

    if norm is None:
        loss_r, dh_r, dw_r = lm_loss_grads_ref(
            hidden, w, labels, mask, vocab_size=vocab, transpose_w=tw)
        np.testing.assert_allclose(float(lr_), float(loss_r), atol=TOL)
        np.testing.assert_allclose(np.asarray(gr[0]), np.asarray(dh_r),
                                   atol=TOL)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_r),
                                   atol=TOL)
        return

    def oracle(h, w_, sc, bi):
        return lm_loss_ref(_norm_ref(norm, h, sc, bi), w_, labels, mask,
                           vocab_size=vocab, transpose_w=tw)

    loss_r, g_r = jax.value_and_grad(oracle, argnums=(0, 1, 2, 3))(
        hidden, w, scale, bias)
    np.testing.assert_allclose(float(lr_), float(loss_r), atol=TOL)
    for x, y, name in zip(gr, g_r, ("dh", "dw", "dscale", "dbias")):
        if norm == "rms" and name == "dbias":
            continue  # rms has no bias; the fused arg gets zero cotangent
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5,
                                   err_msg=name)


@pytest.mark.parametrize("tied", [True, False])
def test_ragged_chunks_sample_as_dividing_chunks(tied):
    """The in-sweep Gumbel draw is keyed on global columns: a ragged last
    chunk draws bit-identical labels to chunks that divide Vp, never a
    column past the vocabulary, and the sampled loss and its gradients
    match the oracle."""
    vocab = 300
    hidden, w, _, mask = _setup(jnp.float32, tied, V=vocab, Vp=RAGGED_VP)
    tw = not tied
    rng = jax.random.PRNGKey(11)
    yh = {bv: fused_lm_sample(hidden, w, rng, vocab_size=vocab,
                              transpose_w=tw, block_n=16, block_v=bv)
          for bv in (128, 256, RAGGED_VP)}
    np.testing.assert_array_equal(np.asarray(yh[256]), np.asarray(yh[128]))
    np.testing.assert_array_equal(np.asarray(yh[256]),
                                  np.asarray(yh[RAGGED_VP]))
    assert int(jnp.max(yh[256])) < vocab

    def f(h, w_):
        return fused_lm_loss_sampled(h, w_, rng, mask, vocab_size=vocab,
                                     transpose_w=tw, block_n=16,
                                     block_v=256)[0]

    loss, (dh, dw) = jax.value_and_grad(f, argnums=(0, 1))(hidden, w)
    loss_r, yhat_r, dh_r, dw_r = lm_loss_sampled_ref(
        hidden, w, rng, mask, vocab_size=vocab, transpose_w=tw)
    np.testing.assert_array_equal(
        np.asarray(yh[256]).reshape(-1), np.asarray(yhat_r).reshape(-1))
    np.testing.assert_allclose(float(loss), float(loss_r), atol=TOL)
    np.testing.assert_allclose(np.asarray(dh), np.asarray(dh_r), atol=TOL)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_r), atol=TOL)


@pytest.mark.parametrize("norm", [None, "ln"])
def test_ragged_backward_keeps_normed_cotangent_fp32(norm):
    """With a fused norm the backward's d(normed hidden) stays fp32 at a
    ragged tiling (it is pulled back through the norm afterwards); without
    one it comes back in the hidden dtype."""
    from repro.kernels.fused_ce import _ce_backward, _ce_forward
    hidden, w, labels, _ = _setup(jnp.bfloat16, True, V=RAGGED_VP,
                                  Vp=RAGGED_VP)
    h2 = hidden.reshape(-1, hidden.shape[-1])
    lab = labels.reshape(-1)
    normp = jnp.zeros((2, h2.shape[1]), jnp.float32)
    kw = dict(vocab=RAGGED_VP, transpose_w=False, softcap=None, norm=norm,
              eps=1e-6, bn=16, bv=256, interpret=True)
    lse, _ = _ce_forward(h2, w, normp, lab, **kw)
    rs = jnp.full(lab.shape, 1.0 / lab.size, jnp.float32)
    dhn, dw = _ce_backward(h2, w, normp, lab, rs, lse, schedule="split",
                           **kw)
    assert dhn.dtype == (jnp.float32 if norm else jnp.bfloat16)
    assert dw.dtype == w.dtype and dw.shape == w.shape
    assert np.all(np.isfinite(np.asarray(dhn, np.float32)))
    assert np.all(np.isfinite(np.asarray(dw)))
