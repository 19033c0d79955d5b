"""Flat-buffer optimizer engine: layout round-trip, fused-vs-reference
parity across Hessian refreshes, bf16 state, telemetry agreement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (OptimizerEngine, build_layout, ravel_shards,
                               unravel_shards)

SOPHIA_HYPERS = dict(beta1=0.96, beta2=0.99, gamma=0.05, eps=1e-12,
                     weight_decay=0.2, clip_threshold=1.0)


def _params(key, *, dtype=jnp.float32):
    """Deliberately awkward leaf sizes: nothing is a block multiple."""
    ks = jax.random.split(key, 4)
    return {
        "emb": jax.random.normal(ks[0], (13, 7), dtype),
        "blocks": [
            {"w": jax.random.normal(ks[1], (5, 11), dtype),
             "b": jnp.zeros((11,), dtype)},
            {"w": jax.random.normal(ks[2], (11, 3), dtype),
             "b": jnp.zeros((3,), dtype)},
        ],
        "scale": jax.random.normal(ks[3], (), dtype),  # scalar leaf
    }


def _grads_like(params, key, scale=0.1):
    leaves, treedef = jax.tree.flatten(params)
    ks = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, l.shape, jnp.float32) * scale
        for k, l in zip(ks, leaves)])


def _engines(optimizer, hypers, **kw):
    ref = OptimizerEngine(optimizer, hypers=hypers, backend="reference", **kw)
    fused = OptimizerEngine(optimizer, hypers=hypers, backend="pallas", **kw)
    return ref, fused


# ---------------------------------------------------------------------------
# layout


def test_layout_roundtrip_mixed_dtypes():
    p = _params(jax.random.PRNGKey(0))
    p["half"] = jnp.arange(37, dtype=jnp.bfloat16)  # second dtype shard
    lay = build_layout(p, block=64)
    assert lay.n_shards == 2
    assert all(s % 64 == 0 for s in lay.shard_sizes)
    assert lay.n_params == sum(x.size for x in jax.tree.leaves(p))
    shards = ravel_shards(lay, p)
    assert [s.dtype for s in shards] == list(lay.shard_dtypes)
    back = unravel_shards(lay, shards)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_layout_pad_is_tail_only():
    p = _params(jax.random.PRNGKey(1))
    lay = build_layout(p, block=128)
    (shard,) = ravel_shards(lay, p)
    used = lay.shard_used[0]
    assert shard.shape[0] == lay.shard_sizes[0]
    np.testing.assert_array_equal(np.asarray(shard[used:]), 0.0)


# ---------------------------------------------------------------------------
# multi-step parity: grad steps + Hessian-EMA refreshes interleaved


def _run_sophia(engine, *, steps=16, k=5, state_dtype=None, seed=0):
    """Sophia schedule over >= 3 Hessian intervals (refresh at 0, 5, 10, 15).

    Estimates come from a synthetic ghat^2-style positive tree with a folded
    batch scale, exactly like the trainer's GNB path."""
    key = jax.random.PRNGKey(seed)
    params = _params(key)
    state = engine.init(params)
    clip_fracs = []
    for t in range(steps):
        kt = jax.random.fold_in(key, t)
        if t % k == 0:
            est = jax.tree.map(jnp.square,
                               _grads_like(params, jax.random.fold_in(kt, 1)))
            state = engine.update_hessian(state, est, scale=240.0,
                                          params=params)
        grads = _grads_like(params, kt)
        lr = 3e-4 * (1.0 + 0.1 * t)
        params, state = engine.step(state, params, grads, lr)
        clip_fracs.append(float(state.clip_fraction))
    return params, state, clip_fracs


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_sophia_fused_matches_reference_across_refreshes(state_dtype):
    sdt = jnp.bfloat16 if state_dtype == "bfloat16" else jnp.float32
    ref, fused = _engines("sophia_g", SOPHIA_HYPERS, block=128,
                          state_dtype=sdt)
    p1, s1, cf1 = _run_sophia(ref)
    p2, s2, cf2 = _run_sophia(fused)
    assert int(s1.hess_count) == int(s2.hess_count) == 4
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(s1.m, s2.m):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(s1.h, s2.h):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)
    # in-kernel clip telemetry must agree step by step
    np.testing.assert_allclose(cf1, cf2, rtol=1e-6, atol=1e-7)


def test_clip_fraction_counts_only_real_params():
    """Telemetry denominator is true param count; padding never clips."""
    ref, fused = _engines("sophia_g", dict(SOPHIA_HYPERS, gamma=1e3),
                          block=128)
    params = _params(jax.random.PRNGKey(3))
    n = sum(x.size for x in jax.tree.leaves(params))
    for eng in (ref, fused):
        state = eng.init(params)
        # tiny h, huge m -> every real coordinate hits the clip
        est = jax.tree.map(lambda x: jnp.full(x.shape, 1e-8), params)
        state = eng.update_hessian(state, est, scale=1.0, params=params)
        grads = jax.tree.map(lambda x: jnp.full(x.shape, 100.0), params)
        _, state = eng.step(state, params, grads, 1e-3)
        assert abs(float(state.clip_fraction) - 1.0) < 1e-6, eng.backend
        # padded shard is larger than n: fraction uses n, not padded size
        assert state.m[0].shape[0] > n


@pytest.mark.parametrize("optimizer,hypers", [
    ("adamw", dict(beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1)),
    ("lion", dict(beta1=0.95, beta2=0.98, weight_decay=0.2)),
    ("signgd", dict(beta1=0.96, weight_decay=0.0)),
    ("sgd", dict(momentum=0.9)),
])
def test_baseline_families_fused_matches_reference(optimizer, hypers):
    ref, fused = _engines(optimizer, hypers, block=128)
    key = jax.random.PRNGKey(7)
    p1 = p2 = _params(key)
    s1, s2 = ref.init(p1), fused.init(p2)
    for t in range(5):
        g = _grads_like(p1, jax.random.fold_in(key, t))
        p1, s1 = ref.step(s1, p1, g, 1e-3)
        p2, s2 = fused.step(s2, p2, g, 1e-3)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_adahessian_squared_refresh_parity():
    hypers = dict(beta1=0.92, beta2=0.99, eps=1e-8, weight_decay=0.0)
    ref, fused = _engines("adahessian", hypers, block=128)
    key = jax.random.PRNGKey(11)
    p1 = p2 = _params(key)
    s1, s2 = ref.init(p1), fused.init(p2)
    for t in range(6):
        est = _grads_like(p1, jax.random.fold_in(key, 100 + t), scale=1.0)
        s1 = ref.update_hessian(s1, est, scale=1.0, params=p1)
        s2 = fused.update_hessian(s2, est, scale=1.0, params=p2)
        g = _grads_like(p1, jax.random.fold_in(key, t))
        p1, s1 = ref.step(s1, p1, g, 1e-3)
        p2, s2 = fused.step(s2, p2, g, 1e-3)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(s1.h, s2.h):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# engine invariants


def test_state_stays_flat_and_padded_region_is_fixed_point():
    ref = OptimizerEngine("sophia_g", hypers=SOPHIA_HYPERS, block=128)
    params = _params(jax.random.PRNGKey(5))
    state = ref.init(params)
    used = ref.layout(params).shard_used[0]
    for t in range(4):
        est = jax.tree.map(jnp.square,
                           _grads_like(params, jax.random.PRNGKey(50 + t)))
        state = ref.update_hessian(state, est, scale=32.0, params=params)
        grads = _grads_like(params, jax.random.PRNGKey(t))
        params, state = ref.step(state, params, grads, 1e-3)
        assert state.m[0].ndim == 1  # never unraveled
        np.testing.assert_array_equal(np.asarray(state.m[0][used:]), 0.0)
        np.testing.assert_array_equal(np.asarray(state.h[0][used:]), 0.0)


def test_engine_under_jit_with_traced_lr_and_scale():
    fused = OptimizerEngine("sophia_g", hypers=SOPHIA_HYPERS, block=128)
    params = _params(jax.random.PRNGKey(9))
    state = fused.init(params)

    @jax.jit
    def one(params, state, grads, est, lr, scale):
        state = fused.update_hessian(state, est, scale=scale, params=params)
        return fused.step(state, params, grads, lr)

    grads = _grads_like(params, jax.random.PRNGKey(10))
    est = jax.tree.map(jnp.square, grads)
    p2, s2 = one(params, state, grads, est, jnp.float32(1e-3),
                 jnp.float32(240.0))
    assert int(s2.count) == 1 and int(s2.hess_count) == 1
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree.leaves(p2))


def test_lion_has_no_curvature_slot():
    eng = OptimizerEngine("lion", hypers=dict(beta1=0.95, beta2=0.98,
                                              weight_decay=0.2))
    state = eng.init(_params(jax.random.PRNGKey(0)))
    assert state.h == ()
    assert not eng.hessian_aware


def test_layout_manifest_is_json_ready():
    import json
    eng = OptimizerEngine("sophia_g", hypers=SOPHIA_HYPERS, block=256)
    man = eng.describe(_params(jax.random.PRNGKey(0)))
    txt = json.dumps(man)
    assert "shards" in man and man["block"] == 256
    assert man["n_params"] == sum(s["used"] for s in man["shards"])
    assert json.loads(txt) == man


@pytest.mark.parametrize("optimizer,hypers", [
    ("sophia_g", SOPHIA_HYPERS),
    ("adamw", dict(beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1)),
])
def test_init_state_buffers_are_distinct(optimizer, hypers):
    """The train step donates its state; on a TPU a buffer that backs two
    leaves (m and h once shared one zeros tuple) is donated twice and the
    step refuses to run.  Every state leaf owns its buffer."""
    p = _params(jax.random.PRNGKey(0))
    p["half"] = jnp.arange(37, dtype=jnp.bfloat16)
    state = OptimizerEngine(optimizer, hypers=hypers).init(p)
    ptrs = [x.unsafe_buffer_pointer() for x in jax.tree.leaves(state)]
    assert len(ptrs) == len(set(ptrs))
