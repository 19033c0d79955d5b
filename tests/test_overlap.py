"""Bucketed-collective invariance + planning + telemetry (fast tier).

The bucketed overlapped reduction (distributed/overlap.py) is only
shippable because of one property: ANY bucketing of a flat shard
dequantizes bit-identically to the monolithic path, for the same seed —
scales and stochastic-rounding noise are keyed on the global element
index, and bucket boundaries stay 256-block-aligned.  The property tests
here sweep bucket sizes that straddle block boundaries (hypothesis when
installed); the 8-device mesh version of the same assertion lives in the
slow tier (tests/test_distributed_engine.py bucketed parity case and the
HLO audit).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core.engine import bucket_slices, build_layout
from repro.distributed.compression import FlatCompressionState, GradCompressor
from repro.distributed.overlap import (allreduce_shards_bucketed,
                                       delta_seconds, plan_buckets, stamp)
from repro.launch.roofline import choose_bucket_elems, ring_collective_seconds


# ---------------------------------------------------------------------------
# bucket planning


def test_bucket_slices_tile_exactly():
    for n, b in [(2048, 512), (2048, 500), (2048, 2048), (2048, 4096),
                 (2048, 0), (256, 256), (0, 128)]:
        sl = bucket_slices(n, b, align=256)
        if n == 0:
            assert sl == ()
            continue
        # disjoint, ordered, exact cover
        assert sl[0][0] == 0 and sl[-1][1] == n
        for (a0, a1), (b0, b1) in zip(sl, sl[1:]):
            assert a1 == b0
        # every boundary block-aligned
        assert all(s % 256 == 0 for s, _ in sl)


def test_bucket_slices_monolithic_cases():
    # 0 => monolithic; >= n => monolithic; unaligned n => monolithic
    assert bucket_slices(2048, 0) == ((0, 2048),)
    assert bucket_slices(2048, 2048) == ((0, 2048),)
    assert bucket_slices(1000, 256, align=256) == ((0, 1000),)


def test_plan_buckets_semantics():
    # explicit N rounds up to block*ndev alignment
    (plan,) = plan_buckets([256 * 24], 4, bucket_elems=1000)
    assert all((b - a) % (256 * 4) == 0 for a, b in plan[:-1])
    # auto on <= 1 device is monolithic (nothing to overlap)
    assert plan_buckets([256 * 24], 1) == (((0, 256 * 24),),)
    # 0 forces monolithic regardless of devices
    assert plan_buckets([256 * 24], 8, bucket_elems=0) == (((0, 256 * 24),),)


def test_choose_bucket_elems_alignment_and_bounds():
    for total in (128 * 1024, 16 * 1024 * 1024):
        for ndev in (2, 4, 8):
            b = choose_bucket_elems(total, ndev)
            assert 0 < b <= total
            assert b == total or b % (256 * ndev) == 0
    # tiny shard: one bucket
    assert choose_bucket_elems(256, 8) == 256
    # launch-dominated regime keeps buckets above the latency floor
    assert ring_collective_seconds(0, 4) > 0  # pure launch cost
    assert ring_collective_seconds(0, 1) == 0.0


def test_exposed_comm_model_bucketing_wins():
    from repro.launch.roofline import exposed_comm_seconds

    n, ndev, budget = 917504, 8, 0.2
    mono = exposed_comm_seconds([n], ndev, budget)
    plan = plan_buckets([n], ndev, bucket_elems=128 * 1024)[0]
    buck = exposed_comm_seconds([b - a for a, b in plan], ndev, budget)
    # monolithic exposes its ENTIRE wire time (1 bucket, ready only when
    # backward completes); the bucketed schedule hides all but the tail
    assert mono > 0
    assert buck < mono
    # with no compute to hide behind, bucketing cannot win (launch
    # overhead makes it strictly worse) — the model must not fantasize
    assert exposed_comm_seconds([b - a for a, b in plan], ndev, 0.0) \
        >= exposed_comm_seconds([n], ndev, 0.0)
    # single device: no interconnect, nothing exposed
    assert exposed_comm_seconds([n], 1, budget) == 0.0


# ---------------------------------------------------------------------------
# bit-parity: bucketed vs monolithic (mesh-less fast path; the 8-device
# mesh version is in the slow tier)


def _setup(n_shards=2, n=256 * 24):
    c = GradCompressor()
    g = tuple(jax.random.normal(jax.random.PRNGKey(i + 1),
                                (n // (i + 1) // 256 * 256,))
              for i in range(n_shards))
    st_ = FlatCompressionState(error=tuple(
        jax.random.normal(jax.random.PRNGKey(40 + i), e.shape) * 1e-3
        for i, e in enumerate(g)))
    return c, g, st_


def _assert_bit_equal(a, b, what):
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y)), what


@pytest.mark.parametrize("bucket_elems", [256, 512, 1000, 4096, 10**9])
def test_bucketed_matches_monolithic_bitwise(bucket_elems):
    c, g, st_ = _setup()
    rng = jax.random.PRNGKey(7)
    mono_g, mono_s = c.allreduce_shards(g, st_, rng, bucket_elems=0)
    bg, bs = c.allreduce_shards(g, st_, rng, bucket_elems=bucket_elems)
    _assert_bit_equal(mono_g, bg, f"deq mismatch at bucket={bucket_elems}")
    _assert_bit_equal(mono_s.error, bs.error,
                      f"error-feedback mismatch at bucket={bucket_elems}")


def test_bucketed_matches_monolithic_none_rng():
    """rng=None (deterministic round-to-nearest) survives bucketing too."""
    c, g, st_ = _setup()
    mono_g, _ = c.allreduce_shards(g, st_, None, bucket_elems=0)
    bg, _ = c.allreduce_shards(g, st_, None, bucket_elems=512)
    _assert_bit_equal(mono_g, bg, "rng=None bucketed mismatch")


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=3000))
def test_bucketed_bit_parity_hypothesis(bucket_elems):
    """Property: EVERY bucket size — aligned, unaligned, straddling
    256-block boundaries, larger than the shard — dequantizes bit-
    identically to monolithic (scales + noise keyed on global index)."""
    c = GradCompressor()
    g = (jax.random.normal(jax.random.PRNGKey(1), (256 * 9,)),)
    st_ = FlatCompressionState(error=(jnp.full((256 * 9,), 1e-3),))
    rng = jax.random.PRNGKey(3)
    mono_g, mono_s = c.allreduce_shards(g, st_, rng, bucket_elems=0)
    bg, bs = c.allreduce_shards(g, st_, rng, bucket_elems=bucket_elems)
    _assert_bit_equal(mono_g, bg, f"deq mismatch at bucket={bucket_elems}")
    _assert_bit_equal(mono_s.error, bs.error,
                      f"EF mismatch at bucket={bucket_elems}")


def test_bucketed_jit_parity_and_shapes():
    """Under one jit program, bucketed == monolithic bitwise (same
    compilation regime), and outputs keep the shard shapes."""
    c, g, st_ = _setup()
    rng = jax.random.PRNGKey(11)
    f = jax.jit(lambda be: c.allreduce_shards(g, st_, rng, bucket_elems=be),
                static_argnums=0)
    mg, ms = f(0)
    bg, bs = f(768)
    _assert_bit_equal(mg, bg, "jit deq mismatch")
    _assert_bit_equal(ms.error, bs.error, "jit EF mismatch")
    assert all(a.shape == b.shape for a, b in zip(g, bg))


def test_layout_bucket_slices_method():
    lay = build_layout({"w": jnp.zeros((300_000,))}, block=256)
    plans = lay.bucket_slices(1024)
    assert len(plans) == len(lay.shard_sizes)
    for n, plan in zip(lay.shard_sizes, plans):
        assert plan[0][0] == 0 and plan[-1][1] == int(n)


# ---------------------------------------------------------------------------
# telemetry


def test_stamp_orders_by_dataflow_and_measures():
    def fn(x):
        t0, x = stamp(x)
        y = x * 2.0
        t1, y = stamp(y)
        return y, t0, t1, delta_seconds(t0, t1)

    y, t0, t1, dt = jax.jit(fn)(jnp.arange(8.0))
    jax.block_until_ready(y)
    np.testing.assert_array_equal(np.asarray(y), np.arange(8.0) * 2)
    # the stamp on the product fires after the one on its input: its
    # [whole, fraction] pair is the later time, by the returned delta
    t0, t1 = np.asarray(t0, np.float64), np.asarray(t1, np.float64)
    assert t0.shape == t1.shape == (2,)
    assert 0.0 <= t0[1] < 1.0 and 0.0 <= t1[1] < 1.0
    assert t1.sum() >= t0.sum()
    assert float(dt) >= 0.0
    assert float(dt) == pytest.approx(t1.sum() - t0.sum(), abs=1e-4)


def test_allreduce_telemetry_returns_window_and_keeps_values():
    c, g, st_ = _setup(n_shards=1)
    rng = jax.random.PRNGKey(5)
    f = jax.jit(lambda tele: c.allreduce_shards(
        g, st_, rng, bucket_elems=512, telemetry=tele), static_argnums=0)
    plain_g, plain_s = f(False)
    tg, ts, tele = f(True)
    jax.block_until_ready(tg)
    _assert_bit_equal(plain_g, tg, "telemetry changed dequantized values")
    _assert_bit_equal(plain_s.error, ts.error, "telemetry changed EF")
    assert float(tele["comm_seconds"]) >= 0.0
    assert tele["comm_t0"].shape == (2,)


def test_trainer_telemetry_metrics_and_parity():
    """comm_telemetry + bucketing produce the new metrics WITHOUT changing
    the training trajectory."""
    import dataclasses as dc

    from repro.configs.gpt2 import GPT2_TINY
    from repro.data import DataConfig, make_source
    from repro.train.trainer import TrainerConfig, make_train_fns

    cfg = dc.replace(GPT2_TINY, dtype="float32")
    src = make_source(DataConfig(seq_len=32, global_batch=4,
                                 vocab_size=cfg.vocab_size, seed=0))

    def run(**kw):
        tc = TrainerConfig(optimizer="sophia_g", peak_lr=1e-3,
                           total_steps=50, warmup_steps=2, hess_interval=2,
                           hess_subbatch=2, compress_grads=True, seed=0,
                           **kw)
        init_fn, step = make_train_fns(cfg, tc)
        state = init_fn(jax.random.PRNGKey(0))
        sj = jax.jit(step)
        out = []
        for t in range(3):
            batch = {k: jnp.asarray(v) for k, v in src.batch_at(t).items()}
            state, m = sj(state, batch, jnp.asarray(t % 2 == 0))
            out.append(m)
        jax.block_until_ready(state)
        return out

    base = run()
    tele = run(comm_bucket_elems=256 * 17, comm_telemetry=True)
    assert [float(m["loss"]) for m in base] == \
        [float(m["loss"]) for m in tele]
    last = tele[-1]
    for key in ("comm_seconds", "step_seconds", "exposed_comm_fraction"):
        assert key in last and float(last[key]) >= 0.0
    assert float(last["exposed_comm_fraction"]) <= 1.5  # sane, not garbage
    assert "comm_seconds" not in base[-1]


# ---------------------------------------------------------------------------
# elastic: node-loss classification (unit; the subprocess walk is in
# tests/test_multiprocess.py)


def test_is_distributed_failure_classification():
    from repro.train.elastic import NodeLoss, is_distributed_failure

    class XlaRuntimeError(Exception):
        pass

    assert is_distributed_failure(
        XlaRuntimeError("DEADLINE_EXCEEDED: barrier timed out"))
    assert is_distributed_failure(
        RuntimeError("gloo: connection reset by peer"))
    assert not is_distributed_failure(ValueError("connection refused"))
    assert not is_distributed_failure(XlaRuntimeError("shape mismatch"))
    assert issubclass(NodeLoss, RuntimeError)


def test_run_resumable_reraises_node_loss():
    from repro.train.elastic import NodeLoss, run_resumable

    calls = {"n": 0}

    def run(state, start):
        calls["n"] += 1
        raise NodeLoss("peer died")

    with pytest.raises(NodeLoss):
        run_resumable(lambda: 0, run, lambda: None, max_restarts=3)
    assert calls["n"] == 1  # no in-process retry against a dead peer


def test_latency_hiding_flags_platform_keyed():
    """The overlap scheduler options are keyed on the mesh: only a TPU mesh
    of more than one device gets them (as per-compile options, which the
    TPU compiler validates; tests/test_tpu_compile.py compiles with them)."""
    import types

    import numpy as np

    from repro.launch.mesh import TPU_OVERLAP_OPTIONS, overlap_compiler_options

    def fake_mesh(platform, n):
        dev = types.SimpleNamespace(platform=platform)
        return types.SimpleNamespace(devices=np.asarray([dev] * n))

    assert overlap_compiler_options(None) == {}
    assert overlap_compiler_options(fake_mesh("cpu", 8)) == {}
    assert overlap_compiler_options(fake_mesh("tpu", 1)) == {}
    opts = overlap_compiler_options(fake_mesh("tpu", 4))
    assert opts == TPU_OVERLAP_OPTIONS
    assert opts and all(k.startswith("xla_tpu_") for k in opts)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """launch/mesh.enable_compile_cache: $JAX_COMPILATION_CACHE_DIR, when
    set, holds the cache and the in-checkout default stays empty; unset,
    the cache lands in the fixed default directory.  Runs in a subprocess
    (the cache is process-global once a compile has used it)."""
    import os
    import subprocess
    import sys
    import textwrap

    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    env_dir.mkdir()
    default_dir.mkdir()
    prog = textwrap.dedent(f"""
        from pathlib import Path
        import jax, jax.numpy as jnp
        from repro.launch import mesh
        mesh.DEFAULT_COMPILE_CACHE = Path({str(default_dir)!r})
        print(mesh.enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    used, unused = (env_dir, default_dir) if from_env else (default_dir,
                                                            env_dir)
    assert r.stdout.splitlines()[0] == str(used)
    assert any(used.iterdir())
    assert not any(unused.iterdir())
