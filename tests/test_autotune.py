"""The fused-CE block autotuner (kernels/autotune.py) + the fused JVP rule.

Fast-tier policy: every test here runs the tuner in roofline-only mode
(``measure=False`` — deterministic, no wall-clock timing, no disk writes);
the single measured-persistence test is marked ``slow``.  Covers the
ISSUE-6 satellite contracts:

  * cache determinism — same key -> same config, and the tuned fused loss
    is bit-identical across independent tuner runs;
  * parity vs the kernels/ref.py closed-form oracles at tuned (bn, bv)
    configs that cross chunk boundaries, both backward schedules;
  * fused-JVP vs chunked-HVP equivalence <= 3e-6, and the trainer's
    Hutchinson refresh actually traces through the fused JVP rule (no
    silent chunked fallback — KERNEL_CALLS counter);
  * interpret-mode clamps (``_pick_bv`` / candidate caps) so CPU CI never
    unrolls a pathological interpreter grid;
  * residency cap — no candidate can reconstruct the [N, Vp] logits
    buffer the memory audit forbids.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (TunedCE, cache_key, candidate_blocks,
                                    clear_memory_cache, get_tuned,
                                    predict_seconds, residency_cap)
from repro.kernels.fused_ce import (VMEM_LIMIT_BYTES, _pick_bv,
                                    fused_lm_loss, fused_lm_loss_jvp,
                                    kernel_calls, reset_kernel_calls,
                                    vmem_bytes)
from repro.kernels.ref import lm_loss_grads_ref, lm_loss_ref

N, D, VOCAB, VP = 64, 32, 200, 256
TOL = 3e-6


@pytest.fixture(autouse=True)
def _fresh_tuner(monkeypatch, tmp_path):
    """Every test gets an empty in-memory cache and a throwaway disk path
    (never the user's ~/.cache)."""
    monkeypatch.setenv("REPRO_FUSED_CE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.setenv("REPRO_FLASH_ATTN_CACHE",
                       str(tmp_path / "attn_autotune.json"))
    clear_memory_cache()
    yield
    clear_memory_cache()


def _data(n=N, d=D, vp=VP, transpose_w=False, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(ks[0], (n, d), jnp.float32) * 0.5
    wshape = (d, vp) if transpose_w else (vp, d)
    w = jax.random.normal(ks[1], wshape, jnp.float32) * 0.5
    labels = jax.random.randint(ks[2], (n,), 0, VOCAB)
    return h, w, labels


# ---------------------------------------------------------------------------
# cache determinism + hermeticity


def test_same_key_same_config():
    kw = dict(dtype="float32", transpose_w=False, softcap=None, norm=None,
              interpret=True)
    a = get_tuned(N, D, VP, **kw)
    clear_memory_cache()
    b = get_tuned(N, D, VP, **kw)
    assert dataclasses.asdict(a) == dataclasses.asdict(b)
    assert a.source == "roofline"
    # and the in-memory hit is the exact same decision
    assert get_tuned(N, D, VP, **kw) == b


def test_tuned_loss_bit_identical_across_tuner_runs():
    """Two independent tuner resolutions (cache cleared between) must
    produce bit-identical losses — the tuner is part of the numerics
    contract, not just a performance hint."""
    h, w, labels = _data()

    def run():
        f = jax.jit(lambda h, w: fused_lm_loss(
            h, w, labels, vocab_size=VOCAB)[0])
        return np.asarray(f(h, w))

    a = run()
    clear_memory_cache()
    jax.clear_caches()
    b = run()
    assert a.tobytes() == b.tobytes()


def test_roofline_only_mode_touches_no_disk(tmp_path):
    path = os.environ["REPRO_FUSED_CE_CACHE"]
    for vp in (VP, 2 * VP):
        get_tuned(N, D, vp, dtype="float32", transpose_w=False,
                  softcap=None, norm=None, interpret=True)
    assert not os.path.exists(path)


@pytest.mark.slow
def test_measured_entry_persists_and_reloads():
    t = autotune.tune_shape(N, D, VP, interpret=True)
    assert t.source == "measured" and t.measured_ms is not None
    assert os.path.exists(os.environ["REPRO_FUSED_CE_CACHE"])
    clear_memory_cache()       # force the disk round-trip
    t2 = get_tuned(N, D, VP, dtype="float32", transpose_w=False,
                   softcap=None, norm=None, interpret=True)
    assert t2 == t


def test_cache_key_separates_backends_and_layouts():
    keys = {cache_key(N, D, VP, dtype="float32", transpose_w=tw,
                      softcap=sc, norm=nm, backend=be)
            for tw in (False, True) for sc in (None, 30.0)
            for nm in (None, "rms", "ln") for be in ("tpu", "interpret")}
    assert len(keys) == 2 * 2 * 3 * 2


# ---------------------------------------------------------------------------
# candidate legality


def test_candidates_respect_residency_cap():
    for interpret in (False, True):
        cands = candidate_blocks(1024, 256, 32768, bytes_h=2,
                                 interpret=interpret)
        assert cands
        cap = residency_cap(1024, 32768)
        for bn, bv, schedule in cands:
            # a chunk is whole 128-lane groups; the last may be ragged
            assert 1024 % bn == 0 and bv % 128 == 0 and bv <= 32768
            assert bn * bv <= cap
            if schedule == "fused":
                assert 1024 // bn == 1 or -(-32768 // bv) == 1


def test_predict_prefers_fewer_cells_in_interpret():
    """The interpret cost model must rank a single-row-block tiling above
    a many-cell one of equal arithmetic — per-cell dispatch dominates."""
    few = predict_seconds(256, 64, 4096, 256, 4096, "fused", bytes_h=4,
                          bytes_w=4, interpret=True)
    many = predict_seconds(256, 64, 4096, 8, 128, "split", bytes_h=4,
                           bytes_w=4, interpret=True)
    assert few < many


def test_pick_bv_interpret_clamp():
    # an explicit tiny chunk at a big vocab would unroll 256 interpreter
    # cells per row block; the clamp caps the vocab grid at 64
    assert _pick_bv(32768, 128, interpret=True) >= 32768 // 64
    # ... but passes through where the grid is already small
    assert _pick_bv(1024, 128, interpret=True) == 128
    # and never clamps for a real backend
    assert _pick_bv(32768, 128, interpret=False) == 128


def test_counters_report_grid_steps_of_tiling_used():
    """The trace-time counters give each CE kernel call's grid steps
    (``ce_grid_steps``, summed), the calls whose last chunk is ragged, and
    the call shape's tiling with its steps per kernel call."""
    h, w, labels = _data(vp=384)
    for bv, n_v, ragged in ((256, 2, 3), (128, 3, 0)):
        reset_kernel_calls()
        jax.grad(lambda h_: fused_lm_loss(
            h_, w, labels, vocab_size=VOCAB, block_n=16, block_v=bv,
            interpret=True)[0])(h)
        calls = kernel_calls()
        n_r = N // 16
        # forward, d_hidden and d_W sweeps, each n_r x n_v steps
        assert calls["ce_grid_steps"] == 3 * n_r * n_v, calls
        assert calls.get("ce_ragged_calls", 0) == ragged, calls
        key = f"ce_tiling {N}x{D}x384 bn16 bv{bv} split steps{n_r * n_v}"
        assert calls[key] == 1, calls


def test_autotuned_defaults_keep_interpret_grid_small():
    t = get_tuned(256, 64, 32768, dtype="float32", transpose_w=False,
                  softcap=None, norm=None, interpret=True)
    assert (256 // t.bn) * (32768 // t.bv) <= 64


# ---------------------------------------------------------------------------
# parity at tuned configs (vs the closed-form oracles in kernels/ref.py)


def _three_tuned_configs():
    """Three tuner-legal (bn, bv, schedule) configs crossing chunk
    boundaries: a multi-cell split grid, a fused row-grid (n_v == 1), and
    a fused vocab-grid (n_r == 1)."""
    cands = candidate_blocks(N, D, VP, bytes_h=4, interpret=True)
    split = next(c for c in cands
                 if c[2] == "split" and N // c[0] > 1 and VP // c[1] > 1)
    fused_rows = next(c for c in cands if c[2] == "fused" and VP // c[1] == 1
                      and N // c[0] > 1)
    fused_cols = next(c for c in cands if c[2] == "fused" and N // c[0] == 1
                      and VP // c[1] > 1)
    return [split, fused_rows, fused_cols]


@pytest.mark.parametrize("transpose_w", [False, True])
def test_parity_at_tuned_configs(transpose_w):
    h, w, labels = _data(transpose_w=transpose_w)
    ref_l, ref_dh, ref_dw = lm_loss_grads_ref(
        h, w, labels, vocab_size=VOCAB, transpose_w=transpose_w)
    for bn, bv, schedule in _three_tuned_configs():
        f = jax.jit(lambda h, w: fused_lm_loss(
            h, w, labels, vocab_size=VOCAB, transpose_w=transpose_w,
            block_n=bn, block_v=bv, schedule=schedule)[0])
        loss, (dh, dw) = jax.value_and_grad(f, argnums=(0, 1))(h, w)
        tag = f"bn={bn} bv={bv} {schedule}"
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_l),
                                   rtol=TOL, atol=TOL, err_msg=tag)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(ref_dh),
                                   rtol=TOL, atol=TOL, err_msg=tag)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(ref_dw),
                                   rtol=TOL, atol=TOL, err_msg=tag)


# ---------------------------------------------------------------------------
# the fused JVP rule (Hutchinson's HVP path)


@pytest.mark.parametrize("transpose_w", [False, True])
def test_fused_jvp_matches_chunked_hvp(transpose_w):
    """H·u through the fused custom_jvp twin == H·u through the
    materialized-logits oracle, <= 3e-6 (forward-over-reverse both)."""
    h, w, labels = _data(transpose_w=transpose_w)
    u = jax.random.normal(jax.random.PRNGKey(9), h.shape, jnp.float32)

    def loss_fused(h):
        return fused_lm_loss_jvp(h, w, labels, vocab_size=VOCAB,
                                 transpose_w=transpose_w)[0]

    def loss_ref(h):
        return lm_loss_ref(h, w, labels, vocab_size=VOCAB,
                           transpose_w=transpose_w)

    g_f, hvp_f = jax.jvp(jax.grad(loss_fused), (h,), (u,))
    g_r, hvp_r = jax.jvp(jax.grad(loss_ref), (h,), (u,))
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_r),
                               rtol=TOL, atol=TOL)
    np.testing.assert_allclose(np.asarray(hvp_f), np.asarray(hvp_r),
                               rtol=1e-5, atol=TOL)


def test_hutchinson_traces_through_fused_jvp_rule():
    """The trainer's Hutchinson refresh with ``fused_loss=True`` (the
    default) must enter the fused JVP rule — and never the plain fused
    forward, which would mean the custom_vjp path (no HVP) or a silent
    chunked fallback."""
    from repro.configs.gpt2 import GPT2_TINY
    from repro.data import DataConfig, make_source
    from repro.train import TrainerConfig, make_train_fns

    tc = TrainerConfig(optimizer="sophia_h", estimator="hutchinson",
                       total_steps=4, warmup_steps=1, hess_interval=1,
                       hess_subbatch=2, seed=0)
    assert tc.fused_loss, "fused_loss must default to True (ISSUE 6)"
    init_fn, train_step = make_train_fns(GPT2_TINY, tc)
    state = init_fn(jax.random.PRNGKey(0))
    src = make_source(DataConfig(seq_len=16, global_batch=2,
                                 vocab_size=GPT2_TINY.vocab_size, seed=0))
    batch = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}
    reset_kernel_calls()
    state, _ = jax.jit(train_step)(state, batch, jnp.asarray(True))
    calls = kernel_calls()
    assert calls.get("jvp_rule", 0) >= 1, calls
    jax.block_until_ready(state)


# ---------------------------------------------------------------------------
# flash-attention tuner (same contracts, separate cache)

ATTN = dict(B=2, H=4, Hkv=2, Sq=256, Sk=256, hd=32)
ATTN_KW = dict(dtype="float32", causal=True, softcap=None, interpret=True)


def test_attn_same_key_same_config():
    a = autotune.get_tuned_attn(**ATTN, **ATTN_KW)
    clear_memory_cache()
    b = autotune.get_tuned_attn(**ATTN, **ATTN_KW)
    assert dataclasses.asdict(a) == dataclasses.asdict(b)
    assert a.source == "roofline"
    assert ATTN["Sq"] % a.bq == 0 and ATTN["Sk"] % a.bk == 0
    assert a.schedule in ("skip", "dense")
    # in-memory hit is the exact same decision object
    assert autotune.get_tuned_attn(**ATTN, **ATTN_KW) == b


def test_attn_roofline_only_touches_no_disk():
    path = os.environ["REPRO_FLASH_ATTN_CACHE"]
    autotune.get_tuned_attn(**ATTN, **ATTN_KW)
    assert not os.path.exists(path)


def test_attn_cache_is_separate_from_ce_cache():
    """TunedAttn and TunedCE have disjoint fields — a shared JSON would
    crash either loader, so the caches must be separate files."""
    assert autotune.attn_cache_path() != autotune.cache_path()


def test_attn_key_separates_configs():
    keys = {autotune.attn_cache_key(2, 4, hkv, 256, 256, 32, dtype=dt,
                                    causal=ca, softcap=sc, backend=be)
            for hkv in (2, 4) for dt in ("float32", "bfloat16")
            for ca in (True, False) for sc in (None, 20.0)
            for be in ("interpret", "tpu")}
    assert len(keys) == 32


def test_attn_interpret_candidates_fit_cell_cap():
    from repro.kernels.flash_attention import INTERPRET_CELL_CAP
    t = autotune.get_tuned_attn(**ATTN, **ATTN_KW)
    cells = (ATTN["Sq"] // t.bq) * (ATTN["Sk"] // t.bk)
    assert ATTN["B"] * ATTN["H"] * cells <= INTERPRET_CELL_CAP


def _rank_as_v5e(monkeypatch):
    """The real-backend ranking reads the peaks of the chip it runs on;
    on this CPU host, steer it to the v5e entry."""
    from repro.launch import roofline
    monkeypatch.setattr(roofline, "device_peaks",
                        lambda kind=None: roofline.CHIP_PEAKS["TPU v5 lite"])


def test_attn_predict_skip_beats_dense_when_causal(monkeypatch):
    """The roofline cost charges only in-band tiles under "skip": on a
    multi-block causal grid it must price below "dense" (which streams the
    full rectangle), on the real backend where cells aren't emulated."""
    _rank_as_v5e(monkeypatch)
    kw = dict(bytes_el=2, causal=True, interpret=False)
    skip = autotune.attn_predict_seconds(8, 12, 4, 2048, 2048, 128,
                                         256, 256, "skip", **kw)
    dense = autotune.attn_predict_seconds(8, 12, 4, 2048, 2048, 128,
                                          256, 256, "dense", **kw)
    assert skip < dense


#: the benchmark cells' attention calls: gpt2-medium's batch, its refresh
#: sub-batch, gpt2-small's batch
CELL_ATTN = {"gpt2m": (8, 16, 16, 1024, 1024, 64),
             "gpt2m_refresh": (4, 16, 16, 1024, 1024, 64),
             "gpt2s": (16, 12, 12, 1024, 1024, 64)}


@pytest.mark.parametrize("shape", CELL_ATTN.values(), ids=CELL_ATTN.keys())
def test_attn_ranking_counts_grid_steps(monkeypatch, shape):
    """With v5e peaks the cost model charges each grid step
    (``ATTN_STEP_S``), so the ranking puts fewer grid steps first: the
    pick has fewer than 256 x 256 (the pick before the step term), and
    among candidates with the same in-band tile area the one with fewer
    steps ranks first.  512 x 512 was the fastest of ten tilings timed on
    a v5e at these shapes."""
    _rank_as_v5e(monkeypatch)
    kw = dict(bytes_el=2, causal=True)

    def steps(bq, bk):
        return sum(p.steps for p in autotune._attn_plans(
            *shape, bq, bk, "skip", **kw))

    def pred(bq, bk):
        return autotune.attn_predict_seconds(*shape, bq, bk, "skip",
                                             interpret=False, **kw)

    t = autotune.get_tuned_attn(*shape, dtype="bfloat16", causal=True,
                                softcap=None, interpret=False)
    assert (t.bq, t.bk, t.schedule) == (512, 512, "skip")
    assert steps(t.bq, t.bk) < steps(256, 256)

    def area(bq, bk):
        plan = autotune._attn_plans(*shape, bq, bk, "skip", **kw)[0]
        return bq * bk * (plan.tiles_full + plan.tiles_masked)

    # same q-blocks and the same walked area, dkv's grid twice as long:
    # the candidate with fewer steps ranks first, by exactly their cost
    for few, many in (((256, 256), (256, 128)), ((512, 512), (512, 256))):
        assert area(*few) == area(*many)
        extra = steps(*many) - steps(*few)
        assert extra > 0
        np.testing.assert_allclose(pred(*many) - pred(*few),
                                   extra * autotune.ATTN_STEP_S, rtol=1e-9)
    cands = autotune.attn_candidate_blocks(1024, 1024, 64, bytes_el=2,
                                           interpret=False)
    assert all(bq % 128 == 0 and bk % 128 == 0 for bq, bk, _ in cands)


@pytest.mark.parametrize("S", [1024, 2000])
def test_attn_q_blocks_slice_lse_lanes(S):
    """On a TPU a q-block offsets the lse/delta rows along lanes: every
    candidate's is a multiple of 128, or the whole sequence where 128 does
    not divide it."""
    cands = autotune.attn_candidate_blocks(S, S, 64, bytes_el=2,
                                           interpret=False)
    assert cands
    assert all(bq % 128 == 0 if S % 128 == 0 else bq == S
               for bq, _, _ in cands)


#: the benchmark cells' fused-CE calls (N, D, Vp): gpt2-small's batch and
#: its refresh sub-batch, gpt2-medium's batch and its refresh sub-batch
CELL_CE = {"gpt2s": (16384, 768, 50304), "gpt2s_refresh": (8192, 768, 50304),
           "gpt2m": (8192, 1024, 50304),
           "gpt2m_refresh": (4096, 1024, 50304)}


def _ce_tuned(N, D, Vp):
    return get_tuned(N, D, Vp, dtype="bfloat16", transpose_w=False,
                     softcap=None, norm="ln", interpret=False)


@pytest.mark.parametrize("shape", CELL_CE.values(), ids=CELL_CE.keys())
def test_ce_picks_wide_chunks_at_cell_shapes(monkeypatch, shape):
    """With v5e peaks the CE tuner charges each grid step
    (``ce_step_seconds``), so chunk widths no longer tie: it picks chunks
    of 1024 columns or more (ragged, since no such width divides 50304 =
    3 x 131 x 128) where the roofline alone made 128 the pick, within the
    VMEM the kernels request and the logits-tile cap.  The fwd/dh/dw calls
    take at most a tenth of the 128-wide grid's steps."""
    _rank_as_v5e(monkeypatch)
    N, D, Vp = shape
    t = _ce_tuned(N, D, Vp)
    assert t.bv >= 1024 and t.bv % 128 == 0, t
    assert vmem_bytes(t.bn, t.bv, D, bytes_h=2) <= VMEM_LIMIT_BYTES
    assert t.bn * t.bv <= autotune.CE_TILE_ELEMS, t
    steps = (N // t.bn) * -(-Vp // t.bv)
    assert steps * 10 <= (N // 512) * (Vp // 128), (t, steps)


def test_ce_ranking_charges_grid_steps(monkeypatch):
    """Two tilings that sweep the same columns with the same row tiles,
    compute-bound on every pass: the one with twice the chunks is priced
    higher by exactly its extra grid steps times the cost of a step
    (``ce_step_seconds``: ``CE_STEP_S`` and the hidden tile's norm)."""
    _rank_as_v5e(monkeypatch)
    N, D, Vp = 16384, 768, 4096
    kw = dict(bytes_h=2, bytes_w=4, interpret=False)
    few = predict_seconds(N, D, Vp, 1024, 2048, "split", **kw)
    many = predict_seconds(N, D, Vp, 1024, 1024, "split", **kw)
    extra = 3 * (N // 1024) * (4 - 2)          # fwd, dh, dw
    np.testing.assert_allclose(many - few,
                               extra * autotune.ce_step_seconds(1024, D),
                               rtol=1e-9)


@pytest.mark.parametrize("shape", [(16384, 768), (8192, 768)],
                         ids=["gpt2s", "gpt2s_refresh"])
def test_ce_pick_divides_vocab_with_good_divisors(monkeypatch, shape):
    """A ragged last chunk is charged its full width: at Vp 32768 (= 2^15)
    the pick divides Vp (2048 columns, the widest chunk the VMEM and the
    logits-tile cap allow at d 768); at Vp 32000 a ragged chunk costs more than a
    dividing one of the same chunk count (3328 against 3200 columns, ten
    chunks each); at 50304 the pick runs past Vp."""
    _rank_as_v5e(monkeypatch)
    t = _ce_tuned(*shape, 32768)
    assert 32768 % t.bv == 0 and t.bv >= 1024, t
    kw = dict(bytes_h=2, bytes_w=4, interpret=False)
    assert (predict_seconds(*shape, 32000, 256, 3328, "split", **kw)
            > predict_seconds(*shape, 32000, 256, 3200, "split", **kw))
    assert 50304 % _ce_tuned(*shape, 50304).bv


def test_real_backend_ranking_needs_known_chip():
    """Peaks come from a table keyed by device_kind: an unknown chip is an
    error, never silently ranked with another chip's numbers."""
    from repro.launch.roofline import CHIP_PEAKS, device_peaks
    assert device_peaks("TPU v5 lite") is CHIP_PEAKS["TPU v5 lite"]
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("TPU v99")
    with pytest.raises(ValueError, match="no published peaks"):
        autotune.predict_seconds(512, 64, 1024, 128, 128, "split",
                                 bytes_h=2, bytes_w=4, interpret=False)


@pytest.mark.slow
def test_attn_measured_entry_persists_and_reloads():
    t = autotune.tune_attn_shape(1, 2, 1, 128, 128, 32, interpret=True,
                                 refresh=True)
    assert t.source == "measured" and t.measured_ms is not None
    assert os.path.exists(os.environ["REPRO_FLASH_ATTN_CACHE"])
    clear_memory_cache()       # force the disk round-trip
    t2 = autotune.get_tuned_attn(1, 2, 1, 128, 128, 32, **ATTN_KW)
    assert t2 == t


def test_attn_tuned_flash_bit_identical_across_tuner_runs():
    """Tuner resolution is part of the numerics contract for attention
    too: two independent resolutions give bit-identical outputs."""
    from repro.kernels.flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32)) * 0.5
    k = jax.random.normal(ks[1], (1, 1, 128, 32)) * 0.5
    v = jax.random.normal(ks[2], (1, 1, 128, 32)) * 0.5

    def run():
        return np.asarray(jax.jit(flash_attention)(q, k, v))

    a = run()
    clear_memory_cache()
    jax.clear_caches()
    b = run()
    assert a.tobytes() == b.tobytes()
