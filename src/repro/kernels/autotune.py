"""Shape-keyed block autotuner for the fused CE kernels.

Picking (block_rows ``bn``, block_v ``bv``, backward schedule) for
``kernels/fused_ce.py`` is a classic tiling problem: the kernels are
correct for ANY divisor pair, but wall-clock swings several-fold with the
tile shape (weight-tile re-reads scale with the row-grid size, the
combined backward schedule is only legal on single-axis grids, and in
interpret mode per-grid-cell dispatch overhead dwarfs the arithmetic).
This module resolves it in three stages:

1. **candidates** — every (bn, bv) with bn | N (multiple of 8) and bv a
   multiple of 128 up to Vp — dividing Vp or not: the kernels sweep
   ``cdiv(Vp, bv)`` chunks and mask a ragged last one — filtered, on a
   real TPU, by the kernels' VMEM working set (``fused_ce.vmem_bytes``
   against the scoped VMEM each CE kernel requests,
   ``fused_ce.VMEM_LIMIT_BYTES``) and the measured logits-tile cap
   ``CE_TILE_ELEMS``, in interpret mode by a tile-size sanity cap, and
   always by the logits-residency cap
   ``bn * bv <= max(N * Vp / 2, 8 * 128)`` so the tuner can never pick the
   degenerate whole-[N, V]-tile config that the memory audit exists to
   forbid.  Each tiling carries its legal schedules ("fused" iff one grid
   axis is 1).
2. **predict** — the analytic cost model (``predict_seconds``): per-pass
   ``max(flops/peak_flops, bytes/hbm_bw)`` with the peaks of the chip the
   process runs on (``launch/roofline.device_peaks``), plus the cost of
   each grid step of each kernel (``ce_step_seconds``: ``CE_STEP_S`` and
   the (bn, D) hidden tile every step norms and casts again), for a real
   backend; a ragged last chunk is charged its full width, so a dividing
   chunk wins where one is about as wide.  For interpret mode a CPU model
   ``flops/CPU_FLOPS + cells * CELL_OVERHEAD_S`` (the interpreter unrolls
   the grid, so cell count — not bandwidth — is the first-order term).
   Candidates are ranked by predicted time.
3. **measure (optional refinement)** — ``measure=True`` times
   ``value_and_grad`` of the real kernel at the top ``MEASURE_TOP_K``
   predicted candidates and keeps the fastest.  Only *measured* winners are
   persisted to the on-disk cache; roofline-only picks stay in-memory so
   CI stays hermetic and deterministic.

The cache is keyed on ``(N, D, Vp, dtype, transpose_w, softcap?, norm,
backend)`` and lives at ``$REPRO_FUSED_CE_CACHE`` (default
``<checkout>/.autotune/fused_ce_autotune.json``), written atomically.  Lookup
(``get_tuned``) is pure host-side Python on static shapes — safe to call
at trace time from inside ``jit``; measurement only ever runs eagerly
(benchmarks, ``launch/train.py --retune``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time

import numpy as np

from .. import CHECKOUT_DIR

CACHE_VERSION = 2                 # 2: ragged vocabulary chunks, step cost
MEASURE_TOP_K = 4
MEASURE_REPS = 3

# interpret-mode cost model, calibrated on the loss_memory bench host:
# a grid cell of the unrolled interpreter costs ~2.5 ms of dispatch +
# discharge overhead regardless of tile size, and the jnp arithmetic
# inside sustains ~50 GFLOP/s
CPU_FLOPS = 5.0e10
CELL_OVERHEAD_S = 2.5e-3

# flash attention's VMEM working-set budget on a real TPU (the default
# scoped VMEM is 16 MiB; leave headroom for pipelining double-buffers).
# The CE kernels request their own scope (fused_ce.VMEM_LIMIT_BYTES).
VMEM_BUDGET_BYTES = 12 << 20

# the cost of one CE kernel grid step on a TPU beyond its products'
# roofline, fitted to value_and_grad timings of 18 tilings at gpt2-small
# and gpt2-medium shapes on a v5e: a fixed part (the pipeline's step, the
# (bn, 1) running-statistic columns read and written) and a part per
# element of the (bn, D) hidden tile, which every step norms and casts
# again whatever its chunk's width
CE_STEP_S = 0.35e-6
CE_HIDDEN_ELEM_S = 2.3e-12
# largest fp32 logits tile on a TPU: past 2^20 elements (4 MiB) the
# backward kernels ran 30-55% slower per unit of work on a v5e, at
# (1024, 1536) and (2048, 1024) tiles of gpt2-small and (1024, 1152) and
# (2048, 512) of gpt2-medium, though within the requested VMEM
CE_TILE_ELEMS = 1 << 20
# interpret mode has no VMEM, but a tile of jnp intermediates still costs
# host RAM — cap the fp32 logits tile at 2^24 elements (64 MiB)
INTERPRET_TILE_ELEMS = 1 << 24

_LOCK = threading.Lock()
_MEM: dict = {}          # key -> TunedCE (both measured and roofline picks)
_DISK_LOADED = False


@dataclasses.dataclass(frozen=True)
class TunedCE:
    """One tuning decision: the block sizes and backward schedule for a
    fused-CE shape, plus provenance ("seed" | "roofline" | "measured")."""
    bn: int
    bv: int
    schedule: str                 # "split" | "fused"
    source: str
    predicted_ms: float = 0.0
    measured_ms: float | None = None


AUTOTUNE_DIR = CHECKOUT_DIR / ".autotune"


def cache_path() -> str:
    return os.environ.get("REPRO_FUSED_CE_CACHE",
                          str(AUTOTUNE_DIR / "fused_ce_autotune.json"))


def cache_key(N, D, Vp, *, dtype, transpose_w, softcap, norm,
              backend) -> str:
    dt = np.dtype(dtype).name if not isinstance(dtype, str) else dtype
    return (f"N{N}-D{D}-V{Vp}-{dt}-"
            f"{'untied' if transpose_w else 'tied'}-"
            f"cap{softcap if softcap else 0}-norm{norm or 'none'}-"
            f"{backend}")


def _dtype_bytes(dtype) -> int:
    return np.dtype(dtype).itemsize


def residency_cap(N: int, Vp: int) -> int:
    """Max legal logits-tile elements: half the full [N, Vp] buffer (so the
    no-materialization audit keeps meaning something), floored at one
    minimal (8, 128) tile for tiny shapes."""
    return max((N * Vp) // 2, 8 * 128)


def _divisors(n: int, quantum: int, cap: int) -> list:
    """Multiples of ``quantum`` dividing n, geometrically thinned (each
    kept divisor at least ~1.4x the previous) so huge shapes don't explode
    the search, always keeping quantum and n itself (if <= cap)."""
    ds = [d for d in range(quantum, min(n, cap) + 1, quantum) if n % d == 0]
    out = []
    for d in ds:
        if not out or d >= out[-1] * 1.4 or d == ds[-1]:
            out.append(d)
    return out or [quantum]


def candidate_blocks(N: int, D: int, Vp: int, *, bytes_h: int,
                     interpret: bool) -> list:
    """All legal (bn, bv, schedule) triples for the shape, budget- and
    residency-filtered.  ``bn`` divides N; ``bv`` is any whole number of
    128-lane groups up to Vp, the last chunk ragged where it does not
    divide Vp.  On a real backend the logits tile holds at most
    ``CE_TILE_ELEMS`` and the kernels' working set
    (``fused_ce.vmem_bytes``) must fit the VMEM they request
    (``fused_ce.VMEM_LIMIT_BYTES``).  ``schedule="fused"`` appears only
    for tilings where one grid axis is 1 (the combined backward kernel's
    legality condition — no non-consecutive output-block revisits)."""
    from .fused_ce import LANES, VMEM_LIMIT_BYTES, vmem_bytes
    cap = residency_cap(N, Vp)
    cands = []
    for bn in _divisors(N, 8, max(N, 8)):
        for bv in range(LANES, Vp + 1, LANES):
            if bn * bv > cap:
                continue
            if interpret:
                if bn * bv > INTERPRET_TILE_ELEMS:
                    continue
            elif (bn * bv > CE_TILE_ELEMS
                  or vmem_bytes(bn, bv, D, bytes_h=bytes_h)
                  > VMEM_LIMIT_BYTES):
                continue
            n_r, n_v = N // bn, -(-Vp // bv)
            cands.append((bn, bv, "split"))
            if n_r == 1 or n_v == 1:
                cands.append((bn, bv, "fused"))
    return cands


def predict_seconds(N: int, D: int, Vp: int, bn: int, bv: int,
                    schedule: str, *, bytes_h: int, bytes_w: int,
                    interpret: bool) -> float:
    """Analytic cost of one fwd+bwd of the fused NLL at this tiling.

    Real backend: per-pass ``max(compute, memory)`` against the
    ``launch/roofline.py`` constants, plus ``ce_step_seconds`` for every
    grid step of every kernel.  The memory terms are DMA counts from the
    BlockSpecs: the forward re-reads the full W once per row block
    (``n_r * w_bytes``), the split backward adds a second full logits
    recompute plus an h re-stream per vocab chunk, and the fused schedule
    reads each operand exactly once.  A ragged last chunk is charged in
    full, so its columns past Vp cost their products and bytes: a chunk
    that divides Vp wins where one is about as wide.  Interpret: grid
    cells are unrolled by the interpreter, so cost = flops/CPU_FLOPS +
    cells * CELL_OVERHEAD_S (memory ignored — everything is host RAM)."""
    n_r, n_v = N // bn, -(-Vp // bv)
    vc = n_v * bv                            # columns swept, tail included
    mm = 2.0 * N * D * vc                    # one full-projection matmul
    h_b = N * D * bytes_h
    w_b = vc * D * bytes_w

    if schedule == "fused":
        passes = [
            (mm, h_b + n_r * w_b),                    # forward
            (3.0 * mm, h_b + w_b + h_b + w_b),        # combined backward
        ]
        cells = n_r * n_v * 2
    else:
        passes = [
            (mm, h_b + n_r * w_b),                    # forward
            (2.0 * mm, h_b + n_r * w_b + h_b),        # d_hidden sweep
            (2.0 * mm, n_v * h_b + w_b + w_b),        # d_W sweep
        ]
        cells = n_r * n_v * 3

    if interpret:
        flops = sum(f for f, _ in passes)
        return flops / CPU_FLOPS + cells * CELL_OVERHEAD_S
    return _roofline_seconds(passes) + cells * ce_step_seconds(bn, D)


def ce_step_seconds(bn: int, D: int) -> float:
    """Cost of one CE kernel grid step on a TPU beyond its products'
    roofline: ``CE_STEP_S`` plus ``CE_HIDDEN_ELEM_S`` per element of the
    (bn, D) hidden tile the step norms and casts."""
    return CE_STEP_S + CE_HIDDEN_ELEM_S * bn * D


def _roofline_seconds(passes) -> float:
    """Sum of per-pass ``max(flops / peak, bytes / bandwidth)`` on the
    peaks of the chip this process runs on."""
    from ..launch.roofline import device_peaks
    pk = device_peaks()
    return sum(max(f / pk.bf16_flops, b / pk.hbm_bw) for f, b in passes)


# ---------------------------------------------------------------------------
# persistent cache


def _load_disk() -> None:
    global _DISK_LOADED
    if _DISK_LOADED:
        return
    _DISK_LOADED = True
    try:
        with open(cache_path()) as f:
            blob = json.load(f)
    except (OSError, ValueError):
        return
    if blob.get("version") != CACHE_VERSION:
        return
    for k, e in blob.get("entries", {}).items():
        _MEM.setdefault(k, TunedCE(**e))


def _save_disk() -> None:
    """Persist the *measured* entries atomically (tmp + rename).  Roofline
    picks are deliberately not written: they are cheap to recompute and
    letting them pin the cache would freeze a model-based guess as if it
    were ground truth."""
    path = cache_path()
    entries = {k: dataclasses.asdict(t) for k, t in _MEM.items()
               if t.source == "measured"}
    blob = {"version": CACHE_VERSION, "entries": entries}
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(blob, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass                     # read-only FS: run with the in-memory pick


def clear_memory_cache() -> None:
    """Forget in-process picks (tests); the disk cache is untouched."""
    global _DISK_LOADED, _ATTN_DISK_LOADED
    with _LOCK:
        _MEM.clear()
        _DISK_LOADED = False
        _MEM_ATTN.clear()
        _ATTN_DISK_LOADED = False


def drop_entry(key: str) -> None:
    with _LOCK:
        _load_disk()
        if _MEM.pop(key, None) is not None:
            _save_disk()


# ---------------------------------------------------------------------------
# measurement


def _measure_ms(N, D, Vp, bn, bv, schedule, *, dtype, transpose_w, softcap,
                norm, interpret) -> float:
    """Median wall-clock (ms) of one jitted value_and_grad of the fused
    NLL at this tiling, on synthetic operands of the keyed shape."""
    import jax
    import jax.numpy as jnp

    from . import fused_ce

    k = jax.random.PRNGKey(0)
    kh, kw, kl = jax.random.split(k, 3)
    h = (jax.random.normal(kh, (N, D), jnp.float32) * 0.02).astype(dtype)
    wshape = (D, Vp) if transpose_w else (Vp, D)
    w = (jax.random.normal(kw, wshape, jnp.float32) * 0.02)
    labels = jax.random.randint(kl, (N,), 0, Vp)
    kwargs = dict(vocab_size=Vp, transpose_w=transpose_w, softcap=softcap,
                  block_n=bn, block_v=bv, schedule=schedule,
                  interpret=interpret)
    if norm:
        kwargs.update(norm_kind=norm, norm_scale=jnp.zeros((D,)),
                      norm_bias=jnp.zeros((D,)))

    def f(h, w):
        return fused_ce.fused_lm_loss(h, w, labels, **kwargs)[0]

    g = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))
    jax.block_until_ready(g(h, w))            # compile
    ts = []
    for _ in range(MEASURE_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(g(h, w))
        ts.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(ts))


# ---------------------------------------------------------------------------
# entry points


def get_tuned(N: int, D: int, Vp: int, *, dtype, transpose_w: bool,
              softcap, norm, interpret: bool, measure: bool = False,
              refresh: bool = False) -> TunedCE:
    """The (bn, bv, schedule) to use for this fused-CE shape.

    Deterministic host-side Python (trace-safe).  Order of precedence:
    in-memory hit -> disk hit (measured entries only) -> roofline-ranked
    search, optionally refined by measurement (``measure=True``, eager
    contexts only).  ``refresh=True`` ignores caches and re-tunes."""
    backend = "interpret" if interpret else "tpu"
    key = cache_key(N, D, Vp, dtype=dtype, transpose_w=transpose_w,
                    softcap=softcap, norm=norm, backend=backend)
    with _LOCK:
        _load_disk()
        if not refresh and key in _MEM:
            hit = _MEM[key]
            if hit.source == "measured" or not measure:
                return hit

    bytes_h = _dtype_bytes(dtype)
    cands = candidate_blocks(N, D, Vp, bytes_h=bytes_h, interpret=interpret)
    if not cands:
        t = TunedCE(8, 128, "split", "seed")
        with _LOCK:
            _MEM[key] = t
        return t
    scored = sorted(
        cands,
        key=lambda c: (predict_seconds(N, D, Vp, c[0], c[1], c[2],
                                       bytes_h=bytes_h, bytes_w=4,
                                       interpret=interpret), c))
    best = scored[0]
    pred = predict_seconds(N, D, Vp, *best, bytes_h=bytes_h, bytes_w=4,
                           interpret=interpret)

    if not measure:
        t = TunedCE(best[0], best[1], best[2], "roofline",
                    predicted_ms=pred * 1e3)
        with _LOCK:
            _MEM[key] = t
        return t

    timed = []
    for c in scored[:MEASURE_TOP_K]:
        ms = _measure_ms(N, D, Vp, c[0], c[1], c[2], dtype=dtype,
                         transpose_w=transpose_w, softcap=softcap,
                         norm=norm, interpret=interpret)
        timed.append((ms, c))
    ms, win = min(timed, key=lambda t: (t[0], t[1]))
    t = TunedCE(win[0], win[1], win[2], "measured",
                predicted_ms=predict_seconds(
                    N, D, Vp, *win, bytes_h=bytes_h, bytes_w=4,
                    interpret=interpret) * 1e3,
                measured_ms=ms)
    with _LOCK:
        _MEM[key] = t
        _save_disk()
    return t


def tune_shape(N: int, D: int, Vp: int, *, dtype="float32",
               transpose_w=False, softcap=None, norm=None,
               interpret=None, refresh: bool = False) -> TunedCE:
    """Eager measured tuning for one shape (benchmarks, ``--retune``)."""
    if interpret is None:
        from .fused_ce import _interpret_default
        interpret = _interpret_default()
    return get_tuned(N, D, Vp, dtype=dtype, transpose_w=transpose_w,
                     softcap=softcap, norm=norm, interpret=interpret,
                     measure=True, refresh=refresh)


# ===========================================================================
# flash-attention tuning (kernels/flash_attention.py)
#
# Same three-stage design as the CE tuner: divisor candidates filtered by a
# VMEM working-set budget, the analytic roofline plus a per-grid-step cost
# (both from the kernels' own ``attn_grid_plan``: its exact causal band of
# tiles and its grid), optional measured refinement, and a separate
# persistent cache (only measured winners are written).  Keys deliberately exclude the sliding
# window: it is traced at call time (transformer.layer_windows), so one
# tuning decision per (shape, causal, softcap, dtype, backend) serves every
# window the layer stack produces.

ATTN_CACHE_VERSION = 2            # 2: the band walk inside the kernel
_MEM_ATTN: dict = {}
_ATTN_DISK_LOADED = False


@dataclasses.dataclass(frozen=True)
class TunedAttn:
    """One attention tuning decision: (block_q, block_k, schedule) plus
    provenance ("seed" | "roofline" | "measured")."""
    bq: int
    bk: int
    schedule: str                 # "skip" | "dense"
    source: str
    predicted_ms: float = 0.0
    measured_ms: float | None = None


def attn_cache_path() -> str:
    return os.environ.get("REPRO_FLASH_ATTN_CACHE",
                          str(AUTOTUNE_DIR / "flash_attn_autotune.json"))


def attn_cache_key(B, H, Hkv, Sq, Sk, hd, *, dtype, causal, softcap,
                   backend) -> str:
    dt = np.dtype(dtype).name if not isinstance(dtype, str) else dtype
    return (f"B{B}-H{H}-Hkv{Hkv}-Sq{Sq}-Sk{Sk}-hd{hd}-{dt}-"
            f"{'causal' if causal else 'bidi'}-"
            f"cap{softcap if softcap else 0}-{backend}")


#: fixed cost of one Pallas grid step on a TPU, about 0.35 us (the Pallas
#: TPU pipelining docs: each grid iteration carries that overhead, whatever
#: its DMAs and compute)
ATTN_STEP_S = 0.35e-6
_ATTN_KERNELS = ("fwd", "dq", "dkv")


def _attn_plans(B, H, Hkv, Sq, Sk, hd, bq, bk, schedule, *, bytes_el,
                causal):
    from .flash_attention import attn_grid_plan
    return [attn_grid_plan(kern, B, H, Hkv, Sq, Sk, hd, block_q=bq,
                           block_k=bk, itemsize=bytes_el, causal=causal,
                           schedule=schedule) for kern in _ATTN_KERNELS]


def attn_candidate_blocks(Sq: int, Sk: int, hd: int, *, bytes_el: int,
                          interpret: bool, group: int = 1) -> list:
    """All legal (bq, bk, schedule) triples: divisor blocks, VMEM
    working-set filtered (real backend) or tile-size capped (interpret).

    Blocks are multiples of ``flash_attention.block_quantum``, and on a
    real TPU the working set counts each kernel's resident operand as the
    kernel sizes it (``attn_grid_plan``): K and V in the forward and dq,
    the KV head's ``group`` query heads of Q, dO, lse and delta in dkv."""
    from .flash_attention import block_quantum
    cands = []
    for bq in _divisors(Sq, block_quantum(Sq, interpret, q=True), Sq):
        for bk in _divisors(Sk, block_quantum(Sk, interpret, q=False), Sk):
            if interpret:
                if bq * bk > INTERPRET_TILE_ELEMS:
                    continue
            elif _attn_vmem_bytes(Sq, Sk, hd, bq, bk, bytes_el=bytes_el,
                                  group=group) > VMEM_BUDGET_BYTES:
                continue
            cands.append((bq, bk, "dense"))
            cands.append((bq, bk, "skip"))
    if interpret:
        # never the whole score matrix in one tile: a (Sq, Sk) block is
        # exactly the residency the kernel exists to avoid, and measured
        # interpret wall time shows sub-matrix tiles cost nothing (band
        # skipping pays for the extra dispatches).  Keep the full tile
        # only when it is the sole legal choice (tiny Sq/Sk).
        sub = [c for c in cands if c[0] * c[1] < Sq * Sk]
        if sub:
            cands = sub
    return cands


def _attn_vmem_bytes(Sq, Sk, hd, bq, bk, *, bytes_el, group) -> int:
    """The larger kernel's VMEM: its resident operand, its double-buffered
    tiles, fp32 scratch (a (n, 1) column pads to 128 lanes) and about four
    fp32 score-sized temporaries."""
    from .flash_attention import LANES, _lane_pad
    hp = _lane_pad(hd)
    fwd, _, dkv = _attn_plans(1, group, 1, Sq, Sk, hd, bq, bk, "skip",
                              bytes_el=bytes_el, causal=True)
    scores = 4 * 4 * bq * bk
    ws_fwd = (2 * fwd.block_major * 2 * hp * bytes_el      # K, V
              + 2 * 3 * bq * hp * bytes_el                 # q, dO, dq
              + 4 * bq * (hp + 2 * LANES) + scores)
    ws_dkv = (2 * dkv.block_major * group * (2 * hp * bytes_el + 64)
              + 2 * 4 * bk * hp * bytes_el                 # k, v, dk, dv
              + 4 * 2 * bk * hp + scores)
    return max(ws_fwd, ws_dkv)


def attn_predict_seconds(B, H, Hkv, Sq, Sk, hd, bq, bk, schedule, *,
                         bytes_el, causal, interpret) -> float:
    """Analytic cost of one fused fwd + bwd (dQ + dKV) at this tiling.

    The band walk computes only in-band tiles under "skip" and every tile
    under "dense"; each kernel pays ``ATTN_STEP_S`` per grid step of its
    plan.  Bytes: q-like planes and the resident K/V (fwd, dq) or Q/dO
    (dkv) once per head while one chunk holds the head, else re-read per
    in-band tile; ``hd`` padded to the 128 lanes it occupies in the
    kernel's layout; lse/delta as lane-dense rows.  Interpret mode charges
    per-cell dispatch overhead for every grid cell of all three kernels —
    the in-kernel walk saves arithmetic but not dispatch."""
    plans = _attn_plans(B, H, Hkv, Sq, Sk, hd, bq, bk, schedule,
                        bytes_el=bytes_el, causal=causal)
    tiles = plans[0].tiles_full + plans[0].tiles_masked
    area = float(bq * bk * hd) * tiles
    f_fwd, f_dq, f_dkv = 4.0 * area, 6.0 * area, 8.0 * area
    steps = sum(p.steps for p in plans)

    if interpret:
        flops = f_fwd + f_dq + f_dkv
        return flops / CPU_FLOPS + steps * CELL_OVERHEAD_S

    from .flash_attention import _lane_pad
    be, hp = bytes_el, _lane_pad(hd)
    q_pl = B * H * Sq * hp * be            # one (B, H, Sq, hd) plane
    kv_pl = B * Hkv * Sk * hp * be         # one (B, Hkv, Sk, hd) plane
    row = 4 * B * H * Sq                   # one lse / delta row plane
    fwd, _, dkv = plans
    kv_read = 2 * kv_pl if fwd.grid[3] == 1 else 2 * be * hp * bk * tiles
    q_read = 2 * q_pl + 2 * row if dkv.grid[3] == 1 else \
        (2 * be * hp + 2 * 4) * bq * tiles
    passes = [
        (f_fwd, 2 * q_pl + kv_read + row),
        (f_dq, 3 * q_pl + kv_read + 2 * row),
        (f_dkv, 4 * kv_pl + q_read),
    ]
    return _roofline_seconds(passes) + steps * ATTN_STEP_S


def _load_attn_disk() -> None:
    global _ATTN_DISK_LOADED
    if _ATTN_DISK_LOADED:
        return
    _ATTN_DISK_LOADED = True
    try:
        with open(attn_cache_path()) as f:
            blob = json.load(f)
    except (OSError, ValueError):
        return
    if blob.get("version") != ATTN_CACHE_VERSION:
        return
    for k, e in blob.get("entries", {}).items():
        _MEM_ATTN.setdefault(k, TunedAttn(**e))


def _save_attn_disk() -> None:
    path = attn_cache_path()
    entries = {k: dataclasses.asdict(t) for k, t in _MEM_ATTN.items()
               if t.source == "measured"}
    blob = {"version": ATTN_CACHE_VERSION, "entries": entries}
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(blob, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


def _measure_attn_ms(B, H, Hkv, Sq, Sk, hd, bq, bk, schedule, *, dtype,
                     causal, softcap, interpret) -> float:
    """Median wall-clock (ms) of one jitted value_and_grad through the
    flash kernel at this tiling, on synthetic operands."""
    import jax
    import jax.numpy as jnp

    from .flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, hd),
                          jnp.float32).astype(dtype)

    def f(q, k, v):
        o = flash_attention(q, k, v, causal=causal, softcap=softcap,
                            block_q=bq, block_k=bk, schedule=schedule,
                            interpret=interpret)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))
    jax.block_until_ready(g(q, k, v))          # compile
    ts = []
    for _ in range(MEASURE_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(g(q, k, v))
        ts.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(ts))


def get_tuned_attn(B, H, Hkv, Sq, Sk, hd, *, dtype, causal, softcap,
                   interpret, measure: bool = False,
                   refresh: bool = False) -> TunedAttn:
    """The (bq, bk, schedule) to use for this attention shape.

    Deterministic host-side Python (trace-safe), same precedence as
    :func:`get_tuned`: in-memory -> disk (measured only) -> roofline
    ranking, optionally measure-refined."""
    backend = "interpret" if interpret else "tpu"
    key = attn_cache_key(B, H, Hkv, Sq, Sk, hd, dtype=dtype, causal=causal,
                         softcap=softcap, backend=backend)
    with _LOCK:
        _load_attn_disk()
        if not refresh and key in _MEM_ATTN:
            hit = _MEM_ATTN[key]
            if hit.source == "measured" or not measure:
                return hit

    bytes_el = _dtype_bytes(dtype)
    cands = attn_candidate_blocks(Sq, Sk, hd, bytes_el=bytes_el,
                                  interpret=interpret, group=H // Hkv)
    if interpret:
        # prefer candidates the interpret grid clamp wouldn't rewrite
        from .flash_attention import INTERPRET_CELL_CAP
        fit = [c for c in cands
               if B * H * (Sq // c[0]) * (Sk // c[1])
               <= INTERPRET_CELL_CAP]
        cands = fit or cands
    if not cands:
        t = TunedAttn(min(Sq, 128), min(Sk, 128),
                      "skip" if causal else "dense", "seed")
        with _LOCK:
            _MEM_ATTN[key] = t
        return t

    def _pred(c):
        return attn_predict_seconds(B, H, Hkv, Sq, Sk, hd, c[0], c[1],
                                    c[2], bytes_el=bytes_el, causal=causal,
                                    interpret=interpret)

    scored = sorted(cands, key=lambda c: (_pred(c), c))
    best = scored[0]

    if not measure:
        t = TunedAttn(best[0], best[1], best[2], "roofline",
                      predicted_ms=_pred(best) * 1e3)
        with _LOCK:
            _MEM_ATTN[key] = t
        return t

    timed = []
    for c in scored[:MEASURE_TOP_K]:
        ms = _measure_attn_ms(B, H, Hkv, Sq, Sk, hd, c[0], c[1], c[2],
                              dtype=dtype, causal=causal, softcap=softcap,
                              interpret=interpret)
        timed.append((ms, c))
    ms, win = min(timed, key=lambda t: (t[0], t[1]))
    t = TunedAttn(win[0], win[1], win[2], "measured",
                  predicted_ms=_pred(win) * 1e3, measured_ms=ms)
    with _LOCK:
        _MEM_ATTN[key] = t
        _save_attn_disk()
    return t


def tune_attn_shape(B, H, Hkv, Sq, Sk, hd, *, dtype="float32", causal=True,
                    softcap=None, interpret=None,
                    refresh: bool = False) -> TunedAttn:
    """Eager measured attention tuning (benchmarks, ``--retune``)."""
    if interpret is None:
        from .fused_ce import _interpret_default
        interpret = _interpret_default()
    return get_tuned_attn(B, H, Hkv, Sq, Sk, hd, dtype=dtype, causal=causal,
                          softcap=softcap, interpret=interpret,
                          measure=True, refresh=refresh)
