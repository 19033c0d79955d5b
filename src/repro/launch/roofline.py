"""Roofline-term extraction from compiled dry-run artifacts.

    compute    = HLO_FLOPs_per_device / peak_FLOPs
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw

with the peaks of :data:`CHIP_PEAKS` (the dry-run models ``TARGET_KIND``).

``cost_analysis`` reports per-device (post-SPMD) flops and bytes.
Collective bytes are NOT in cost_analysis: we parse the compiled HLO text,
build a symbol table of instruction result sizes, and sum the *operand*
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (counting async ``-start`` once, skipping ``-done``).

Predicted vs measured (the kernel autotuners' pruning model)
------------------------------------------------------------
The same HBM roofline that :func:`loss_stage_seconds` and
:func:`attention_stage_seconds` evaluate per *path* (fused vs unfused) is
evaluated per *candidate block config* by
``kernels.autotune.predict_seconds`` (fused CE) and
``kernels.autotune.attn_predict_seconds`` (flash attention): each kernel
pass contributes ``max(flops / PEAK_FLOPS, bytes / HBM_BW)`` where the
bytes term counts the tiles each grid arrangement actually streams (e.g.
the CE backward re-reads W once per row-block, so shrinking ``bn``
multiplies W traffic; the attention cost counts only in-band tiles under
the causal/window schedule).  The prediction is deliberately coarse — it
only has to *rank* candidates so the top-K survive to measurement
(``MEASURE_TOP_K``); wall-clock timing of the survivors picks the winner,
and ONLY measured entries persist to the on-disk cache.  Roofline-only
mode (``measure=False``, used by the fast CI tier) stops after ranking:
deterministic, hermetic, no timing noise in version control.

A Pallas call is opaque to XLA's cost model, so neither kernel appears in
dry-run ``cost_analysis``; the stage overlays below are the analytic
substitute (the former ``launch/flash_overlay.py`` structural measurement
is folded into :func:`attention_stage_seconds` +
``benchmarks/roofline_report.py``).
"""
from __future__ import annotations

import re
from typing import Dict, NamedTuple


class ChipPeaks(NamedTuple):
    """Published per-chip peaks."""
    bf16_flops: float       # FLOP/s
    hbm_bw: float           # HBM bandwidth, bytes/s
    ici_bw: float           # bytes/s per ICI link


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud TPU
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
# 1,600 Gbit/s of chip-to-chip interconnect (four links: 50 GB/s each).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def device_peaks(device_kind: str | None = None) -> ChipPeaks:
    """Peaks of ``device_kind`` (default: the first local device's).  A
    device missing from :data:`CHIP_PEAKS` is an error, never a default."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"launch/roofline.CHIP_PEAKS with its source") from None


# The chip the dry-run terms and byte-model overlays below are modeled for
# (the production meshes in configs/shapes.py are v5e pods).
TARGET_KIND = "TPU v5 lite"
PEAK_FLOPS = CHIP_PEAKS[TARGET_KIND].bf16_flops
HBM_BW = CHIP_PEAKS[TARGET_KIND].hbm_bw
ICI_BW = CHIP_PEAKS[TARGET_KIND].ici_bw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*([\w\-]+)\(")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")


def _shape_bytes(type_str: str) -> int:
    """bytes of 'f32[16,32]{1,0}' or tuple '(f32[8], bf16[4,4])'."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _iter_collectives(hlo_text: str):
    """Yield ``(kind, result_dtype, operand_bytes)`` per collective
    instruction in compiled HLO text (async ``-start`` counted once,
    ``-done`` skipped)."""
    sizes: Dict[str, int] = {}
    lines = hlo_text.splitlines()
    for line in lines:
        m = _INSTR_RE.match(line)
        if m:
            name, type_str, _op = m.groups()
            sizes[name] = _shape_bytes(type_str)

    for line in lines:
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, op = m.groups()
        kind = next((c for c in _COLLECTIVES if op.startswith(c)), None)
        if kind is None:
            continue
        if op.endswith("-done"):
            continue  # async completion: counted at -start
        # operand list: everything inside the first (...) after the opcode
        paren = line[line.index(op) + len(op):]
        depth = 0
        args = ""
        for ch in paren:
            if ch == "(":
                depth += 1
                if depth == 1:
                    continue
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            if depth >= 1:
                args += ch
        operand_names = re.findall(r"%([\w.\-]+)", args)
        b = sum(sizes.get(n, 0) for n in operand_names)
        if b == 0:
            b = _shape_bytes(type_str)  # fallback: result size
        dm = _SHAPE_RE.search(type_str)
        yield kind, (dm.group(1) if dm else "?"), b


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind from compiled HLO text."""
    out = {k: 0 for k in _COLLECTIVES}
    out["total"] = 0
    for kind, _dtype, b in _iter_collectives(hlo_text):
        out[kind] += b
        out["total"] += b
    return out


def collective_buffer_bytes(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """MAX single-instruction operand bytes per (collective kind, result
    dtype) — the peak-comm-buffer audit for the bucketed reduction: the
    int8 gradient gather shows up as ``["all-gather"]["s8"]``, and
    bucketing must cap it at O(bucket) instead of O(shard) while the fp32
    params/FSDP gathers (f32/bf16 dtypes) stay untouched."""
    out: Dict[str, Dict[str, int]] = {}
    for kind, dtype, b in _iter_collectives(hlo_text):
        d = out.setdefault(kind, {})
        d[dtype] = max(d.get(dtype, 0), b)
    return out


def roofline_terms(cost: dict, coll_bytes: int) -> dict:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    return {
        "flops_per_device": flops,
        "bytes_per_device": byts,
        "collective_bytes_per_device": float(coll_bytes),
        "t_compute_s": flops / PEAK_FLOPS,
        "t_memory_s": byts / HBM_BW,
        "t_collective_s": coll_bytes / ICI_BW,
    }


def dominant_term(terms: dict) -> str:
    t = {"compute": terms["t_compute_s"], "memory": terms["t_memory_s"],
         "collective": terms["t_collective_s"]}
    return max(t, key=t.get)


def loss_stage_seconds(batch_tokens: int, d_model: int, padded_vocab: int,
                       *, fused: bool, bytes_act: int = 2) -> float:
    """HBM-bound time of the LM loss+grad stage (the roofline overlay for
    the fused chunked-vocab CE, analogous to the flash-attention term).

    ``fused=False`` models the legacy path's ~5 HBM crossings of the fp32
    ``[B*T, V]`` logits; ``fused=True`` models the logits-free kernel
    (kernels/fused_ce.py): 3 streams of hidden+W, no N*V term.

    This is the per-path overlay.  The per-block-config variant the
    autotuner ranks candidates with is ``kernels.autotune.predict_seconds``
    (see the module docstring above on the predicted-vs-measured split)."""
    from ..kernels.fused_ce import (lm_loss_hbm_bytes_fused,
                                    lm_loss_hbm_bytes_unfused)
    fn = lm_loss_hbm_bytes_fused if fused else lm_loss_hbm_bytes_unfused
    return fn(batch_tokens, d_model, padded_vocab,
              bytes_h=bytes_act) / HBM_BW


def attention_stage_seconds(B: int, H: int, Hkv: int, S: int, hd: int,
                            *, fused: bool, train: bool = True,
                            bytes_act: int = 2) -> float:
    """HBM-bound time of ONE layer's attention middle (scores/softmax/AV)
    — the roofline overlay for the flash kernel, analogous to
    :func:`loss_stage_seconds` for the fused CE.

    ``fused=False`` models the unfused path's materialized fp32 score
    tiles: the backward re-reads/rewrites them, charged at ~5 crossings of
    ``[B, H, S, block_k]`` strips (kernels/flash_attention.py's
    ``attention_hbm_bytes_unfused``).  ``fused=True`` charges the flash
    kernel's streaming floor: each of fwd/dQ/dKV reads Q,K,V once and
    writes its output once — no ``O(S^2)`` term.  ``train=False`` drops
    the backward passes (serving prefill)."""
    from ..kernels.flash_attention import (attention_hbm_bytes_flash,
                                           attention_hbm_bytes_train_flash,
                                           attention_hbm_bytes_unfused)
    if fused:
        fn = (attention_hbm_bytes_train_flash if train
              else attention_hbm_bytes_flash)
        return fn(B, H, Hkv, S, hd, bytes_per_el=bytes_act) / HBM_BW
    passes = 5 if train else 2
    return attention_hbm_bytes_unfused(B, H, S, hd, passes=passes) / HBM_BW


def kv_cache_slot_bytes(cfg, cache_len: int, *, kv_dtype=None) -> int:
    """HBM bytes one serve slot's KV cache holds across all layers.

    The per-token cost comes from :func:`repro.quant.kv_bytes_per_token`:
    bf16 charges 2 bytes/element, int8 charges 1 byte/element plus two
    fp32 per-token scales (K and V planes) per layer.  This is the
    analytic side of the serve-tier capacity model — at a fixed HBM
    budget the sustainable slot count is ``budget // slot_bytes``, so
    int8 buys ``2E/(E+4)`` more slots for ``E = n_kv_heads * head_dim``
    (~2x once E >> 4).  benchmarks/serve_sustained.py checks this
    prediction against ``jax.Array.nbytes`` of the live engine state."""
    from ..quant import kv_bytes_per_token
    kv_dtype = kv_dtype or cfg.kv_dtype
    return cfg.n_layers * cache_len * kv_bytes_per_token(
        cfg.n_kv_heads, cfg.hd, kv_dtype)


def kv_slots_at_budget(cfg, cache_len: int, hbm_budget_bytes: int,
                       *, kv_dtype=None) -> int:
    """Concurrent slots a fixed HBM budget sustains for the KV cache."""
    return int(hbm_budget_bytes
               // kv_cache_slot_bytes(cfg, cache_len, kv_dtype=kv_dtype))


# ---------------------------------------------------------------------------
# gradient-collective bucket model (distributed/overlap.py)

#: fixed per-collective cost — dispatch + ring latency — that dominates
#: tiny buckets.  ~10us is the TPU-generation ICI ballpark; the value only
#: has to be the right order of magnitude to keep the bucket chooser away
#: from the latency-bound regime.
COLLECTIVE_LAUNCH_S = 10e-6

#: how many buckets the overlap scheduler wants in flight per shard: more
#: buckets = finer backward/comm interleaving, fewer = less launch overhead.
TARGET_OVERLAP_BUCKETS = 8


def ring_collective_seconds(nbytes: int, ndev: int, *,
                            bw: float = ICI_BW,
                            launch: float = COLLECTIVE_LAUNCH_S) -> float:
    """Ring reduce-scatter + all-gather time for ``nbytes`` of payload:
    each phase moves ``(ndev-1)/ndev * nbytes`` per link."""
    if ndev <= 1:
        return 0.0
    return launch + 2.0 * (ndev - 1) / ndev * nbytes / bw


def ring_phase_seconds(nbytes: int, ndev: int, *, bw: float = ICI_BW,
                       launch: float = COLLECTIVE_LAUNCH_S) -> float:
    """ONE ring phase (a reduce-scatter OR an all-gather) of ``nbytes``."""
    if ndev <= 1:
        return 0.0
    return launch + (ndev - 1) / ndev * nbytes / bw


def exposed_comm_seconds(bucket_elems_list, ndev: int,
                         compute_budget_s: float, *, block: int = 256,
                         bw: float = ICI_BW,
                         launch: float = COLLECTIVE_LAUNCH_S) -> float:
    """Event-driven exposed-comm model for a gradient bucket schedule.

    The compressed reduction of bucket ``j`` (fp32 ring reduce-scatter,
    then int8+scales ring all-gather) is enqueued on a single comm channel
    the moment its slice of the backward pass has been produced — XLA
    rewrites slice-of-concatenate to the contributing operands, so bucket
    ``j``'s collective chain really does depend on only a suffix of the
    backward, modeled here as ready at ``compute_budget_s * (j+1) / B``.
    Exposed comm is whatever the channel still owes once compute is done:

        exposed  =  max(0, channel_finish - compute_budget_s)

    The monolithic schedule is the 1-bucket case: ready only when backward
    completes, so its ENTIRE wire time is exposed — while a bucketed
    schedule with ample compute exposes only the tail bucket's wire.  This
    is the quantity ``benchmarks/comm_overlap.py`` reports at ICI
    bandwidth (host CPUs serialize collectives, so wall clock cannot
    express it); the same model gives ``choose_bucket_elems`` its launch
    floor."""
    buckets = [int(n) for n in bucket_elems_list]
    B = len(buckets)
    channel = 0.0
    for j, n in enumerate(buckets):
        ready = compute_budget_s * (j + 1) / B
        wire = (ring_phase_seconds(4 * n, ndev, bw=bw, launch=launch)
                + ring_phase_seconds(n + 4 * (-(-n // block)), ndev,
                                     bw=bw, launch=launch))
        channel = max(channel, ready) + wire
    return max(0.0, channel - compute_budget_s)


def choose_bucket_elems(total_elems: int, ndev: int, *, block: int = 256,
                        bytes_per_elem: float = 1.0 + 4.0 / 256,
                        target_buckets: int = TARGET_OVERLAP_BUCKETS,
                        bw: float = ICI_BW,
                        launch: float = COLLECTIVE_LAUNCH_S) -> int:
    """Bucket size (elements) for the bucketed compressed all-reduce.

    Two pressures, both from the ring model above:

      * overlap granularity wants MANY buckets — the first bucket's
        collective can only hide behind the backward compute of the buckets
        still being produced, so per-shard we aim for
        ``TARGET_OVERLAP_BUCKETS``;
      * launch overhead wants FEW — a bucket whose wire time is dominated
        by ``COLLECTIVE_LAUNCH_S`` burns link time on latency, so the
        bucket floor is the size at which launch is <= 10% of wire time.

    ``bytes_per_elem`` defaults to the int8-plus-scales wire format
    (``1 + 4/256``).  The result is rounded to a multiple of
    ``block * ndev`` so per-device segments stay aligned with the
    quantization scale blocks (the device-count-invariance requirement)."""
    align = block * max(1, ndev)
    if total_elems <= align:
        return total_elems
    # floor: launch <= 10% of the bucket's ring wire time
    wire_bw = bw / max(1, 2 * (ndev - 1)) * max(1, ndev) if ndev > 1 else bw
    floor_bytes = 10.0 * launch * wire_bw
    floor_elems = int(floor_bytes / bytes_per_elem)
    want = max(floor_elems, total_elems // max(1, target_buckets))
    want = min(want, total_elems)
    b = -(-want // align) * align
    return min(b, total_elems)


def model_flops_train(n_params_active: int, tokens: int) -> float:
    """6*N*D per step (fwd+bwd)."""
    return 6.0 * n_params_active * tokens


def model_flops_infer(n_params_active: int, tokens: int) -> float:
    """2*N*D (forward only)."""
    return 2.0 * n_params_active * tokens
