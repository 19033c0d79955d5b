"""Continuous-batching serve engine: slot scheduler over the compiled
decode burst.

Requests stream in through :meth:`ServeEngine.submit`; the engine admits
them into free slots, chunk-prefills (one ``page_len`` chunk per admitted
request per tick, so in-flight decodes never stall behind a long prompt),
decodes every active slot in compiled bursts of ``steps_per_tick`` tokens,
and evicts finished sequences — freeing their slots for the queue.

Exactly two compiled programs per arch, independent of batch composition:

  * prefill: ``model.prefill_into_slot`` with traced (slot, start,
    n_valid) — every chunk of every request is the same program;
  * decode:  the ``make_decode_burst`` scan — per-slot positions,
    budgets, temperatures and EOS ids are all traced vectors.

Slot state is the family's ``init_slots`` pytree (slot-major ring/paged KV
for attention families, slot-major recurrent state for rwkv/griffin);
slots are fully independent rows, so a *greedy* request's tokens are
identical whatever else shares the batch (pinned by
tests/test_serve_engine.py).  Temperature sampling draws from the engine's
single RNG chain, so sampled tokens depend on scheduling (reproducible
only for a fixed seed + request stream).

Telemetry: per-request queue/prefill/first-token/total latency and
per-tick slot utilization, aggregated by :meth:`stats` (p50/p95/p99
TTFT, TPOT and queue wait).

Serving tier-2 options:

  * ``prefix_cache=True`` — shared-prefix page reuse.  Completed
    page-aligned prefill chunks are copied into a device-side pool and
    indexed by :class:`~repro.serve.prefix_cache.PrefixCache`; admission
    restores the longest cached prefix by copying whole pages back and
    starts chunked prefill at the cache boundary.  Restored pages are
    bit-copies and chunk boundaries are unchanged, so greedy outputs are
    token-identical to a cold prefill.
  * ``kv_dtype="int8"`` — per-token int8 KV payloads with fp32 scales
    (see models/layers.py); roughly halves cache HBM so a fixed budget
    sustains ~2x the slots.  Forks the compiled programs per dtype via
    the config, never per batch composition.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import ModelConfig, get_model
from ..models.layers import get_decode_attn_impl
from .decode import NO_EOS, make_decode_burst, sample_tokens
from .prefix_cache import ROOT, PrefixCache

FREE, PREFILL, ACTIVE = 0, 1, 2


def _pct(xs, q: float) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    return float(xs[min(len(xs) - 1, int(len(xs) * q))])


@functools.lru_cache(maxsize=32)
def _compiled_fns(cfg: ModelConfig, steps_per_tick: int, decode_impl: str):
    """Jitted prefill/reset/burst shared by every engine on this config —
    a fresh ServeEngine (e.g. after a timed warmup run) must not recompile.
    ModelConfig is a frozen dataclass, so it keys the cache directly.
    ``decode_impl`` (``layers.get_decode_attn_impl()`` when the engine is
    built) is read at trace time, so it keys the programs too: an engine
    built after switching the decode kernel must not reuse programs traced
    with the other one.

    The slot-state argument is donated everywhere (the caller immediately
    rebinds it): the KV cache is the engine's dominant allocation, and
    without donation every tick would copy it whole."""
    model = get_model(cfg)
    prefill = jax.jit(
        lambda p, s, slot, toks, start, n: model.prefill_into_slot(
            cfg, p, s, slot, toks, start, n), donate_argnums=(1,))
    reset = jax.jit(lambda s, slot: model.reset_slot(cfg, s, slot),
                    donate_argnums=(0,))
    burst = jax.jit(make_decode_burst(cfg, steps_per_tick),
                    donate_argnums=(1,))
    enc = (jax.jit(lambda p, s, slot, fr: model.prefill_encoder_slot(
        cfg, p, s, slot, fr), donate_argnums=(1,))
        if cfg.family == "encdec" else None)
    return prefill, reset, burst, enc


@functools.lru_cache(maxsize=32)
def _page_copy_fns(cfg: ModelConfig, page_len: int):
    """Two jitted one-page copies between a slot cache and the prefix pool.

    slot/start/pool_idx are traced scalars, so each direction compiles
    exactly once per (config, page_len) whatever pages move — the engine's
    one-program-per-family invariant extends to the prefix cache.  The
    slice indexing is generic over leaf rank: 5-D k/v (L, N, C, Hkv, hd)
    and 3-D int8 scale planes (L, N, C) both have (layer, row, position)
    as their leading axes, which is all a page copy touches."""
    del cfg  # jit keys on leaf shapes; cfg keys the lru_cache entry

    def _copy_page(dst, src, dst_row, dst_off, src_row, src_off):
        def leaf(d, s):
            sizes = (s.shape[0], 1, page_len) + s.shape[3:]
            zeros = (0,) * (s.ndim - 3)
            page = jax.lax.dynamic_slice(s, (0, src_row, src_off) + zeros,
                                         sizes)
            return jax.lax.dynamic_update_slice(
                d, page, (0, dst_row, dst_off) + zeros)
        return jax.tree.map(leaf, dst, src)

    pool_to_slot = jax.jit(
        lambda state, pool, slot, start, pidx: _copy_page(
            state, pool, slot, start, pidx, 0), donate_argnums=(0,))
    slot_to_pool = jax.jit(
        lambda pool, state, slot, start, pidx: _copy_page(
            pool, state, pidx, 0, slot, start), donate_argnums=(0,))
    return pool_to_slot, slot_to_pool


@dataclasses.dataclass
class Request:
    """One generation request.  ``tokens`` is the prompt (1-D int32; for
    encdec the decoder prefix, usually just BOS, with ``frames`` carrying
    the encoder input).  ``temperature <= 0`` decodes greedily."""
    uid: Any
    tokens: Any
    max_new: int
    temperature: float = 0.0
    eos_id: Optional[int] = None
    frames: Any = None


@dataclasses.dataclass
class RequestResult:
    uid: Any
    tokens: List[int]
    submitted_t: float
    admitted_t: float
    first_token_t: float
    done_t: float

    @property
    def latency_s(self) -> float:
        return self.done_t - self.submitted_t

    @property
    def ttft_s(self) -> float:
        """Time to first token (queue wait + prefill)."""
        return self.first_token_t - self.submitted_t


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 4,
                 cache_len: int = 256, page_len: int = 32,
                 steps_per_tick: int = 8, seed: int = 0, src_len: int = 0,
                 prefill_chunks_per_tick: int = 1,
                 prefix_cache: bool = False, prefix_pool_pages: int = 0,
                 kv_dtype: Optional[str] = None):
        if kv_dtype is not None and kv_dtype != cfg.kv_dtype:
            # fork the config so _compiled_fns keys per-dtype programs
            cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
        self.cfg = cfg
        self.params = params
        self.model = get_model(cfg)
        self.n_slots = n_slots
        self.page_len = page_len
        # round the ring up to whole pages so a final prefill chunk's
        # dynamic_update_slice never clamps (start + page_len <= cache_len)
        self.cache_len = -(-cache_len // page_len) * page_len
        self.steps_per_tick = steps_per_tick
        self.prefill_chunks_per_tick = prefill_chunks_per_tick
        self.src_len = src_len
        self._rng = jax.random.PRNGKey(seed)

        if cfg.family == "encdec":
            self.state = self.model.init_slots(cfg, n_slots, self.cache_len,
                                               src_len)
        else:
            self.state = self.model.init_slots(cfg, n_slots, self.cache_len)
        (self._prefill_jit, self._reset_jit, self._burst_jit,
         self._enc_jit) = _compiled_fns(self.cfg, steps_per_tick,
                                        get_decode_attn_impl())

        # shared-prefix page pool: same pytree layout as the slot cache
        # with the slot axis replaced by pool pages of one page_len each
        self._prefix: Optional[PrefixCache] = None
        self._pool = None
        if prefix_cache:
            if self.cfg.family not in ("dense", "moe"):
                raise ValueError(
                    "prefix cache needs a paged KV cache; family "
                    f"{self.cfg.family!r} has none")
            pool_pages = prefix_pool_pages or 4 * n_slots
            self._prefix = PrefixCache(pool_pages, page_len)
            self._pool = jax.tree.map(
                lambda l: jnp.zeros(
                    (l.shape[0], pool_pages, page_len) + l.shape[3:],
                    l.dtype), self.state)
            self._pool_to_slot, self._slot_to_pool = _page_copy_fns(
                self.cfg, page_len)

        # host-side slot table
        self.slot_mode = [FREE] * n_slots
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_cursor = [0] * n_slots          # prefill progress (tokens)
        self.slot_out: List[List[int]] = [[] for _ in range(n_slots)]
        self.slot_meta: List[Optional[dict]] = [None] * n_slots
        # per-slot prefix-cache chain: held nodes + tail key for inserts
        # (None tail = pool exhausted mid-chain, stop inserting)
        self.slot_prefix_nodes: List[list] = [[] for _ in range(n_slots)]
        self.slot_chain_key: List[Optional[str]] = [ROOT] * n_slots
        self._last_tok = np.zeros((n_slots,), np.int32)
        self._pos = np.zeros((n_slots,), np.int32)
        self._rem = np.zeros((n_slots,), np.int32)
        self._temps = np.zeros((n_slots,), np.float32)
        self._eos = np.full((n_slots,), NO_EOS, np.int32)

        self.queue: deque = deque()
        self.results: List[RequestResult] = []
        # telemetry
        self.tick_utilization: List[float] = []
        self.token_latencies: List[float] = []
        self.tokens_emitted = 0
        self.decode_ticks = 0

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        prompt_len = int(np.asarray(req.tokens).shape[0])
        if prompt_len + req.max_new > self.cache_len:
            raise ValueError(
                f"request {req.uid}: prompt {prompt_len} + max_new "
                f"{req.max_new} exceeds cache_len {self.cache_len}")
        if self.cfg.family == "encdec":
            # frames must fill the slot's cross-K/V rows exactly: a shorter
            # stream would leave a previous occupant's (or zero-init) rows
            # attendable — cross-attention has no source-length mask
            frames_len = np.asarray(req.frames).shape[-2]
            if frames_len != self.src_len:
                raise ValueError(
                    f"request {req.uid}: frames length {frames_len} != "
                    f"engine src_len {self.src_len}")
        self.queue.append((req, time.perf_counter()))

    def idle(self) -> bool:
        return not self.queue and all(m == FREE for m in self.slot_mode)

    # ------------------------------------------------------------------
    def _split(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _admit(self) -> None:
        for slot in range(self.n_slots):
            if self.slot_mode[slot] != FREE or not self.queue:
                continue
            req, submitted_t = self.queue.popleft()
            self.state = self._reset_jit(self.state, slot)
            if self.cfg.family == "encdec":
                frames = jnp.asarray(req.frames)
                if frames.ndim == 2:
                    frames = frames[None]
                self.state = self._enc_jit(self.params, self.state, slot,
                                           frames)
            self.slot_mode[slot] = PREFILL
            self.slot_req[slot] = req
            self.slot_cursor[slot] = 0
            self.slot_out[slot] = []
            self.slot_prefix_nodes[slot] = []
            self.slot_chain_key[slot] = ROOT
            if self._prefix is not None and req.frames is None:
                prompt = np.asarray(req.tokens, np.int32).reshape(-1)
                # cap below prompt_len so >= one real chunk still runs and
                # emits the last-token logits _activate samples from
                max_pages = min((prompt.shape[0] - 1) // self.page_len,
                                self.cache_len // self.page_len)
                chain = self._prefix.lookup(prompt, max_pages)
                self._prefix.acquire(chain)
                for i, node in enumerate(chain):
                    self.state = self._pool_to_slot(
                        self.state, self._pool, jnp.int32(slot),
                        jnp.int32(i * self.page_len),
                        jnp.int32(node.pool_idx))
                self.slot_prefix_nodes[slot] = list(chain)
                if chain:
                    self.slot_chain_key[slot] = chain[-1].key
                    self.slot_cursor[slot] = len(chain) * self.page_len
            self.slot_meta[slot] = {"submitted_t": submitted_t,
                                    "admitted_t": time.perf_counter()}
            self._temps[slot] = req.temperature
            self._eos[slot] = NO_EOS if req.eos_id is None else req.eos_id

    def _prefill_tick(self) -> None:
        P = self.page_len
        for slot in range(self.n_slots):
            if self.slot_mode[slot] != PREFILL:
                continue
            req = self.slot_req[slot]
            prompt = np.asarray(req.tokens, np.int32).reshape(-1)
            for _ in range(self.prefill_chunks_per_tick):
                start = self.slot_cursor[slot]
                chunk = prompt[start:start + P]
                n_valid = chunk.shape[0]
                if n_valid < P:
                    chunk = np.pad(chunk, (0, P - n_valid))
                self.state, logits = self._prefill_jit(
                    self.params, self.state, jnp.int32(slot),
                    jnp.asarray(chunk)[None], jnp.int32(start),
                    jnp.int32(n_valid))
                self.slot_cursor[slot] = start + n_valid
                if (self._prefix is not None and req.frames is None
                        and self.slot_chain_key[slot] is not None
                        and n_valid == P and start % P == 0):
                    node, fresh = self._prefix.insert(
                        self.slot_chain_key[slot], prompt[start:start + P])
                    if node is None:
                        self.slot_chain_key[slot] = None
                    else:
                        if fresh:
                            self._pool = self._slot_to_pool(
                                self._pool, self.state, jnp.int32(slot),
                                jnp.int32(start), jnp.int32(node.pool_idx))
                        self.slot_prefix_nodes[slot].append(node)
                        self.slot_chain_key[slot] = node.key
                if self.slot_cursor[slot] >= prompt.shape[0]:
                    self._activate(slot, logits)
                    break

    def _activate(self, slot: int, logits) -> None:
        """Prefill done: sample the first token and open the slot."""
        req = self.slot_req[slot]
        first = int(sample_tokens(self._split(), logits[None],
                                  jnp.asarray(self._temps[slot:slot + 1]))[0])
        now = time.perf_counter()
        self.slot_meta[slot]["first_token_t"] = now
        self.slot_out[slot].append(first)
        self.tokens_emitted += 1
        self._last_tok[slot] = first
        self._pos[slot] = self.slot_cursor[slot]
        hit_eos = self._eos[slot] != NO_EOS and first == self._eos[slot]
        self._rem[slot] = 0 if hit_eos else req.max_new - 1
        self.slot_mode[slot] = ACTIVE
        if self._rem[slot] == 0:
            self._finish(slot)

    def _decode_tick(self) -> None:
        if not any(self.slot_mode[s] == ACTIVE and self._rem[s] > 0
                   for s in range(self.n_slots)):
            return
        t0 = time.perf_counter()
        (self.state, toks, pos, rem, ys, act) = self._burst_jit(
            self.params, self.state, jnp.asarray(self._last_tok[:, None]),
            jnp.asarray(self._pos), jnp.asarray(self._rem),
            jnp.asarray(self._temps), jnp.asarray(self._eos), self._split())
        ys = np.asarray(ys)
        act = np.asarray(act)
        dt = time.perf_counter() - t0
        n_emitted = int(act.sum())
        if n_emitted:
            self.token_latencies.extend([dt / self.steps_per_tick] * n_emitted)
        self.tokens_emitted += n_emitted
        self.decode_ticks += 1
        self.tick_utilization.append(
            sum(m == ACTIVE for m in self.slot_mode) / self.n_slots)
        self._last_tok = np.asarray(toks)[:, 0].copy()
        self._pos = np.asarray(pos).copy()
        self._rem = np.asarray(rem).copy()
        for t in range(ys.shape[0]):
            for slot in range(self.n_slots):
                if act[t, slot]:
                    self.slot_out[slot].append(int(ys[t, slot]))
        for slot in range(self.n_slots):
            if self.slot_mode[slot] == ACTIVE and self._rem[slot] == 0:
                self._finish(slot)

    def _finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        meta = self.slot_meta[slot]
        self.results.append(RequestResult(
            uid=req.uid, tokens=list(self.slot_out[slot]),
            submitted_t=meta["submitted_t"], admitted_t=meta["admitted_t"],
            first_token_t=meta.get("first_token_t", time.perf_counter()),
            done_t=time.perf_counter()))
        self.slot_mode[slot] = FREE
        self.slot_req[slot] = None
        if self._prefix is not None:
            self._prefix.release(self.slot_prefix_nodes[slot])
            self.slot_prefix_nodes[slot] = []
            self.slot_chain_key[slot] = ROOT
        self._rem[slot] = 0
        self._temps[slot] = 0.0
        self._eos[slot] = NO_EOS

    # ------------------------------------------------------------------
    def tick(self) -> None:
        """One scheduler round: admit -> chunk-prefill -> decode burst."""
        self._admit()
        self._prefill_tick()
        self._decode_tick()

    def run(self, max_ticks: int = 100_000) -> List[RequestResult]:
        """Drive ticks until every submitted request has finished."""
        ticks = 0
        while not self.idle():
            self.tick()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("engine did not drain "
                                   f"within {max_ticks} ticks")
        return self.results

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        lat = sorted(self.token_latencies) or [0.0]
        util = self.tick_utilization or [0.0]
        ttft = [r.ttft_s for r in self.results]
        # time-per-output-token after the first (steady decode cadence)
        tpot = [(r.done_t - r.first_token_t) / max(1, len(r.tokens) - 1)
                for r in self.results]
        qwait = [r.admitted_t - r.submitted_t for r in self.results]
        out = {
            "tokens_emitted": self.tokens_emitted,
            "decode_ticks": self.decode_ticks,
            "slot_utilization": float(np.mean(util)),
            "token_lat_p50_s": float(lat[len(lat) // 2]),
            "token_lat_p95_s": float(lat[min(len(lat) - 1,
                                             int(len(lat) * 0.95))]),
            "mean_request_latency_s": float(np.mean(
                [r.latency_s for r in self.results])) if self.results else 0.0,
            "mean_ttft_s": float(np.mean(ttft)) if ttft else 0.0,
            "ttft_p50_s": _pct(ttft, 0.50),
            "ttft_p95_s": _pct(ttft, 0.95),
            "ttft_p99_s": _pct(ttft, 0.99),
            "tpot_p50_s": _pct(tpot, 0.50),
            "tpot_p95_s": _pct(tpot, 0.95),
            "tpot_p99_s": _pct(tpot, 0.99),
            "queue_wait_p50_s": _pct(qwait, 0.50),
            "queue_wait_p95_s": _pct(qwait, 0.95),
            "queue_wait_p99_s": _pct(qwait, 0.99),
        }
        if self._prefix is not None:
            out.update(self._prefix.stats())
        return out
