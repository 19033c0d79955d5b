"""Mixture-of-Experts FFN: token-choice top-k routing, capacity-bounded
sort-based dispatch (TPU-native).

Why not GShard one-hot dispatch: the (T, E, C) combine tensor (or even the
(T*K, E) one-hot cumsum for slot assignment) is O(T*E) memory — at the
assigned train shape (1M tokens, 128 experts) that is hundreds of GB.
Instead we do MEGABLOCKS-style group-local assignment:

  * tokens are viewed as G groups of ``moe_group_size`` (the group axis
    inherits the batch/data sharding — assignment is embarrassingly
    parallel and costs O(S_g log S_g) per group via XLA sort);
  * within a group, a token's slot in its expert = its rank among the
    group's tokens choosing that expert (argsort + searchsorted — no
    one-hot materialization);
  * tokens are scattered into an (E, G*C_g, D) buffer (E sharded over the
    ``model`` axis = expert parallelism; the scatter/gather lowers to
    all-to-alls), batched-matmul'd per expert, and gathered back.

Over-capacity tokens are dropped (their routed contribution is zero);
shared experts are dense and always-on.  Covers both assigned MoE archs:
llama4-maverick (128e top-1 + 1 shared, MoE every other layer) and
deepseek-moe-16b (64e top-6 + 2 shared, fine-grained).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import activation_mesh, batch_axis, constrain
from .common import ModelConfig
from .layers import dense_init

GROUP_SIZE = 4096  # tokens per assignment group

# dispatch implementation: "gspmd" (scatter/gather, compiler-chosen
# collectives — the baseline) or "a2a" (shard_map with explicit all-to-all —
# the EP-optimized path, see EXPERIMENTS.md §Perf hillclimb 1)
_MOE_IMPL = {"impl": "gspmd"}


def set_moe_impl(impl: str) -> None:
    assert impl in ("gspmd", "a2a"), impl
    _MOE_IMPL["impl"] = impl


def get_moe_impl() -> str:
    return _MOE_IMPL["impl"]


def init_moe(key, cfg: ModelConfig):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E)),
        "w_gate": dense_init(ks[1], (E, D, F)),
        "w_up": dense_init(ks[2], (E, D, F)),
        "w_down": dense_init(ks[3], (E, F, D), in_axis=1),
    }
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": dense_init(kk[0], (D, Fs)),
                       "w_up": dense_init(kk[1], (D, Fs)),
                       "w_down": dense_init(kk[2], (Fs, D), in_axis=0)}
    return p


def _group_capacity(sg: int, k: int, n_experts: int, factor: float) -> int:
    c = int(sg * k * factor / n_experts)
    return max(8, (c + 7) // 8 * 8)  # 8-align for TPU layouts


def _slots_in_group(e_g: jnp.ndarray) -> jnp.ndarray:
    """e_g: (N,) expert ids -> slot of each entry within its expert
    (rank among same-expert entries, group-local).  O(N log N), no one-hot.
    """
    order = jnp.argsort(e_g, stable=True)
    e_sorted = e_g[order]
    first = jnp.searchsorted(e_sorted, e_sorted, side="left")
    pos_sorted = jnp.arange(e_g.shape[0], dtype=jnp.int32) - first
    return jnp.zeros_like(e_g).at[order].set(pos_sorted.astype(e_g.dtype))


def moe_ffn(p, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss).  Dispatches on the installed impl."""
    mesh = activation_mesh()
    if (_MOE_IMPL["impl"] == "a2a" and mesh is not None
            and "model" in mesh.axis_names
            and cfg.n_experts % mesh.shape["model"] == 0):
        return moe_ffn_a2a(p, x, cfg, mesh)
    return moe_ffn_gspmd(p, x, cfg)


def moe_ffn_gspmd(p, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Baseline dispatch: scatter/gather with compiler-chosen collectives."""
    dt = x.dtype
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    T = B * S
    Gsz = min(GROUP_SIZE, T)
    G = T // Gsz
    Cg = _group_capacity(Gsz, K, E, cfg.capacity_factor)
    xt = x.reshape(T, D)

    # --- router (fp32) ---
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)               # (T, K)
    if K > 1:  # deepseek renormalizes the selected gates
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- load-balance aux (Switch-style): E * sum(me * ce) ---
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (T * K))
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # --- group-local slot assignment (sort-based, O(T log Sg) memory O(T)) ---
    flat_e = expert_idx.reshape(G, Gsz * K).astype(jnp.int32)
    slot = jax.vmap(_slots_in_group)(flat_e)                      # (G, Sg*K)
    keep = slot < Cg
    flat_e = flat_e.reshape(-1)
    slot = slot.reshape(-1)
    keep = keep.reshape(-1)
    g_idx = jnp.repeat(jnp.arange(G, dtype=jnp.int32), Gsz * K)
    buf_slot = jnp.where(keep, g_idx * Cg + slot, 0)

    # --- scatter tokens into (E, G*Cg, D) expert buffers ---
    tok_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    xk = jnp.where(keep[:, None], xt[tok_idx], 0).astype(dt)
    buf = jnp.zeros((E, G * Cg, D), dt).at[flat_e, buf_slot].add(xk)
    buf = constrain(buf, "model", None, None)  # expert parallelism

    # --- batched expert FFN on the MXU ---
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt)))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    eout = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(dt))

    # --- gather back, gate, combine over K ---
    yk = eout[flat_e, buf_slot] * keep[:, None].astype(dt)
    yk = yk * gate_vals.reshape(-1)[:, None].astype(dt)
    out = jnp.zeros((T, D), dt).at[tok_idx].add(yk)

    # --- shared experts (dense, always-on) ---
    if cfg.n_shared_experts:
        sp = p["shared"]
        sg = jax.nn.silu(xt @ sp["w_gate"].astype(dt))
        out = out + (sg * (xt @ sp["w_up"].astype(dt))) @ sp["w_down"].astype(dt)

    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# EP-optimized dispatch: shard_map + explicit all-to-all (hillclimb 1)
#
# The gspmd scatter above has data-dependent indices, which GSPMD cannot turn
# into an all-to-all: it all-gathers the (T*K, D) dispatch tensor to every
# device (~2 x T*K*D bytes/device/layer).  Here the collective schedule is
# written by hand: each (data, model) shard routes 1/M of its local tokens,
# packs per-destination send buffers, and one all_to_all each way moves only
# the tokens themselves (T_loc*K*cf*D / M bytes per device per direction).


def moe_ffn_a2a(p, x, cfg: ModelConfig, mesh):
    from jax.sharding import PartitionSpec as P

    dt = x.dtype
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    M = mesh.shape["model"]
    ep = E // M
    b_ax = batch_axis(mesh)

    def _local(xb, router, wg, wu, wd_, shared):
        """One (data, model) shard.  xb: (B_loc, S, D) replicated over model;
        wg/wu/wd_: this shard's (ep, D, F) expert slice."""
        m_idx = jax.lax.axis_index("model")
        T_loc = xb.shape[0] * S
        xt = xb.reshape(T_loc, D)
        # my 1/M slice of the local tokens (model shards split routing work)
        Tm = T_loc // M
        xm = jax.lax.dynamic_slice_in_dim(xt, m_idx * Tm, Tm, axis=0)

        logits = xm.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)        # (Tm, K)
        if K > 1:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(0)
        ce_ = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
            1.0 / (Tm * K))
        aux = cfg.router_aux_coef * E * jnp.sum(me * ce_)
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))

        # pack per-destination send buffers: dst shard = expert // ep
        flat_e = expert_idx.reshape(-1).astype(jnp.int32)      # (Tm*K,)
        dst = flat_e // ep
        cap = _group_capacity(Tm, K, M, cfg.capacity_factor)
        slot = _slots_in_group(dst)                            # rank per dst
        keep = slot < cap
        slot = jnp.where(keep, slot, 0)
        tok = jnp.repeat(jnp.arange(Tm, dtype=jnp.int32), K)
        send_x = jnp.zeros((M, cap, D), dt).at[dst, slot].add(
            jnp.where(keep[:, None], xm[tok].astype(dt), 0))
        send_e = jnp.full((M, cap), E, jnp.int32).at[dst, slot].set(
            jnp.where(keep, flat_e, E))                        # E = invalid

        # all-to-all over the model axis: tokens travel to expert owners
        recv_x = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, "model", 0, 0, tiled=False)
        rx = recv_x.reshape(M * cap, D)
        re_ = recv_e.reshape(M * cap) - m_idx * ep             # local expert id
        valid = (re_ >= 0) & (re_ < ep)
        re_c = jnp.where(valid, re_, 0)

        # local capacity dispatch into (ep, C2, D)
        C2 = _group_capacity(M * cap, 1, ep, 1.25)
        slot2 = _slots_in_group(jnp.where(valid, re_c, ep).astype(jnp.int32))
        keep2 = valid & (slot2 < C2)
        slot2 = jnp.where(keep2, slot2, 0)
        buf = jnp.zeros((ep, C2, D), dt).at[re_c, slot2].add(
            jnp.where(keep2[:, None], rx, 0))
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt)))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
        eout = jnp.einsum("ecf,efd->ecd", g * u, wd_.astype(dt))
        y = eout[re_c, slot2] * keep2[:, None].astype(dt)      # (M*cap, D)

        # return trip + combine
        back = jax.lax.all_to_all(y.reshape(M, cap, D), "model", 0, 0,
                                  tiled=False)
        yk = back[dst, slot] * keep[:, None].astype(dt)
        yk = yk * gate_vals.reshape(-1)[:, None].astype(dt)
        out_m = jnp.zeros((Tm, D), dt).at[tok].add(yk)

        # reassemble the full local token set across model shards
        out_full = jax.lax.all_gather(out_m, "model", axis=0, tiled=True)

        if cfg.n_shared_experts:
            sg = jax.nn.silu(xt @ shared["w_gate"].astype(dt))
            out_full = out_full + (sg * (xt @ shared["w_up"].astype(dt))) \
                @ shared["w_down"].astype(dt)
        return out_full.reshape(xb.shape), aux

    shared = p.get("shared", {"w_gate": jnp.zeros((1, 1)),
                              "w_up": jnp.zeros((1, 1)),
                              "w_down": jnp.zeros((1, 1))})
    x_spec = P(b_ax if B % _bsize(mesh, b_ax) == 0 else None, None, None)
    ew = P("model", None, None)
    shared_spec = jax.tree.map(lambda _: P(None, None), shared)
    fn = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(x_spec, P(None, None), ew, ew, ew, shared_spec),
        out_specs=(x_spec, P()), check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], shared)


def _bsize(mesh, b_ax):
    import numpy as _np
    return (int(_np.prod([mesh.shape[a] for a in b_ax]))
            if isinstance(b_ax, tuple) else mesh.shape[b_ax])
