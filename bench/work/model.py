"""Model operations of a dense decoder LM, from its sizes.

``flops_per_token``: the operations that the forward and backward passes
require per trained token, counting no recomputation: 6 per weight of every
matrix product (the layers' projections and the LM head; 2 forward, 4
backward) plus causal attention, 6 x L x S x H x hd (Q K^T and P V take
4 x S x H x hd per token forward over the whole sequence, the causal mask
halves that, and the backward takes twice the forward).  This is the
PaLM appendix-B count (Chowdhery et al., 2022) with the causal halving.
Embedding lookups, norms, biases and the softmax are not counted.
"""


def dims(m):
    D, H = m["d_model"], m["n_heads"]
    hd = m.get("head_dim") or D // H
    return D, H, m["n_kv_heads"], hd, m["d_ff"], m["n_layers"]


def matmul_weights(m) -> int:
    """Weights that enter a matrix product once per token."""
    D, H, Hkv, hd, F, L = dims(m)
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    mlp = (3 if m["activation"] in ("swiglu", "geglu") else 2) * D * F
    return L * (attn + mlp) + D * m["vocab_size"]


def flops_per_token(m, seq_len: int) -> float:
    D, H, Hkv, hd, F, L = dims(m)
    return 6.0 * matmul_weights(m) + 6.0 * L * seq_len * H * hd


def n_params(m) -> int:
    """Every parameter of the dense block as the program stores it."""
    D, H, Hkv, hd, F, L = dims(m)
    vp = -(-m["vocab_size"] // 128) * 128
    emb = vp * D * (1 if m["tie_embeddings"] else 2)
    if m["learned_pos"]:
        emb += m["max_position_embeddings"] * D
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    mlp = 2 * D * F + F + D
    norms = 2 * 2 * D
    return emb + L * (attn + mlp + norms) + 2 * D
