"""Work of the fused cross-entropy kernels (``kernels/fused_ce.py``) over
N rows of hidden size D and a vocabulary of V columns.

Counted from shapes: each operand read once, each output written once,
whatever tiles a blocking streams; the logits are never stored, and their
recomputation in the backward is not counted.  Hidden rows are ``eh``
bytes, the LM-head matrix ``ew`` bytes (fp32 weights), row scalars 4.

* forward: logits 2 N D V; reads h, W, labels; writes lse and the label
  logit;
* forward with sampling (GNB refresh): the same products; reads h, W;
  writes lse, the sampled logit and the sampled label;
* backward: dh = dZ W and dW = dZ^T h, 2 x 2 N D V; reads h, W, labels,
  lse and the row scales; writes dh and dW (fp32).
"""


def forward(N, D, V, eh=2, ew=4):
    return 2.0 * N * D * V, N * D * eh + V * D * ew + 3 * N * 4


def forward_sample(N, D, V, eh=2, ew=4):
    return 2.0 * N * D * V, N * D * eh + V * D * ew + 3 * N * 4


def backward(N, D, V, eh=2, ew=4):
    return (4.0 * N * D * V,
            2 * N * D * eh + V * D * ew + 3 * N * 4 + V * D * 4)
