"""Work of the flash attention kernels (``kernels/flash_attention.py``),
causal, for one call over a (B, S) batch of one layer.

Counted from shapes, as the algorithm needs it: each operand read once and
each output written once, whatever tiles a blocking streams; causal masking
halves the products; recomputation in the backward is not counted.

* forward: Q K^T and P V, 2 x 2 x B x H x S^2 x hd, halved; reads q, k, v,
  writes o (element bytes ``e``) and the fp32 log-sum-exp per row;
* backward (dq and dkv kernels together): dV = P^T dO, dP = dO V^T,
  dQ = dS K, dK = dS^T Q, 4 x 2 x B x H x S^2 x hd, halved; reads q, k, v,
  dO (``e``) and the fp32 log-sum-exp and delta per row, writes dq, dk, dv.
"""


def forward(B, H, Hkv, S, hd, e=2):
    flops = 2.0 * B * H * S * S * hd
    nbytes = (2 * B * S * H * hd + 2 * B * S * Hkv * hd) * e + B * H * S * 4
    return flops, nbytes


def backward(B, H, Hkv, S, hd, e=2):
    flops = 4.0 * B * H * S * S * hd
    nbytes = ((3 * B * S * H * hd + 4 * B * S * Hkv * hd) * e
              + 2 * B * H * S * 4)
    return flops, nbytes
