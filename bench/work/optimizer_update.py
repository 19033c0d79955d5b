"""Work of the optimizer engine's update sweep (``kernels/sophia_update.py``)
over n fp32 parameters: bound by HBM bytes, so only the bytes matter.

Each element is read and written once per call, as the algorithm needs:

* Sophia, plain step: p, m, h, g in; p, m out: 24 bytes;
* Sophia, refresh step: p, m, h, g and the estimate in; p, m, h out:
  32 bytes;
* AdamW: p, m, v, g in; p, m, v out: 28 bytes.

The operations (about ten per element) are counted for completeness.
"""

BYTES = {("sophia_g", False): 24, ("sophia_g", True): 32,
         ("adamw", False): 28}
FLOPS = 10


def update(n, optimizer, refresh):
    return float(FLOPS * n), BYTES[(optimizer, refresh)] * n
