"""Flash attention's share of its roofline (kernels/flash_attention.py:
forward, dq and dkv kernels): the least time of the window's calls by
bench/work/flash_attention.py, over the kernels' summed device time.
Each plain step runs the forward and backward once per layer on the whole
batch; a refresh step runs them again on the estimator's sub-batch."""
from bench import common, roofline

LAYER = "flash attention"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s"
SOURCE = "device_trace"
KERNELS = ("_fwd_kernel", "_dq_kernel", "_dkv_kernel")


def read(ctx):
    spec = ctx["spec"]
    m, tr = spec["config"]["model"], spec["traffic"]
    w = common.work("flash_attention")
    D, H = m["d_model"], m["n_heads"]
    hd, Hkv, L, S = m.get("head_dim") or D // H, m["n_kv_heads"], \
        m["n_layers"], tr["seq_len"]
    B = tr["global_batch"] // ctx["chips"]
    sub = spec["traffic"]["trainer"].get("hess_subbatch", 0) // ctx["chips"]

    def layer(b):
        return sum(roofline.least_s(*f(b, H, Hkv, S, hd), ctx["peaks"])
                   for f in (w.forward, w.backward))

    def per_step(refresh):
        return L * (layer(B) + (layer(sub) if refresh else 0.0))

    return roofline.share(ctx, KERNELS, per_step)
