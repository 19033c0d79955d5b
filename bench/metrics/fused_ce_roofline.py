"""The fused cross-entropy's share of its roofline (kernels/fused_ce.py:
forward, sampling forward and backward kernels): the least time of the
window's calls by bench/work/fused_ce.py, over the kernels' summed device
time.  Each plain step runs the forward and backward on all rows; a refresh
step adds the sampling forward and a backward on the sub-batch's rows."""
from bench import common, roofline

LAYER = "fused ce"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s"
SOURCE = "device_trace"
KERNELS = ("_ce_fwd_kernel", "_ce_fwd_sample_kernel", "_ce_bwd_fused_kernel",
           "_ce_bwd_dh_kernel", "_ce_bwd_dw_kernel")


def read(ctx):
    spec = ctx["spec"]
    m, tr = spec["config"]["model"], spec["traffic"]
    w = common.work("fused_ce")
    S, D = tr["seq_len"], m["d_model"]
    V = -(-m["vocab_size"] // 128) * 128
    N = tr["global_batch"] * S // ctx["chips"]
    Ns = spec["traffic"]["trainer"].get("hess_subbatch", 0) * S \
        // ctx["chips"]
    pk = ctx["peaks"]

    def per_step(refresh):
        t = (roofline.least_s(*w.forward(N, D, V), pk)
             + roofline.least_s(*w.backward(N, D, V), pk))
        if refresh:
            t += (roofline.least_s(*w.forward_sample(Ns, D, V), pk)
                  + roofline.least_s(*w.backward(Ns, D, V), pk))
        return t

    return roofline.share(ctx, KERNELS, per_step)
