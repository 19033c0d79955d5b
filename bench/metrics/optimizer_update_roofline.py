"""The optimizer engine's update sweep's share of its roofline
(kernels/sophia_update.py via core/engine.py; one call per step over the
flat fp32 shard): the HBM floor of the bytes it must move
(bench/work/optimizer_update.py) over the kernels' summed device time."""
from bench import common, roofline

LAYER = "optimizer engine"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s"
SOURCE = "device_trace"
KERNELS = ("_sophia_kernel", "_sophia_refresh_kernel", "_adamw_kernel")


def read(ctx):
    spec = ctx["spec"]
    n = common.work("model").n_params(spec["config"]["model"]) \
        / ctx["chips"]
    opt = spec["traffic"]["trainer"]["optimizer"]
    w = common.work("optimizer_update")

    def per_step(refresh):
        return roofline.least_s(*w.update(n, opt, refresh), ctx["peaks"])

    return roofline.share(ctx, KERNELS, per_step)
