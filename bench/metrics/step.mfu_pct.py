"""Model FLOP/s utilization of the whole train step: model operations per
token (bench/work/model.py: forward and backward, no recomputation, the
refresh's extra work not counted) x tokens per second of the window, over
chips x peak bf16 FLOP/s (bench/peaks.json)."""
from bench import common

LAYER = "train step"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def read(ctx):
    if ctx["peaks"] is None:
        return None
    m = ctx["spec"]["config"]["model"]
    seq = ctx["spec"]["traffic"]["seq_len"]
    fpt = common.work("model").flops_per_token(m, seq)
    rate = ctx["tokens"] / ctx["window_s"]
    return 100.0 * fpt * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
