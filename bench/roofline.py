"""Roofline arithmetic shared by the kernel metric readers."""


def least_s(flops, nbytes, peaks) -> float:
    """The least time the chip could take: the larger of the operations
    over peak bf16 FLOP/s and the bytes over peak HBM bandwidth."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def step_counts(ctx):
    """(plain steps, refresh steps) in the measured window."""
    n_ref = sum(1 for r in ctx["records"] if r["refresh"])
    return len(ctx["records"]) - n_ref, n_ref


def share(ctx, kernels, least_per_step) -> float | None:
    """100 x (least time of the window's calls) / (their device time), or
    None where the trace holds none of ``kernels``.  ``least_per_step(
    refresh)`` gives one step's least time on one chip."""
    tr = ctx["trace"]
    if tr is None:
        return None
    t = tr.op_s(kernels)
    if t <= 0:
        return None
    plain, refresh = step_counts(ctx)
    least = plain * least_per_step(False) + refresh * least_per_step(True)
    return 100.0 * least / t
