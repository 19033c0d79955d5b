"""Sophia's amortized Hessian-refresh overhead, the paper's measure: from
the host clock of each blocked step of the window (dispatch to
``block_until_ready``), (mean refresh step / mean plain step - 1) / k x 100.
Nothing to read in a cell without refresh steps."""
LAYER = "hessian refresh"
UNIT = "%"
BETTER = "lower"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def read(ctx):
    recs = ctx["records"]
    ref = [r["dispatch"] + r["wait"] for r in recs if r["refresh"]]
    plain = [r["dispatch"] + r["wait"] for r in recs if not r["refresh"]]
    if not ref or not plain:
        return None
    mean = lambda xs: sum(xs) / len(xs)  # noqa: E731
    return 100.0 * (mean(ref) / mean(plain) - 1.0) / ctx["k"]
