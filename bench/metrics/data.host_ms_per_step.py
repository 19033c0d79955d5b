"""Host milliseconds per window step spent making and placing the batch:
the benchmark's spans around ``batch_at`` (data/pipeline.py) and
``_put_tree`` (launch/train.py), summed over the window's steps and divided
by the steps.  The device waits for this while the host does it."""
LAYER = "data"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def read(ctx):
    recs = ctx["records"]
    return 1e3 * sum(r["batch_at"] + r["put_tree"] for r in recs) / len(recs)
