"""Share of the traced window in which no operation ran on the chip:
100 x (1 - union of the device op intervals / window), averaged over the
chips (bench/trace.py)."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())
