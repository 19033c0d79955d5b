"""Seconds to lower and compile the cell's train step
(``launch/train.compile_train_step``'s jitted step, ``.lower().compile()``),
on the host clock; with a warm persistent cache this is the cache read."""
LAYER = "launch"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(ctx):
    return ctx["compile_s"]
