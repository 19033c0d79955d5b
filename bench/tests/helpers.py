"""Small-size cells for the self-tests."""
import copy

# a cell's shapes cut to what a test run holds; every other setting, and
# the cell's limits, stay as the cell states them
TINY_MODEL = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                  d_ff=512, vocab_size=512, max_position_embeddings=64)
TINY_TRAFFIC = dict(seq_len=64, global_batch=4)
TINY_SUBBATCH = 2


def tiny_spec(cell):
    from bench import common
    spec = copy.deepcopy(common.cell_spec(cell))
    spec["config"]["model"].update(TINY_MODEL)
    spec["traffic"].update(TINY_TRAFFIC)
    spec["traffic"]["trainer"]["hess_subbatch"] = TINY_SUBBATCH
    return spec
