"""Device milliseconds of the block group (``deq_block``) inside the forward
solve (``deq_solve``), per forward iteration of the traced training window."""

from chipbench.metrics_lib import train_scopes


def read(run):
    return train_scopes(run).get("block_eval_ms")
