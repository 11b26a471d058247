"""Device milliseconds of the quasi-Newton update (``qn_update``) inside the
forward solve (``deq_solve``), per forward iteration of the traced training
window."""

from chipbench.metrics_lib import train_scopes


def read(run):
    return train_scopes(run).get("qn_update_ms")
