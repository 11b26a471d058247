"""Device time of the forward solve (the program's ``deq_solve`` scope) in %
of the traced training window's busy time."""

from chipbench.metrics_lib import train_scopes


def read(run):
    return train_scopes(run).get("solve_share")
