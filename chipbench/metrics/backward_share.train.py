"""Device time of the backward (the program's ``implicit_backward`` scope) in
% of the traced training window's busy time."""

from chipbench.metrics_lib import train_scopes


def read(run):
    return train_scopes(run).get("backward_share")
