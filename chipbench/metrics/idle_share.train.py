"""Device idle share of the traced training window: 1 - (union of device
operation intervals) / window, in percent."""

from chipbench.metrics_lib import idle_share as read  # noqa: F401
