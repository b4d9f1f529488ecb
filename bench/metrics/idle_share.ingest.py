"""Share of the traced window in which no operation ran on the chip."""
from bench.metrics_lib import idle_share

read = idle_share
