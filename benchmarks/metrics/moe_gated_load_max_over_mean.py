# the standing reader: the mean of the window's events' max_over_mean
from benchmarks.metrics.moe_load_max_over_mean import read  # noqa: F401
