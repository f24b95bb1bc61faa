"""device_idle.records: share of the traced window in which no operation
ran on a chip, 1 - (union of op intervals) / window, as the mean over the
cell's chips. Each chip's own share is printed on an earlier line."""
from harness.trace import mean_idle_pct


def read(run):
    return mean_idle_pct(run.trace)
