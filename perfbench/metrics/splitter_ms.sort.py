"""splitter_ms.sort: device time per `sort()` call of the HSS splitter
loop (the launch's top-level `while`: every round's sampling, sample
sort, histogram kernel and collectives), the mean over the chips."""
from harness import hss_launch

OPS = [r"^while(\.\d+)?$"]


def read(run):
    return hss_launch.per_call_ms(run, OPS, top_level=True)
