"""merge_roofline.sort: the post-exchange merge against its roofline.
Least time: one read and one write of the keys a chip keeps after the
exchange (its share, n / p) at peak HBM bandwidth. Device time: the
Pallas merge kernels (launched as `merge_sorted_runs_batched`) and the
XLA flip of odd runs before them (`rev`), per call, the mean over the
chips. The dense exchange pads each pair's run to `pair_factor` times
its share, so streaming padding shows as a lower share."""
from harness import hss_launch, roofline

OPS = [r"^merge_sorted_runs", r"^rev(\.\d+)?$"]


def read(run):
    seconds = hss_launch.per_call_s(run, OPS)
    if seconds is None:
        return None
    return roofline.share_pct(
        roofline.sort_bytes(run.data["keys_per_chip"]), seconds, run.peaks)
