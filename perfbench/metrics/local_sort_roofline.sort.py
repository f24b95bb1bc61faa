"""local_sort_roofline.sort: each chip's local sort in the HSS launch
against its roofline. Least time: one read and one write of the chip's
keys at peak HBM bandwidth. Device time: the launch's sort ops outside
the splitter loop, per call, the mean over the chips (2^27 keys exceed
the bitonic kernel's ceiling, so XLA sorts). The sample sorts inside
each splitter round and the front door's detection sort (`jit_sort`)
are not counted."""
from harness import hss_launch, roofline

OPS = [r"^sort(\.\d+)?$"]


def read(run):
    seconds = hss_launch.per_call_s(run, OPS, top_level=True)
    if seconds is None:
        return None
    return roofline.share_pct(
        roofline.sort_bytes(run.data["keys_per_chip"]), seconds, run.peaks)
