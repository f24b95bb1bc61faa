"""sort_roofline.records: the record launch's two sorts, the local sort
and the merge of the received runs, against their roofline. Least time:
two sorts, each one read and one write of the 100 bytes of every record a
chip holds, at peak HBM bandwidth. Device time: the top-level `sort` ops
of the HSS launch (every pass of both sorts; the sample sorts inside the
splitter loop are not counted), per call, the mean over the chips. The
two sorts are not told apart here."""
from harness import hss_launch, roofline

OPS = [r"^sort(\.\d+)?$"]
RECORD_BYTES = 100


def read(run):
    seconds = hss_launch.per_call_s(run, OPS, top_level=True)
    if seconds is None:
        return None
    n_bytes = 2 * roofline.sort_bytes(run.data["records_per_chip"],
                                      RECORD_BYTES)
    return roofline.share_pct(n_bytes, seconds, run.peaks)
