"""collective_ms.records: device time per `sort_records()` call of the
collectives in the HSS launch (each splitter round's all-gather and
all-reduce, the exchange's all-to-all of whole records), the mean over
the chips."""
from harness import hss_launch

OPS = [r"^all[-_]to[-_]all", r"^ragged[-_]all[-_]to[-_]all", r"^all[-_]gather",
       r"^all[-_]reduce", r"^psum", r"^reduce[-_]scatter",
       r"^collective[-_]permute"]


def read(run):
    return hss_launch.per_call_ms(run, OPS)
