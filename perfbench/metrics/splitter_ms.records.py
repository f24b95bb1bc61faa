"""splitter_ms.records: device time per `sort_records()` call of the HSS
splitter loop over key tuples (the launch's top-level `while`: every
round's sampling, sample sort, probe search and collectives), the mean
over the chips."""
from harness import hss_launch

OPS = [r"^while(\.\d+)?$"]


def read(run):
    return hss_launch.per_call_ms(run, OPS, top_level=True)
