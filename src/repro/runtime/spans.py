"""Every name the program records in a profiler trace, in one table.

Four kinds of name, all read by `perfbench/` and listed in PERF.md:

  host spans   `jax.profiler.TraceAnnotation`s on the host thread that
               does the work; they land in the profiler's trace on the
               device trace's clock, and nest by time on their thread.
               With no profiler recording, each costs one inactive
               TraceMe (about a microsecond).
  scopes       `jax.named_scope`s inside traced programs: they become the
               HLO `op_name` metadata of every op beneath them (the
               `tf_op` stat of a TPU trace event) and change nothing that
               runs. Scopes never nest in one another, except
               `SPLITTER_ROUND` (the round scan: its loop and body) inside
               `SPLITTERS`, and `PROBE_SEARCH` inside `SPLITTER_ROUND`.
  kernels      the `name=` of each `pallas_call`: on a TPU the kernel's op
               takes this name in a trace. The bitonic network and the
               strided compare-exchange run both in the local sort and in
               the merge, so a kernel's name is its caller's family plus
               the kernel: `merge_sorted_runs_network`,
               `local_sort_strided`, ...
  counters     what `repro.sort.sort_records` returns beside the records
               (`RecordSortOutput.counters()` keys them by these names).

Nothing here switches anything on: there is no exporter and no knob.
"""
from __future__ import annotations

import jax

# -- host spans: the sort front door (repro.sort.api, repro.sort.driver) ---
FRONT_DOOR = "sort.front_door"    # a whole sort/sort_batched/argsort call
PLAN = "sort.plan"                # make_plan, with its key-range read
DETECT = "sort.detect"            # in PLAN: detection and its host sync
LAUNCH = "sort.launch"            # cache lookup + dispatch of the program
DECODE = "sort.decode"            # AdapterPlan.decode / decode_batched
OVERFLOW_SYNC = "sort.overflow_sync"   # the retry policy's counter read
GATHER = "sort.gather"            # masked_concat: the device-to-host copy

# -- host spans: the service's dispatch thread (repro.serve.service) -------
BATCH = "serve.batch"             # one _run_batch / _run_one; batch=, requests=
STACK = "serve.stack"             # stack the requests and pad the batch
H2D = "serve.h2d"                 # the batch's host-to-device copy
RESULTS = "serve.results"         # the per-request gathers

# -- host spans: the runtime (repro.runtime.compile_cache) -----------------
COMPILE = "runtime.compile"       # marks the end of each backend compile

# -- scopes inside the shard_map program (sort/partitioners.py and below) --
LOCAL_SORT = "local_sort"
SPLITTERS = "splitters"
SPLITTER_ROUND = "splitter_round"   # the round scan, in SPLITTERS
PROBE_SEARCH = "probe_search"       # records: lexicographic probe ranks
EXCHANGE = "exchange"               # send-buffer build and all-to-alls
MERGE = "merge"                     # merge of the received runs, cap_to
AUDIT = "audit"                     # sort/verify.py's fused audit
SCOPES = (LOCAL_SORT, SPLITTERS, SPLITTER_ROUND, EXCHANGE, MERGE, AUDIT)

#: The front door's duplicate detection is its own jitted program; its
#: module in a trace is `jit_detect_duplicates`.
DETECT_MODULE = "jit_detect_duplicates"
#: The front door's key range (min and max in one read) is another.
RANGE_MODULE = "jit_key_range"

# -- Pallas kernel names: <family>_<kernel> ---------------------------------
SORT_FAMILY = "local_sort"          # bitonic_sort.ops entry points
MERGE_FAMILY = "merge_sorted_runs"  # merge.ops entry points
NETWORK = "network"                 # bitonic_sort.kernel.network_pass
STRIDED = "strided"                 # merge.kernel.strided_compare_exchange
HISTOGRAM_KERNEL = "probe_ranks_histogram"   # histogram.kernel

# -- counters a record sort returns (repro.sort.RecordSortOutput) ----------
ROUNDS_USED = "rounds_used"       # splitter rounds run: SplitterStats
WIRE_BYTES = "wire_bytes"         # per chip: the padded all-to-all buffer
SENT_BYTES = "sent_bytes"         # per chip: bytes of real records in it
COUNTERS = (ROUNDS_USED, WIRE_BYTES, SENT_BYTES)

HOST_SPANS = (FRONT_DOOR, PLAN, DETECT, LAUNCH, DECODE, OVERFLOW_SYNC, GATHER,
              BATCH, STACK, H2D, RESULTS, COMPILE)


def span(name: str, **metadata):
    """A host span: `with span(BATCH, batch=3, requests=5): ...`."""
    return jax.profiler.TraceAnnotation(name, **metadata)


def scope(name: str):
    """A scope on every op traced inside it: `with scope(EXCHANGE): ...`."""
    return jax.named_scope(name)
