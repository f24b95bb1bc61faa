"""Set-up shared by the benchmark's tests.

`rehearsal.make_root` moves the real metrics onto its two test cells by
name: `hss_sort.*` onto `t.hss`, every other cell onto `t.serve`. The
record cell `hss_records.gensort_p4` is neither; its metrics read a
record sort's data, so they are left off both test cells here, and
`test_perfbench_records_faults.py` gives them a test cell of their own."""
import rehearsal

_retarget = rehearsal._retarget


def _without_record_cells(metric: dict) -> dict:
    m = dict(metric)
    if "workloads" in m:
        m["workloads"] = [w for w in m["workloads"]
                          if not w.startswith("hss_records.")]
    return _retarget(m)


rehearsal._retarget = _without_record_cells
