"""What the program names in a trace: its host spans, its scopes, and its
kernels (`repro.runtime.spans` holds every name).

Metric readers call the first half with the run's `Trace`:

    table()                    the program's span table, or None where the
                               program predates it (then a reader of a
                               program span returns None)
    spans_in_window(trace, n)  [(start ns, duration ns)] of the host spans
                               named n that lie inside the window
    ops_started(trace, device, op_regex, module_regex)
                               how many such device ops began in the window

The second half reads a profiler file itself, keeping each device op's
scope, the `op_name` path of the HLO metadata (`jit(per_shard)/shard_map/
exchange/gather`). On a TPU it is the `tf_op` stat of the op's event
metadata, which `jax.profiler.ProfileData` leaves out, so `device_scopes`
reads it from the raw `XSpace` keyed by (program id, HLO text); an
event's own `tf_op` or `long_name` stat serves where a backend gives one:

    load_scoped(path)          {"devices": {device: [[op, module, start ns,
                               duration ns, scope], ...]}, "host", "window"}
    scope_seconds(scoped, device, scope_regex, module_regex=None)
                               seconds of the window in which an op whose
                               scope matches ran (the union of their
                               intervals, so a loop and its body count once)
    program_label(host, start, end, label, names)
                               `<innermost program span> > <label>` where a
                               span named in `names` (the table's host
                               spans) holds the whole gap

    python3 perfbench/harness/scopes.py <file.xplane.pb> [n_devices]

prints each chip's device time by top-level scope and module, the ops'
scopes, and the ten longest idle gaps labelled by program span.
"""
from __future__ import annotations

import bisect
import collections
import importlib
import re
import sys
import warnings

if __package__ in (None, ""):   # run as a script: the harness is a package
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from harness import trace as tr  # noqa: E402


def table():
    """`repro.runtime.spans`, or None where the program has no such table."""
    try:
        return importlib.import_module("repro.runtime.spans")
    except ImportError:
        return None


def spans_in_window(trace, name: str) -> list:
    """[(start ns, duration ns)] of the host spans called `name` that lie
    wholly inside the trace's window."""
    lo, hi = trace.window
    return [(s, d) for n, s, d in trace.host
            if n == name and s >= lo and s + d <= hi]


def ops_started(trace, device, op_regex: str, module_regex: str) -> int:
    """How many of the device's ops that match both started in the window."""
    lo, hi = trace.window
    op, mod = re.compile(op_regex), re.compile(module_regex)
    return sum(1 for name, module, s, _ in trace.devices[device]
               if lo <= s < hi and op.search(name) and mod.search(module))


# -- reading a profiler file with scopes ------------------------------------

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PROGRAM = re.compile(r"\((\d+)\)$")
_U64 = (1 << 64) - 1


def op_scope(stats: dict) -> str:
    """The op's `op_name` path: the `tf_op` stat, else the `op_name=` of
    the HLO text in `long_name`; empty where neither is there."""
    tf_op = stats.get("tf_op")
    if tf_op:
        return str(tf_op)
    m = _OP_NAME.search(str(stats.get("long_name", "")))
    return m.group(1) if m else ""


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as an int,
    a length-delimited field as a memoryview; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} in an XSpace")
        yield key >> 3, value


def device_scopes(path: str) -> dict:
    """{device: {(program id, HLO text): tf_op, HLO text: tf_op}} from the
    raw `XSpace` (the text alone where every program agrees on it):
    a TPU keeps each op's `tf_op` in the op's event metadata, which
    `jax.profiler.ProfileData` does not expose. (Field numbers from
    tsl/profiler/protobuf/xplane.proto.)"""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pnum, value in _fields(plane):
            if pnum == 2:
                name = bytes(value).decode()
            elif pnum == 4:
                metas.append(value)
            elif pnum == 5:   # map<int64, XStatMetadata> entry
                stat = dict(_fields(dict(_fields(value))[2]))
                stat_names[stat.get(1, 0)] = bytes(stat.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        table = out.setdefault(name[len("/device:"):], {})
        for entry in metas:   # map<int64, XEventMetadata> entry
            meta = dict(_fields(entry)).get(2)
            if meta is None:
                continue
            text, stats = "", {}
            for mnum, value in _fields(meta):
                if mnum == 2:
                    text = bytes(value).decode(errors="replace")
                elif mnum == 5:
                    st = dict(_fields(value))
                    key = stat_names.get(st.get(1), "")
                    if 5 in st:
                        stats[key] = bytes(st[5]).decode(errors="replace")
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
                    elif 4 in st or 3 in st:
                        stats[key] = st.get(4, st.get(3))
            if stats.get("tf_op"):
                pid = int(stats.get("program_id", 0)) & _U64
                table[(pid, text)] = scope = str(stats["tf_op"])
                # one metadata entry can serve every program holding the
                # same HLO text: keep the text alone where it is unambiguous
                table[text] = scope if table.get(text, scope) == scope else ""
    return out


def load_scoped(path: str, n_devices: int | None = None) -> dict:
    """Read an `.xplane.pb` into the plain form of `harness.trace.Trace`,
    each device op with its scope as a fifth field."""
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        planes = list(ProfileData.from_file(path).planes)
        scoped = device_scopes(path)
        devices = {}
        for plane in planes:
            if not plane.name.startswith("/device:"):
                continue
            lines = {line.name: line for line in plane.lines}
            if tr.OPS_LINE not in lines:
                continue
            module_of = tr._module_lookup(plane)
            known = scoped.get(plane.name[len("/device:"):], {})
            ops = []
            for ev in lines[tr.OPS_LINE].events:
                st = tr._stats(ev)
                module = str(st.get("hlo_module") or module_of(ev.start_ns))
                pid = _PROGRAM.search(module)
                scope = (op_scope(st) or known.get(
                    (int(pid.group(1)) & _U64 if pid else 0, ev.name))
                    or known.get(ev.name, ""))
                ops.append([tr.op_name(ev.name), tr.module_family(module),
                            ev.start_ns, ev.duration_ns, scope])
            devices[plane.name[len("/device:"):]] = ops
        host, window, cpu_ops = tr._host_spans(planes, want_ops=not devices)
    if not devices and cpu_ops:   # a CPU run: the scope is not recorded
        devices = {"CPU:0": [op + [""] for op in cpu_ops]}
    if n_devices is not None:
        keep = sorted(devices, key=tr._device_index)[:n_devices]
        devices = {d: devices[d] for d in keep}
    if window is None:
        raise ValueError(f"{path}: no {tr.WINDOW_SPAN!r} span in the trace")
    return {"devices": devices, "host": host, "window": window}


def plain(scoped: dict) -> "tr.Trace":
    """The same trace without scopes, for `harness.trace.Trace`."""
    return tr.Trace({d: [op[:4] for op in ops]
                     for d, ops in scoped["devices"].items()},
                    scoped["host"], scoped["window"])


def _union_s(intervals) -> float:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def scope_seconds(scoped: dict, device, scope_regex: str,
                  module_regex: str | None = None) -> float:
    """Seconds of the window in which the device ran an op whose scope
    matches `scope_regex` (and whose module matches `module_regex`)."""
    lo, hi = scoped["window"]
    want = re.compile(scope_regex)
    mod = re.compile(module_regex) if module_regex else None
    spans = []
    for name, module, start, dur, scope in scoped["devices"][device]:
        if not want.search(scope) or (mod and not mod.search(module or "")):
            continue
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            spans.append((s, e))
    return _union_s(spans)


def top_scope(scope: str, names) -> str:
    """The first of `names` on the op's scope path, else ""."""
    for part in scope.split("/"):
        if part in names:
            return part
    return ""


def program_label(host: list, start, end, label: str, names) -> str:
    """`<innermost span named in names> > <label>` where such a span holds
    all of [start, end); `label` unchanged where none does."""
    holder = None
    starts = [h[1] for h in host]
    for name, s, dur in host[:bisect.bisect_right(starts, start)]:
        if name in names and s + dur >= end and (
                holder is None or dur < holder[1]):
            holder = (name, dur)
    return f"{holder[0]} > {label}" if holder else label


# -- the command-line view --------------------------------------------------

def report(scoped: dict, top: int = 10) -> None:
    names = table()
    scope_names = names.SCOPES if names else ()
    span_names = set(names.HOST_SPANS) if names else set()
    t = plain(scoped)
    for d, ops in sorted(scoped["devices"].items(),
                         key=lambda kv: tr._device_index(kv[0])):
        busy = t.busy_s(d)
        by = collections.Counter()
        for name, module, start, dur, scope in ops:
            if tr.op_family(name) in tr.CONTROL_FLOW:
                continue
            key = (module, top_scope(scope, scope_names) or "-")
            by[key] += dur / 1e9
        print(f"{d}: busy {busy:.6f} s of {t.window_s:.6f} s")
        for (module, scope), secs in by.most_common(top):
            print(f"  {module:32s} {scope:16s} {secs:.6f} s")
        fams = collections.Counter()
        for name, _, _, dur, scope in ops:
            fams[(tr.op_family(name), top_scope(scope, scope_names))] += dur
        for (fam, scope), ns in fams.most_common(top):
            print(f"    op {fam:36s} scope {scope or '-':14s} {ns / 1e9:.6f} s")
    gaps = []
    for d in t.devices:
        gaps += [(e - s, s, e) for s, e in t.idle_gaps(d)]
    print("longest idle gaps:")
    for dur, s, e in sorted(gaps, reverse=True)[:top]:
        label = program_label(t.host, s, e, t.host_label(s, e), span_names)
        print(f"  {dur / 1e9:.6f} s  {label}")


if __name__ == "__main__":
    report(load_scoped(sys.argv[1],
                       int(sys.argv[2]) if len(sys.argv) > 2 else None))
