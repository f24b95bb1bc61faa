"""Profiler traces of the measured window, reduced to what metrics read.

A `--trace 1` run wraps its window in `Tracer`, which records a JAX
profiler trace into a temporary directory and reads it back with
`jax.profiler.ProfileData` into a `Trace`:

    devices   {device: [[op name, HLO module, start ns, duration ns], ...]}
              the operations each chip ran (on the CPU, where a rehearsal
              runs, the XLA CPU client's ops stand in for a device)
    host      [[name, start ns, duration ns], ...] host spans: the
              benchmark's own annotations and the runtime's
    window    [start ns, end ns] of the `perfbench.window` annotation

Metric readers match operations by name and module with the regular
expressions their own files list. The plain dict form (`as_dict`,
`from_dict`) is what the recorded test trace keeps.

    python3 perfbench/harness/trace.py <file.xplane.pb>   # what a trace holds
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re
import shutil
import sys
import tempfile
import time
import warnings

WINDOW_SPAN = "perfbench.window"
OPS_LINE = "XLA Ops"
CONTROL_FLOW = ("while", "conditional")
MODULES_LINE = "XLA Modules"


class Trace:
    def __init__(self, devices: dict, host: list, window: list):
        # by start, and an enclosing op before the ops it encloses
        self.devices = {d: sorted(ops, key=lambda e: (e[2], -e[3]))
                        for d, ops in devices.items()}
        self.host = sorted(host, key=lambda e: e[1])
        self._host_starts = [h[1] for h in self.host]
        self.window = window

    # -- plain form -------------------------------------------------------

    def as_dict(self) -> dict:
        return {"devices": self.devices, "host": self.host,
                "window": self.window}

    @classmethod
    def from_dict(cls, d: dict) -> "Trace":
        return cls(d["devices"], d["host"], d["window"])

    # -- reductions -------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _clipped(self, device, top_level=False):
        """The device's ops clipped to the window; with `top_level`, only
        those that no control-flow op (`while`, `conditional`) encloses,
        so a loop counts once and not again through its body."""
        lo, hi = self.window
        open_until = -1
        for name, module, start, dur in self.devices[device]:
            nested = start < open_until
            if op_family(name) in CONTROL_FLOW and not nested:
                open_until = start + dur
            if top_level and nested:
                continue
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                yield name, module, s, e

    def busy_intervals(self, device) -> list:
        """Union of the device's op intervals inside the window."""
        merged = []
        for _, _, s, e in self._clipped(device):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    def busy_s(self, device) -> float:
        return sum(e - s for s, e in self.busy_intervals(device)) / 1e9

    def idle_share(self, device) -> float:
        return 1.0 - self.busy_s(device) / self.window_s

    def op_seconds(self, device, op_patterns, module_pattern=None,
                   top_level=False) -> float:
        """Seconds of the device's ops whose name matches one of
        `op_patterns` (and whose module matches `module_pattern`; with
        `top_level`, outside any loop or branch)."""
        ops = [re.compile(p) for p in op_patterns]
        mod = re.compile(module_pattern) if module_pattern else None
        total = 0.0
        for name, module, s, e in self._clipped(device, top_level):
            if any(p.search(name) for p in ops) and (
                    mod is None or mod.search(module or "")):
                total += e - s
        return total / 1e9

    def idle_gaps(self, device) -> list:
        """[(start ns, end ns)] of the window the device spent idle."""
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy_intervals(device):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def host_label(self, start, end) -> str:
        """What the host was doing over [start, end): of the host spans
        that lie partly inside it, the name with the most time there;
        where none does, the innermost span that holds all of it."""
        inside = collections.Counter()
        holder = None
        # spans are sorted by start; any overlapping one starts before `end`
        last = bisect.bisect_left(self._host_starts, end)
        for name, s, dur in self.host[:last]:
            overlap = min(end, s + dur) - max(start, s)
            if overlap <= 0 or name == WINDOW_SPAN:
                continue
            if s <= start and s + dur >= end:
                if holder is None or dur < holder[1]:
                    holder = (name, dur)
            else:
                inside[name] += overlap
        if inside:
            return inside.most_common(1)[0][0]
        return holder[0] if holder else "no host span"

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time (seconds per chip, summed by
        name with numeric suffixes dropped; loops and branches count
        through the ops inside them) and the longest idle gaps, each
        labelled by what the host was doing."""
        per_op = collections.Counter()
        for d in self.devices:
            for name, _, s, e in self._clipped(d):
                if op_family(name) not in CONTROL_FLOW:
                    per_op[op_family(name)] += (e - s) / 1e9
        n = max(len(self.devices), 1)
        ops = [[k, v / n] for k, v in per_op.most_common(top)]
        gaps = []
        for d in self.devices:
            for s, e in self.idle_gaps(d):
                gaps.append((e - s, s, e))
        gaps.sort(reverse=True)
        idle = [[self.host_label(s, e), dur / 1e9]
                for dur, s, e in gaps[:top]]
        return {"device_ops": ops, "idle_gaps": idle}


def mean_idle_pct(trace) -> float | None:
    """1 - (union of op intervals) / window, in percent, the mean over the
    trace's chips; None without a trace or with no device in it."""
    if trace is None or not trace.devices:
        return None
    shares = [trace.idle_share(d) for d in trace.devices]
    return 100.0 * sum(shares) / len(shares)


def op_family(name: str) -> str:
    """`fusion.123` -> `fusion`, `all-to-all.4` -> `all-to-all`."""
    return re.sub(r"(\.\d+)+$", "", name)


def op_name(text: str) -> str:
    """The instruction name of an op event, which on a TPU is the HLO
    text: `%sort.4 = (s32[8]...) sort(...)` -> `sort.4`."""
    m = re.match(r"%?([^\s=(]+)", text)
    return m.group(1) if m else text


def module_family(name: str) -> str:
    """`jit_sort(7562706252836517394)` -> `jit_sort`."""
    return re.sub(r"\(\d+\)$", "", name)


# -- reading a profiler file ----------------------------------------------

def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _module_lookup(plane):
    """Module of an op by the module event that encloses it in time."""
    for line in plane.lines:
        if line.name == MODULES_LINE:
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events)
            starts = [m[0] for m in mods]

            def find(t):
                i = bisect.bisect_right(starts, t) - 1
                return mods[i][2] if i >= 0 and t < mods[i][1] else ""
            return find
    return lambda t: ""


def load_xspace(path: str, n_devices: int | None = None) -> Trace:
    """Read an `.xplane.pb` into a Trace. Device planes are
    `/device:<kind>:<i>` with an `XLA Ops` line; where none exists (a CPU
    rehearsal) the host's XLA client threads, whose events carry an
    `hlo_module`, stand in as one device."""
    from jax.profiler import ProfileData

    with warnings.catch_warnings():   # jaxlib's stats type warns on repr
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(path)
        planes = list(data.planes)   # an iterator; both passes read it
        devices = _device_ops(planes)
        host, window, cpu_ops = _host_spans(planes, want_ops=not devices)
    devices = devices or ({"CPU:0": cpu_ops} if cpu_ops else {})
    if n_devices is not None:
        keep = sorted(devices, key=_device_index)[:n_devices]
        devices = {d: devices[d] for d in keep}
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    return Trace(devices, host, window)


def _device_ops(planes) -> dict:
    devices = {}
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        module_of = _module_lookup(plane)
        ops = []
        for ev in lines[OPS_LINE].events:
            module = _stats(ev).get("hlo_module") or module_of(ev.start_ns)
            ops.append([op_name(ev.name), module_family(str(module)),
                        ev.start_ns, ev.duration_ns])
        devices[plane.name[len("/device:"):]] = ops
    return devices


def _host_spans(planes, want_ops: bool):
    """Host spans, the window, and (where `want_ops`) the XLA CPU
    client's op events."""
    host, window, cpu_ops = [], None, []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if want_ops:
                    st = _stats(ev)
                    if "hlo_module" in st and "hlo_op" in st:
                        cpu_ops.append([op_name(ev.name),
                                        module_family(str(st["hlo_module"])),
                                        ev.start_ns, ev.duration_ns])
                        continue
                if ev.duration_ns <= 0 or ev.name.startswith("$"):
                    continue   # markers, and Python tracer frames
                host.append([ev.name, ev.start_ns, ev.duration_ns])
                if ev.name == WINDOW_SPAN:
                    window = [ev.start_ns, ev.start_ns + ev.duration_ns]
    return host, window, cpu_ops


def _device_index(name: str):
    m = re.search(r"(\d+)$", name)
    return (name.rsplit(":", 1)[0], int(m.group(1)) if m else 0)


class Tracer:
    """Context manager around a measured window: a profiler trace when
    enabled, and always the window's host-clock length."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.window_s = None
        self._dir = None
        self._stack = None

    def __enter__(self):
        import jax
        if self.enabled:
            self._dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=options)
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(jax.profiler.TraceAnnotation(WINDOW_SPAN))
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax
        self.window_s = time.perf_counter() - self._t0
        self._stack.close()
        if self.enabled:
            jax.profiler.stop_trace()
        return False

    def load(self, n_devices: int) -> Trace:
        """Read the trace back and delete its files."""
        try:
            files = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise FileNotFoundError(f"no trace written under {self._dir}")
            return load_xspace(files[0], n_devices)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def describe(path: str) -> None:
    """Print the planes, lines and commonest event names of a trace."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(op_family(e.name) for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events; "
                  f"{names.most_common(25)}")
            for ev in events[:2]:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    stats = _stats(ev)
                print(f"    e.g. {ev.name!r} dur={ev.duration_ns} "
                      f"stats={stats}")


if __name__ == "__main__":
    describe(sys.argv[1])
