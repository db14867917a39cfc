"""From a profiler trace of the window to the numbers the readers need.

``Recorder`` profiles the window (device and host tracers on, Python
tracer off) and turns on the program's telemetry spans and counters.
``WindowTrace`` holds the reduction: the device operations clipped to the
window, the union of their intervals (busy time), kernel time by name, the
program's spans and counter changes inside the window, and the breakdown of
the longest device operations and of the idle gaps by what the host was
doing in them. The window is the benchmark's ``bench/window`` annotation;
the program's spans, timed on ``time.perf_counter``, are put on the
profiler's clock by the offset between that annotation and the host clock
read as it opened.
"""

from __future__ import annotations

import collections
import glob
import os
import shutil
import sys
import tempfile
import time

WINDOW = "bench/window"
OP_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10


def merge(intervals):
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def gaps(merged, start, end):
    """Intervals of [start, end] not covered by the merged intervals."""
    out, cur = [], start
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, end)))
        cur = max(cur, e)
        if cur >= end:
            break
    if cur < end:
        out.append((cur, end))
    return [g for g in out if g[1] > g[0]]


class WindowTrace:
    """Reduction of one traced window. Times in seconds.

    ``device_ops``: per device, [(name, start_ns, end_ns)] clipped to the
    window; ``op_meta``: per op name, the text of its string stats (such as
    the ``jit(...)/pallas_call`` op name XLA records for a kernel whose HLO
    instruction has a generic name); ``host``: [(name, start_ns, end_ns)]
    host spans on the same clock;
    ``spans_s``: {name: [durations]} of the program's spans that started in
    the window; ``counters``: the program's counter changes over the window.
    """

    def __init__(self, window_ns, device_ops, host, spans_s, counters, op_meta=None):
        self.window_ns = window_ns
        self.device_ops = device_ops
        self.op_meta = op_meta or {}
        self.host = host
        self._spans = spans_s
        self._counters = counters
        self.window_s = (window_ns[1] - window_ns[0]) / 1e9
        self._busy = [merge([(s, e) for _, s, e in ops]) for ops in device_ops.values()]
        self.busy_s = (sum(sum(e - s for s, e in m) for m in self._busy)
                       / max(1, len(self._busy)) / 1e9)

    def kernel_seconds(self, patterns) -> float:
        """Summed device time of the operations whose name or stats contain
        any of ``patterns``, averaged over the devices."""
        hit = {n: any(p in n or p in self.op_meta.get(n, "") for p in patterns)
               for ops in self.device_ops.values() for n, _, _ in ops}
        tot = sum(e - s for ops in self.device_ops.values() for n, s, e in ops if hit[n])
        return tot / max(1, len(self.device_ops)) / 1e9

    def spans(self, name):
        return self._spans.get(name, [])

    def counter_delta(self, name) -> float:
        return self._counters.get(name, 0.0)

    def breakdown(self) -> dict:
        """The device operations that took most time, and the idle gaps summed
        by the host span open across most of each gap."""
        per_op = collections.Counter()
        for ops in self.device_ops.values():
            for n, s, e in ops:
                per_op[n] += (e - s) / 1e9 / len(self.device_ops)
        idle = collections.Counter()
        host = sorted(self.host, key=lambda h: h[1])
        for m in self._busy[:1]:
            # one sweep: the gaps are sorted and disjoint, so a span that
            # ends before a gap opens is done with for every later gap
            j, active = 0, []
            for gs, ge in gaps(m, *self.window_ns):
                while j < len(host) and host[j][1] < ge:
                    active.append(host[j])
                    j += 1
                active = [h for h in active if h[2] > gs]
                best, label = 0, "no host span"
                for n, hs, he in active:
                    ov = min(he, ge) - max(hs, gs)
                    if ov > best:
                        best, label = ov, n
                idle[label] += (ge - gs) / 1e9
        return {"device_ops": [[n, v] for n, v in per_op.most_common(TOP)],
                "idle_gaps": [[n, v] for n, v in idle.most_common(TOP)]}


def reduce_profile(path, t0_perf, program_events, program_t0, counters):
    """Read the ``.xplane.pb`` under ``path`` and reduce it to a WindowTrace.

    ``program_events``: the program's telemetry trace events (``ts``/``dur``
    in microseconds from ``program_t0`` on ``time.perf_counter``)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one profile under {path}, found {files}")
    pd = ProfileData.from_file(files[0])
    window = None
    host, raw_ops, meta = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OP_LINE:
                    evs = raw_ops[plane.name] = []
                    for e in line.events:
                        evs.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
                        if e.name not in meta:
                            meta[e.name] = " ".join(v for _, v in e.stats if isinstance(v, str))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    else:
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    if window is None or not raw_ops:
        seen = {p.name: {ln.name: sum(1 for _ in ln.events) for ln in p.lines}
                for p in pd.planes}
        msg = f"no {DEVICE_PREFIX}* {OP_LINE!r} line in the profile; planes: {seen}"
        if window is None:
            raise RuntimeError(f"no {WINDOW!r} annotation; " + msg)
        print(msg, file=sys.stderr)
    ws, we = window
    ops = {dev: [(n, max(s, ws), min(e, we)) for n, s, e in evs if e > ws and s < we]
           for dev, evs in raw_ops.items()}
    offset = ws - t0_perf * 1e9  # profiler ns = perf_counter ns + offset
    spans = collections.defaultdict(list)
    for ev in program_events:
        if ev.get("ph") != "X":
            continue
        s = (program_t0 + ev["ts"] / 1e6) * 1e9 + offset
        e = s + ev["dur"] * 1e3
        if ws <= s < we:
            spans[ev["name"]].append(ev["dur"] / 1e6)
            host.append((ev["name"], s, e))
    return WindowTrace(window, ops, host, dict(spans), counters, meta)


class Recorder:
    """Profile one window; ``stop`` returns its WindowTrace."""

    def start(self):
        import jax

        from repro.common import telemetry

        self._reg = telemetry.enable(trace=True)
        self._dir = tempfile.mkdtemp(prefix="chip-bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)

    def stop(self, t0_perf: float) -> WindowTrace:
        import jax

        from repro.common import telemetry

        jax.profiler.stop_trace()
        snap = self._reg.snapshot()["counters"]
        events = self._reg.trace_json()["traceEvents"]
        telemetry.disable()
        try:
            t = time.perf_counter()
            out = reduce_profile(self._dir, t0_perf, events, self._reg._t0, snap)
            out.reduce_s = time.perf_counter() - t
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return out
