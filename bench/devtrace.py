"""The device trace of one run, reduced to what the benchmark reports.

``Capture`` records a JAX profiler trace of the measured window with the
Python tracer off, so the host pays only for the spans the benchmark
writes itself (``jax.profiler.TraceAnnotation``).  ``reduce`` reads the
trace back with ``jax.profiler.ProfileData`` and returns:

- ``window_s``: the length of the benchmark's ``window`` span;
- ``busy_s``: per device, the union of the intervals in which an
  operation ran, clipped to the window, averaged over the devices used;
- ``device_ops``: the ten operations that took most device time of
  their own (a loop's time less its body's);
- ``idle_gaps``: the ten longest stretches with no operation on the
  device, cut where a payload starts or ends and each named by what the
  host was doing (see ``_host_state``).
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

WINDOW = "window"
# "payload <id>" spans one job's payload and "payload <id> start" marks its
# start; a payload still running when the trace stops leaves only the mark
PAYLOAD = "payload"
STEP_MODULE = "train_step"   # part of the name of the program's step module
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10

Interval = Tuple[int, int]   # [start_ns, end_ns)


def annotate(name: str, enabled: bool):
    """A host span in the trace when tracing, else nothing at all."""
    if not enabled:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Capture:
    """Start and stop one trace; the files live in a temporary directory
    that ``close`` removes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> str:
        import jax
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return files[0]

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _host_state(gap: Interval, payloads: List[Interval],
                steps: List[Interval]) -> str:
    """Name an idle stretch by the host: inside a payload before its
    first step module ran ("job set-up": trace, lower, compile or cache
    load, initialisation), between two step modules ("between steps"),
    after the last ("job wind-down"); outside every payload ("dispatch
    gap")."""
    mid = (gap[0] + gap[1]) // 2
    for ps, pe in payloads:
        if ps <= mid < pe:
            inside = [s for s in steps if ps <= s[0] < pe]
            if not inside or mid < inside[0][0]:
                return "job set-up"
            if mid >= inside[-1][1]:
                return "job wind-down"
            return "between steps"
    return "dispatch gap"


def _split(gaps: List[Interval], payloads: List[Interval]) -> List[Interval]:
    """Cut idle gaps where a payload starts or ends, so that each piece
    lies under one host state."""
    cuts = sorted({t for p in payloads for t in p})
    out = []
    for lo, hi in gaps:
        edges = [lo] + [t for t in cuts if lo < t < hi] + [hi]
        out.extend(zip(edges, edges[1:]))
    return out


def _self_times(ops) -> Dict[str, int]:
    """Device time per operation less the time of operations nested in
    it on the same line (a loop and its body), by the operation's short
    name (the HLO text up to ``=``)."""
    totals: Dict[str, int] = {}
    stack: List[list] = []          # [name, end, self]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, own = stack.pop()
            totals[n] = totals.get(n, 0) + own
        if stack:
            stack[-1][2] -= e - s
        stack.append([name.split(" = ")[0], e, e - s])
    for n, _, own in stack:
        totals[n] = totals.get(n, 0) + own
    return totals


def reduce(path: str) -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window: Optional[Interval] = None
    spans: Dict[str, Interval] = {}
    starts: Dict[str, int] = {}
    devices = []     # per device: ([(name, start, end)], step modules)
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    iv = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    words = ev.name.split()
                    if ev.name == WINDOW:
                        window = iv
                    elif words[0] == PAYLOAD and words[-1] == "start":
                        starts[words[1]] = iv[0]
                    elif words[0] == PAYLOAD:
                        spans[words[1]] = iv
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, steps = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns))
                               for ev in line.events)
                elif line.name == MODULES_LINE:
                    steps.extend((int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
                                 for ev in line.events
                                 if STEP_MODULE in ev.name)
            if ops:
                devices.append((ops, sorted(steps)))
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} span in {path}")
    if not devices:
        raise RuntimeError(f"no device operations in {path}")
    lo, hi = window
    payloads = sorted(list(spans.values()) + [
        (t, max(t, hi)) for job, t in starts.items() if job not in spans])
    busy_ns = []
    totals: Dict[str, int] = {}
    for ops, _ in devices:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        busy_ns.append(sum(e - s for s, e in _union(
            [(s, e) for _, s, e in inside])))
        for n, ns in _self_times(inside).items():
            totals[n] = totals.get(n, 0) + ns
    ops0, steps0 = devices[0]
    gaps = _split(_gaps(_union(_clip([(s, e) for _, s, e in ops0], lo, hi)),
                        lo, hi), payloads)
    gaps.sort(key=lambda g: g[0] - g[1])
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    n_dev = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "device_ops": [[n, ns / n_dev / 1e9] for n, ns in top],
        "idle_gaps": [[_host_state(g, payloads, steps0), (g[1] - g[0]) / 1e9]
                      for g in gaps[:TOP]],
    }
