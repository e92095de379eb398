"""From a profiler trace to the per-layer metrics.

A traced run records its window under JAX's profiler (``capture``).  The
reduction (``summarize``) reads the ``.xplane.pb`` it writes with
``jax.profiler.ProfileData`` and keeps what the readers need:

- the window: the host span ``bench.window`` that the harness puts
  around the measured loop; device time outside it does not count;
- busy time: the union of the intervals in which an operation ran on
  the device (``XLA Ops`` lines of the ``/device:TPU:<n>`` planes),
  averaged over the chips used.  A control-flow operation (``while``,
  ``conditional``, ``call``) spans the operations of its body and is
  left out, so that it neither hides the gaps between them nor counts
  twice;
- kernel time: the summed device durations of the operations whose HLO
  name matches a kernel's pattern (a ``pallas_call`` shows as the HLO
  custom call named after its wrapper, e.g. ``%maxmin_pool_pallas.1``);
- the number of device operations;
- the breakdown: the device operations that took most time, and the
  longest idle gaps, each named by the innermost host span of the
  harness (``bench.*``) or of a system driver open at the gap's middle.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPAN = re.compile(r"^(bench|ecg|lm)\.")

CONTROL_FLOW = re.compile(r"^%(while|conditional|call)[.\s]")

# HLO names of the kernels' custom calls in a TPU trace
MAXMIN_POOL = re.compile(r"^%maxmin_pool_pallas\b")
MEGAKERNEL = re.compile(r"^%analog_plan_pallas\b")
ANALOG_MVM = re.compile(r"^%analog_mvm(_split)?_pallas\b")


@contextlib.contextmanager
def capture(trace_dir):
    """Trace what runs inside (nothing when ``trace_dir`` is None), the
    measured loop inside a ``bench.window`` host span."""
    if trace_dir is None:
        yield
        return
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0    # host spans, not every Python call
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: int                              # device operations in the window
    op_time_s: dict                       # name -> summed device seconds
    gaps: list                            # (seconds, host span) longest first

    def kernel_s(self, pattern: re.Pattern) -> float:
        return sum(t for name, t in self.op_time_s.items()
                   if pattern.search(name))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_time_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[name, t] for t, name in self.gaps[:top]]}


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_events(path: str):
    """(device events per plane ``[(name, start_ns, end_ns)]``, host spans
    ``[(name, start_ns, end_ns)]``) of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    if not CONTROL_FLOW.match(ev.name):
                        evs.append((op_name(ev.name), ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN or HOST_SPAN.match(ev.name):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return devices, host


def op_name(hlo: str) -> str:
    """``%name.N`` of an HLO instruction's text in the trace."""
    return hlo.split(" = ", 1)[0]


def reduce(devices: dict, host: list, window_s: float) -> Summary:
    """The summary of one trace's events (see the module doc)."""
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    spans = [(n, s, e) for n, s, e in host if n != WINDOW_SPAN]
    all_ev = [ev for evs in devices.values() for ev in evs]
    if windows:
        lo, hi = windows[0]
    elif all_ev:
        lo, hi = min(s for _, s, _ in all_ev), max(e for _, _, e in all_ev)
    else:
        return Summary(window_s, 0.0, 0, {}, [])
    busy, ops, op_time, gaps = 0.0, 0, {}, []
    for evs in devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if e > lo and s < hi]
        merged = union((s, e) for _, s, e in inside)
        busy += sum(e - s for s, e in merged)
        ops += len(inside)
        for n, s, e in inside:
            op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) / 1e9, _span_at(spans, (a + b) / 2)))
    n_dev = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[0])
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / n_dev / 1e9,
                   ops=ops // n_dev, op_time_s=op_time, gaps=gaps)


def _span_at(spans, t) -> str:
    """The innermost host span open at time ``t`` ("none" outside any)."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "none"


def summarize(trace_dir, window_s: float) -> Summary:
    devices, host = read_events(_find_xplane(trace_dir))
    return reduce(devices, host, window_s)


# ------------------------------------------------------------- readers
def roofline_pct(run, kernel: str, pattern: re.Pattern):
    """A kernel's share of its roofline: the least time its counted work
    needs at the chip's peaks over its time in the trace; ``None`` where
    the trace holds no such kernel or the run counted no such work."""
    if run.trace is None:
        return None
    work = run.system.kernels.get(kernel)
    measured = run.trace.kernel_s(pattern)
    if work is None or not work.calls or measured <= 0:
        return None
    return 100.0 * work.min_s / measured


def mfu_pct(run):
    """Counted operations of every request served in the traced window
    over the window's time at the chip's int8 peak."""
    rec = run.record
    if run.trace is None or not run.system.step.ops or rec.elapsed_s <= 0:
        return None
    return 100.0 * run.system.step.ops / (rec.elapsed_s
                                          * run.peak["int8_ops_per_s"])
