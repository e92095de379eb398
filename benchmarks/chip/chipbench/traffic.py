"""The one general traffic generator.  A traffic mix is a JSON file of
parameters (``traffic/<name>.json``); this module turns it and a seed
into a schedule of requests, and drives a system with it.

Parameters of a mix:

- ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last one is answered) or ``"open"`` (requests arrive
  at ``rate_per_s``, exponentially spaced, whether or not the system
  keeps up);
- ``batch``: the most requests one call of the system takes;
- ``request``: the size of each request, one entry per size the system
  driver reads (e.g. ``windows``, ``prompt_tokens``, ``new_tokens``).
  A size is a whole number, ``{"uniform": [lo, hi]}`` (both included) or
  ``{"choice": [a, b, ...]}``;
- ``trace_seconds``: how long the traced run's window lasts (at most
  ``--seconds``), so that its trace stays small;
- anything else is for the system driver (pool sizes, cache lengths)
  and for the check (``check``).

Every seed gets the same kinds of sizes and the same loop; the seed
draws the contents and, where a size is not fixed, the order.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import traceback

import numpy as np


def draw(spec, rng: np.random.Generator) -> int:
    if isinstance(spec, int):
        return spec
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        return int(rng.integers(lo, hi + 1))
    if "choice" in spec:
        return int(rng.choice(spec["choice"]))
    raise ValueError(f"unknown size spec {spec!r}")


def sizes(traffic: dict) -> dict:
    """Every value each size of the mix can take (what set-up warms)."""
    out = {}
    for k, spec in traffic["request"].items():
        if isinstance(spec, int):
            out[k] = [spec]
        elif "uniform" in spec:
            out[k] = list(range(spec["uniform"][0], spec["uniform"][1] + 1))
        else:
            out[k] = sorted(set(spec["choice"]))
    return out


class Schedule:
    """Request sizes and, for an open loop, arrival times, in order."""

    def __init__(self, traffic: dict, rng: np.random.Generator):
        self.traffic = traffic
        self.rng = rng
        self.t_next = 0.0

    def next_sizes(self) -> dict:
        return {k: draw(v, self.rng) for k, v in
                self.traffic["request"].items()}

    def next_arrival(self) -> float:
        """Seconds from the window's start at which the next request of an
        open loop is due."""
        self.t_next += float(self.rng.exponential(
            1.0 / self.traffic["rate_per_s"]))
        return self.t_next


@dataclasses.dataclass
class Record:
    """What the window saw, for the end-to-end metrics."""

    elapsed_s: float = 0.0         # to the end of the last call started
    latencies_s: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    units: int = 0                 # windows classified / tokens generated
    calls: int = 0
    late_s: float = 0.0            # open loop: longest wait for a call


def span(name: str, on: bool):
    """A host span in the profiler's trace when ``on``."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_window(system, traffic: dict, schedule: Schedule, seconds: float,
               *, annotate: bool = False) -> Record:
    """Drive ``system`` for ``seconds`` and record every request.

    ``system.request(sizes)`` makes one request (its payload ready on the
    host); ``system.call(requests)`` serves up to ``traffic["batch"]`` of
    them, blocks until each reply is on the host and returns the units
    completed.  A call that starts inside the window is finished and
    counted; none starts after it.  A call that raises fails its
    requests and ends the window.
    """
    batch = int(traffic.get("batch", 1))
    rec = Record()
    queue = collections.deque()        # (due time, request)
    t0 = time.perf_counter()
    end = t0 + seconds
    if traffic["loop"] == "closed":
        for _ in range(int(traffic["clients"])):
            queue.append((t0, system.request(schedule.next_sizes())))
    elif traffic["loop"] != "open":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    due = t0 + schedule.next_arrival() if traffic["loop"] == "open" else None
    last_end = t0
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if due is not None:
            while due <= now:
                queue.append((due, system.request(schedule.next_sizes())))
                due = t0 + schedule.next_arrival()
            if not queue:
                time.sleep(min(due, end) - now)
                continue
            rec.late_s = max(rec.late_s, now - queue[0][0])
        group = [queue.popleft() for _ in range(min(batch, len(queue)))]
        rec.attempted += len(group)
        with span("bench.call", annotate):
            try:
                units = system.call([r for _, r in group])
            except Exception:          # a failed call fails its requests
                traceback.print_exc()
                rec.failed += len(group)
                break
        last_end = time.perf_counter()
        rec.calls += 1
        rec.units += int(units)
        rec.latencies_s.extend(last_end - t for t, _ in group)
        if traffic["loop"] == "closed":
            for _ in group:
                queue.append((last_end,
                              system.request(schedule.next_sizes())))
    rec.elapsed_s = last_end - t0
    return rec
