"""repro.obs - host-side observability: tracing, metrics, energy telemetry.

Everything here observes from the host side, *around* jitted calls:
instrumentation never enters a traced computation, so it cannot grow the
jit cache or trigger re-lowering (pinned by ``tests/test_obs.py`` via
``repro.verify.retrace``).

    from repro import obs

    with obs.collect("serve-run") as tr:
        with obs.span("serve.batch", batch=8):
            ...
        obs.event("drift.probe", lsb=0.3)
    obs.metrics.histogram("serve.decode_us").record(120.0)
    obs.report.dump_run("run.jsonl", tr, obs.metrics.registry())

Render with ``python -m repro.obs run.jsonl``.
"""

from . import energy, metrics, report, trace
from .energy import PAPER_UJ_PER_INFERENCE, PAPER_US_PER_INFERENCE, energy_report
from .metrics import counter, gauge, histogram, registry, reset_metrics
from .trace import (Trace, active_trace, collect, event, log, observed, span,
                    time_block, timeit)

__all__ = [
    "trace", "metrics", "energy", "report",
    "Trace", "collect", "active_trace", "observed", "span", "event", "log",
    "timeit", "time_block",
    "counter", "gauge", "histogram", "registry", "reset_metrics",
    "energy_report", "PAPER_US_PER_INFERENCE", "PAPER_UJ_PER_INFERENCE",
]
