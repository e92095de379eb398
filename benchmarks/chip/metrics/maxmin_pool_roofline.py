"""The Pallas max-min pooling kernel's share of its roofline: the least
time its bytes and operations need at the chip's peaks, over the
kernel's time in the trace."""
from chipbench import tracing


def read(run):
    return tracing.roofline_pct(run, "maxmin_pool", tracing.MAXMIN_POOL)
