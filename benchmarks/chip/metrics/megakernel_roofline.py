"""The ECG megakernel's (conv -> fc1 -> fc2 in one Pallas call) share of
its roofline."""
from chipbench import tracing


def read(run):
    return tracing.roofline_pct(run, "megakernel", tracing.MEGAKERNEL)
