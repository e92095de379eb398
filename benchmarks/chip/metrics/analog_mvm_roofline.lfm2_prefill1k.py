"""The Pallas analog VMM kernel's (signed-split, per-chunk ADC) share of
its roofline, summed over every dense projection and the head."""
from chipbench import tracing


def read(run):
    return tracing.roofline_pct(run, "analog_mvm", tracing.ANALOG_MVM)
