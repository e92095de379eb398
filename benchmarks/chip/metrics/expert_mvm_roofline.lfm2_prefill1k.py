"""The grouped held-expert kernel's share of its roofline: the least time
the routed rows' counted work needs at the chip's peaks, over the
kernel's time in the trace (its custom call, ``%expert_mvm_pallas``)."""
import re

from chipbench import tracing

EXPERT_MVM = re.compile(r"^%expert_mvm_pallas\b")


def read(run):
    return tracing.roofline_pct(run, "expert_mvm", EXPERT_MVM)
