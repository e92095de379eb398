"""The whole step's share of the chip's int8 peak: the operations the
window's requests need (counted from shapes and the program's routed
rows) over the traced window's time."""
from chipbench import tracing


def read(run):
    return tracing.mfu_pct(run)
