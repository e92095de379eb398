"""Share of the rows of the expert row tiles that the grouped kernel ran
that carry no routed (token, held expert) pair, over the window: read
from the program's counters ``lm.moe.held_rows`` (routed pairs) and
``lm.moe.row_tiles`` (tiles run, with their rows in
``lm.moe.tile_rows``).  ``None`` where the program counts neither."""


def read(run):
    moe = getattr(run.system, "moe", None)
    if not moe or not moe.get("tile_rows"):
        return None
    return 100.0 * (1.0 - moe["held_rows"] / moe["tile_rows"])
