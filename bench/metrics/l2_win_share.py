"""Hierarchy: the share of the window's cache answers that the L2 won,
from the decisions the read program returned (its winning level per
query): reads the L1 answered count against it."""
UNIT = "%"


def read(run):
    won = [lv for c in run.calls for lv, cls in zip(c.level, c.cls) if cls != "miss"]
    if not won:
        return None
    return 100.0 * sum(lv == 1 for lv in won) / len(won)
