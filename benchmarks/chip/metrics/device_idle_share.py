"""Share of the traced slice in which no operation ran on the device
(averaged over the chips the cell uses)."""

UNIT = "fraction"


def read(run):
    if run.trace is None or run.trace.slice_s <= 0:
        return None
    return 1.0 - run.trace.busy_s / run.trace.slice_s
