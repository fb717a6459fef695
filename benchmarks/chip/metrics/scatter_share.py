"""Share of the device's busy time in operations whose HLO opcode, or
whose fusion's root, is ``scatter``: the round flush's fluence,
exitance and detector deposits."""

UNIT = "fraction"


def read(run):
    t = run.trace
    if t is None or t.busy_total_s <= 0:
        return None
    return t.class_s.get("scatter", 0.0) / t.busy_total_s
