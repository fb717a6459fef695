"""Share of the round flush's per-lane-segment updates that carry
weight: ``(sum(dep_live) + sum(exit_live)) / (2 * sum(lane_segments))``
over every answer of every job in the window (``RoundStats``).  The
fluence and the exitance flush each scatter one update per
lane-segment.  Nothing to read where the counters are missing."""

UNIT = "fraction"


def read(run):
    live = lanes = 0.0
    for answers in run.answers:
        for a in answers:
            st = a["stats"]
            if st is None or not hasattr(st, "exit_live"):
                return None
            live += float(st.dep_live) + float(st.exit_live)
            lanes += 2.0 * float(st.lane_segments)
    return live / lanes if lanes > 0 else None
