"""Live share of the lane-segments the round executor ran, summed over
every answer of every job in the window (``RoundStats``)."""

UNIT = "fraction"


def read(run):
    live = lanes = 0.0
    for answers in run.answers:
        for a in answers:
            if a["stats"] is None:
                return None
            live += float(a["stats"].live_segments)
            lanes += float(a["stats"].lane_segments)
    return live / lanes if lanes > 0 else None
