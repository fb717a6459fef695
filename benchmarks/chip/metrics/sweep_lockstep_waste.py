"""Share of the vmapped campaign loop spent carrying scenarios that have
already finished: per batch ``1 - sum(steps) / (B * max(steps))``,
weighted by the batch's photons.  Nothing to read where a job is one
answer."""

UNIT = "fraction"


def read(run):
    num = den = 0.0
    for answers in run.answers:
        if len(answers) < 2:
            return None
        steps = [int(a["steps"]) for a in answers]
        photons = sum(a["photons"] for a in answers)
        waste = 1.0 - sum(steps) / (len(steps) * max(steps))
        num += waste * photons
        den += photons
    return num / den if den > 0 else None
