"""Faults planted under a cell's entry point, for the check to catch.

``Faulty(entry, fault)`` is the entry with its timed path broken
underneath; the tests drive whole runs with it on the CPU, and
``control.py --fault-seeds`` reads the check's numbers with it on the
chip at the cell's own size.  A sweep's job returns one result per
scenario, and each is broken alike.
"""

from __future__ import annotations

ACCUMULATORS = ("energy", "exitance", "escaped_w", "timed_out_w", "det_w",
                "det_ppath")
FAULTS = ("nothing_moves", "half_left_out", "answer_altered")


def _each(res, fn):
    """``fn`` of one result, or of each result of a sweep's list."""
    if hasattr(res, "_replace"):
        return fn(res)
    return [fn(r) for r in res]


def _doubled(res):
    return res._replace(**{k: getattr(res, k) * 2 for k in ACCUMULATORS})


def _flipped(res):
    return res._replace(energy=res.energy[:, :, ::-1])


class Faulty:
    def __init__(self, entry, fault: str):
        if fault not in FAULTS:
            raise ValueError(fault)
        self._entry = entry
        self._fault = fault

    def __getattr__(self, name):
        return getattr(self._entry, name)

    def job(self, seed, photons=None):
        n = photons
        if n is None:
            # a sweep's call takes its photons per scenario
            n = getattr(self._entry, "photons_per_scenario",
                        self._entry.photons)
        if self._fault == "nothing_moves":
            # the step returns its state unchanged: no photon leaves
            # the source
            return self._entry.job(seed, 0)
        if self._fault == "half_left_out":
            # half the photons left out, the totals scaled up to the rest
            return _each(self._entry.job(seed, n // 2), _doubled)
        # answer_altered: the fluence laid down in the wrong voxels
        return _each(self._entry.job(seed, n), _flipped)
