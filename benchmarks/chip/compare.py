"""The comparison that decides ``correct``.

The program's answer for one job and the plain reference
(``reference.py``) are two Monte Carlo estimates of the same
expectations from independent random numbers, so each quantity is
compared per launched photon by a two-sample z score:

    z = (P / N_p - R / N_r) / (s * sqrt(1 / N_p + 1 / N_r))

with ``P`` the program's total over its ``N_p`` photons, ``R`` the
reference's over ``N_r`` photons, and ``s`` the per-photon standard
deviation that the reference's batch-to-batch spread gives (the two
agree in distribution when the program is right).  Only quantities that
the reference resolves to a relative standard error of ``MAX_REL_SE``
or better are compared; the rest are too rare at this photon count for
a z score to mean anything.

Each compared number is the widest |z| of a group of quantities
(``totals``, coarse ``fluence`` blocks times gates, ``detector``
histograms, pathlengths and capture counts), or an exact count that
has to be 0: photons not launched as asked (``launched_gap``), records
dropped at capacity (``overflow``) and records that no launched photon
could have written (``records_bad``).
"""

from __future__ import annotations

import numpy as np

MAX_REL_SE = 0.05


def block_sums(energy: np.ndarray, blocks: int) -> np.ndarray:
    """Sum a ``(nx, ny, nz[, ntg])`` energy grid into ``(blocks**3,
    ntg)`` coarse blocks, in the reference's block order."""
    e = np.asarray(energy, np.float64)
    if e.ndim == 3:
        e = e[..., None]
    nx, ny, nz, g = e.shape
    b = blocks
    e = e.reshape(b, nx // b, b, ny // b, b, nz // b, g)
    return e.sum(axis=(1, 3, 5)).reshape(b ** 3, g)


def observe(result, blocks: int, n_det: int, n_gates: int) -> dict:
    """Host-side totals of one program answer (a ``SimResult`` or one
    scenario of a batch), in the reference's layout."""
    obs = {
        "absorbed": float(np.asarray(result.energy, np.float64).sum()),
        "escaped": float(np.asarray(result.escaped_w)),
        "exit0": float(np.asarray(result.exitance, np.float64).sum()),
        "timed": float(np.asarray(result.timed_out_w)),
        "bins": block_sums(result.energy, blocks),
    }
    if n_det:
        obs["det_w"] = np.asarray(result.det_w, np.float64).reshape(
            n_det, n_gates)
        obs["det_pp"] = np.asarray(result.det_ppath, np.float64).reshape(
            n_det, -1)
        obs["records"] = valid_records(result)
        obs["captures"] = np.bincount(obs["records"][:, 2].astype(np.int64),
                                      minlength=n_det)[:n_det].astype(float)
    return obs


def valid_records(result) -> np.ndarray:
    """The valid rows of the detected-photon id buffer, with the
    rank-1 per-shard counts of a mesh run laid out shard by shard."""
    rec = np.asarray(result.det_rec)
    counts = np.atleast_1d(np.asarray(result.det_rec_n))
    if rec.size == 0:
        return np.zeros((0, 4), np.uint32)
    cap = rec.shape[0] // counts.size
    rows = [rec[i * cap: i * cap + int(n)] for i, n in enumerate(counts)]
    return np.concatenate(rows, axis=0)


def records_bad(records: np.ndarray, n_photons: int, n_det: int,
                n_gates: int, id_offset: int = 0) -> int:
    """Records that name a photon outside the launched id range, repeat
    a photon, or name a detector or gate that does not exist."""
    if records.size == 0:
        return 0
    ids = (records[:, 0].astype(np.uint64)
           | (records[:, 1].astype(np.uint64) << np.uint64(32)))
    ids = ids.astype(np.int64) - int(id_offset)
    bad = ((ids < 0) | (ids >= n_photons) | (records[:, 2] >= n_det)
           | (records[:, 3] >= n_gates))
    return int(bad.sum()) + int(ids.size - np.unique(ids).size)


def z_scores(prog: np.ndarray, n_prog: int, ref_batches: np.ndarray,
             n_ref: int) -> np.ndarray:
    """|z| per quantity, NaN where the reference does not resolve it.

    ``prog`` holds the program's totals; ``ref_batches`` the reference's
    per-batch totals, batch axis first, over ``n_ref`` photons in all.
    """
    prog = np.asarray(prog, np.float64)
    rb = np.asarray(ref_batches, np.float64)
    m = rb.shape[0]
    per = n_ref / m                        # photons per batch
    mean_r = rb.sum(axis=0) / n_ref
    # per-photon variance from the spread of the batch means
    var = rb.var(axis=0, ddof=1) / per
    se_r = np.sqrt(var / n_ref)
    resolved = (mean_r > 0) & (se_r <= MAX_REL_SE * mean_r)
    denom = np.sqrt(var * (1.0 / n_prog + 1.0 / n_ref))
    with np.errstate(divide="ignore", invalid="ignore"):
        z = np.abs(prog / n_prog - mean_r) / denom
    z = np.where(np.isfinite(prog), z, np.inf)
    return np.where(resolved, z, np.nan)


def _widest(zs) -> float:
    zs = np.concatenate([np.ravel(z) for z in zs])
    zs = zs[~np.isnan(zs)]
    return float(zs.max()) if zs.size else float("nan")


def numbers(obs: dict, ref: dict, n_prog: int, n_ref: int) -> dict:
    """The statistical numbers of one answer against the reference."""
    out = {
        "totals_z": _widest([z_scores(
            [obs[k] for k in ("absorbed", "escaped", "exit0", "timed")],
            n_prog,
            np.stack([ref[k] for k in ("absorbed", "escaped", "exit0",
                                       "timed")], axis=1),
            n_ref)]),
        "fluence_z": _widest([z_scores(obs["bins"], n_prog, ref["bins"],
                                       n_ref)]),
    }
    if "det_w" in obs:
        out["detector_z"] = _widest([
            z_scores(obs["det_w"], n_prog, ref["det_w"], n_ref),
            z_scores(obs["det_pp"], n_prog, ref["det_pp"], n_ref),
            z_scores(obs["captures"], n_prog, ref["captures"], n_ref),
        ])
    return out


def worst(readings: list[dict]) -> dict:
    """Merge the numbers of several answers (a sweep's scenarios) into
    the largest reading of each."""
    out: dict = {}
    for r in readings:
        for k, v in r.items():
            out[k] = v if k not in out or np.isnan(out[k]) else (
                out[k] if np.isnan(v) else max(out[k], v))
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the table of each number beside its limit.

    A number that is missing or not finite fails, and is written as
    ``None``; so does a limit with no number (the check did not run)."""
    table = {}
    ok = True
    for name, limit in limits.items():
        v = float(values.get(name, float("nan")))
        good = bool(np.isfinite(v) and v <= limit)
        ok &= good
        table[name] = {"value": v if np.isfinite(v) else None,
                       "limit": float(limit)}
    return ok, table


def observe_reference(ref: dict, n_det: int) -> dict:
    """A reference run's totals in the layout of :func:`observe`, for
    the control: the reference in the program's place."""
    obs = {
        "absorbed": float(ref["absorbed"].sum()),
        "escaped": float(ref["escaped"].sum()),
        "exit0": float(ref["exit0"].sum()),
        "timed": float(ref["timed"].sum()),
        "bins": ref["bins"].sum(axis=0),
    }
    if n_det:
        obs["det_w"] = ref["det_w"].sum(axis=0)
        obs["det_pp"] = ref["det_pp"].sum(axis=0)
        obs["captures"] = ref["captures"].sum(axis=0)
    return obs
