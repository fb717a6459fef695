"""Detector subsystem: TPSF recording on the z=0 (illuminated) face.

MCX-CL's primary diffuse-optics output besides the fluence volume is the
set of *detected photons*: packets that exit the domain through a
user-defined detector aperture, recorded with their time-of-flight and
per-medium partial pathlengths.  Those records give the detector
time-point-spread function (TPSF) and allow re-scaling detected weight
for perturbed absorption coefficients without re-simulating
(``analysis.rescale_detected``).

This module adapts that to the lock-step engine (DESIGN.md
§time-resolved):

  * A :class:`Detector` is a disk on the z=0 face — ``(x, y)`` center
    and ``radius`` in voxel units.  Detectors are static trace-time
    configuration, like sources.
  * Capture is evaluated with the same z=0-face predicate as the
    exitance image (``photon.Z_EXIT_FACE_VOX``), so every detected
    packet is a subset of the exitance energy.
  * Fixed-shape accumulators instead of per-photon record lists (the
    lock-step engine cannot grow a buffer): per detector the engine
    keeps a ``(n_det, n_time_gates)`` detected-weight TPSF histogram
    and a ``(n_det, n_media)`` weight-weighted partial-pathlength sum.
    Dividing the latter by the detector's total detected weight gives
    the mean partial pathlength per medium — the first-order statistic
    MCX's per-photon records are most commonly reduced to.
  * Overlapping detectors: a photon is credited to the *first* detector
    (lowest index) whose disk contains the exit point, mirroring MCX's
    first-match semantics.

``detector_bins`` is pure jnp and shared by the engine, the pure-jnp
oracle and the Pallas kernel so all three capture identically.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.photon import Z_EXIT_FACE_VOX


@dataclasses.dataclass(frozen=True)
class Detector:
    """One detector disk on the z=0 face (voxel units)."""

    x: float
    y: float
    radius: float

    def __post_init__(self):
        if self.radius <= 0:
            raise ValueError(f"detector radius must be > 0, got {self.radius}")


def as_detectors(spec) -> tuple[Detector, ...]:
    """Coerce a detector spec into a tuple of :class:`Detector`.

    Accepts ``None`` (no detectors), an iterable of :class:`Detector`,
    ``(x, y, radius)`` triples, or ``{"x": .., "y": .., "radius": ..}``
    dicts (the CLI's ``--detectors`` JSON form).
    """
    if spec is None:
        return ()
    out = []
    for d in spec:
        if isinstance(d, Detector):
            out.append(d)
        elif isinstance(d, dict):
            out.append(Detector(float(d["x"]), float(d["y"]),
                                float(d["radius"])))
        else:
            x, y, r = d
            out.append(Detector(float(x), float(y), float(r)))
    return tuple(out)


def to_dicts(detectors: Sequence[Detector]) -> list[dict]:
    """JSON-friendly campaign config (inverse of :func:`as_detectors`)."""
    return [{"x": d.x, "y": d.y, "radius": d.radius} for d in detectors]


def validate_detectors(detectors: Sequence[Detector],
                       shape: tuple[int, int, int]) -> None:
    """Reject detector disks that cannot capture anything on this volume.

    A detector lies on the z=0 face, so its disk must intersect the
    ``[0, nx] x [0, ny]`` footprint of the volume; a disk placed fully
    outside (or tangent to) the footprint silently records zero weight
    forever — almost always a units mistake (mm vs voxel) or a detector
    meant for a different volume.  Called at ``make_simulator`` time so
    the error carries the actionable context, not a mid-campaign NaN
    hunt.
    """
    nx, ny = float(shape[0]), float(shape[1])
    for i, d in enumerate(detectors):
        # distance from the disk center to the closest point of the
        # footprint rectangle (0 when the center lies inside it)
        dx = max(0.0 - d.x, 0.0, d.x - nx)
        dy = max(0.0 - d.y, 0.0, d.y - ny)
        if dx * dx + dy * dy >= d.radius * d.radius:
            raise ValueError(
                f"detector {i} (x={d.x}, y={d.y}, radius={d.radius}) lies "
                f"entirely outside the z=0 face of the volume (footprint "
                f"[0, {nx}] x [0, {ny}] voxels) and can never capture a "
                f"photon — detector coordinates are in voxel units on the "
                f"z=0 face; move the disk inside the footprint or enlarge "
                f"its radius")


def det_geometry(detectors: Sequence[Detector]) -> jnp.ndarray:
    """(n_det, 3) float32 rows of (x, y, radius^2) for the capture test."""
    rows = [[d.x, d.y, d.radius * d.radius] for d in detectors]
    return jnp.asarray(np.asarray(rows, np.float32).reshape(-1, 3))


def detector_bins(esc_pos, esc_w, det_geom):
    """Match z=0-face escapes against the detector disks.

    ``det_geom`` is the (n_det, 3) array from :func:`det_geometry`.
    Returns ``(det_idx, w)``: per lane the index of the first detector
    whose disk contains the exit point, and the weight to credit it
    (0 for lanes that did not exit through the z=0 face or missed every
    disk; their index is 0 so the masked scatter is in-range).
    """
    z_exit = esc_pos[:, 2] < Z_EXIT_FACE_VOX
    dx = esc_pos[:, None, 0] - det_geom[None, :, 0]   # (N, n_det)
    dy = esc_pos[:, None, 1] - det_geom[None, :, 1]
    inside = (dx * dx + dy * dy) <= det_geom[None, :, 2]
    hit_any = jnp.any(inside, axis=1) & z_exit & (esc_w > 0)
    det_idx = jnp.argmax(inside, axis=1).astype(jnp.int32)  # first match
    return det_idx, jnp.where(hit_any, esc_w, 0.0)


@jax.named_scope("capture")
def accumulate_capture(pp, dw, dp, res, gate, det_geom, ntg):
    """One segment of detector bookkeeping, shared by the jnp engine,
    the Pallas kernel and the ref oracle so all three capture
    identically (the same contract as ``exitance_bins``).

    Adds the segment's pathlength to the per-lane per-medium ``pp``
    (N, n_media) BEFORE testing capture — a photon escaping this
    segment is recorded with the final segment included — then
    histograms detected weight into the flat gate-major ``dw``
    (n_det * ntg,) and the weighted pathlength sums ``dp``
    (n_det, n_media).  ``res`` is the segment's ``photon.StepResult``,
    ``gate`` its per-lane time-gate index.  Returns the updated
    ``(pp, dw, dp)``.
    """
    n_media = pp.shape[1]
    med_cols = jnp.arange(n_media, dtype=jnp.int32)[None, :]
    pp = pp + jnp.where(res.seg_med[:, None] == med_cols,
                        res.seg_len[:, None], 0.0)
    didx, dwgt = detector_bins(res.esc_pos, res.esc_w, det_geom)
    dw = dw.at[didx * ntg + gate].add(dwgt)
    dp = dp.at[didx].add(dwgt[:, None] * pp)
    return pp, dw, dp


@jax.named_scope("capture")
def update_capture(cap_det, cap_gate, res, gate, det_geom):
    """One segment of detected-photon id bookkeeping (DESIGN.md §replay).

    ``cap_det``/``cap_gate`` are per-lane int32 state for the current
    fused round: the detector index (-1: not captured this round) and
    exit time gate of the lane's capture.  A lane captures at most once
    per round — escape kills the lane and regeneration only runs
    between rounds — so a plain masked select is race-free.  Shared by
    the jnp round executor, the Pallas kernel and the ref oracle so all
    three record identically (the ``detector_bins`` call is common
    subexpression with :func:`accumulate_capture` and fuses away under
    jit).
    """
    didx, dwgt = detector_bins(res.esc_pos, res.esc_w, det_geom)
    newly = dwgt > 0
    cap_det = jnp.where(newly, didx, cap_det)
    cap_gate = jnp.where(newly, gate, cap_gate)
    return cap_det, cap_gate
