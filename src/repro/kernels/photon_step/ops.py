"""Jit'd public wrapper around the photon_step Pallas kernel."""

from __future__ import annotations

import functools

import jax

from repro.core import photon as ph
from repro.core import rng as xrng
from repro.core.volume import SimConfig, Source, Volume
from repro.detectors import as_detectors, det_geometry, validate_detectors
from repro.kernels.photon_step.photon_step import (default_interpret,
                                                  photon_step_pallas)
from repro.sources import PhotonSource, as_source


@functools.partial(jax.jit, static_argnames=(
    "shape", "unitinmm", "cfg", "n_steps", "block_lanes", "interpret",
    "record", "jac_cols", "stats"))
def _photon_steps_jit(labels_flat, media, state, shape, unitinmm,
                      cfg: SimConfig, n_steps: int, block_lanes: int,
                      interpret: bool, ppath=None, det_geom=None,
                      record: bool = False, jac_w=None, jac_col=None,
                      jac_cols: int = 0, stats: bool = False):
    return photon_step_pallas(labels_flat, media, state, shape, unitinmm,
                              cfg, n_steps, block_lanes, interpret,
                              ppath=ppath, det_geom=det_geom, record=record,
                              jac_w=jac_w, jac_col=jac_col,
                              jac_cols=jac_cols, stats=stats)


def photon_steps(labels_flat, media, state, shape, unitinmm, cfg: SimConfig,
                 n_steps: int, block_lanes: int = 256,
                 interpret: bool | None = None, ppath=None, det_geom=None,
                 record: bool = False, jac_w=None, jac_col=None,
                 jac_cols: int = 0, stats: bool = False):
    """Returns ``(new_state, fluence_flat, exitance_flat,
    escaped_per_lane, timed_per_lane)`` — plus
    ``(ppath, det_w_flat, det_ppath)`` when detectors are configured,
    plus per-lane ``(cap_det, cap_gate)`` capture records when
    ``record`` is set, plus the ``(nvox * jac_cols,)`` replay-Jacobian
    accumulator when ``jac_cols > 0``, plus the trailing ``(n, 4)``
    telemetry counter block when ``stats`` is set (see
    ``photon_step_pallas``).

    ``interpret=None`` auto-detects: interpreter off TPU, compiled
    Mosaic kernel on TPU.  Resolved here, outside jit, so ``None`` and
    the equivalent explicit mode share one cached executable.
    """
    if interpret is None:
        interpret = default_interpret()
    return _photon_steps_jit(labels_flat, media, state, shape, unitinmm,
                             cfg, n_steps, block_lanes, interpret,
                             ppath=ppath, det_geom=det_geom, record=record,
                             jac_w=jac_w, jac_col=jac_col,
                             jac_cols=jac_cols, stats=stats)


def simulate_kernel(volume: Volume, cfg: SimConfig, n_photons: int,
                    n_steps: int, seed: int = 1234,
                    source: PhotonSource | Source | None = None,
                    block_lanes: int = 256, interpret: bool | None = None,
                    detectors=None, record: bool = False,
                    id_offset: int = 0):
    """Launch one photon per lane and advance n_steps with the kernel.

    Any registered source (repro.sources) works: the source samples the
    launch states outside the kernel, so the Pallas step body is
    source-agnostic.  ``detectors`` (repro.detectors spec) enables
    in-kernel TPSF capture; fresh photons start with zero partial
    pathlengths.  ``record`` adds the per-lane capture records; with
    one photon per lane, ``cap_det[k]`` directly refers to global
    photon id ``id_offset + k`` (64-bit ids via rng.PhotonId).
    """
    source = as_source(source)
    dets = as_detectors(detectors)
    lo, hi = xrng.split_id64(id_offset)
    ids = xrng.PhotonId(
        lo=jax.numpy.uint32(lo) + jax.numpy.arange(
            n_photons, dtype=jax.numpy.uint32),
        hi=jax.numpy.full((n_photons,), hi, jax.numpy.uint32),
    )
    # carry the low-word wraparound into the high word so ids straddling
    # a 2**32 boundary stay distinct
    ids = ids._replace(hi=ids.hi + (ids.lo < jax.numpy.uint32(lo)).astype(
        jax.numpy.uint32))
    pos, direc, w0, rng = source.sample(ids, jax.numpy.uint32(seed))
    state = ph.launch(pos, direc, w0, rng,
                      jax.numpy.ones((n_photons,), bool), volume.shape,
                      volume.labels.reshape(-1))
    ppath = det_geom = None
    if dets:
        validate_detectors(dets, volume.shape)
        n_media = volume.media.shape[0]
        ppath = jax.numpy.zeros((n_photons, n_media), jax.numpy.float32)
        det_geom = det_geometry(dets)
    return photon_steps(volume.labels.reshape(-1), volume.media, state,
                        volume.shape, volume.unitinmm, cfg, n_steps,
                        block_lanes, interpret, ppath=ppath,
                        det_geom=det_geom, record=record)
