"""Lock-step vectorized photon simulation engine.

Implements the paper's two thread-level workload strategies:

  * ``mode="dynamic"`` — the workgroup-level dynamic load balancing of
    the paper (Fig. 3a): all lanes draw photons from a shared remaining
    counter; a lane whose photon terminates immediately *regenerates* a
    new one.  On a GPU this needed a local-memory atomic counter; in the
    lock-step TPU/JAX formulation it is a masked prefix-sum over dead
    lanes — race-free by construction.
  * ``mode="static"`` — the thread-level baseline: every lane is
    pre-assigned ``n_photons / n_lanes`` photons and idles once its
    quota is done (the divergence-waste case the paper measures).

The outer loop is organized in **fused rounds** of
``K = cfg.steps_per_round`` transport segments (DESIGN.md §rounds):
regeneration runs once per round and the global fluence / exitance /
escape accumulators are flushed once per round, amortizing the
bookkeeping the paper amortizes by keeping its OpenCL kernel resident
over many steps.  The round executor is pluggable:
``engine="jnp"`` runs the segments in an in-graph ``fori_loop``;
``engine="pallas"`` dispatches the Pallas photon-step kernel
(repro.kernels.photon_step), which accumulates all three quantities
in-kernel.  Trajectories and RNG streams are bit-identical across K and
engines (DESIGN.md §determinism); only fp accumulation order differs,
and K=1 with the jnp engine reproduces the unfused engine exactly.

The engine is shape-polymorphic in the photon count (traced int32), so
pilot runs for the device-level load balancer (loadbalance.py) reuse the
same compiled executable.
"""

from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import photon as ph
from repro.core import rng as xrng
from repro.core.volume import SimConfig, Source, Volume
from repro.detectors import (Detector, accumulate_capture, as_detectors,
                             det_geometry, update_capture,
                             validate_detectors)
from repro.sources import PhotonSource, as_source, launch_point
from repro.telemetry.stats import RoundStats
from repro.telemetry.trace import default_tracer

ENGINES = ("jnp", "pallas")


class SimResult(NamedTuple):
    energy: jnp.ndarray     # (nx, ny, nz) float32 deposited energy for the
    #                          CW case (cfg.n_time_gates == 1), else
    #                          (nx, ny, nz, ntg) binned over time gates
    exitance: jnp.ndarray   # (nx, ny) float32 weight escaping the z=0 face
    escaped_w: jnp.ndarray  # () float32 total escaped weight
    n_launched: jnp.ndarray  # () int32 photons actually launched
    launched_w: jnp.ndarray  # () float32 total initial weight launched
    #                          (== n_launched for unit-weight sources; differs
    #                          for weighted launches, e.g. Planar patterns)
    steps: jnp.ndarray      # () int32 lock-step iterations executed
    # -- accounting / detector fields (defaulted so legacy constructors,
    #    e.g. the verbatim seed-engine copy in tests, keep working; the
    #    defaults are numpy, not jnp, so importing this module does not
    #    initialize the JAX backend as a side effect) --
    timed_out_w: jnp.ndarray = np.float32(0.0)  # () weight retired by the
    #                          tmax_ns gate or the max_steps cap —
    #                          deterministic loss, excluded from the
    #                          roulette residue (analysis.energy_balance)
    det_w: jnp.ndarray = np.zeros((0, 1), np.float32)  # (n_det, ntg)
    #                          detected-weight TPSF histogram per detector
    det_ppath: jnp.ndarray = np.zeros((0, 0), np.float32)  # (n_det,
    #                          n_media) weight-weighted partial pathlength
    #                          sums (mm) of detected photons
    # -- detected-photon id records (DESIGN.md §replay; populated when
    #    build_sim_fn(record_detected=capacity) is set) --
    det_rec: jnp.ndarray = np.zeros((0, 4), np.uint32)  # (capacity, 4)
    #                          rows of [id_lo, id_hi, det, gate]: the
    #                          64-bit global photon id (two uint32
    #                          words), detector index and exit time gate
    #                          of each capture, in capture order.  Only
    #                          the first det_rec_n rows are valid.
    det_rec_n: jnp.ndarray = np.int32(0)  # () valid record count
    det_rec_overflow: jnp.ndarray = np.int32(0)  # () captures dropped
    #                          once the buffer filled (det_w still
    #                          counts them; only the id record is lost)
    stats: RoundStats | None = None  # round-level telemetry counters
    #                          (telemetry.RoundStats) when
    #                          cfg.collect_stats is set; None otherwise
    #                          (an empty pytree node, so jit/shard_map
    #                          signatures stay stable either way)


class _Carry(NamedTuple):
    state: ph.PhotonState
    energy: jnp.ndarray      # (nvox * ntg,) flat gate-major deposited energy
    exitance: jnp.ndarray    # (nx*ny,) flat z=0-face exitance image
    escaped_w: jnp.ndarray
    timed_out_w: jnp.ndarray  # weight retired by the tmax_ns gate so far
    ppath: jnp.ndarray       # (n_lanes, n_media) per-medium partial path-
    #                          lengths (mm) of the in-flight photon; width 0
    #                          when no detectors are configured
    det_w: jnp.ndarray       # (n_det * ntg,) flat detected-weight TPSF
    det_ppath: jnp.ndarray   # (n_det, n_media) detected ppath sums
    rec: jnp.ndarray         # (capacity + 1, 4) uint32 detected-photon id
    #                          records [id_lo, id_hi, det, gate]; the last
    #                          row is a write-off slot for masked /
    #                          overflowing scatters ((0, 4) when recording
    #                          is off)
    rec_n: jnp.ndarray       # () int32 record cursor
    rec_overflow: jnp.ndarray  # () int32 captures dropped at capacity
    lane_ids: jnp.ndarray    # (n_lanes, 2) uint32 [lo, hi] global photon
    #                          id of each lane's in-flight photon ((0, 2)
    #                          when recording is off)
    remaining: jnp.ndarray   # dynamic mode: shared photon counter
    launched_per_lane: jnp.ndarray  # static mode: per-lane launch count
    next_id_lo: jnp.ndarray  # global 64-bit photon id counter (RNG
    next_id_hi: jnp.ndarray  #   seeding), as a uint32 (lo, hi) pair
    launched_w: jnp.ndarray  # total initial weight launched so far
    steps: jnp.ndarray
    stats: tuple | RoundStats = ()  # RoundStats of jnp scalars when
    #                          cfg.collect_stats, else () — an empty
    #                          pytree, so the loop structure is
    #                          identical with collection off


def _as_id_pair(next_id):
    """Coerce a legacy scalar id counter to a (lo, hi) uint32 pair."""
    if isinstance(next_id, tuple):
        lo, hi = next_id
        return jnp.asarray(lo).astype(jnp.uint32), \
            jnp.asarray(hi).astype(jnp.uint32)
    return jnp.asarray(next_id).astype(jnp.uint32), jnp.uint32(0)


def lane_sum(x):
    """Sum a float over the lane axis in a fixed pairwise order.

    ``jnp.sum`` leaves the order to the compiler, which on a TPU follows
    the array layout, so a vmapped batch (``repro.scenarios``) and a
    single run rounded the escaped and launched weight differently.
    Halving with elementwise adds fixes the order for any batching.
    """
    while x.shape[-1] > 1:
        if x.shape[-1] % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[..., :1])], axis=-1)
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def _regenerate(state, remaining, launched_per_lane, next_id, quota,
                source, seed, mode, shape, launch_labels, ppath=None,
                lane_ids=None):
    """Relaunch photons in dead lanes according to the workload mode.

    ``next_id`` is the 64-bit global photon id counter as a ``(lo, hi)``
    uint32 scalar pair (a legacy plain scalar is accepted and means
    ``hi = 0``); it is returned advanced, as a pair, with the low-word
    carry propagated so campaigns beyond 2**32 photons keep distinct
    RNG streams instead of wrapping (DESIGN.md §replay).  Ids below
    2**32 produce bit-identical launch states to the historical 32-bit
    counter.

    ``launch_labels`` gives fresh photons their starting medium label
    (``photon.launch``): the flat label grid, gathered over the lanes,
    or the one label of a point source's voxel.

    ``ppath`` (detector runs only) is the per-lane partial-pathlength
    accumulator; relaunched lanes start their new photon with zeroed
    pathlengths.  ``lane_ids`` (detected-photon recording only) is the
    (n_lanes, 2) uint32 [lo, hi] id of each lane's in-flight photon,
    updated on relaunch.  Each is threaded through (and returned as a
    trailing element) only when given, so detector-free engines keep
    the historical tuple contract.
    """
    dead = ~state.alive
    if mode == "dynamic":
        order = jnp.cumsum(dead.astype(jnp.int32))  # 1-based rank among dead
        relaunch = dead & (order <= remaining)
    else:  # static pre-assigned quota per lane
        relaunch = dead & (launched_per_lane < quota)
    n_relaunch = jnp.sum(relaunch.astype(jnp.int32))
    rank = jnp.cumsum(relaunch.astype(jnp.int32)) - 1  # 0-based among relaunched
    next_lo, next_hi = _as_id_pair(next_id)
    ids_lo = (next_lo + rank.astype(jnp.uint32)).astype(jnp.uint32)
    # low-word wraparound carries into the high word (only meaningful on
    # relaunch lanes, whose rank is >= 0; masked lanes may compute a
    # garbage id but their sample is discarded by the merge below)
    ids_hi = (next_hi + (ids_lo < next_lo).astype(jnp.uint32)).astype(
        jnp.uint32)
    ids = xrng.PhotonId(lo=ids_lo, hi=ids_hi)
    pos, direc, w0, rng = source.sample(ids, seed)
    fresh = ph.launch(pos, direc, w0, rng, relaunch, shape, launch_labels)

    def merge(new, old):
        mask = relaunch
        if new.ndim > 1:
            mask = relaunch[:, None]
        return jnp.where(mask, new, old)

    merged = ph.PhotonState(*(merge(n, o) for n, o in zip(fresh, state)))
    merged = merged._replace(alive=state.alive | relaunch)
    new_lo = (next_lo + n_relaunch.astype(jnp.uint32)).astype(jnp.uint32)
    new_hi = (next_hi + (new_lo < next_lo).astype(jnp.uint32)).astype(
        jnp.uint32)
    out = (
        merged,
        remaining - n_relaunch,
        launched_per_lane + relaunch.astype(jnp.int32),
        (new_lo, new_hi),
        lane_sum(jnp.where(relaunch, w0, 0.0)),
    )
    if ppath is not None:
        out = out + (jnp.where(relaunch[:, None], 0.0, ppath),)
    if lane_ids is not None:
        fresh_ids = jnp.stack([ids_lo, ids_hi], axis=1)
        out = out + (jnp.where(relaunch[:, None], fresh_ids, lane_ids),)
    return out


@jax.named_scope("regen")
def _maybe_regenerate(state, remaining, launched_per_lane, next_id, quota,
                      source, seed, mode, shape, launch_labels, ppath=None,
                      lane_ids=None):
    """Regenerate only when some lane will actually relaunch.

    The full regeneration path costs two prefix-sums plus a
    ``source.sample`` over *all* lanes; rounds in which every lane is
    still in flight (the common case for K>1 between termination
    bursts) skip it entirely via ``lax.cond``.  The predicates are
    exact: in dynamic mode the first dead lane has rank 1 <= remaining,
    so ``any(dead) & (remaining > 0)`` relaunches at least one photon;
    in static mode the mask is the relaunch mask itself.  Skipping is
    bit-identical to running ``_regenerate`` with an all-False mask.
    """
    dead = ~state.alive
    if mode == "dynamic":
        any_relaunch = jnp.any(dead) & (remaining > 0)
    else:
        any_relaunch = jnp.any(dead & (launched_per_lane < quota))
    next_pair = _as_id_pair(next_id)

    def do(_):
        return _regenerate(state, remaining, launched_per_lane, next_pair,
                           quota, source, seed, mode, shape, launch_labels,
                           ppath, lane_ids)

    def skip(_):
        out = (state, remaining, launched_per_lane, next_pair,
               jnp.float32(0.0))
        if ppath is not None:
            out = out + (ppath,)
        if lane_ids is not None:
            out = out + (lane_ids,)
        return out

    return jax.lax.cond(any_relaunch, do, skip, None)


def build_sim_fn(shape: tuple[int, int, int], unitinmm: float,
                 cfg: SimConfig, n_lanes: int, mode: str = "dynamic",
                 source: PhotonSource | None = None,
                 engine: str = "jnp", block_lanes: int = 256,
                 interpret: bool | None = None,
                 detectors: tuple[Detector, ...] | None = None,
                 record_detected: int = 0,
                 det_geom_override=None):
    """Build the raw (unjitted) simulation function.

    Returns ``sim_fn(labels_flat, media, n_photons, seed, id_offset=0,
    id_offset_hi=0) -> SimResult``; ``n_photons``, ``seed`` and the id
    offset are traced, so one executable serves pilot runs and
    production runs.
    ``source`` is any registered photon source (repro.sources; pencil
    beam by default) and is baked in at trace time — its parameters are
    static, its randomness counter-seeded per photon id.  ``id_offset``
    (with ``id_offset_hi`` the high uint32 word of the 64-bit offset)
    gives this shard a disjoint global photon-id range — the
    counter-based RNG (both the source's launch stream and the in-flight
    stream) then makes multi-device / elastic / restarted runs simulate
    *exactly* the same photon set as a single-device run
    (DESIGN.md §determinism, §sources).  Ids are carried as two-word
    uint32 pairs end-to-end, so campaigns beyond 2**32 photons never
    wrap onto already-simulated RNG streams (DESIGN.md §replay).

    ``record_detected`` > 0 additionally records the global photon id,
    detector index and exit time gate of up to that many detector
    captures into the fixed-capacity ``SimResult.det_rec`` buffer
    (requires ``detectors``; DESIGN.md §replay).  Once full, further
    captures still accumulate into ``det_w``/``det_ppath`` but their id
    records are dropped and counted in ``det_rec_overflow``.

    ``det_geom_override`` (scenario batching, DESIGN.md §batching)
    substitutes a traced ``(n_det, 3)`` array of (x, y, radius²) rows
    for the statically-derived detector geometry; ``detectors`` still
    fixes the detector *count* and validates the concrete set.

    ``engine`` selects the round executor (DESIGN.md §rounds):
    ``"jnp"`` advances ``cfg.steps_per_round`` segments in an in-graph
    ``fori_loop`` and flushes batched deposition/exitance scatters once
    per round; ``"pallas"`` dispatches the Pallas photon-step kernel
    per round (``block_lanes`` lanes per grid step; ``interpret=None``
    auto-detects the backend).  Both engines simulate bit-identical
    trajectories; accumulated grids agree to fp-accumulation order.

    ``cfg.n_time_gates`` widens the energy accumulator to a gate-major
    flat ``(nvox * ntg,)`` grid (DESIGN.md §time-resolved); the gate
    index is computed at deposit time from the photon's time-of-flight.
    ``detectors`` (repro.detectors) enables TPSF recording: escapes
    through the z=0 face inside a detector disk are histogrammed per
    (detector, time gate), with weight-weighted per-medium partial
    pathlengths tracked per lane.  Both are static trace-time config;
    the default (CW, no detectors) is bit-identical to the ungated
    engine.

    The raw function is shard_map-composable; ``make_simulator`` wraps
    it in jit for single-device use.
    """
    if mode not in ("dynamic", "static"):
        raise ValueError(f"unknown workload mode: {mode}")
    if engine not in ENGINES:
        raise ValueError(f"unknown engine: {engine!r} (choose from {ENGINES})")
    source = as_source(source)
    detectors = as_detectors(detectors)
    n_det = len(detectors)
    if n_det:
        validate_detectors(detectors, shape)
    det_geom = det_geometry(detectors) if n_det else None
    if det_geom_override is not None:
        # scenario batching (repro.scenarios): the capture geometry is a
        # *traced* (n_det, 3) array — ``detectors`` still fixes n_det and
        # carries the host-side validation, but the coordinates flow
        # through the graph so one executable serves many detector sets
        if not n_det:
            raise ValueError("det_geom_override requires detectors: the "
                             "override replaces their traced geometry, "
                             "not their count")
        if tuple(det_geom_override.shape) != (n_det, 3):
            raise ValueError(
                f"det_geom_override shape {tuple(det_geom_override.shape)} "
                f"!= ({n_det}, 3) from the detectors tuple")
        det_geom = jnp.asarray(det_geom_override, jnp.float32)
    capacity = int(record_detected)
    if capacity < 0:
        raise ValueError(f"record_detected must be >= 0, got {capacity}")
    record = capacity > 0
    if record and not n_det:
        raise ValueError(
            "record_detected > 0 requires detectors: the id buffer records "
            "detector captures (DESIGN.md §replay)")
    nx, ny, nz = shape
    nvox = nx * ny * nz
    nxy = nx * ny
    K = int(cfg.steps_per_round)
    if K < 1:
        raise ValueError(f"cfg.steps_per_round must be >= 1, got {K}")
    ntg = int(cfg.n_time_gates)
    if ntg < 1:
        raise ValueError(f"cfg.n_time_gates must be >= 1, got {ntg}")
    collect = bool(cfg.collect_stats)
    if engine == "pallas":
        from repro.kernels.photon_step.photon_step import (
            default_interpret, photon_step_pallas, resolve_block_lanes)

        block_lanes = resolve_block_lanes(n_lanes, block_lanes)
        if interpret is None:
            interpret = default_interpret()

    def sim_fn(labels_flat, media, n_photons, seed, id_offset=0,
               id_offset_hi=0):
        n_photons = jnp.asarray(n_photons, jnp.int32)
        seed = jnp.asarray(seed, jnp.uint32)
        id_lo = jnp.asarray(id_offset, jnp.uint32)
        id_hi = jnp.asarray(id_offset_hi, jnp.uint32)
        # static mode: equal distribution with the remainder spread over the
        # first (n_photons mod n_lanes) lanes, so exactly n_photons launch
        lane_idx = jnp.arange(n_lanes, dtype=jnp.int32)
        quota = n_photons // n_lanes + (lane_idx < n_photons % n_lanes)
        n_media = media.shape[0]
        # fresh photons' medium label: for a point source one lookup,
        # made here once instead of in every round that relaunches
        point = launch_point(source)
        launch_labels = (labels_flat if point is None
                         else ph.point_label(labels_flat, shape, point))
        # partial pathlengths are only tracked when a detector can consume
        # them; width-0 otherwise so the carry structure stays fixed
        ppath_w = n_media if n_det else 0

        state0 = ph.PhotonState(
            pos=jnp.zeros((n_lanes, 3), jnp.float32),
            dir=jnp.tile(jnp.asarray([0.0, 0.0, 1.0], jnp.float32), (n_lanes, 1)),
            ivox=jnp.zeros((n_lanes, 3), jnp.int32),
            # the label of voxel (0, 0, 0), which ivox = 0 names
            label=jnp.full((n_lanes,), labels_flat[0], jnp.int32),
            w=jnp.zeros((n_lanes,), jnp.float32),
            s_left=jnp.zeros((n_lanes,), jnp.float32),
            t=jnp.zeros((n_lanes,), jnp.float32),
            rng=jnp.zeros((n_lanes, 4), jnp.uint32),
            alive=jnp.zeros((n_lanes,), bool),
        )
        carry0 = _Carry(
            state=state0,
            energy=jnp.zeros((nvox * ntg,), jnp.float32),
            exitance=jnp.zeros((nxy,), jnp.float32),
            escaped_w=jnp.float32(0.0),
            timed_out_w=jnp.float32(0.0),
            ppath=jnp.zeros((n_lanes, ppath_w), jnp.float32),
            det_w=jnp.zeros((n_det * ntg,), jnp.float32),
            det_ppath=jnp.zeros((n_det, n_media), jnp.float32),
            # one write-off row past the capacity absorbs masked and
            # overflowing record scatters (lock-step-safe: slots come
            # from a prefix sum, so live writes never collide)
            rec=jnp.zeros((capacity + 1 if record else 0, 4), jnp.uint32),
            rec_n=jnp.int32(0),
            rec_overflow=jnp.int32(0),
            lane_ids=jnp.zeros((n_lanes if record else 0, 2), jnp.uint32),
            remaining=n_photons,
            launched_per_lane=jnp.zeros((n_lanes,), jnp.int32),
            next_id_lo=id_lo,
            next_id_hi=id_hi,
            launched_w=jnp.float32(0.0),
            steps=jnp.int32(0),
            stats=(RoundStats(
                rounds=jnp.int32(0), regen_rounds=jnp.int32(0),
                relaunched=jnp.int32(0), live_segments=jnp.float32(0.0),
                lane_segments=jnp.float32(0.0),
                deposited_w=jnp.float32(0.0), escaped_w=jnp.float32(0.0),
                timed_out_w=jnp.float32(0.0), detected_w=jnp.float32(0.0),
                dep_live=jnp.float32(0.0), exit_live=jnp.float32(0.0),
            ) if collect else ()),
        )

        def cond(c: _Carry):
            has_work = jnp.any(c.state.alive)
            if mode == "dynamic":
                has_work = has_work | (c.remaining > 0)
            else:
                has_work = has_work | jnp.any(c.launched_per_lane < quota)
            return has_work & (c.steps < cfg.max_steps)

        def round_jnp(state, ppath):
            """Advance K segments in-graph; returns the new state plus
            round-local (K, n_lanes) deposition/exitance buffers (the
            deposition index is gate-major: voxel * ntg + gate) and the
            round's escaped / timed-out weights — flushed by the caller
            in ONE scatter per grid instead of one per segment.
            Detector capture scatters into round-local (n_det * ntg,)
            and (n_det, n_media) accumulators per segment (they are
            tiny, unlike the fluence volume).  With recording on, the
            trailing (cap_det, cap_gate) carry tracks the round's
            per-lane capture (at most one: escape kills the lane).
            With ``cfg.collect_stats``, the final ``live`` element
            counts lane-segments entered alive — a reduction over the
            mask the step already computes, never fed back into any
            physics value."""
            def seg(k, rc):
                (st, pp, dep_i, dep_w, ex_i, ex_w, esc, timed, dw, dp,
                 capd, capg, live) = rc
                if collect:
                    live = live + jnp.sum(st.alive, dtype=jnp.float32)
                res = ph.step(st, labels_flat, media, shape, unitinmm, cfg)
                gate = ph.time_gate_bins(res.dep_t, cfg.tmax_ns, ntg)
                dep_i = dep_i.at[k].set(res.dep_idx * ntg + gate)
                dep_w = dep_w.at[k].set(res.dep_w)
                xy, xw = ph.exitance_bins(res.esc_pos, res.esc_w, shape)
                ex_i = ex_i.at[k].set(xy)
                ex_w = ex_w.at[k].set(xw)
                esc = esc + lane_sum(res.esc_w)
                timed = timed + lane_sum(res.timed_w)
                if n_det:
                    pp, dw, dp = accumulate_capture(pp, dw, dp, res, gate,
                                                    det_geom, ntg)
                if record:
                    capd, capg = update_capture(capd, capg, res, gate,
                                                det_geom)
                return (res.state, pp, dep_i, dep_w, ex_i, ex_w, esc,
                        timed, dw, dp, capd, capg, live)

            cap_w = n_lanes if record else 0
            init = (
                state,
                ppath,
                jnp.zeros((K, n_lanes), jnp.int32),
                jnp.zeros((K, n_lanes), jnp.float32),
                jnp.zeros((K, n_lanes), jnp.int32),
                jnp.zeros((K, n_lanes), jnp.float32),
                jnp.float32(0.0),
                jnp.float32(0.0),
                jnp.zeros((n_det * ntg,), jnp.float32),
                jnp.zeros((n_det, n_media), jnp.float32),
                jnp.full((cap_w,), -1, jnp.int32),
                jnp.zeros((cap_w,), jnp.int32),
                jnp.float32(0.0),
            )
            return jax.lax.fori_loop(0, K, seg, init)

        @jax.named_scope("record")
        def append_records(c: _Carry, lane_ids, capd, capg):
            """Append this round's captures to the fixed-capacity id
            buffer: slots come from a prefix sum over captured lanes
            (lock-step-safe, like the dynamic-mode regeneration), and
            masked / over-capacity writes land in the write-off row."""
            captured = capd >= 0
            cap_i = captured.astype(jnp.int32)
            slot = c.rec_n + jnp.cumsum(cap_i) - 1
            ok = captured & (slot < capacity)
            slot = jnp.where(ok, slot, capacity)
            vals = jnp.stack([lane_ids[:, 0], lane_ids[:, 1],
                              capd.astype(jnp.uint32),
                              capg.astype(jnp.uint32)], axis=1)
            rec = c.rec.at[slot].set(vals)
            n_cap = jnp.sum(cap_i)
            rec_n = jnp.minimum(c.rec_n + n_cap, capacity)
            overflow = c.rec_overflow + (c.rec_n + n_cap - rec_n)
            return rec, rec_n, overflow

        def body(c: _Carry):
            next_pair = (c.next_id_lo, c.next_id_hi)
            lane_ids = c.lane_ids
            if record:
                (state, remaining, launched, next_id, w_new, ppath,
                 lane_ids) = _maybe_regenerate(
                    c.state, c.remaining, c.launched_per_lane, next_pair,
                    quota, source, seed, mode, shape, launch_labels,
                    c.ppath, c.lane_ids)
            elif n_det:
                (state, remaining, launched, next_id, w_new,
                 ppath) = _maybe_regenerate(
                    c.state, c.remaining, c.launched_per_lane, next_pair,
                    quota, source, seed, mode, shape, launch_labels,
                    c.ppath)
            else:
                state, remaining, launched, next_id, w_new = _maybe_regenerate(
                    c.state, c.remaining, c.launched_per_lane, next_pair,
                    quota, source, seed, mode, shape, launch_labels)
                ppath = c.ppath
            capd = capg = None
            live = dep_sum = det_new = dep_live = exit_live = None
            if engine == "pallas":
                outs = photon_step_pallas(
                    labels_flat, media, state, shape, unitinmm, cfg, K,
                    block_lanes, interpret,
                    ppath=ppath if n_det else None, det_geom=det_geom,
                    record=record, stats=collect)
                state, flu, exi, esc, timed = outs[:5]
                energy = c.energy + flu
                exitance = c.exitance + exi
                escaped_w = c.escaped_w + lane_sum(esc)
                timed_out_w = c.timed_out_w + lane_sum(timed)
                cur = 5
                if n_det:
                    ppath, dw, dp = outs[cur:cur + 3]
                    cur += 3
                    det_w = c.det_w + dw
                    det_ppath = c.det_ppath + dp
                else:
                    det_w, det_ppath = c.det_w, c.det_ppath
                if record:
                    capd, capg = outs[cur:cur + 2]
                    cur += 2
                if collect:
                    # the kernel's (n_lanes, 4) stats block: col 0 counts
                    # segments entered alive, col 1 sums deposited weight,
                    # cols 2 and 3 count segments with a deposit and with
                    # an exitance weight > 0
                    st_block = outs[cur]
                    live = jnp.sum(st_block[:, 0])
                    dep_sum = jnp.sum(st_block[:, 1])
                    dep_live = jnp.sum(st_block[:, 2])
                    exit_live = jnp.sum(st_block[:, 3])
                    det_new = (jnp.sum(dw) if n_det
                               else jnp.float32(0.0))
            else:
                (state, ppath, dep_i, dep_w, ex_i, ex_w, esc, timed,
                 dw, dp, capd, capg, live) = round_jnp(state, ppath)
                with jax.named_scope("flush.fluence"):
                    energy = c.energy.at[dep_i.reshape(-1)].add(
                        dep_w.reshape(-1))
                with jax.named_scope("flush.exitance"):
                    exitance = c.exitance.at[ex_i.reshape(-1)].add(
                        ex_w.reshape(-1))
                escaped_w = c.escaped_w + esc
                timed_out_w = c.timed_out_w + timed
                det_w = c.det_w + dw
                det_ppath = c.det_ppath + dp
                if collect:
                    dep_sum = jnp.sum(dep_w)
                    det_new = jnp.sum(dw)
                    # the (K, n_lanes) updates the two flushes scatter
                    # that carry weight
                    dep_live = jnp.sum(dep_w > 0, dtype=jnp.float32)
                    exit_live = jnp.sum(ex_w > 0, dtype=jnp.float32)
            if collect:
                # uint32 difference is exact across a low-word wrap, and
                # per-round relaunch counts stay far below 2**31
                rel = (next_id[0] - c.next_id_lo).astype(jnp.int32)
                s = c.stats
                stats = RoundStats(
                    rounds=s.rounds + 1,
                    regen_rounds=s.regen_rounds + (rel > 0).astype(
                        jnp.int32),
                    relaunched=s.relaunched + rel,
                    live_segments=s.live_segments + live,
                    lane_segments=s.lane_segments,  # derived at the end
                    deposited_w=s.deposited_w + dep_sum,
                    # escaped/timed totals mirror the main carry's exact
                    # accumulation, so they stay bit-equal to SimResult
                    escaped_w=escaped_w,
                    timed_out_w=timed_out_w,
                    detected_w=s.detected_w + det_new,
                    dep_live=s.dep_live + dep_live,
                    exit_live=s.exit_live + exit_live,
                )
            else:
                stats = ()
            if record:
                rec, rec_n, rec_overflow = append_records(
                    c, lane_ids, capd, capg)
            else:
                rec, rec_n, rec_overflow = c.rec, c.rec_n, c.rec_overflow
            return _Carry(
                state=state,
                energy=energy,
                exitance=exitance,
                escaped_w=escaped_w,
                timed_out_w=timed_out_w,
                ppath=ppath,
                det_w=det_w,
                det_ppath=det_ppath,
                rec=rec,
                rec_n=rec_n,
                rec_overflow=rec_overflow,
                lane_ids=lane_ids,
                remaining=remaining,
                launched_per_lane=launched,
                next_id_lo=next_id[0],
                next_id_hi=next_id[1],
                launched_w=c.launched_w + w_new,
                steps=c.steps + K,
                stats=stats,
            )

        final = jax.lax.while_loop(cond, body, carry0)
        # weight still in flight when the max_steps cap fires is retired
        # deterministically, like the time gate — account it there so the
        # energy-balance residue only measures roulette statistics
        capped_w = lane_sum(jnp.where(final.state.alive, final.state.w, 0.0))
        if collect:
            # mirror the SimResult timed_out_w accounting (capped weight
            # retires deterministically) and fill the occupancy
            # denominator; float avoids int32 overflow at large
            # steps * n_lanes products
            stats_out = final.stats._replace(
                timed_out_w=final.stats.timed_out_w + capped_w,
                lane_segments=final.steps.astype(jnp.float32) * n_lanes)
        else:
            stats_out = None
        energy = final.energy
        energy = (energy.reshape(shape + (ntg,)) if ntg > 1
                  else energy.reshape(shape))
        return SimResult(
            energy=energy,
            exitance=final.exitance.reshape((nx, ny)),
            escaped_w=final.escaped_w,
            timed_out_w=final.timed_out_w + capped_w,
            det_w=final.det_w.reshape((n_det, ntg)),
            det_ppath=final.det_ppath,
            det_rec=final.rec[:capacity],
            det_rec_n=final.rec_n,
            det_rec_overflow=final.rec_overflow,
            # launches per run stay < 2**31, so the uint32 low-word
            # difference is the exact count even across a 2**32 boundary
            n_launched=(final.next_id_lo - id_lo).astype(jnp.int32),
            launched_w=final.launched_w,
            steps=final.steps,
            stats=stats_out,
        )

    return sim_fn


def make_simulator(volume: Volume, cfg: SimConfig, n_lanes: int,
                   mode: str = "dynamic",
                   source: PhotonSource | Source | None = None,
                   engine: str = "jnp", block_lanes: int = 256,
                   interpret: bool | None = None,
                   detectors=None, record_detected: int = 0):
    """Jitted single-device simulator for a fixed (volume, cfg, lanes,
    source, engine, detectors).  Detector geometry is validated here
    against the volume footprint (a disk that misses the z=0 face can
    never capture)."""
    raw = build_sim_fn(volume.shape, volume.unitinmm, cfg, n_lanes, mode,
                       source, engine, block_lanes, interpret, detectors,
                       record_detected)
    return jax.jit(raw)


def simulate(volume: Volume, cfg: SimConfig, n_photons: int,
             n_lanes: int = 4096, seed: int = 1234,
             source: PhotonSource | Source | None = None,
             mode: str = "dynamic", engine: str = "jnp",
             block_lanes: int = 256,
             interpret: bool | None = None,
             detectors=None, record_detected: int = 0,
             tracer=None) -> SimResult:
    """Convenience one-shot simulation on the current default device.

    ``source`` accepts any registered source type (repro.sources), the
    legacy pencil :class:`Source`, or a ``sources.to_dict``-style config
    dict; ``None`` is the paper's pencil beam.  ``engine`` selects the
    round executor (``"jnp"`` | ``"pallas"``, DESIGN.md §rounds);
    ``block_lanes`` / ``interpret`` tune the Pallas executor only.
    ``detectors`` (repro.detectors spec) enables TPSF recording on the
    z=0 face (DESIGN.md §time-resolved); ``record_detected`` sets the
    detected-photon id buffer capacity for replay (DESIGN.md §replay).

    Returns once the result is ready.  ``tracer`` (else
    :func:`repro.telemetry.default_tracer`) records a ``simulate`` span
    with two children: ``simulate.compile`` (building the jit, tracing,
    lowering, and compiling or loading from the persistent cache) and
    ``simulate.execute`` (dispatch of that executable to its outputs
    ready).
    """
    tracer = default_tracer() if tracer is None else tracer
    args = (volume.labels.reshape(-1), volume.media, n_photons, seed)
    with tracer.span("simulate", device=jax.devices()[0], engine=engine,
                     photons=n_photons):
        with tracer.span("simulate.compile", engine=engine):
            sim_fn = make_simulator(volume, cfg, n_lanes, mode, source,
                                    engine, block_lanes, interpret,
                                    detectors, record_detected)
            # the call below finds this executable in the jit's caches
            sim_fn.lower(*args).compile()
        with tracer.span("simulate.execute", engine=engine,
                         photons=n_photons, lanes=n_lanes):
            return jax.block_until_ready(sim_fn(*args))


# ---------------------------------------------------------------------------
# Opt2: (lane count x steps-per-round) autotuning
# ---------------------------------------------------------------------------

def autotune_rounds(volume: Volume, cfg: SimConfig, n_pilot: int = 20_000,
                    lane_candidates=(1024, 2048, 4096, 8192, 16384),
                    round_candidates=(1, 4, 8, 16, 32),
                    seed: int = 7,
                    source: PhotonSource | Source | None = None,
                    repeats: int = 2, mode: str = "dynamic",
                    engine: str = "jnp",
                    ) -> tuple[tuple[int, int], dict[tuple[int, int], float]]:
    """2-D pilot sweep over (n_lanes, steps_per_round).

    The paper's Opt2 computes the balanced thread number from hardware
    occupancy; lacking introspectable occupancy on this runtime, we
    measure it — and the fused-round depth K trades regeneration /
    flush amortization against masked-lane waste (DESIGN.md §rounds),
    so the two knobs are tuned jointly.  Returns
    ``((best_lanes, best_k), timings_s)`` with timings keyed by
    ``(lanes, k)``.
    """
    labels_flat = volume.labels.reshape(-1)
    timings: dict[tuple[int, int], float] = {}
    for lanes in lane_candidates:
        for k in round_candidates:
            kcfg = dataclasses.replace(cfg, steps_per_round=int(k))
            sim_fn = make_simulator(volume, kcfg, lanes, mode, source, engine)
            args = (labels_flat, volume.media, n_pilot, seed)
            jax.block_until_ready(sim_fn(*args))  # compile + warm up
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()  # reprolint: disable=REP201 - autotune timing, host only
                jax.block_until_ready(sim_fn(*args))
                best = min(best, time.perf_counter() - t0)  # reprolint: disable=REP201 - autotune timing, host only
            timings[(lanes, k)] = best
    best_cfg = min(timings, key=timings.get)
    return best_cfg, timings


def autotune_lanes(volume: Volume, cfg: SimConfig, n_pilot: int = 20_000,
                   candidates=(1024, 2048, 4096, 8192, 16384),
                   seed: int = 7,
                   source: PhotonSource | Source | None = None,
                   repeats: int = 2, mode: str = "dynamic",
                   engine: str = "jnp") -> tuple[int, dict[int, float]]:
    """Pick the lane count with the highest pilot throughput.

    1-D slice of :func:`autotune_rounds` at the config's own
    ``steps_per_round`` — kept as the paper's original Opt2 interface.
    Tune with the same ``engine`` the production run will use: the
    throughput-vs-lane-count curve differs between executors.
    Returns (best_lane_count, timings_s).
    """
    (best_lanes, _), timings = autotune_rounds(
        volume, cfg, n_pilot, candidates,
        round_candidates=(int(cfg.steps_per_round),),
        seed=seed, source=source, repeats=repeats, mode=mode, engine=engine)
    return best_lanes, {lanes: t for (lanes, _), t in timings.items()}
