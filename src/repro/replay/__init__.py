"""Detected-photon replay: bit-exact re-simulation + absorption Jacobians.

The counter-seeded RNG (repro.core.rng) makes every photon's trajectory
a pure function of ``(seed, photon_id)`` — any photon can be
re-simulated bit-exactly on any device, any engine, any time.  The
detected-photon id buffer (``SimResult.det_rec``, DESIGN.md §replay)
tells us *which* photon ids reached each detector.  This module
combines the two into the workload every image-reconstruction pipeline
downstream of MCX-CL consumes: the absorption sensitivity (Jacobian)
volume of each detector reading.

For a detected packet exiting with weight ``w`` after a path spending
``L_v`` mm in voxel ``v`` (exact Beer-Lambert deposition),

    w = w0 * exp(-sum_v mua_v * L_v)   =>   dw/dmua_v = -w * L_v.

Summing over a detector's packets gives the exact first-order
sensitivity of its detected weight.  :func:`replay_jacobian` therefore
re-launches exactly the recorded ids in two lock-step passes:

  pass A  re-runs the trajectories and reads off each packet's exit
          weight, detector and exit gate (bit-identical to the forward
          run by the determinism contract);
  pass B  re-runs them again (the RNG makes both passes identical) and
          scatter-adds ``w_exit * seg_len`` of every transport segment
          into the Jacobian column of the packet's recorded detector
          (and, with ``gate_resolved=True``, its recorded exit time
          gate — the ``(nvox, n_det)`` scatter widens to
          ``(nvox, n_det, ntg)``).

Both passes run in **fused rounds** of ``cfg.steps_per_round``
segments through a pluggable round executor (DESIGN.md §replay,
§rounds): ``engine="jnp"`` advances the segments in-graph,
``engine="pallas"`` dispatches the photon-step kernel
(repro.kernels.photon_step), which accumulates the Jacobian scatter
in-kernel.  Trajectories — and therefore the per-record outputs
``w_exit``/``gate``/``replayed_det`` — are bit-identical across
engines, fused-round depths and batch sizes; the Jacobian agrees to
fp-accumulation order (bit-identical too when the Pallas grid is a
single block).  Passing ``mesh=`` shards each record batch across the
mesh's devices with ``shard_map`` (one ``psum`` per batch, the same
collective structure as the forward ``simulate_sharded``), turning
million-record Jacobians into a device-parallel fan-out instead of a
host-side loop.

The per-medium row sums of the result equal the forward run's
``det_ppath`` (weight-weighted partial pathlengths) — the consistency
check :func:`repro.core.analysis.jacobian_medium_sums` exposes and
tests/test_replay.py pins, alongside a finite-difference validation
against a perturbed forward run.

Replay cost is ~2x forward transport for the detected subset only —
typically a tiny fraction of the campaign — and is embarrassingly
parallel over records (chunked over fixed-size lane batches, sharded
over devices when a mesh is given).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import photon as ph
from repro.core import rng as xrng
from repro.core.simulator import ENGINES, SimResult
from repro.core.volume import SimConfig, Volume
from repro.detectors import (as_detectors, det_geometry, update_capture,
                             validate_detectors)
from repro.sources import as_source


class ReplayResult(NamedTuple):
    """Output of :func:`replay_jacobian`."""

    jacobian: np.ndarray   # (nx, ny, nz, n_det) float64 — or
    #                        (nx, ny, nz, n_det, ntg) with
    #                        gate_resolved=True, the extra axis keyed by
    #                        each record's exit time gate: J[v, d(, g)] =
    #                        sum over detector-d records of
    #                        w_exit * L_v (weight * mm).  The detected
    #                        weight's first-order response to a voxel
    #                        absorption change is dW_d = -J[., d] . dmua
    #                        (dmua in 1/mm); normalize by launched_w for
    #                        per-unit-weight sensitivities.
    w_exit: np.ndarray     # (n_records,) float32 replayed exit weight
    det: np.ndarray        # (n_records,) int32 detector index (from the
    #                        forward record)
    gate: np.ndarray       # (n_records,) int32 replayed exit time gate
    #                        (-1: the replayed photon was not captured
    #                        by a detector)
    replayed_det: np.ndarray  # (n_records,) int32 detector index
    #                        recomputed from the replayed exit position
    #                        (-1: the replayed photon did not hit a
    #                        detector — always equals ``det`` when
    #                        volume/cfg/source/seed match the forward
    #                        run)
    n_records: int


def detected_records(result: SimResult) -> np.ndarray:
    """Extract the valid detected-photon id records of a forward run.

    Returns an ``(n, 4)`` uint32 array of ``[id_lo, id_hi, det, gate]``
    rows.  Handles both single-run results (scalar ``det_rec_n``) and
    ``simulate_sharded`` results, whose ``det_rec`` is the concatenation
    of every shard's fixed-capacity buffer with per-shard valid counts
    in the rank-1 ``det_rec_n``.
    """
    rec = np.asarray(result.det_rec, np.uint32).reshape(-1, 4)
    n = np.asarray(result.det_rec_n)
    if n.ndim == 0:
        return rec[: int(n)]
    n_shards = n.shape[0]
    if n_shards == 0 or rec.shape[0] % n_shards:
        raise ValueError(
            f"sharded det_rec of {rec.shape[0]} rows does not split over "
            f"{n_shards} shards")
    cap = rec.shape[0] // n_shards
    parts = [rec[i * cap: i * cap + int(k)] for i, k in enumerate(n)]
    return np.concatenate(parts, axis=0) if parts else rec[:0]


def _build_replay_fn(shape, unitinmm, cfg: SimConfig, n_lanes: int,
                     n_det: int, source, det_geom, jac_cols: int,
                     engine: str = "jnp", block_lanes: int = 256,
                     interpret: bool | None = None):
    """Raw (unjitted, shard_map-composable) two-pass replay over one
    batch of ``n_lanes`` records.

    Returns ``fn(labels_flat, media, id_lo, id_hi, jac_col, active,
    seed) -> (jac_flat, w_exit, gate, replayed_det)`` with ``jac_flat``
    of shape ``(nvox * jac_cols,)``; ``jac_col`` is the per-lane fixed
    Jacobian column (``det`` — or ``det * ntg + record_gate`` for
    gate-resolved scatters) and ``active`` masks batch-padding lanes,
    whose contribution is exactly zero regardless of their (0, 0) id.

    Both passes advance ``cfg.steps_per_round`` fused segments per
    round through the selected executor; round boundaries and
    round-local accumulators match between the engines, so a
    single-block Pallas grid reproduces the jnp Jacobian bit-for-bit
    and the per-lane outputs are bit-identical for any blocking.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine: {engine!r} (choose from {ENGINES})")
    source = as_source(source)
    nx, ny, nz = shape
    nvox = nx * ny * nz
    ntg = int(cfg.n_time_gates)
    K = int(cfg.steps_per_round)
    if K < 1:
        raise ValueError(f"cfg.steps_per_round must be >= 1, got {K}")
    if engine == "pallas":
        from repro.kernels.photon_step.photon_step import (
            default_interpret, photon_step_pallas, resolve_block_lanes)

        # same grid-divisibility fallback as the forward executor
        block_lanes = resolve_block_lanes(n_lanes, block_lanes)
        if interpret is None:
            interpret = default_interpret()

    def fn(labels_flat, media, id_lo, id_hi, jac_col, active, seed):
        n_media = media.shape[0]
        ids = xrng.PhotonId(lo=id_lo, hi=id_hi)
        pos, direc, w0, rng = source.sample(ids,
                                            jnp.asarray(seed, jnp.uint32))

        def cond(c):
            return jnp.any(c[0].alive) & (c[-1] < cfg.max_steps)

        # -- pass A: exit weight / gate / replayed detector ------------
        # per-round accumulators start from zero and merge into the
        # carry once per round, mirroring the in-kernel structure so
        # both engines produce bit-identical per-lane outputs (a lane
        # escapes at most once: replay never regenerates)
        def body_a(c):
            st, w_exit, rdet, gate, pp, steps = c
            if engine == "pallas":
                outs = photon_step_pallas(
                    labels_flat, media, st, shape, unitinmm, cfg, K,
                    block_lanes, interpret, ppath=pp, det_geom=det_geom,
                    record=True)
                st, esc_r, pp = outs[0], outs[3], outs[5]
                capd, capg = outs[8], outs[9]
            else:
                def seg(k, sc):
                    st_k, esc_k, capd_k, capg_k = sc
                    res = ph.step(st_k, labels_flat, media, shape,
                                  unitinmm, cfg)
                    g = ph.time_gate_bins(res.dep_t, cfg.tmax_ns, ntg)
                    capd_k, capg_k = update_capture(capd_k, capg_k, res,
                                                    g, det_geom)
                    return (res.state, esc_k + res.esc_w, capd_k, capg_k)

                st, esc_r, capd, capg = jax.lax.fori_loop(
                    0, K, seg,
                    (st, jnp.zeros((n_lanes,), jnp.float32),
                     jnp.full((n_lanes,), -1, jnp.int32),
                     jnp.zeros((n_lanes,), jnp.int32)))
            w_exit = w_exit + esc_r
            rdet = jnp.where(capd >= 0, capd, rdet)
            gate = jnp.where(capd >= 0, capg, gate)
            return (st, w_exit, rdet, gate, pp, steps + K)

        # the Pallas capture path threads the per-lane ppath state; the
        # jnp pass reads none of it, so it carries a width-0 placeholder
        pp_w = n_media if engine == "pallas" else 0
        carry_a = (ph.launch(pos, direc, w0, rng, active, shape,
                             labels_flat),
                   jnp.zeros((n_lanes,), jnp.float32),
                   jnp.full((n_lanes,), -1, jnp.int32),
                   jnp.full((n_lanes,), -1, jnp.int32),
                   jnp.zeros((n_lanes, pp_w), jnp.float32),
                   jnp.int32(0))
        _, w_exit, rdet, gate, _, _ = jax.lax.while_loop(cond, body_a,
                                                         carry_a)

        # -- pass B: scatter w_exit * seg_len into J[., jac_col] -------
        # the counter-seeded RNG re-creates the identical trajectory, so
        # the exit weight from pass A is available from segment one
        wscale = jnp.where(active, w_exit, 0.0)

        def body_b(c):
            st, jac, steps = c
            if engine == "pallas":
                outs = photon_step_pallas(
                    labels_flat, media, st, shape, unitinmm, cfg, K,
                    block_lanes, interpret, jac_w=wscale, jac_col=jac_col,
                    jac_cols=jac_cols)
                st, jac_r = outs[0], outs[-1]
            else:
                def seg(k, sc):
                    st_k, jac_k = sc
                    res = ph.step(st_k, labels_flat, media, shape,
                                  unitinmm, cfg)
                    # seg_len is 0 for dead lanes and wscale 0 for
                    # padding, so masked lanes add exact zeros
                    jac_k = jac_k.at[res.dep_idx * jac_cols + jac_col].add(
                        wscale * res.seg_len)
                    return (res.state, jac_k)

                st, jac_r = jax.lax.fori_loop(
                    0, K, seg,
                    (st, jnp.zeros((nvox * jac_cols,), jnp.float32)))
            return (st, jac + jac_r, steps + K)

        _, jac, _ = jax.lax.while_loop(
            cond, body_b,
            (ph.launch(pos, direc, w0, rng, active, shape,
                       labels_flat),
             jnp.zeros((nvox * jac_cols,), jnp.float32),
             jnp.int32(0)))
        return jac, w_exit, gate, rdet

    return fn


def _batch_arrays(records, start, n_lanes, gate_resolved, ntg):
    """Pad one record batch to ``n_lanes`` lanes; padding lanes carry
    id (0, 0) with ``active=False`` (their launch weight is masked to
    zero, so they transport nothing — even when a *real* detected
    photon has id 0)."""
    batch = records[start: start + n_lanes]
    nb = batch.shape[0]
    pad = n_lanes - nb
    id_lo = np.concatenate([batch[:, 0], np.zeros(pad, np.uint32)])
    id_hi = np.concatenate([batch[:, 1], np.zeros(pad, np.uint32)])
    det = batch[:, 2].astype(np.int32)
    col = det * ntg + batch[:, 3].astype(np.int32) if gate_resolved else det
    col = np.concatenate([col, np.zeros(pad, np.int32)]).astype(np.int32)
    active = np.concatenate([np.ones(nb, bool), np.zeros(pad, bool)])
    return nb, id_lo, id_hi, col, active


def replay_jacobian(volume: Volume, cfg: SimConfig, records,
                    detectors, source=None, seed: int = 1234,
                    n_lanes: int = 4096, engine: str = "jnp",
                    gate_resolved: bool = False, block_lanes: int = 256,
                    interpret: bool | None = None, mesh=None,
                    axis_names: tuple[str, ...] = ("data",),
                    tracer=None) -> ReplayResult:
    """Replay detected-photon records into per-detector absorption
    Jacobian volumes (DESIGN.md §replay).

    ``records`` is the ``(n, 4)`` uint32 ``[id_lo, id_hi, det, gate]``
    array from :func:`detected_records` (or a forward ``SimResult``
    directly).  ``volume``/``cfg``/``detectors``/``source``/``seed``
    must match the forward run — the determinism contract then makes
    every replayed trajectory bit-identical, which
    ``ReplayResult.replayed_det``/``gate`` let callers assert.

    ``engine`` selects the fused-round executor for both transport
    passes (``"jnp"`` | ``"pallas"``; ``block_lanes``/``interpret``
    tune the Pallas executor, ``cfg.steps_per_round`` the round depth).
    ``gate_resolved=True`` widens the scatter to ``(nvox, n_det, ntg)``
    keyed by each record's exit time gate — the gate axis *partitions*
    the ungated Jacobian, so its gate-sum recovers the
    ``gate_resolved=False`` result.  ``mesh`` distributes each record
    batch over the mesh's ``axis_names`` devices via ``shard_map``
    (``n_lanes`` lanes per device, Jacobian psum'd per batch —
    ``repro.core.multidevice.sharded_replay_fn``).

    Records are replayed in fixed-size lane batches through one jitted
    two-pass transport; the Jacobian is accumulated on the host in
    float64.  ``tracer`` (a ``repro.telemetry.Tracer``) records one span
    per batch — blocked on inside the span, tagged with the record count
    so records/s throughput lands on the trace timeline (DESIGN.md
    §observability).
    """
    if isinstance(records, SimResult):
        records = detected_records(records)
    records = np.asarray(records, np.uint32).reshape(-1, 4)
    detectors = as_detectors(detectors)
    n_det = len(detectors)
    if n_det == 0:
        raise ValueError("replay_jacobian needs the forward run's "
                         "detectors")
    validate_detectors(detectors, volume.shape)
    if records.shape[0] and int(records[:, 2].max()) >= n_det:
        raise ValueError(
            f"record refers to detector {int(records[:, 2].max())} but "
            f"only {n_det} detectors were given — records and detectors "
            f"must come from the same forward run")
    ntg = int(cfg.n_time_gates)
    if gate_resolved and records.shape[0] and \
            int(records[:, 3].max()) >= ntg:
        raise ValueError(
            f"record refers to time gate {int(records[:, 3].max())} but "
            f"cfg.n_time_gates={ntg} — gate-resolved replay needs the "
            f"forward run's gate count")
    jac_cols = n_det * ntg if gate_resolved else n_det
    n_rec = records.shape[0]
    nx, ny, nz = volume.shape
    labels_flat = volume.labels.reshape(-1)

    if mesh is not None:
        from repro.core.multidevice import sharded_replay_fn

        n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
        n_lanes = max(1, min(int(n_lanes),
                             -(-max(n_rec, 1) // n_shards)))
        fn = sharded_replay_fn(volume, cfg, detectors, mesh, axis_names,
                               n_lanes, source, engine, gate_resolved,
                               block_lanes, interpret)
        from jax.sharding import NamedSharding, PartitionSpec as P

        lane_sh = NamedSharding(mesh, P(axis_names))
        repl = NamedSharding(mesh, P())
        labels_dev = jax.device_put(labels_flat, repl)
        media_dev = jax.device_put(volume.media, repl)
        batch_lanes = n_shards * n_lanes

        def run_batch(id_lo, id_hi, col, active):
            return fn(labels_dev, media_dev,
                      jax.device_put(jnp.asarray(id_lo), lane_sh),
                      jax.device_put(jnp.asarray(id_hi), lane_sh),
                      jax.device_put(jnp.asarray(col), lane_sh),
                      jax.device_put(jnp.asarray(active), lane_sh),
                      jnp.uint32(seed))
    else:
        n_lanes = max(1, min(int(n_lanes), max(n_rec, 1)))
        raw = _build_replay_fn(volume.shape, volume.unitinmm, cfg, n_lanes,
                               n_det, source, det_geometry(detectors),
                               jac_cols, engine, block_lanes, interpret)
        jit_fn = jax.jit(raw)
        batch_lanes = n_lanes

        def run_batch(id_lo, id_hi, col, active):
            return jit_fn(labels_flat, volume.media, jnp.asarray(id_lo),
                          jnp.asarray(id_hi), jnp.asarray(col),
                          jnp.asarray(active), jnp.uint32(seed))

    jac = np.zeros((nx * ny * nz * jac_cols,), np.float64)  # reprolint: disable=REP301 - host-side Jacobian accumulator
    w_exit = np.zeros((n_rec,), np.float32)
    gate = np.full((n_rec,), -1, np.int32)
    rdet = np.full((n_rec,), -1, np.int32)
    trace_dev = "mesh" if mesh is not None else jax.devices()[0]
    for start in range(0, n_rec, batch_lanes):
        nb, id_lo, id_hi, col, active = _batch_arrays(
            records, start, batch_lanes, gate_resolved, ntg)
        span = None
        if tracer is not None:
            span = tracer.span("replay_batch", device=trace_dev,
                               engine=engine, records=nb, batch_start=start)
        jac_b, w_b, g_b, rd_b = run_batch(id_lo, id_hi, col, active)
        if span is not None:
            jax.block_until_ready(jac_b)
            span.end()
        jac += np.asarray(jac_b, np.float64)  # reprolint: disable=REP301 - host-side Jacobian accumulator
        w_exit[start: start + nb] = np.asarray(w_b)[:nb]
        gate[start: start + nb] = np.asarray(g_b)[:nb]
        rdet[start: start + nb] = np.asarray(rd_b)[:nb]

    shape_out = ((nx, ny, nz, n_det, ntg) if gate_resolved
                 else (nx, ny, nz, n_det))
    return ReplayResult(
        jacobian=jac.reshape(shape_out),
        w_exit=w_exit,
        det=records[:, 2].astype(np.int32),
        gate=gate,
        replayed_det=rdet,
        n_records=n_rec,
    )
