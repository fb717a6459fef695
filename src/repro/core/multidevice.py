"""Multi-device / multi-pod distributed photon simulation.

Maps the paper's heterogeneous multi-device execution (Fig. 1, Fig. 3b/c)
onto JAX-native constructs:

  * :func:`simulate_sharded` — shard_map over the mesh's photon axes.
    Each device simulates a (possibly unequal) slice of the photon
    budget (the device-level load-balancing partition) and the fluence
    volume is combined with a single ``psum`` — the only collective in
    the whole simulation, which is why MC scales near-linearly
    (paper Fig. 3c).
  * :class:`ChunkScheduler` — dynamic work-stealing over photon chunks
    using JAX's async dispatch; the runtime analogue of the paper's
    "host waits for all devices" barrier, but without the straggler
    penalty: fast devices pull more chunks.
  * :class:`ElasticSimulator` — fault-tolerant chunk accounting.  The
    counter-based RNG keys photons by *global id*, so a chunk lost to a
    device failure is re-simulated bit-identically elsewhere, and a
    checkpoint is just (accumulated grids + chunk cursor).
"""

from __future__ import annotations

import dataclasses
import json
import re
import time
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.loadbalance import DeviceModel
from repro.core.rng import split_id64
from repro.core.simulator import SimResult, build_sim_fn
from repro.core.volume import SimConfig, Source, Volume
from repro.detectors import as_detectors
from repro.resilience import (DevicePool, DeviceSpec, FaultInjector,
                              InjectedFault, RetryPolicy, corrupt_harvest,
                              harvest_result, validate_chunk)
from repro.sources import PhotonSource, as_source
from repro.telemetry.stats import RoundStats
from repro.telemetry.trace import default_tracer, device_label

# the vma type check is off: the while_loop carry mixes shard-varying
# (photon counts) and replicated (volume) values
_shard_map = partial(jax.shard_map, check_vma=False)

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


def collective_kinds(hlo_text: str) -> dict[str, int]:
    """Count the collective ops of compiled HLO text by kind (an async
    pair counts once, at its ``-start``).  The mesh runners' contract is
    ``{"all-reduce": n}``: the psum'd accumulators and nothing else."""
    kinds: dict[str, int] = {}
    pattern = r"\s(" + "|".join(COLLECTIVES) + r")(?:-start)?\("
    for op in re.findall(pattern, hlo_text):
        kinds[op] = kinds.get(op, 0) + 1
    return kinds


# ---------------------------------------------------------------------------
# shard_map distribution (single-pod and multi-pod meshes)
# ---------------------------------------------------------------------------

def sharded_sim_fn(volume: Volume, cfg: SimConfig, n_lanes: int,
                   mesh: Mesh, axis_names: tuple[str, ...] = ("data",),
                   mode: str = "dynamic",
                   source: PhotonSource | Source | None = None,
                   engine: str = "jnp", detectors=None,
                   record_detected: int = 0):
    """Build a shard_map'd simulator over ``axis_names`` of ``mesh``.

    The returned fn takes per-device photon counts and 64-bit id offsets
    (as uint32 lo/hi words, one entry per device on the sharded axes)
    and returns a globally-reduced SimResult.  Volume data is replicated
    and the source / detector configs are baked in statically; the
    fluence volume (time-gated when ``cfg.n_time_gates > 1``), the
    detector TPSF histograms and the scalar accounting are psum'd.
    ``engine`` selects the per-shard round executor (``"jnp"`` |
    ``"pallas"``, DESIGN.md §rounds) — each shard runs the fused
    ``cfg.steps_per_round`` rounds locally, so the collective structure
    (one psum per grid) is engine- and gate-independent.

    ``record_detected`` gives every shard its own ``record_detected``-row
    detected-photon id buffer (DESIGN.md §replay); the per-shard buffers
    are concatenated over the mesh (``det_rec`` becomes
    ``(n_shards * capacity, 4)`` with per-shard valid counts in the
    rank-1 ``det_rec_n``) and the overflow counters are psum'd —
    ``repro.replay.detected_records`` reassembles the global record
    list.
    """
    raw = build_sim_fn(volume.shape, volume.unitinmm, cfg, n_lanes, mode,
                       source, engine, detectors=detectors,
                       record_detected=record_detected)
    ax = axis_names
    collect = bool(cfg.collect_stats)

    def worker(labels_flat, media, counts, offsets_lo, offsets_hi, seed):
        res = raw(labels_flat, media, counts[0], seed, offsets_lo[0],
                  offsets_hi[0])
        summed = {
            "energy": res.energy,
            "exitance": res.exitance,
            "escaped_w": res.escaped_w,
            "timed_out_w": res.timed_out_w,
            "det_w": res.det_w,
            "det_ppath": res.det_ppath,
            "det_rec_overflow": res.det_rec_overflow,
            "n_launched": res.n_launched,
            "launched_w": res.launched_w,
        }
        if collect:
            # RoundStats totals are additive over disjoint photon
            # subsets, so the cross-shard reduction is the same psum as
            # every other accumulator
            summed["stats"] = res.stats
        with jax.named_scope("psum"):
            for a in ax:
                summed = {k: jax.lax.psum(v, a) for k, v in summed.items()}
        # steps and the record buffer/cursor stay per-shard (rank-1 /
        # row-blocked so they concatenate over the mesh)
        return SimResult(steps=res.steps[None], det_rec=res.det_rec,
                         det_rec_n=res.det_rec_n[None], **summed)

    pspec = P(ax)  # counts/offsets sharded across the photon axes
    stats_spec = (RoundStats(*([P()] * len(RoundStats._fields)))
                  if collect else None)
    mapped = _shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(), P(), pspec, pspec, pspec, P()),
        out_specs=SimResult(energy=P(), exitance=P(), escaped_w=P(),
                            timed_out_w=P(), det_w=P(), det_ppath=P(),
                            det_rec=P(ax), det_rec_n=P(ax),
                            det_rec_overflow=P(),
                            n_launched=P(), launched_w=P(), steps=P(ax),
                            stats=stats_spec),
    )
    return jax.jit(mapped)


def simulate_sharded(volume: Volume, cfg: SimConfig, n_photons: int,
                     mesh: Mesh, axis_names: tuple[str, ...] = ("data",),
                     partition: Sequence[int] | None = None,
                     n_lanes: int = 1024, seed: int = 1234,
                     source: PhotonSource | Source | None = None,
                     mode: str = "dynamic", engine: str = "jnp",
                     detectors=None, record_detected: int = 0,
                     id_offset: int = 0, tracer=None) -> SimResult:
    """Run one distributed simulation over the mesh's photon axes.

    ``id_offset`` shifts the whole campaign's global photon-id range (a
    host-side Python int, 64-bit: chunked mega-campaigns pass their
    chunk start here); per-shard offsets are split into uint32 lo/hi
    words so shards beyond the 2**32 boundary keep disjoint RNG
    streams.

    Returns once the result is ready; ``tracer`` (else the process's
    ``default_tracer``) records the same ``simulate`` span with
    ``simulate.compile`` and ``simulate.execute`` children as
    ``simulator.simulate``.
    """
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    if partition is None:
        base = n_photons // n_shards
        counts = np.full((n_shards,), base, np.int32)
        counts[: n_photons - base * n_shards] += 1
    else:
        counts = np.asarray(partition, np.int32)
        if counts.shape != (n_shards,) or counts.sum() != n_photons:
            raise ValueError("partition must have one entry per shard and "
                             "sum to n_photons")
    offsets = int(id_offset) + np.concatenate(
        [[0], np.cumsum(counts.astype(np.uint64))[:-1]]).astype(np.uint64)
    offsets_lo = (offsets & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    offsets_hi = (offsets >> np.uint64(32)).astype(np.uint32)

    tracer = default_tracer() if tracer is None else tracer
    with tracer.span("simulate", device="mesh", engine=engine,
                     photons=n_photons):
        shard_sharding = NamedSharding(mesh, P(axis_names))
        repl = NamedSharding(mesh, P())
        args = (
            jax.device_put(volume.labels.reshape(-1), repl),
            jax.device_put(volume.media, repl),
            jax.device_put(jnp.asarray(counts), shard_sharding),
            jax.device_put(jnp.asarray(offsets_lo), shard_sharding),
            jax.device_put(jnp.asarray(offsets_hi), shard_sharding),
            jnp.uint32(seed),
        )
        with tracer.span("simulate.compile", engine=engine):
            fn = sharded_sim_fn(volume, cfg, n_lanes, mesh, axis_names, mode,
                                source, engine, detectors, record_detected)
            # the call below finds this executable in the jit's caches
            fn.lower(*args).compile()
        with tracer.span("simulate.execute", engine=engine,
                         photons=n_photons, lanes=n_lanes):
            return jax.block_until_ready(fn(*args))


def sharded_replay_fn(volume: Volume, cfg: SimConfig, detectors, mesh: Mesh,
                      axis_names: tuple[str, ...] = ("data",),
                      n_lanes: int = 1024,
                      source: PhotonSource | Source | None = None,
                      engine: str = "jnp", gate_resolved: bool = False,
                      block_lanes: int = 256,
                      interpret: bool | None = None):
    """Build a shard_map'd two-pass replay executor over ``axis_names``.

    The device-parallel half of ``repro.replay.replay_jacobian``
    (DESIGN.md §replay): every device replays its own ``n_lanes``-lane
    slice of a record batch through the selected round executor
    (``engine="jnp"`` | ``"pallas"``), and the flat Jacobian
    accumulator is combined with one ``psum`` per batch — the same
    single-collective structure as :func:`sharded_sim_fn`, so replay
    scales like the forward pass.  The per-record outputs
    (``w_exit``/``gate``/``replayed_det``) stay sharded over the mesh
    in batch order.

    Returns the jitted ``fn(labels_flat, media, id_lo, id_hi, jac_col,
    active, seed) -> (jac_flat, w_exit, gate, replayed_det)`` taking
    ``n_shards * n_lanes`` global lane arrays.
    """
    # imported lazily: repro.replay imports this module for mesh runs
    from repro.detectors import det_geometry, validate_detectors
    from repro.replay import _build_replay_fn

    dets = as_detectors(detectors)
    n_det = len(dets)
    if n_det == 0:
        raise ValueError("sharded_replay_fn needs the forward run's "
                         "detectors")
    validate_detectors(dets, volume.shape)
    jac_cols = n_det * int(cfg.n_time_gates) if gate_resolved else n_det
    raw = _build_replay_fn(volume.shape, volume.unitinmm, cfg, n_lanes,
                           n_det, source, det_geometry(dets), jac_cols,
                           engine, block_lanes, interpret)
    ax = axis_names

    def worker(labels_flat, media, id_lo, id_hi, jac_col, active, seed):
        jac, w_exit, gate, rdet = raw(labels_flat, media, id_lo, id_hi,
                                      jac_col, active, seed)
        with jax.named_scope("psum"):
            for a in ax:
                jac = jax.lax.psum(jac, a)
        return jac, w_exit, gate, rdet

    pspec = P(ax)
    mapped = _shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(), P(), pspec, pspec, pspec, pspec, P()),
        out_specs=(P(), pspec, pspec, pspec),
    )
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# chunked work queue: straggler mitigation + heterogeneous devices
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Chunk:
    start_id: int
    count: int


def _accumulator_shapes(volume: Volume, cfg: SimConfig, detectors):
    """Host-side numpy accumulator shapes for the gate/detector-aware
    merge: (energy, det_w, det_ppath) shapes matching SimResult."""
    nx, ny, nz = volume.shape
    ntg = int(cfg.n_time_gates)
    n_det = len(as_detectors(detectors))
    n_media = volume.media.shape[0]
    eshape = (nx, ny, nz) if ntg == 1 else (nx, ny, nz, ntg)
    return eshape, (n_det, ntg), (n_det, n_media)


class ChunkScheduler:
    """Greedy dynamic chunk dispatch across devices via async dispatch.

    The device-level generalization of the paper's *workgroup* dynamic
    load balancing: instead of fixing each device's share up front (S1-S3),
    devices pull fixed-size chunks from a shared queue as they finish.
    JAX dispatch is asynchronous, so while a device crunches chunk k the
    host can already enqueue k+1 elsewhere; `jax.Array` readiness is the
    completion signal.

    Since PR 7 this is a front-end over ``repro.resilience.DevicePool``
    (DESIGN.md §resilience): a dispatch that raises requeues the chunk
    through ``RetryPolicy`` instead of losing it, results pass the
    ``validate_chunk`` merge guard, stragglers past their
    ``DeviceModel`` deadline re-dispatch speculatively, ``deadline_s``
    bounds the whole run, and merges happen in chunk-id order so the
    result is bit-independent of completion order.  Heterogeneous
    fleets pass ``specs`` (a list of ``resilience.DeviceSpec``) instead
    of ``devices``; ``fault_injector`` enables the chaos drill.

    ``tracer`` (a ``repro.telemetry.Tracer``) records one span per chunk
    dispatch — opened when the chunk is enqueued, closed when its result
    is ready — tagged with device, engine and photon count, so the run's
    timeline exports to Chrome tracing and its per-device photons/s feed
    ``telemetry.fit_device_models`` (DESIGN.md §observability).
    """

    def __init__(self, volume: Volume, cfg: SimConfig, n_lanes: int = 1024,
                 devices: Sequence[jax.Device] | None = None,
                 mode: str = "dynamic",
                 source: PhotonSource | Source | None = None,
                 engine: str = "jnp", detectors=None,
                 record_detected: int = 0, tracer=None,
                 specs: Sequence[DeviceSpec] | None = None,
                 retry_policy: RetryPolicy | None = None,
                 fault_injector: FaultInjector | None = None,
                 validate: bool = True, max_residue_frac: float = 5e-3,
                 chunk_timeout_s: float | None = None,
                 checkpointer=None, checkpoint_every: int = 0,
                 bind_engines: bool = True,
                 raise_on_quarantine: bool = True):
        self.volume = volume
        self.cfg = cfg
        if specs is None:
            self.devices = list(devices or jax.devices())
            specs = [DeviceSpec(device=d, engine=engine, n_lanes=n_lanes,
                                mode=mode) for d in self.devices]
        else:
            if devices is not None:
                raise ValueError("pass either devices or specs, not both")
            self.devices = [s.device if s.device is not None
                            else jax.devices()[0] for s in specs]
        self.tracer = tracer
        self.pool = DevicePool(
            volume, cfg, specs, source=source, detectors=detectors,
            record_detected=record_detected, retry_policy=retry_policy,
            fault_injector=fault_injector, validate=validate,
            max_residue_frac=max_residue_frac,
            chunk_timeout_s=chunk_timeout_s, bind_engines=bind_engines,
            raise_on_quarantine=raise_on_quarantine,
            checkpointer=checkpointer, checkpoint_every=checkpoint_every,
            tracer=tracer)
        self.last_report = None

    def run(self, n_photons: int, chunk_size: int, seed: int = 1234,
            source: PhotonSource | Source | None = None,
            deadline_s: float | None = None, resume: bool = False
            ) -> tuple[SimResult, dict]:
        """Returns ``(SimResult, {device.id: photons merged})``; the full
        resilience accounting lands on ``self.last_report``."""
        res, report = self.pool.run(n_photons, chunk_size, seed=seed,
                                    source=source, deadline_s=deadline_s,
                                    resume=resume)
        self.last_report = report
        stats = {d.id: 0 for d in self.devices}
        for did, n in report.per_device_photons.items():
            stats[did] = stats.get(did, 0) + n
        return res, stats


# ---------------------------------------------------------------------------
# elastic, fault-tolerant execution
# ---------------------------------------------------------------------------

class ElasticSimulator:
    """Chunk-level fault tolerance + elastic scaling for long campaigns.

    Photons are keyed by global id, so work is an immutable set of
    chunks.  Devices may join/leave between rounds; a failed round's
    chunks are simply re-queued and *re-simulated bit-identically*.
    ``state_dict``/``load_state_dict`` give checkpoint/restart: the
    checkpoint stores only the accumulated grids and the completed-chunk
    cursor — O(volume), independent of photon count.

    Failure handling routes through ``repro.resilience`` (DESIGN.md
    §resilience): a failed chunk requeues at the *back* of ``pending``
    (a deterministic poison chunk can no longer starve the campaign)
    and is quarantined onto ``self.skipped`` once it exhausts
    ``retry_policy.max_attempts``; ``fault_injector`` drives seeded
    chaos drills (dispatch faults, delays, NaN corruption — rejected by
    the ``validate_chunk`` merge guard — and ``kill_after_merges`` host
    crashes); ``checkpointer``/``checkpoint_every`` auto-save the
    campaign state every N merged chunks through the atomic
    ``checkpoint.Checkpointer``.

    ``tracer`` (a ``repro.telemetry.Tracer``) records one span per chunk
    (synchronous: the chunk is blocked on inside the span, so durations
    are true device times), tagged with device, engine and photon count
    (DESIGN.md §observability).
    """

    def __init__(self, volume: Volume, cfg: SimConfig, n_photons: int,
                 chunk_size: int, n_lanes: int = 1024, seed: int = 1234,
                 source: PhotonSource | Source | None = None,
                 engine: str = "jnp", detectors=None,
                 record_detected: int = 0, tracer=None,
                 retry_policy: RetryPolicy | None = None,
                 fault_injector: FaultInjector | None = None,
                 validate: bool = True, max_residue_frac: float = 5e-3,
                 checkpointer=None, checkpoint_every: int = 0):
        self.volume = volume
        self.cfg = cfg
        self.seed = seed
        self.engine = engine
        self.tracer = tracer
        self.source = as_source(source)
        self.detectors = as_detectors(detectors)
        self.chunk_size = chunk_size
        self.n_photons = n_photons
        self.record_detected = int(record_detected)
        self.policy = retry_policy or RetryPolicy()
        self.injector = fault_injector
        self.validate = bool(validate)
        self.max_residue_frac = float(max_residue_frac)
        self.checkpointer = checkpointer
        self.checkpoint_every = int(checkpoint_every)
        self.pending: list[Chunk] = [
            Chunk(s, min(chunk_size, n_photons - s))
            for s in range(0, n_photons, chunk_size)
        ]
        self.completed: list[Chunk] = []
        self.skipped: list[Chunk] = []   # chunks quarantined by the cap
        self.failures: dict[int, int] = {}   # chunk start_id -> attempts
        self.n_retries = 0
        nx, ny = volume.shape[:2]
        eshape, dw_shape, dp_shape = _accumulator_shapes(
            volume, cfg, self.detectors)
        self.energy = np.zeros(eshape, np.float32)
        self.exitance = np.zeros((nx, ny), np.float32)
        self.escaped_w = 0.0
        self.timed_out_w = 0.0
        self.det_w = np.zeros(dw_shape, np.float32)
        self.det_ppath = np.zeros(dp_shape, np.float32)
        # per-chunk record slices, concatenated lazily by the det_rec
        # property — appending per merge keeps many-chunk campaigns
        # linear instead of re-copying the whole buffer every chunk
        self._det_rec_parts: list[np.ndarray] = []
        self.det_rec_overflow = 0
        self.n_launched = 0
        self.launched_w = 0.0
        self.stats = (RoundStats.zeros() if cfg.collect_stats else None)
        self._raw = build_sim_fn(volume.shape, volume.unitinmm, cfg, n_lanes,
                                 source=self.source, engine=engine,
                                 detectors=self.detectors,
                                 record_detected=self.record_detected)
        self._jit = jax.jit(self._raw)

    # -- execution ---------------------------------------------------------

    def run_round(self, devices: Sequence[jax.Device] | None = None,
                  fail: Callable[[Chunk, jax.Device], bool] | None = None,
                  max_chunks: int | None = None) -> int:
        """Assign up to one chunk per device; returns #chunks completed.

        ``fail(chunk, device)`` simulates a device failure: the chunk is
        re-queued instead of merged (used by tests + chaos drills).
        Failed and rejected chunks requeue at the *back* of ``pending``
        (RetryPolicy-capped, then quarantined to ``self.skipped``) so a
        poison chunk cannot starve the rest of the campaign.
        """
        devices = list(devices or jax.devices())
        n_done = 0
        batch = []
        while self.pending and len(batch) < (max_chunks or len(devices)):
            batch.append(self.pending.pop(0))
        requeue = []
        for i, ch in enumerate(batch):
            dev = devices[i % len(devices)]
            attempt = self.failures.get(ch.start_id, 0)
            try:
                if fail is not None and fail(ch, dev):
                    raise InjectedFault(
                        f"fail callback killed chunk {ch.start_id} on "
                        f"{device_label(dev)}")
                if self.injector is not None:
                    self.injector.check_dispatch(ch.start_id, attempt,
                                                 device_label(dev))
                    delay = self.injector.delay_for(ch.start_id, attempt)
                    if delay > 0:
                        # the synchronous simulator has no speculation to
                        # overlap with: a straggler simply takes longer
                        time.sleep(delay)
                harvest = harvest_result(self._run_chunk(ch, dev))
                if self.injector is not None and \
                        self.injector.corrupts(ch.start_id, attempt):
                    harvest = corrupt_harvest(harvest)
                if self.validate:
                    errs = validate_chunk(harvest, ch.count,
                                          self.max_residue_frac)
                    if errs:
                        raise InjectedFault(
                            f"chunk {ch.start_id} rejected by merge "
                            f"guard: {errs}")
            except InjectedFault as e:
                self._record_failure(ch, requeue, e)
                continue
            self._merge(ch, harvest)
            n_done += 1
        self.pending = self.pending + requeue
        return n_done

    def _record_failure(self, ch: Chunk, requeue: list,
                        err: BaseException) -> None:
        n = self.failures.get(ch.start_id, 0) + 1
        self.failures[ch.start_id] = n
        if self.policy.exhausted(n):
            self.skipped.append(ch)
            if self.tracer is not None:
                self.tracer.counter("resilience.chunk_quarantined", 1,
                                    chunk_start=ch.start_id,
                                    reason=str(err))
        else:
            self.n_retries += 1
            requeue.append(ch)
            if self.tracer is not None:
                self.tracer.counter("resilience.retries", 1,
                                    chunk_start=ch.start_id)

    def run_to_completion(self, devices=None) -> SimResult:
        while self.pending:
            self.run_round(devices)
        return self.result()

    def _run_chunk(self, ch: Chunk, dev: jax.Device) -> SimResult:
        vol = self.volume
        lo, hi = split_id64(ch.start_id)
        span = None
        if self.tracer is not None:
            span = self.tracer.span("chunk", device=dev, engine=self.engine,
                                    photons=ch.count,
                                    chunk_start=ch.start_id)
        res = self._jit(
            jax.device_put(vol.labels.reshape(-1), dev),
            jax.device_put(vol.media, dev),
            ch.count, self.seed, lo, hi,
        )
        if span is not None:
            # block inside the span so the duration is the true chunk
            # time, not just the async dispatch
            jax.block_until_ready(res)
            span.end()
        return res

    def _merge(self, ch: Chunk, harvest: dict):
        """Merge one validated host-side harvest (resilience.validate),
        then auto-checkpoint and honor any injected host crash (the
        crash fires *after* the checkpoint, mimicking a host that dies
        between campaigns rather than mid-write — the atomic
        Checkpointer already covers torn writes)."""
        self.energy += harvest["energy"]
        self.exitance += harvest["exitance"]
        self.escaped_w += harvest["escaped_w"]
        self.timed_out_w += harvest["timed_out_w"]
        self.det_w += harvest["det_w"]
        self.det_ppath += harvest["det_ppath"]
        if harvest["det_rec"].size:
            self._det_rec_parts.append(harvest["det_rec"])
        self.det_rec_overflow += harvest["det_rec_overflow"]
        self.n_launched += harvest["n_launched"]
        self.launched_w += harvest["launched_w"]
        if self.stats is not None and harvest["stats"] is not None:
            self.stats = self.stats.add(harvest["stats"])
        self.completed.append(ch)
        n_merged = len(self.completed)
        if (self.checkpointer is not None and self.checkpoint_every
                and n_merged % self.checkpoint_every == 0):
            self.checkpointer.save(n_merged, self.state_dict(),
                                   extra={"kind": "elastic",
                                          "merged": n_merged})
            if self.tracer is not None:
                self.tracer.counter("resilience.checkpoint", n_merged)
        if self.injector is not None:
            self.injector.maybe_kill(n_merged)

    @property
    def det_rec(self) -> np.ndarray:
        """Accumulated (n, 4) uint32 detected-photon id records."""
        if len(self._det_rec_parts) != 1:
            merged = (np.concatenate(self._det_rec_parts, axis=0)
                      if self._det_rec_parts
                      else np.zeros((0, 4), np.uint32))
            self._det_rec_parts = [merged]
        return self._det_rec_parts[0]

    @det_rec.setter
    def det_rec(self, value):
        self._det_rec_parts = [np.asarray(value, np.uint32).reshape(-1, 4)]

    def result(self) -> SimResult:
        return SimResult(
            energy=jnp.asarray(self.energy),
            exitance=jnp.asarray(self.exitance),
            escaped_w=jnp.float32(self.escaped_w),
            timed_out_w=jnp.float32(self.timed_out_w),
            det_w=jnp.asarray(self.det_w),
            det_ppath=jnp.asarray(self.det_ppath),
            det_rec=jnp.asarray(self.det_rec),
            det_rec_n=jnp.int32(self.det_rec.shape[0]),
            det_rec_overflow=jnp.int32(self.det_rec_overflow),
            n_launched=jnp.int32(self.n_launched),
            launched_w=jnp.float32(self.launched_w),
            steps=jnp.int32(0),
            stats=self.stats,
        )

    # -- checkpoint / restart ------------------------------------------------

    def _source_key(self) -> str:
        """Canonical string for the source config.  Registered sources
        serialize via to_dict; custom protocol sources get a class-name
        sentinel (stable across process restarts, unlike repr/id) — it
        catches switching source *types* but not reparameterizing the
        same custom class."""
        from repro.sources import to_dict as _source_to_dict

        if hasattr(self.source, "type_name"):
            return json.dumps(_source_to_dict(self.source), sort_keys=True)
        return f"<custom:{type(self.source).__qualname__}>"

    def _detector_key(self) -> str:
        """Canonical string for the detector config (see DESIGN.md
        §time-resolved checkpoint notes): the TPSF histograms are only
        mergeable with chunks captured by the same detector set."""
        from repro.detectors import to_dicts

        return json.dumps(to_dicts(self.detectors), sort_keys=True)

    def state_dict(self) -> dict:
        extra = {}
        if self.stats is not None:
            # RoundStats totals checkpoint as one float64 vector in field
            # order (only present when cfg.collect_stats, so templates of
            # non-collecting runs are unchanged)
            extra["stats"] = np.asarray([float(v) for v in self.stats],
                                        np.float64)  # reprolint: disable=REP301 - checkpoint payload is f64
        return {
            **extra,
            "energy": self.energy.copy(),
            "exitance": self.exitance.copy(),
            "escaped_w": np.float64(self.escaped_w),  # reprolint: disable=REP301 - checkpoint payload is f64
            "timed_out_w": np.float64(self.timed_out_w),  # reprolint: disable=REP301 - checkpoint payload is f64
            "det_w": self.det_w.copy(),
            "det_ppath": self.det_ppath.copy(),
            "det_rec": self.det_rec.copy(),
            "det_rec_overflow": np.int64(self.det_rec_overflow),
            "n_launched": np.int64(self.n_launched),
            "launched_w": np.float64(self.launched_w),  # reprolint: disable=REP301 - checkpoint payload is f64
            "pending": np.asarray(
                [(c.start_id, c.count) for c in self.pending], np.int64
            ).reshape(-1, 2),
            "completed": np.asarray(
                [(c.start_id, c.count) for c in self.completed], np.int64
            ).reshape(-1, 2),
            "skipped": np.asarray(
                [(c.start_id, c.count) for c in self.skipped], np.int64
            ).reshape(-1, 2),
            "seed": np.int64(self.seed),
            "n_photons": np.int64(self.n_photons),
            # the grids are only mergeable with chunks from the same source /
            # detector set; stored as uint8-encoded strings so every leaf
            # stays a numeric array the Checkpointer can write to npz
            "source": np.frombuffer(self._source_key().encode(), np.uint8),
            "detectors": np.frombuffer(self._detector_key().encode(),
                                       np.uint8),
        }

    @staticmethod
    def _decode_key(raw) -> str:
        return (bytes(np.asarray(raw, np.uint8)).decode()
                if not isinstance(raw, str) else raw)

    def load_state_dict(self, state: dict):
        assert int(state["n_photons"]) == self.n_photons, "photon budget mismatch"
        assert int(state["seed"]) == self.seed, "seed mismatch"
        # "source"/"launched_w"/the PR-3 time-resolved keys may be absent
        # only in state dicts handed over directly (not via Checkpointer,
        # whose restore template requires every current key)
        if "source" in state:
            key = self._decode_key(state["source"])
            assert key == self._source_key(), (
                f"source mismatch: checkpoint {key} vs "
                f"simulator {self._source_key()}"
            )
        if "detectors" in state:
            key = self._decode_key(state["detectors"])
            assert key == self._detector_key(), (
                f"detector mismatch: checkpoint {key} vs "
                f"simulator {self._detector_key()}"
            )
        energy = np.asarray(state["energy"], np.float32)
        assert energy.shape == self.energy.shape, (
            f"energy grid mismatch (time gates?): checkpoint "
            f"{energy.shape} vs simulator {self.energy.shape}"
        )
        self.energy = energy.copy()
        self.exitance = np.asarray(state["exitance"], np.float32).copy()
        self.escaped_w = float(state["escaped_w"])
        self.timed_out_w = float(state.get("timed_out_w", 0.0))
        if "det_w" in state:
            self.det_w = np.asarray(state["det_w"], np.float32).copy()
            self.det_ppath = np.asarray(state["det_ppath"],
                                        np.float32).copy()
        if "det_rec" in state:
            self.det_rec = np.asarray(state["det_rec"],
                                      np.uint32).reshape(-1, 4).copy()
            self.det_rec_overflow = int(state.get("det_rec_overflow", 0))
        self.n_launched = int(state["n_launched"])
        self.launched_w = float(state.get("launched_w", state["n_launched"]))
        if self.stats is not None and "stats" in state:
            self.stats = RoundStats.from_vector(
                np.asarray(state["stats"], np.float64))  # reprolint: disable=REP301 - checkpoint payload is f64
        self.pending = [Chunk(int(s), int(c)) for s, c in state["pending"]]
        self.completed = [Chunk(int(s), int(c)) for s, c in state["completed"]]
        # pre-PR-7 state dicts have no skipped list; attempt counters
        # deliberately reset on restart (a restarted host gets a fresh
        # retry budget for transient faults)
        self.skipped = [Chunk(int(s), int(c))
                        for s, c in state.get("skipped", [])]
        self.failures = {}


def heterogeneous_partition(n_photons: int, models: Sequence[DeviceModel],
                            strategy: str = "S2") -> list[int]:
    """Convenience: partition a photon budget with a paper strategy."""
    from repro.core.loadbalance import PARTITIONERS

    return PARTITIONERS[strategy](n_photons, models)
