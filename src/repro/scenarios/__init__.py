"""Batched multi-scenario execution: ``simulate_many`` + compile cache.

A serving-scale reproduction amortizes compilation and batches *fleets*
of (volume, source, detector) scenarios, not one config per ``sim_fn``
(ROADMAP "Batched multi-scenario execution"; DESIGN.md §batching).  This
package vmaps the round executor over a leading scenario axis:

  * per-scenario **media tables**, **source params** (staged launch
    parameters, ``repro.sources.StagedSource``), **seeds**, **photon
    budgets**, **64-bit id offsets** and **detector geometries** are all
    traced — none of their values bake into the jaxpr;
  * volume **labels** are shared (one copy, ``in_axes=None``) when every
    scenario in a group carries the same grid, stacked otherwise;
  * everything *structural* — volume dims, ``SimConfig``, lane count,
    engine, source type + staged-param shapes, detector count — forms
    the **group key**: scenarios group by it, and each group runs as one
    vmapped call.

Executables live in an explicit :class:`CompileCache` keyed by the
traced config shape (group key + batch size + labels sharing + mesh),
so new scenarios of a known shape reuse compiled code; hit/miss/eviction
counters surface through ``repro.telemetry`` (``scenarios.cache.*``
counters; ``scenarios.simulate_many`` spans with ``scenarios.batch``,
``scenarios.compile`` and ``scenarios.execute`` children).

Bit-identity: JAX's while_loop batching rule select-freezes finished
batch elements, and the staged-source path replays the identical op
sequence as the static one, so every scenario's ``SimResult`` from
``simulate_many`` is bit-identical to its own sequential
:func:`simulate_one` run — per engine, and under a device mesh (the
scenario axis shard_maps with no collectives; zero-photon padding
rounds the batch up to the device count).

    from repro.scenarios import Scenario, simulate_many
    results = simulate_many([Scenario(vol, cfg, n_photons=10_000, seed=s)
                             for s in range(8)], engine="jnp")

CLI: ``python -m repro.launch.simulate --scenarios '[{...}, ...]'``.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import volume as V
from repro.core.rng import split_id64
from repro.core.simulator import ENGINES, SimResult, build_sim_fn
from repro.core.volume import SimConfig, Volume
from repro.detectors import (as_detectors, det_geometry, validate_detectors)
from repro.sources import StagedSource, as_source, stage_source
from repro.telemetry.trace import default_tracer

__all__ = [
    "CompileCache",
    "Scenario",
    "default_cache",
    "group_key",
    "make_batched",
    "simulate_many",
    "simulate_one",
]


# ---------------------------------------------------------------------------
# scenario description
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Scenario:
    """One (volume, physics, source, detectors, budget) work item.

    ``source`` / ``detectors`` accept anything ``sources.as_source`` /
    ``detectors.as_detectors`` accept (instances, config dicts, None).
    ``id_offset`` is the 64-bit global photon-id base — scenarios with
    disjoint id ranges simulate disjoint photon sets even at the same
    seed (DESIGN.md §determinism).
    """

    volume: Volume
    cfg: SimConfig
    n_photons: int
    seed: int = 1234
    source: object = None
    detectors: object = ()
    id_offset: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "Scenario":
        """Build from the CLI's ``--scenarios`` JSON entry form.

        Keys: ``bench`` (B1|B2|B2a, default B1), ``size`` (cube edge,
        default 24), ``photons`` (required), ``seed``, ``source``
        (sources.to_dict form), ``detectors`` (list of disk dicts),
        ``time_gates``, ``steps_per_round``, ``tmax_ns``,
        ``do_reflect``, ``id_offset``.
        """
        d = dict(d)
        bench = d.pop("bench", "B1")
        size = int(d.pop("size", 24))
        shape = (size, size, size)
        if bench == "B1":
            vol, do_reflect = V.benchmark_b1(shape), False
        elif bench in ("B2", "B2a"):
            vol, do_reflect = V.benchmark_b2(shape), True
        else:
            raise ValueError(f"unknown bench {bench!r} (B1|B2|B2a)")
        cfg = SimConfig(
            do_reflect=bool(d.pop("do_reflect", do_reflect)),
            steps_per_round=int(d.pop("steps_per_round", 1)),
            n_time_gates=int(d.pop("time_gates", 1)))
        if "tmax_ns" in d:
            cfg = dataclasses.replace(cfg, tmax_ns=float(d.pop("tmax_ns")))
        sc = cls(volume=vol, cfg=cfg, n_photons=int(d.pop("photons")),
                 seed=int(d.pop("seed", 1234)),
                 source=d.pop("source", None),
                 detectors=tuple(d.pop("detectors", ()) or ()),
                 id_offset=int(d.pop("id_offset", 0)))
        if d:
            raise ValueError(f"unknown scenario keys: {sorted(d)}")
        return sc


@dataclasses.dataclass
class _Prep:
    """A scenario normalized for batching: coerced source/detectors,
    staged launch params, concrete geometry, split id offset."""

    idx: int
    sc: Scenario
    src_cls: type
    staged: dict
    dets: tuple
    det_geom: np.ndarray | None
    id_lo: np.uint32
    id_hi: np.uint32


def _prepare(idx: int, sc: Scenario) -> _Prep:
    src_cls, staged = stage_source(sc.source)
    dets = as_detectors(sc.detectors)
    if dets:
        validate_detectors(dets, sc.volume.shape)
    det_geom = np.asarray(det_geometry(dets)) if dets else None
    lo, hi = split_id64(int(sc.id_offset))
    return _Prep(idx=idx, sc=sc, src_cls=src_cls, staged=staged, dets=dets,
                 det_geom=det_geom, id_lo=lo, id_hi=hi)


# ---------------------------------------------------------------------------
# grouping: the traced config shape
# ---------------------------------------------------------------------------

def group_key(sc: Scenario, n_lanes: int, mode: str = "dynamic",
              engine: str = "jnp", block_lanes: int = 256,
              interpret: bool | None = None) -> tuple:
    """Hashable structural signature of one scenario's traced shape.

    Scenarios sharing this key run in one vmapped call and compile to
    one executable: volume dims + unitinmm + media count, the full
    ``SimConfig`` (K, ntg, reflection, caps — all static), the executor
    config (lanes, mode, engine, block size, interpret), the source's
    staged structure (type + param shapes) and the detector count.
    Per-scenario *values* — media tables, source params, seeds, photon
    budgets, detector coordinates — are deliberately absent: they are
    traced.
    """
    prep = _prepare(0, sc)
    return _group_key(prep, n_lanes, mode, engine, block_lanes, interpret)


def _group_key(prep: _Prep, n_lanes, mode, engine, block_lanes, interpret):
    v = prep.sc.volume
    src_struct = (prep.src_cls.type_name,
                  tuple((k, tuple(np.shape(prep.staged[k])))
                        for k in sorted(prep.staged)))
    return (tuple(int(x) for x in v.shape), float(v.unitinmm),
            int(v.media.shape[0]), prep.sc.cfg, int(n_lanes), mode, engine,
            int(block_lanes), interpret, src_struct, len(prep.dets))


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

class CompileCache:
    """Explicit LRU executable cache for :func:`simulate_many`.

    Keys are ``(group key, padded batch size, labels shared?, mesh
    signature)`` — exactly the trace-time shape of the batched call, so
    a hit is guaranteed to reuse the compiled executable (same jitted
    callable, same input avals).  ``max_entries`` bounds the cache with
    keyed LRU eviction; hit/miss/eviction counts are plain attributes
    (surfaced as telemetry counters by ``simulate_many``).
    """

    def __init__(self, max_entries: int | None = None):
        if max_entries is not None and max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self._entries: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    def get(self, key):
        """Look up an executable; counts a hit or a miss."""
        if key in self._entries:
            self._entries.move_to_end(key)
            self.hits += 1
            return self._entries[key]
        self.misses += 1
        return None

    def put(self, key, fn) -> None:
        self._entries[key] = fn
        self._entries.move_to_end(key)
        while (self.max_entries is not None
               and len(self._entries) > self.max_entries):
            self._entries.popitem(last=False)
            self.evictions += 1

    def clear(self) -> None:
        self._entries.clear()

    def stats(self) -> dict:
        """Counters + hit rate (1.0 on an all-hit repeat-shape run)."""
        total = self.hits + self.misses
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "entries": len(self._entries),
                "hit_rate": self.hits / total if total else 0.0}


_DEFAULT_CACHE = CompileCache(max_entries=64)


def default_cache() -> CompileCache:
    """The process-wide cache ``simulate_many`` uses when none is given."""
    return _DEFAULT_CACHE


# ---------------------------------------------------------------------------
# the batched executor
# ---------------------------------------------------------------------------

def _raw_batched_fn(rep: _Prep, n_lanes, mode, engine, block_lanes,
                    interpret, share_labels: bool):
    """vmap the per-scenario closure over the leading scenario axis.

    The inner ``one`` rebuilds ``build_sim_fn`` at trace time with the
    scenario's *traced* staged source params and detector geometry —
    closures over vmap tracers, so no per-scenario value is baked in.
    """
    vol = rep.sc.volume
    shape, unitinmm, cfg = vol.shape, vol.unitinmm, rep.sc.cfg
    src_cls, dets = rep.src_cls, rep.dets
    n_det = len(dets)

    def one(labels_flat, media, staged, det_geom, n_photons, seed,
            id_lo, id_hi):
        fn = build_sim_fn(shape, unitinmm, cfg, n_lanes, mode,
                          StagedSource(src_cls, staged), engine,
                          block_lanes, interpret, dets,
                          det_geom_override=det_geom)
        return fn(labels_flat, media, n_photons, seed, id_lo, id_hi)

    in_axes = (None if share_labels else 0, 0, 0, 0 if n_det else None,
               0, 0, 0, 0)
    return jax.vmap(one, in_axes=in_axes)


def _mesh_signature(mesh):
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(int(x) for x in mesh.shape.values()),
            tuple(d.id for d in mesh.devices.flat))


def _shard_batched_fn(vmapped, mesh, share_labels: bool, n_det: int):
    """Compose the scenario axis with a device mesh: shard axis 0 of
    every stacked input across the mesh's first axis name.  Disjoint
    scenarios need no collectives — out_specs keep the scenario axis
    sharded and jit reassembles the global batch."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core.multidevice import _shard_map

    # jax.make_mesh gives Explicit axes, under which slicing one scenario
    # out of the sharded batch (simulate_many) is a type error; the same
    # devices with Auto axes leave the placement to the compiler
    mesh = Mesh(mesh.devices, mesh.axis_names)
    ax = mesh.axis_names[0]
    sspec = P(ax)
    in_specs = (P() if share_labels else sspec, sspec, sspec,
                sspec if n_det else P(), sspec, sspec, sspec, sspec)
    return _shard_map(vmapped, mesh=mesh, in_specs=in_specs,
                      out_specs=sspec)


def _stack_group(members: list[_Prep], pad: int, share_labels: bool):
    """Stack the group's per-scenario traced values, zero-photon-padding
    the batch by ``pad`` copies of the first scenario (they terminate
    before the first round, so padding never perturbs real results)."""
    rows = members + [members[0]] * pad
    n_real = len(members)

    def counts(i, m):
        return np.int32(m.sc.n_photons if i < n_real else 0)

    labels0 = np.asarray(rows[0].sc.volume.labels).reshape(-1)
    if share_labels:
        labels = jnp.asarray(labels0)
    else:
        labels = jnp.asarray(np.stack(
            [np.asarray(m.sc.volume.labels).reshape(-1) for m in rows]))
    media = jnp.asarray(np.stack(
        [np.asarray(m.sc.volume.media) for m in rows]))
    staged = {k: jnp.asarray(np.stack(
        [np.asarray(m.staged[k]) for m in rows]))
        for k in rows[0].staged}
    det_geom = (jnp.asarray(np.stack([m.det_geom for m in rows]))
                if rows[0].det_geom is not None else None)
    n_photons = jnp.asarray(
        np.asarray([counts(i, m) for i, m in enumerate(rows)], np.int32))
    seeds = jnp.asarray(
        np.asarray([np.uint32(m.sc.seed) for m in rows], np.uint32))
    id_lo = jnp.asarray(np.asarray([m.id_lo for m in rows], np.uint32))
    id_hi = jnp.asarray(np.asarray([m.id_hi for m in rows], np.uint32))
    return (labels, media, staged, det_geom, n_photons, seeds, id_lo, id_hi)


def _share_labels(members: list[_Prep]) -> bool:
    first = np.asarray(members[0].sc.volume.labels)
    for m in members[1:]:
        lab = m.sc.volume.labels
        if lab is members[0].sc.volume.labels:
            continue
        if not np.array_equal(np.asarray(lab), first):
            return False
    return True


def make_batched(scenarios, *, n_lanes: int = 1024, mode: str = "dynamic",
                 engine: str = "jnp", block_lanes: int = 256,
                 interpret: bool | None = None):
    """Build the raw (unjitted) batched fn + stacked args for scenarios
    that all share one group key.

    The building block ``simulate_many`` jits and caches; exposed so
    tracelint (REP805) and tests can prove the jaxpr is value-free:
    re-tracing with a different same-shape batch must fingerprint
    byte-identically.  Raises when the scenarios span multiple groups.
    """
    preps = [_prepare(i, sc) for i, sc in enumerate(scenarios)]
    if not preps:
        raise ValueError("make_batched needs at least one scenario")
    keys = {_group_key(p, n_lanes, mode, engine, block_lanes, interpret)
            for p in preps}
    if len(keys) != 1:
        raise ValueError(
            f"make_batched needs a single scenario group, got {len(keys)} "
            f"distinct config shapes; group with group_key() first")
    share = _share_labels(preps)
    fn = _raw_batched_fn(preps[0], n_lanes, mode, engine, block_lanes,
                         interpret, share)
    args = _stack_group(preps, 0, share)
    return fn, args


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def simulate_one(sc: Scenario, *, n_lanes: int = 1024,
                 mode: str = "dynamic", engine: str = "jnp",
                 block_lanes: int = 256,
                 interpret: bool | None = None) -> SimResult:
    """The sequential reference: one scenario through the unbatched
    engine (static source path, static detector geometry).  The
    bit-identity contract — and the scenario-matrix CI lane — compare
    ``simulate_many`` against a loop of these."""
    vol = sc.volume
    fn = jax.jit(build_sim_fn(vol.shape, vol.unitinmm, sc.cfg, n_lanes,
                              mode, as_source(sc.source), engine,
                              block_lanes, interpret,
                              as_detectors(sc.detectors)))
    return fn(vol.labels.reshape(-1), vol.media, sc.n_photons, sc.seed,
              *split_id64(int(sc.id_offset)))


def simulate_many(scenarios, *, n_lanes: int = 1024, mode: str = "dynamic",
                  engine: str = "jnp", block_lanes: int = 256,
                  interpret: bool | None = None, mesh=None,
                  cache: CompileCache | None = None,
                  tracer=None) -> list[SimResult]:
    """Run many scenarios through shared vmapped executables.

    Scenarios group by :func:`group_key`; each group becomes one batched
    call whose executable comes from ``cache`` (:func:`default_cache`
    when None) — new scenario *values* of a known shape never recompile.
    ``mesh`` shards each group's scenario axis across the mesh's first
    axis (zero-photon padding rounds the batch up to the device count).
    ``tracer`` (else :func:`repro.telemetry.default_tracer`) records a
    ``scenarios.simulate_many`` span whose children are
    ``scenarios.prepare``, one ``scenarios.batch`` per group (holding a
    ``scenarios.compile`` span per cache miss, compilation alone, and a
    ``scenarios.execute`` span, dispatch to outputs ready) and
    ``scenarios.split``; and ``scenarios.cache.{hit,miss,evictions,
    hit_rate}`` counters.

    Returns per-scenario ``SimResult``\\ s in input order, each
    bit-identical to its own :func:`simulate_one`, once they are ready.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine: {engine!r} (choose from {ENGINES})")
    scenarios = list(scenarios)
    if not scenarios:
        return []
    cache = default_cache() if cache is None else cache
    tracer = default_tracer() if tracer is None else tracer
    with tracer.span("scenarios.simulate_many", engine=engine,
                     scenarios=len(scenarios),
                     photons=int(sum(sc.n_photons for sc in scenarios))):
        with tracer.span("scenarios.prepare", scenarios=len(scenarios)):
            preps = [_prepare(i, sc) for i, sc in enumerate(scenarios)]
            groups: OrderedDict = OrderedDict()
            for p in preps:
                gkey = _group_key(p, n_lanes, mode, engine, block_lanes,
                                  interpret)
                groups.setdefault(gkey, []).append(p)
        n_dev = 1
        if mesh is not None:
            n_dev = int(np.prod([mesh.shape[a]
                                 for a in mesh.axis_names[:1]]))
        batches = []
        evictions0 = cache.evictions
        for gkey, members in groups.items():
            share = _share_labels(members)
            pad = (-len(members)) % n_dev
            s_pad = len(members) + pad
            key = (gkey, s_pad, share, _mesh_signature(mesh))
            fn = cache.get(key)
            hit = fn is not None
            total_photons = int(sum(m.sc.n_photons for m in members))
            tracer.counter("scenarios.cache." + ("hit" if hit else "miss"),
                           1, engine=engine, scenarios=len(members))
            with tracer.span("scenarios.batch", device=(
                    "mesh" if mesh is not None else None), engine=engine,
                    photons=total_photons, scenarios=len(members),
                    cache_hit=hit):
                args = _stack_group(members, pad, share)
                if not hit:
                    with tracer.span("scenarios.compile", engine=engine,
                                     scenarios=s_pad):
                        raw = _raw_batched_fn(members[0], n_lanes, mode,
                                              engine, block_lanes,
                                              interpret, share)
                        if mesh is not None:
                            raw = _shard_batched_fn(raw, mesh, share,
                                                    len(members[0].dets))
                        fn = jax.jit(raw)
                        # the call below finds this executable in the
                        # jit's caches
                        fn.lower(*args).compile()
                    cache.put(key, fn)
                with tracer.span("scenarios.execute", engine=engine,
                                 photons=total_photons, lanes=n_lanes,
                                 scenarios=len(members)):
                    res = jax.block_until_ready(fn(*args))
            batches.append((members, res))
        out: list = [None] * len(scenarios)
        with tracer.span("scenarios.split", scenarios=len(scenarios)):
            for members, res in batches:
                for j, m in enumerate(members):
                    out[m.idx] = jax.tree_util.tree_map(
                        lambda a, j=j: a[j], res)
            jax.block_until_ready(out)
    st = cache.stats()
    tracer.counter("scenarios.cache.hit_rate", st["hit_rate"], engine=engine)
    tracer.counter("scenarios.cache.evictions",
                   cache.evictions - evictions0, engine=engine)
    return out
