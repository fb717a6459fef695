"""Source subsystem core: protocol, registry, RNG streams, geometry helpers.

A *source* turns global photon ids into launch states.  Every source is a
frozen dataclass with static (Python-scalar / tuple) parameters and one
method::

    sample(photon_ids, seed) -> (pos, dir, w0, rng)

with ``pos``/``dir`` of shape (N, 3) float32 (voxel units / unit
vectors), ``w0`` the (N,) initial packet weight and ``rng`` the (N, 4)
uint32 in-flight xorshift128 state.  ``photon_ids`` is either a plain
(N,) uint32 array (legacy 32-bit ids) or an :class:`repro.core.rng.
PhotonId` two-word pair — 64-bit global ids for campaigns beyond 2**32
photons.  Sources never do id arithmetic themselves: they read
``photon_ids.shape[0]`` and hand the ids to the stream constructors
below, so every registered type is 64-bit-clean by construction.

Determinism contract (DESIGN.md §sources):

  * ``sample`` is a pure function of (photon_ids, seed) and the static
    source parameters — no hidden state, no host randomness.
  * Launch-time randomness is drawn from a dedicated *launch stream*,
    counter-seeded from ``(seed ^ LAUNCH_STREAM_SALT, photon_id)``.  The
    in-flight stream stays seeded from ``(seed, photon_id)`` exactly as
    before, so switching source type never perturbs trajectories-given-
    launch-state, and the pencil beam (zero draws) is bit-identical to
    the historical hard-coded launch.
  * Each source type consumes a fixed number of launch-stream uniforms
    per photon (``N_DRAWS``), independent of runtime values.

A type whose photons all start at its staged ``pos`` declares
``POINT = True`` (pencil, isotropic, cone): ``launch_point`` gives that
position, and the engine looks up the starting medium label there once
(``photon.point_label``) instead of once per lane at every launch.

Together with the counter-based seeding this makes every source
bit-reproducible across single-device, shard_map multi-device
(``id_offset`` ranges), chunked, and restarted runs: photon ``k`` gets
the same launch state and trajectory no matter which lane, device, or
process simulates it.
"""

from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import jax.numpy as jnp
import numpy as np

from repro.core import rng as xrng
from repro.core.photon import normalize3

# Domain-separation salt for the launch stream.  XORed into the master
# seed so launch-time draws are decorrelated from the in-flight stream
# (which keeps using the unsalted seed) without consuming from it.
LAUNCH_STREAM_SALT = 0xA511CE50


@runtime_checkable
class PhotonSource(Protocol):
    """Structural type every registered source satisfies."""

    def sample(self, photon_ids, seed):
        """(photon_ids, seed) -> (pos, dir, w0, rng) per-lane launch state."""
        ...


# ---------------------------------------------------------------------------
# RNG streams
# ---------------------------------------------------------------------------

def launch_stream(seed, photon_ids) -> jnp.ndarray:
    """Per-photon launch-time RNG state (salted counter seed).

    ``photon_ids`` may be a plain uint32 array or an ``rng.PhotonId``
    pair (64-bit ids); both words fold into the seeding.
    """
    seed = jnp.asarray(seed, jnp.uint32) ^ jnp.uint32(LAUNCH_STREAM_SALT)
    return xrng.seed_state(seed, photon_ids)


def flight_stream(seed, photon_ids) -> jnp.ndarray:
    """Per-photon in-flight RNG state — identical to the historical
    seeding for ids below 2**32 (plain arrays or ``PhotonId`` alike)."""
    return xrng.seed_state(jnp.asarray(seed, jnp.uint32), photon_ids)


# ---------------------------------------------------------------------------
# geometry helpers (static params -> trace-time numpy, lane math -> jnp)
# ---------------------------------------------------------------------------

def unit(v) -> jnp.ndarray:
    """Normalize a static 3-vector in float64, return float32 (matches the
    historical ``Source.dir_array`` arithmetic bit-for-bit)."""
    d = np.asarray(v, np.float64)  # reprolint: disable=REP301 - f64 normalize, f32 result
    return jnp.asarray(d / np.linalg.norm(d), jnp.float32)


def orthonormal_frame(axis) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two unit vectors spanning the plane perpendicular to a static axis."""
    a = np.asarray(axis, np.float64)  # reprolint: disable=REP301 - f64 normalize, f32 result
    a = a / np.linalg.norm(a)
    h = np.array([0.0, 0.0, 1.0]) if abs(a[2]) < 0.9 else np.array([1.0, 0.0, 0.0])
    e1 = np.cross(h, a)
    e1 = e1 / np.linalg.norm(e1)
    e2 = np.cross(a, e1)
    return jnp.asarray(e1, jnp.float32), jnp.asarray(e2, jnp.float32)


def isotropic_direction(u_cos, u_phi) -> jnp.ndarray:
    """Unit directions uniform over the sphere from two launch uniforms.

    Shared by every isotropically-emitting source so the arithmetic (and
    therefore the bit-level result for a given launch stream) is defined
    in exactly one place.
    """
    cost = 2.0 * u_cos - 1.0
    sint = jnp.sqrt(jnp.maximum(1.0 - cost * cost, 0.0))
    phi = (2.0 * np.pi) * u_phi
    return jnp.stack(
        [sint * jnp.cos(phi), sint * jnp.sin(phi), cost], axis=-1
    )


def radial_offset(pos, r, u_phi, e1, e2) -> jnp.ndarray:
    """Offset (N, 3) positions by radius ``r`` at azimuth ``2π·u_phi`` in
    the plane spanned by ``(e1, e2)``.

    Shared by every radial beam profile (disk, Gaussian) so the offset
    arithmetic — and thus the bit-level launch state for a given stream —
    is defined in exactly one place; only the r(u) formula differs.
    """
    phi = (2.0 * np.pi) * u_phi
    return (
        pos
        + (r * jnp.cos(phi))[:, None] * e1
        + (r * jnp.sin(phi))[:, None] * e2
    )


def direction_from_axis(cost, phi, axis, e1, e2) -> jnp.ndarray:
    """Unit directions at polar cosine ``cost`` / azimuth ``phi`` around
    a static ``axis`` with perpendicular frame ``(e1, e2)``."""
    cost = jnp.clip(cost, -1.0, 1.0)
    sint = jnp.sqrt(jnp.maximum(1.0 - cost * cost, 0.0))
    d = (
        (sint * jnp.cos(phi))[:, None] * e1
        + (sint * jnp.sin(phi))[:, None] * e2
        + cost[:, None] * jnp.asarray(axis, jnp.float32)
    )
    return normalize3(d)


# ---------------------------------------------------------------------------
# staged launch parameters (scenario batching, DESIGN.md §batching)
# ---------------------------------------------------------------------------

class StagedSource:
    """Bind a source class's jnp sampling path to *traced* launch params.

    Every registered source splits its ``sample`` into a host-side
    ``stage()`` (the f64 derivations over static fields — unit vectors,
    orthonormal frames, cos of the half angle — rounded once to f32)
    and a pure-jnp ``sample_staged(staged, photon_ids, seed)`` that
    consumes only the staged dict.  ``sample`` is the composition, so
    the static path is unchanged; a ``StagedSource`` instead feeds
    ``sample_staged`` a dict of *tracers* — per-scenario launch params
    under ``vmap`` — through the identical op sequence, which is what
    makes `simulate_many` bit-identical to per-scenario runs.

    Hashable by identity (the staged values may be tracers), so
    ``as_source`` passes instances through untouched.
    """

    __slots__ = ("source_cls", "staged")

    def __init__(self, source_cls: type, staged: dict):
        self.source_cls = source_cls
        self.staged = dict(staged)

    def sample(self, photon_ids, seed):
        return self.source_cls.sample_staged(self.staged, photon_ids, seed)


def launch_point(source):
    """The one launch position every photon of a point source shares.

    Returns the staged ``pos`` (a traced per-scenario value under
    scenario batching) when the source's type declares ``POINT``, else
    ``None``: each lane's starting voxel may then differ and is looked
    up on its own (``photon.launch``).
    """
    if isinstance(source, StagedSource):
        cls, staged = source.source_cls, source.staged
    else:
        cls, staged = type(source), None
    if not getattr(cls, "POINT", False):
        return None
    return (source.stage() if staged is None else staged)["pos"]


def stage_source(source) -> tuple[type, dict]:
    """Coerce + stage: returns ``(source class, staged param dict)``.

    The staged dict holds concrete f32 arrays (host-derived launch
    parameters); scenario batching stacks them along a leading axis and
    rebinds them through :class:`StagedSource`.
    """
    src = as_source(source)
    if not hasattr(src, "stage"):
        raise TypeError(
            f"source {type(src).__qualname__} does not support staged "
            f"launch parameters (needs stage()/sample_staged(); required "
            f"for simulate_many batching)")
    return type(src), src.stage()


def staged_structure(source) -> tuple:
    """Hashable structural signature of a source's staged params.

    ``(type_name, ((param, shape), ...))`` — two sources share a
    compiled `simulate_many` executable exactly when this matches: the
    *values* of staged params are traced, but their presence and shapes
    (e.g. a Planar pattern's grid, a Line's collimated-vs-isotropic
    variant) are baked into the jaxpr.
    """
    cls, staged = stage_source(source)
    return (cls.type_name,
            tuple((k, tuple(np.shape(staged[k]))) for k in sorted(staged)))


# ---------------------------------------------------------------------------
# registry + config serialization
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type] = {}


def register(name: str):
    """Class decorator: add a source type to the registry under ``name``."""

    def deco(cls):
        cls.type_name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def available_sources() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_source_cls(name: str) -> type:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown source type {name!r}; registered: {available_sources()}"
        ) from None


def _jsonify(v):
    if isinstance(v, tuple):
        return [_jsonify(x) for x in v]
    return v


def _unjsonify(v):
    if isinstance(v, (list, tuple)):
        return tuple(_unjsonify(x) for x in v)
    return v


def to_dict(source) -> dict:
    """Serialize a registered source to a JSON-friendly campaign config."""
    d = dataclasses.asdict(source)
    return {"type": source.type_name, **{k: _jsonify(v) for k, v in d.items()}}


def from_dict(d: dict):
    """Rebuild a source from :func:`to_dict` output (lists become tuples
    so the instance stays frozen/hashable)."""
    d = dict(d)
    cls = get_source_cls(d.pop("type"))
    return cls(**{k: _unjsonify(v) for k, v in d.items()})


def as_source(source=None) -> PhotonSource:
    """Coerce user input to a source instance.

    Accepts ``None`` (pencil-beam default — the paper's configuration),
    a registered source instance, the legacy :class:`repro.core.volume.
    Source` pencil dataclass, or a :func:`to_dict`-style config dict.
    """
    from repro.core.volume import Source as LegacySource
    from repro.sources.types import Pencil

    if source is None:
        return Pencil()
    if isinstance(source, LegacySource):
        return Pencil(pos=tuple(source.pos), dir=tuple(source.dir))
    if isinstance(source, dict):
        return from_dict(source)
    if isinstance(source, PhotonSource):
        try:
            hash(source)
        except TypeError:
            if hasattr(source, "type_name"):
                # e.g. a registered dataclass built with list-typed fields:
                # normalize to tuples so jit caches keyed by source work
                return from_dict(to_dict(source))
            raise
        return source
    raise TypeError(f"cannot interpret {source!r} as a photon source")
