"""The source menu: MCX-style illumination patterns as frozen dataclasses.

All positions/lengths are in *voxel units* (like everything in the
engine; multiply mm by ``1/unitinmm`` to convert), directions need not
be normalized.  Sources should lie within the simulation domain; any
sampled launch position outside it is clamped onto the domain boundary
(see ``photon.launch``).  Each type documents its launch-stream draw count
(``N_DRAWS``) — part of the determinism contract in DESIGN.md §sources.

Registered types (see ``repro.sources.available_sources()``):

  pencil     zero-width collimated beam (the paper's configuration)
  isotropic  point source radiating uniformly over 4π
  cone       uniform solid-angle cone around an axis
  gaussian   collimated beam with Gaussian intensity profile
  disk       uniform-intensity flat circular beam
  planar     uniform parallelogram patch, optional intensity pattern
  line       line segment, collimated (slit) or isotropic emission

Every type is split into ``stage()`` — the host-side f64 derivations
over static fields (unit vectors, frames, trig), rounded once to f32 —
and a pure-jnp ``sample_staged(staged, photon_ids, seed)`` consuming
only the staged dict; ``sample`` is their composition.  Scenario
batching (repro.scenarios, DESIGN.md §batching) stacks staged dicts
along a leading axis and traces them through the same
``sample_staged``, so batched launches are bit-identical to static
ones.  Scalar fields are staged as f32 — the value JAX's weak-typed
promotion would have rounded the Python float to anyway, so the static
path's bits are unchanged.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp

from repro.core import rng as xrng
from repro.sources import base

_TWO_PI = 2.0 * math.pi

Vec3 = tuple[float, float, float]


def _broadcast_pos(pos, n: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.asarray(pos, jnp.float32), (n, 3))


def _ones(n: int) -> jnp.ndarray:
    return jnp.ones((n,), jnp.float32)


@base.register("pencil")
@dataclasses.dataclass(frozen=True)
class Pencil:
    """Zero-width collimated beam — bit-identical to the historical
    hard-coded launch (consumes no launch-stream draws)."""

    pos: Vec3 = (30.0, 30.0, 0.0)
    dir: Vec3 = (0.0, 0.0, 1.0)

    N_DRAWS = 0
    POINT = True  # every photon starts at the staged ``pos``

    def stage(self):
        return {"pos": jnp.asarray(self.pos, jnp.float32),
                "dir": base.unit(self.dir)}

    @staticmethod
    def sample_staged(p, photon_ids, seed):
        n = photon_ids.shape[0]
        direc = jnp.broadcast_to(p["dir"], (n, 3))
        return (_broadcast_pos(p["pos"], n), direc, _ones(n),
                base.flight_stream(seed, photon_ids))

    def sample(self, photon_ids, seed):
        return self.sample_staged(self.stage(), photon_ids, seed)


@base.register("isotropic")
@dataclasses.dataclass(frozen=True)
class IsotropicPoint:
    """Point source radiating uniformly over the full sphere."""

    pos: Vec3 = (30.0, 30.0, 30.0)

    N_DRAWS = 2  # u_cos, u_phi
    POINT = True  # every photon starts at the staged ``pos``

    def stage(self):
        return {"pos": jnp.asarray(self.pos, jnp.float32)}

    @staticmethod
    def sample_staged(p, photon_ids, seed):
        n = photon_ids.shape[0]
        ls = base.launch_stream(seed, photon_ids)
        ls, u_cos = xrng.next_uniform(ls)
        ls, u_phi = xrng.next_uniform(ls)
        direc = base.isotropic_direction(u_cos, u_phi)
        return (_broadcast_pos(p["pos"], n), direc, _ones(n),
                base.flight_stream(seed, photon_ids))

    def sample(self, photon_ids, seed):
        return self.sample_staged(self.stage(), photon_ids, seed)


@base.register("cone")
@dataclasses.dataclass(frozen=True)
class Cone:
    """Point source emitting uniformly into a cone of ``half_angle_deg``
    around ``dir`` (an optical-fiber numerical-aperture model)."""

    pos: Vec3 = (30.0, 30.0, 0.0)
    dir: Vec3 = (0.0, 0.0, 1.0)
    half_angle_deg: float = 15.0

    N_DRAWS = 2  # u_cos, u_phi
    POINT = True  # every photon starts at the staged ``pos``

    def stage(self):
        e1, e2 = base.orthonormal_frame(self.dir)
        cos_half = math.cos(math.radians(self.half_angle_deg))
        return {"pos": jnp.asarray(self.pos, jnp.float32),
                "axis": base.unit(self.dir), "e1": e1, "e2": e2,
                # staged as the 1 - cos form the cap formula consumes, so
                # the single f64->f32 rounding matches the historical
                # weak-scalar promotion of (1.0 - cos_half)
                "one_minus_cos_half": jnp.float32(1.0 - cos_half)}

    @staticmethod
    def sample_staged(p, photon_ids, seed):
        n = photon_ids.shape[0]
        ls = base.launch_stream(seed, photon_ids)
        ls, u_cos = xrng.next_uniform(ls)
        ls, u_phi = xrng.next_uniform(ls)
        # uniform over the spherical cap [cos_half, 1]
        cost = 1.0 - u_cos * p["one_minus_cos_half"]
        direc = base.direction_from_axis(cost, _TWO_PI * u_phi, p["axis"],
                                         p["e1"], p["e2"])
        return (_broadcast_pos(p["pos"], n), direc, _ones(n),
                base.flight_stream(seed, photon_ids))

    def sample(self, photon_ids, seed):
        return self.sample_staged(self.stage(), photon_ids, seed)


@base.register("gaussian")
@dataclasses.dataclass(frozen=True)
class GaussianBeam:
    """Collimated beam with Gaussian intensity profile of 1/e² radius
    ``waist`` (voxel units), centered on ``pos`` and propagating along
    ``dir``: r = waist·sqrt(-ln u / 2)."""

    pos: Vec3 = (30.0, 30.0, 0.0)
    dir: Vec3 = (0.0, 0.0, 1.0)
    waist: float = 3.0

    N_DRAWS = 2  # u_r, u_phi

    def stage(self):
        e1, e2 = base.orthonormal_frame(self.dir)
        return {"pos": jnp.asarray(self.pos, jnp.float32),
                "dir": base.unit(self.dir), "e1": e1, "e2": e2,
                "waist": jnp.float32(self.waist)}

    @staticmethod
    def sample_staged(p, photon_ids, seed):
        n = photon_ids.shape[0]
        ls = base.launch_stream(seed, photon_ids)
        ls, u_r = xrng.next_uniform(ls)
        ls, u_phi = xrng.next_uniform(ls)
        r = p["waist"] * jnp.sqrt(-jnp.log(u_r) * 0.5)
        pos = base.radial_offset(_broadcast_pos(p["pos"], n), r, u_phi,
                                 p["e1"], p["e2"])
        direc = jnp.broadcast_to(p["dir"], (n, 3))
        return pos, direc, _ones(n), base.flight_stream(seed, photon_ids)

    def sample(self, photon_ids, seed):
        return self.sample_staged(self.stage(), photon_ids, seed)


@base.register("disk")
@dataclasses.dataclass(frozen=True)
class Disk:
    """Uniform-intensity collimated circular beam of ``radius`` voxels."""

    pos: Vec3 = (30.0, 30.0, 0.0)
    dir: Vec3 = (0.0, 0.0, 1.0)
    radius: float = 5.0

    N_DRAWS = 2  # u_r, u_phi

    def stage(self):
        e1, e2 = base.orthonormal_frame(self.dir)
        return {"pos": jnp.asarray(self.pos, jnp.float32),
                "dir": base.unit(self.dir), "e1": e1, "e2": e2,
                "radius": jnp.float32(self.radius)}

    @staticmethod
    def sample_staged(p, photon_ids, seed):
        n = photon_ids.shape[0]
        ls = base.launch_stream(seed, photon_ids)
        ls, u_r = xrng.next_uniform(ls)
        ls, u_phi = xrng.next_uniform(ls)
        r = p["radius"] * jnp.sqrt(u_r)  # uniform over the disk area
        pos = base.radial_offset(_broadcast_pos(p["pos"], n), r, u_phi,
                                 p["e1"], p["e2"])
        direc = jnp.broadcast_to(p["dir"], (n, 3))
        return pos, direc, _ones(n), base.flight_stream(seed, photon_ids)

    def sample(self, photon_ids, seed):
        return self.sample_staged(self.stage(), photon_ids, seed)


@base.register("planar")
@dataclasses.dataclass(frozen=True)
class Planar:
    """Collimated area source over the parallelogram ``pos + a·v1 + b·v2``
    (a, b uniform in [0, 1)).

    ``pattern`` (optional, row-major tuple-of-tuples) modulates the
    initial packet weight like MCX's pattern source: the patch is split
    into len(pattern) × len(pattern[0]) cells along (v1, v2) and a photon
    launched in cell (i, j) starts with w0 = pattern[i][j].  Positions
    stay uniform; only weights vary — SDS-style structured illumination
    without rejection sampling.
    """

    pos: Vec3 = (20.0, 20.0, 0.0)
    v1: Vec3 = (20.0, 0.0, 0.0)
    v2: Vec3 = (0.0, 20.0, 0.0)
    dir: Vec3 = (0.0, 0.0, 1.0)
    pattern: tuple = ()

    N_DRAWS = 2  # u_a, u_b

    def stage(self):
        p = {"pos": jnp.asarray(self.pos, jnp.float32),
             "v1": jnp.asarray(self.v1, jnp.float32),
             "v2": jnp.asarray(self.v2, jnp.float32),
             "dir": base.unit(self.dir)}
        # the pattern's *presence and grid shape* are structural (they
        # change the jaxpr); its weights are staged values
        if self.pattern:
            p["pattern"] = jnp.asarray(self.pattern, jnp.float32)
        return p

    @staticmethod
    def sample_staged(p, photon_ids, seed):
        n = photon_ids.shape[0]
        ls = base.launch_stream(seed, photon_ids)
        ls, u_a = xrng.next_uniform(ls)
        ls, u_b = xrng.next_uniform(ls)
        pos = (
            _broadcast_pos(p["pos"], n)
            + u_a[:, None] * p["v1"]
            + u_b[:, None] * p["v2"]
        )
        if "pattern" in p:
            pat = p["pattern"]
            rows, cols = pat.shape
            ia = jnp.clip((u_a * rows).astype(jnp.int32), 0, rows - 1)
            ib = jnp.clip((u_b * cols).astype(jnp.int32), 0, cols - 1)
            w0 = jnp.take(pat.reshape(-1), ia * cols + ib)
        else:
            w0 = _ones(n)
        direc = jnp.broadcast_to(p["dir"], (n, 3))
        return pos, direc, w0, base.flight_stream(seed, photon_ids)

    def sample(self, photon_ids, seed):
        return self.sample_staged(self.stage(), photon_ids, seed)


@base.register("line")
@dataclasses.dataclass(frozen=True)
class Line:
    """Line-segment source from ``start`` to ``end``.

    With ``dir`` set this is a slit (collimated along ``dir``); with
    ``dir=None`` each photon emits isotropically from its launch point.
    Always draws 3 launch uniforms so the stream layout is identical for
    both variants.
    """

    start: Vec3 = (20.0, 30.0, 0.0)
    end: Vec3 = (40.0, 30.0, 0.0)
    dir: Vec3 | None = (0.0, 0.0, 1.0)

    N_DRAWS = 3  # u_t, u_cos, u_phi

    def stage(self):
        p = {"start": jnp.asarray(self.start, jnp.float32),
             "end": jnp.asarray(self.end, jnp.float32)}
        # collimated-vs-isotropic is structural: the staged dict carries
        # a "dir" key exactly when the slit variant is selected
        if self.dir is not None:
            p["dir"] = base.unit(self.dir)
        return p

    @staticmethod
    def sample_staged(p, photon_ids, seed):
        n = photon_ids.shape[0]
        ls = base.launch_stream(seed, photon_ids)
        ls, u_t = xrng.next_uniform(ls)
        ls, u_cos = xrng.next_uniform(ls)
        ls, u_phi = xrng.next_uniform(ls)
        start, end = p["start"], p["end"]
        pos = start[None, :] + u_t[:, None] * (end - start)[None, :]
        if "dir" in p:
            direc = jnp.broadcast_to(p["dir"], (n, 3))
        else:
            direc = base.isotropic_direction(u_cos, u_phi)
        return pos, direc, _ones(n), base.flight_stream(seed, photon_ids)

    def sample(self, photon_ids, seed):
        return self.sample_staged(self.stage(), photon_ids, seed)


def demo_menu(size: int) -> dict:
    """One representative instance of every source type, scaled to a
    cubic domain of edge ``size`` voxels.  Shared by the source gallery
    example and the per-source throughput benchmark so both always
    exercise the same configurations."""
    c = size / 2.0
    q = size / 4.0
    return {
        "pencil": Pencil(pos=(c, c, 0.0)),
        "isotropic": IsotropicPoint(pos=(c, c, c)),
        "cone": Cone(pos=(c, c, 0.0), half_angle_deg=20.0),
        "gaussian": GaussianBeam(pos=(c, c, 0.0), waist=size / 12.0),
        "disk": Disk(pos=(c, c, 0.0), radius=size / 6.0),
        # checkerboard: structured illumination via launch weights
        "planar+pattern": Planar(
            pos=(q, q, 0.0), v1=(2 * q, 0.0, 0.0), v2=(0.0, 2 * q, 0.0),
            pattern=((1.0, 0.1, 1.0), (0.1, 1.0, 0.1), (1.0, 0.1, 1.0)),
        ),
        "line (slit)": Line(start=(q, c, 0.0), end=(3 * q, c, 0.0)),
        "line (isotropic)": Line(start=(q, c, c), end=(3 * q, c, c),
                                 dir=None),
    }
