"""Plain Monte Carlo reference for the benchmark's comparison of outputs.

An independent, straightforward implementation of the semantics the
configurations state (MCX-style voxel transport after Fang & Boas 2009;
Yu et al. 2017): every photon is one lane from launch to death, with no
regeneration, no rounds and no fused flush.  Per segment a photon moves
to its next scattering site or the next voxel wall, whichever is nearer,
deposits ``w (1 - exp(-mua s))`` into the voxel it moved through, then
scatters (Henyey-Greenstein) or crosses the wall (Fresnel reflection and
refraction where the indices differ and reflection is on; escape into
label 0 or out of the grid).  Below the weight threshold a photon
survives Russian roulette with probability ``1/m`` at ``m`` times its
weight; past ``tmax_ns`` its weight is retired as timed out.  A deposit
falls into the time gate of the photon's time at the end of the
segment, and an escape through the z=0 face (within a quarter voxel of
it) into the first detector disk that holds its exit point.

It imports nothing of the program under test.  A voxel's label comes
from the configuration's formula, evaluated where the photon is (a cube
of label 1, and a sphere of another label holding the voxels whose
centres lie within its radius), and a medium's properties by a select
over the few media, so the loop holds no gather.  Its random numbers
come from ``jax.random`` (the hardware generator, ``rbg``), so it never
sees the program's photon streams: the two
agree in distribution, not photon by photon, and the comparison
(``compare.py``) is statistical.  To give that comparison an error bar
of its own, the photons are split into ``n_batches`` equal batches and
every total is returned per batch.

Fluence is returned on coarse blocks (``blocks`` per axis) times time
gates, accumulated per segment by a one-hot contraction instead of a
scatter.  ``dtype`` is the float type of the whole transport and of its
accumulators: float32 is the reference, bfloat16 the lower-precision
control that the comparison has to refuse.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

C_MM_PER_NS = 299.792458
Z_FACE_VOX = 0.25      # an escape this close to z=0 left through that face
MIN_SEG_VOX = 1e-6     # least progress per segment
TINY = 1e-9


@dataclasses.dataclass(frozen=True)
class Domain:
    """Static description of one deployment, read from its config file."""

    shape: tuple[int, int, int]
    voxel_mm: float
    do_reflect: bool
    tmax_ns: float
    w_threshold: float
    roulette_m: float
    src_pos: tuple[float, float, float]
    src_dir: tuple[float, float, float]
    detectors: tuple[tuple[float, float, float], ...]   # (x, y, radius)
    n_gates: int
    n_media: int
    blocks: int
    sphere: tuple | None     # ((cx, cy, cz) mm, radius mm, label) or None

    @property
    def n_bins(self) -> int:
        return self.blocks ** 3


def domain_from_config(config: dict, blocks: int) -> Domain:
    """The reference's view of a configuration file (``configs/*.json``)."""
    beam = config["beam"]
    return Domain(
        shape=tuple(int(s) for s in config["shape"]),
        voxel_mm=float(config["voxel_mm"]),
        do_reflect=bool(config["do_reflect"]),
        tmax_ns=float(config["tmax_ns"]),
        w_threshold=float(config["roulette"]["w_threshold"]),
        roulette_m=float(config["roulette"]["m"]),
        src_pos=tuple(float(v) for v in beam["pos"]),
        src_dir=tuple(float(v) for v in beam["dir"]),
        detectors=tuple((float(d["x"]), float(d["y"]), float(d["radius"]))
                        for d in config.get("detectors", [])),
        n_gates=int(config.get("time_gates", 1)),
        n_media=len(config["media"]),
        blocks=int(blocks),
        sphere=_sphere(config),
    )


def _sphere(config: dict):
    sp = config.get("sphere")
    if sp is None:
        return None
    return (tuple(float(v) for v in sp["center_mm"]), float(sp["radius_mm"]),
            int(sp["label"]))


def media_from_config(config: dict, overrides: dict | None = None):
    """(n_media, 4) rows of (mua/mm, mus/mm, g, n); ``overrides`` maps a
    label to replacement values for some of its keys (sweeps)."""
    rows = [dict(m) for m in config["media"]]
    for label, new in (overrides or {}).items():
        rows[int(label)].update(new)
    return np.asarray([[r["mua"], r["mus"], r["g"], r["n"]] for r in rows],
                      np.float32)


def _block_of(ix, iy, iz, dom: Domain):
    b = dom.blocks
    nx, ny, nz = dom.shape
    bx = ix * b // nx
    by = iy * b // ny
    bz = iz * b // nz
    return (bx * b + by) * b + bz


def _hg_cos(g, u, dtype):
    """Henyey-Greenstein deflection cosine (Prahl's inversion)."""
    g_ok = jnp.abs(g) > 1e-5
    gs = jnp.where(g_ok, g, jnp.asarray(0.5, dtype))
    frac = (1 - gs * gs) / (1 - gs + 2 * gs * u)
    cos_hg = (1 + gs * gs - frac * frac) / (2 * gs)
    return jnp.clip(jnp.where(g_ok, cos_hg, 2 * u - 1), -1, 1)


def _spin(ux, uy, uz, cost, psi):
    """Rotate (ux, uy, uz) by polar cosine ``cost`` and azimuth ``psi``
    (MCML's formulas), then renormalise."""
    sint = jnp.sqrt(jnp.maximum(1 - cost * cost, 0))
    cp, sp = jnp.cos(psi), jnp.sin(psi)
    pole = jnp.abs(uz) > 0.99999
    tmp = jnp.sqrt(jnp.maximum(1 - uz * uz, 1e-12))
    nx = jnp.where(pole, sint * cp,
                   sint * (ux * uz * cp - uy * sp) / tmp + ux * cost)
    ny = jnp.where(pole, sint * sp,
                   sint * (uy * uz * cp + ux * sp) / tmp + uy * cost)
    nz = jnp.where(pole, cost * jnp.sign(uz), -sint * cp * tmp + uz * cost)
    norm = jnp.sqrt(nx * nx + ny * ny + nz * nz)
    return nx / norm, ny / norm, nz / norm


def _fresnel(n1, n2, cos_i):
    """Unpolarised reflectance and transmitted cosine."""
    eta = n1 / n2
    sin2_t = eta * eta * jnp.maximum(1 - cos_i * cos_i, 0)
    tir = sin2_t >= 1
    cos_t = jnp.sqrt(jnp.maximum(1 - sin2_t, 0))
    rs = ((n1 * cos_i - n2 * cos_t) / (n1 * cos_i + n2 * cos_t)) ** 2
    rp = ((n1 * cos_t - n2 * cos_i) / (n1 * cos_t + n2 * cos_i)) ** 2
    r = jnp.where(tir, 1, 0.5 * (rs + rp))
    return jnp.nan_to_num(r, nan=1.0), cos_t


@functools.partial(jax.jit, static_argnames=("dom", "n_photons", "n_batches",
                                             "dtype", "max_iters"))
def _transport(media, seed, *, dom: Domain, n_photons: int,
               n_batches: int, dtype, max_iters: int):
    n = n_photons
    nx, ny, nz = dom.shape
    M, L = n_batches, n_photons // n_batches
    G, NB, NM = dom.n_gates, dom.n_bins, dom.n_media
    f = functools.partial(jnp.asarray, dtype=dtype)
    h = f(dom.voxel_mm)
    media = media.astype(dtype)
    prec = jax.lax.Precision.HIGHEST
    key = jax.random.key(seed, impl="rbg")

    def label_at(ix, iy, iz):
        inside = ((ix >= 0) & (ix < nx) & (iy >= 0) & (iy < ny)
                  & (iz >= 0) & (iz < nz))
        lab = jnp.where(inside, 1, 0)
        if dom.sphere is not None:
            (cx, cy, cz), r, sl = dom.sphere
            hv = dom.voxel_mm
            d2 = (((ix + 0.5) * hv - cx) ** 2 + ((iy + 0.5) * hv - cy) ** 2
                  + ((iz + 0.5) * hv - cz) ** 2)
            lab = jnp.where(inside & (d2 <= r * r), sl, lab)
        return lab

    def prop(lab, k):
        """Column ``k`` of the media table at labels ``lab``."""
        out = jnp.broadcast_to(media[NM - 1, k], lab.shape)
        for m in range(NM - 2, -1, -1):
            out = jnp.where(lab == m, media[m, k], out)
        return out

    zeros = jnp.zeros((n,), dtype)
    px, py, pz = (jnp.full((n,), f(v)) for v in dom.src_pos)
    d = np.asarray(dom.src_dir, np.float64)
    d = d / np.linalg.norm(d)
    ux, uy, uz = (jnp.full((n,), f(v)) for v in d)
    ix, iy, iz = (jnp.full((n,), min(max(int(math.floor(v)), 0), s - 1),
                           jnp.int32)
                  for v, s in zip(dom.src_pos, dom.shape))
    state = dict(
        px=px, py=py, pz=pz, ux=ux, uy=uy, uz=uz, ix=ix, iy=iy, iz=iz,
        w=jnp.ones((n,), dtype), s=zeros, t=zeros,
        alive=jnp.ones((n,), bool),
        absorbed=zeros, escaped=zeros, exit0=zeros, timed=zeros,
        pp=jnp.zeros((NM, n), dtype),
        cap_det=jnp.full((n,), -1, jnp.int32),
        cap_gate=jnp.zeros((n,), jnp.int32),
        cap_w=zeros, cap_pp=jnp.zeros((NM, n), dtype),
        bins=jnp.zeros((M, NB, G), dtype),
        it=jnp.int32(0),
    )

    def cond(c):
        return jnp.any(c["alive"]) & (c["it"] < max_iters)

    def body(c):
        alive = c["alive"]
        u = jax.random.uniform(jax.random.fold_in(key, c["it"]), (5, n),
                               jnp.float32).astype(dtype)
        lab = label_at(c["ix"], c["iy"], c["iz"])
        mua = prop(lab, 0) * h
        mus = prop(lab, 1) * h
        g = prop(lab, 2)
        n_here = prop(lab, 3)
        s = jnp.where(c["s"] <= 0, -jnp.log(1 - u[0]), c["s"])
        pos = (c["px"], c["py"], c["pz"])
        dir_ = (c["ux"], c["uy"], c["uz"])
        vox = (c["ix"], c["iy"], c["iz"])
        dist = []
        for p, v, i in zip(pos, dir_, vox):
            fi = i.astype(dtype)
            up = (fi + 1 - p) / jnp.where(v > TINY, v, 1)
            down = (fi - p) / jnp.where(v < -TINY, v, 1)
            dd = jnp.where(v > TINY, up, jnp.where(v < -TINY, down, 1e30))
            dist.append(jnp.maximum(dd, 0))
        d_wall = jnp.minimum(jnp.minimum(dist[0], dist[1]), dist[2])
        axis = jnp.where(dist[0] <= jnp.minimum(dist[1], dist[2]), 0,
                         jnp.where(dist[1] <= dist[2], 1, 2))
        d_scat = jnp.where(mus > TINY, s / jnp.maximum(mus, TINY), 1e30)
        wall = d_wall < d_scat
        seg = jnp.maximum(jnp.where(wall, d_wall, d_scat), MIN_SEG_VOX)
        npos = [p + v * seg for p, v in zip(pos, dir_)]
        s = jnp.where(wall, s - seg * mus, 0)
        t = c["t"] + seg * h * n_here / C_MM_PER_NS
        w_after = c["w"] * jnp.exp(-mua * seg)
        dep = jnp.where(alive, c["w"] - w_after, 0)
        gate = jnp.clip(jnp.floor(t * (G / dom.tmax_ns)).astype(jnp.int32),
                        0, G - 1)
        pp = c["pp"] + jnp.where(
            (jnp.arange(NM)[:, None] == lab[None, :]) & alive[None, :],
            (seg * h)[None, :], 0)

        # deposit into (batch, block, gate) by a one-hot contraction
        blk = _block_of(c["ix"], c["iy"], c["iz"], dom)
        oh_b = (blk.reshape(M, 1, L) == jnp.arange(NB)[None, :, None])
        oh_g = (gate.reshape(M, 1, L) == jnp.arange(G)[None, :, None])
        bins = c["bins"] + jnp.einsum(
            "ml,mbl,mgl->mbg", dep.reshape(M, L), oh_b.astype(dtype),
            oh_g.astype(dtype), precision=prec,
            preferred_element_type=dtype)

        # scatter
        cost = _hg_cos(g, u[1], dtype)
        sx, sy, sz = _spin(*dir_, cost, 2 * np.pi * u[2])

        # wall crossing
        on = [axis == k for k in range(3)]
        d_ax = jnp.where(on[0], dir_[0], jnp.where(on[1], dir_[1], dir_[2]))
        step = jnp.where(d_ax > 0, 1, -1).astype(jnp.int32)
        nvox = [i + jnp.where(o, step, 0) for i, o in zip(vox, on)]
        lab_next = label_at(*nvox)
        n_next = prop(lab_next, 3)
        mismatch = jnp.abs(n_next - n_here) > 1e-6
        bx, by, bz = dir_
        reflect = jnp.zeros((n,), bool)
        if dom.do_reflect:
            r, cos_t = _fresnel(n_here, n_next, jnp.abs(d_ax))
            reflect = wall & mismatch & (u[3] < r)
            eta = n_here / n_next
            tx, ty, tz = (jnp.where(o, jnp.sign(d_ax) * cos_t, v * eta)
                          for o, v in zip(on, dir_))
            tn = jnp.sqrt(tx * tx + ty * ty + tz * tz)
            tx, ty, tz = tx / tn, ty / tn, tz / tn
            bend = mismatch
            bx, by, bz = (jnp.where(reflect, jnp.where(o, -v, v),
                                    jnp.where(bend, tv, v))
                          for o, v, tv in zip(on, dir_, (tx, ty, tz)))
        escape = alive & wall & ~reflect & (lab_next == 0)
        advance = alive & wall & ~reflect & ~escape
        scat = alive & ~wall
        new_dir = [jnp.where(scat, sv, jnp.where(wall, bv, v))
                   for sv, bv, v in zip((sx, sy, sz), (bx, by, bz), dir_)]

        # escape, detectors
        esc_w = jnp.where(escape, w_after, 0)
        face = escape & (npos[2] < Z_FACE_VOX)
        cap_det = c["cap_det"]
        cap_gate, cap_w, cap_pp = c["cap_gate"], c["cap_w"], c["cap_pp"]
        hit_any = jnp.zeros((n,), bool)
        for k, (dx, dy, rad) in reversed(list(enumerate(dom.detectors))):
            hit = face & ((npos[0] - dx) ** 2 + (npos[1] - dy) ** 2
                          <= rad * rad)
            cap_det = jnp.where(hit, k, cap_det)
            hit_any = hit_any | hit
        cap_gate = jnp.where(hit_any, gate, cap_gate)
        cap_w = jnp.where(hit_any, w_after, cap_w)
        cap_pp = jnp.where(hit_any[None, :], pp, cap_pp)

        # roulette, then the time limit
        live = alive & ~escape
        low = live & (w_after < dom.w_threshold)
        survive = u[4] < 1 / dom.roulette_m
        w = jnp.where(low, jnp.where(survive, w_after * dom.roulette_m, 0),
                      w_after)
        live = live & ~(low & ~survive)
        late = live & (t > dom.tmax_ns)
        live = live & ~late

        keep = lambda new, old: jnp.where(alive, new, old)  # noqa: E731
        return dict(
            px=keep(npos[0], pos[0]), py=keep(npos[1], pos[1]),
            pz=keep(npos[2], pos[2]),
            ux=keep(new_dir[0], dir_[0]), uy=keep(new_dir[1], dir_[1]),
            uz=keep(new_dir[2], dir_[2]),
            ix=jnp.where(advance, nvox[0], vox[0]),
            iy=jnp.where(advance, nvox[1], vox[1]),
            iz=jnp.where(advance, nvox[2], vox[2]),
            w=jnp.where(live, w, 0), s=keep(s, c["s"]), t=keep(t, c["t"]),
            alive=live,
            absorbed=c["absorbed"] + dep,
            escaped=c["escaped"] + esc_w,
            exit0=c["exit0"] + jnp.where(face, w_after, 0),
            timed=c["timed"] + jnp.where(late, w, 0),
            pp=pp, cap_det=cap_det, cap_gate=cap_gate, cap_w=cap_w,
            cap_pp=cap_pp, bins=bins, it=c["it"] + 1,
        )

    c = jax.lax.while_loop(cond, body, state)
    capped = c["alive"]
    timed = c["timed"] + jnp.where(capped, c["w"], 0)

    def per_batch(x):
        return x.astype(jnp.float32).reshape(M, L).sum(axis=1)

    nd = max(len(dom.detectors), 1)
    det = c["cap_det"].reshape(M, L)
    oh_d = (det[:, None, :] == jnp.arange(nd)[None, :, None])
    oh_dg = oh_d[:, :, None, :] & (
        c["cap_gate"].reshape(M, 1, 1, L)
        == jnp.arange(G)[None, None, :, None])
    cw = c["cap_w"].astype(jnp.float32).reshape(M, L)
    return dict(
        absorbed=per_batch(c["absorbed"]),
        escaped=per_batch(c["escaped"]),
        exit0=per_batch(c["exit0"]),
        timed=per_batch(timed),
        bins=c["bins"].astype(jnp.float32),
        det_w=jnp.sum(oh_dg * cw[:, None, None, :], axis=-1),
        det_pp=jnp.einsum(
            "mdl,ml,kml->mdk", oh_d.astype(jnp.float32), cw,
            c["cap_pp"].astype(jnp.float32).reshape(NM, M, L),
            precision=prec),
        captures=jnp.sum(oh_d, axis=-1).astype(jnp.float32),
        capped=jnp.sum(capped.astype(jnp.int32)),
        iters=c["it"],
    )


def simulate(config: dict, n_photons: int, seed: int, *, n_batches: int = 64,
             blocks: int = 4, dtype=jnp.float32, media=None,
             max_iters: int = 40_000, device=None) -> dict:
    """Run the reference for ``n_photons`` photons of ``config``.

    Returns numpy arrays of per-batch totals: ``absorbed``, ``escaped``,
    ``exit0`` (escaped through the z=0 face), ``timed`` (retired at
    ``tmax_ns``, plus any weight alive at ``max_iters``) of shape
    ``(n_batches,)``; ``bins`` of shape ``(n_batches, blocks**3,
    n_gates)``; ``det_w`` ``(n_batches, n_det, n_gates)``, ``det_pp``
    ``(n_batches, n_det, n_media)`` and ``captures`` ``(n_batches,
    n_det)``; and the counts ``capped`` (photons still alive when the
    iteration cap hit; 0 in a sound run) and ``iters``.
    ``media`` replaces the configuration's table (a sweep's scenario).
    """
    if n_photons % n_batches:
        raise ValueError(f"n_photons {n_photons} is not a multiple of "
                         f"n_batches {n_batches}")
    dom = domain_from_config(config, blocks)
    if media is None:
        media = media_from_config(config)
    put = (functools.partial(jax.device_put, device=device) if device
           else jnp.asarray)
    out = _transport(put(np.asarray(media)),
                     put(np.int32(int(seed) % (2 ** 31))), dom=dom,
                     n_photons=int(n_photons), n_batches=int(n_batches),
                     dtype=dtype, max_iters=int(max_iters))
    out = jax.device_get(out)
    return {k: np.asarray(v) for k, v in out.items()}
