"""Hop-drop-spin photon transport physics, vectorized over lanes.

This is the JAX port of the MCX-CL simulation kernel (Fig. 1 of the
paper; the per-photon loop of Fang & Boas 2009).  One call to
:func:`step` advances every lane by one *segment*: the photon moves to
either its next scattering site or the next voxel boundary, whichever
comes first, deposits absorbed energy along the way, and then scatters
(Henyey-Greenstein) or crosses the boundary (Snell/Fresnel or escape).

GPU -> TPU adaptation notes (see DESIGN.md):
  * The OpenCL kernel's per-thread while-loop with divergent branches
    becomes a lock-step masked step over N lanes.  Thread divergence
    (62% in the paper's profile) turns into masked-lane waste; we reduce
    it with photon *regeneration* (simulator.py) — the paper's
    workgroup-level dynamic load balancing, moved into the vector lanes.
  * Every step draws a FIXED number of uniforms (5) regardless of the
    path taken, so trajectories are bit-reproducible across the pure-jnp
    oracle, the specialized step, and the Pallas kernel.
  * The paper's optimizations map as follows:
      Opt1 (native math)      -> cfg.deposit_mode == "taylor" (first-order
                                 Beer-Lambert, one fewer transcendental per
                                 segment) — hardware-dependent-accuracy math.
      Opt2 (thread config)    -> lane-count autotuning (simulator.py).
      Opt3 (control-flow
            simplification)   -> cfg.specialize: trace-time specialization
                                 of the kernel to the benchmark config.  The
                                 unspecialized baseline keeps the *general*
                                 kernel alive in the graph via traced flags
                                 (reflection/refraction math always present),
                                 mirroring the paper's "complex kernel"
                                 baseline that the JIT compiler struggles
                                 to optimize.

Positions are kept in *voxel units* (as MCX does); optical coefficients
are scaled by ``unitinmm`` on entry.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import rng as xrng
from repro.core.volume import C_MM_PER_NS, SimConfig

# plain Python floats (not jnp scalars): the Pallas kernel traces
# photon.step inside its body, and captured jnp constants are rejected
_EPS_STEP = 1e-4   # minimum-progress guard, voxel units
_INF = 1e30
_DIR_EPS = 1e-9

# Escape positions within this distance (voxel units) of the z=0 plane
# count as exits through the illuminated face and are binned into the 2-D
# exitance (diffuse reflectance) image.  Exit positions land exactly on a
# voxel wall up to fp32 rounding, so any value in (0, 1) separates the
# z=0 face from the z=1 wall; a quarter voxel leaves slack on both sides.
# Shared with the exitance binning in simulator.py.
Z_EXIT_FACE_VOX = 0.25


class PhotonState(NamedTuple):
    pos: jnp.ndarray     # (N, 3) float32, voxel units
    dir: jnp.ndarray     # (N, 3) float32, unit vectors
    ivox: jnp.ndarray    # (N, 3) int32 — authoritative voxel index.  Carried
    #                      explicitly (as MCX does) instead of floor(pos):
    #                      grazing rays can land on a wall where the crossing-
    #                      axis nudge is below fp32 resolution, freezing
    #                      floor(pos) and the photon with it.
    label: jnp.ndarray   # (N,)  int32 medium label of ``ivox``.  Invariant,
    #                      for every lane, dead or alive:
    #                      label == labels_flat[flat(clip(ivox))].  ``step``
    #                      reads it instead of gathering the label grid at
    #                      the current voxel, and takes it over from the
    #                      next-voxel gather when ``ivox`` advances; launch
    #                      supplies it for fresh photons (DESIGN.md §rounds).
    w: jnp.ndarray       # (N,)  float32 packet weight
    s_left: jnp.ndarray  # (N,)  float32 remaining dimensionless scat. length
    t: jnp.ndarray       # (N,)  float32 elapsed time, ns
    rng: jnp.ndarray     # (N, 4) uint32 xorshift128 state
    alive: jnp.ndarray   # (N,)  bool


class StepResult(NamedTuple):
    state: PhotonState
    dep_idx: jnp.ndarray  # (N,) int32 flat voxel index of deposition
    dep_w: jnp.ndarray    # (N,) float32 deposited weight (0 for dead lanes)
    esc_w: jnp.ndarray    # (N,) float32 weight escaping the domain this step
    esc_pos: jnp.ndarray  # (N, 3) float32 exit position (voxel units)
    dep_t: jnp.ndarray    # (N,) float32 photon time at deposit (end of the
    #                       segment, ns) — the time the gate index is
    #                       computed from (DESIGN.md §time-resolved)
    seg_med: jnp.ndarray  # (N,) int32 medium label of the segment's voxel
    seg_len: jnp.ndarray  # (N,) float32 segment length in mm (0: dead lanes)
    timed_w: jnp.ndarray  # (N,) float32 weight retired by the tmax_ns gate
    #                       this step (deterministic loss, tracked apart
    #                       from the statistical roulette residue)


def _clamp_to_domain(pos, shape):
    """Clamp positions onto ``[0, shape]``; return them and their voxel."""
    pos = jnp.clip(jnp.asarray(pos, jnp.float32), 0.0,
                   jnp.asarray(shape, jnp.float32))
    bounds = jnp.asarray(shape, jnp.int32) - 1
    return pos, jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, bounds)


def point_label(labels_flat, shape, point):
    """Medium label of the voxel a launch at ``point`` starts in: the one
    starting label of every photon of a point source
    (``sources.launch_point``)."""
    label, _ = _lookup_label(labels_flat, shape,
                             _clamp_to_domain(point, shape)[1])
    return label.astype(jnp.int32)


def launch(pos, direc, w0, rng, active, shape, labels) -> PhotonState:
    """Assemble fresh photons from per-lane source samples.

    ``pos``/``direc``/``w0``/``rng`` come from a source's
    ``sample(photon_ids, seed)`` (repro.sources): per-lane positions and
    unit directions, initial packet weights, and the counter-seeded
    in-flight RNG state.  ``active`` masks lanes that have no photon to
    simulate.

    ``labels`` gives each photon its starting medium label: the flat
    label grid, read at each lane's voxel, or a scalar, the label that
    every lane of a point source starts with (:func:`point_label`),
    which spares the gather over the lanes.

    Sources are expected to lie within the domain; a sampled position
    outside ``[0, shape]`` (e.g. the tail of a wide Gaussian beam, or a
    disk overhanging a face) is clamped onto the domain boundary so
    ``pos`` and the voxel index stay geometrically consistent — without
    the position clamp a lane could carry an in-bounds ``ivox`` with an
    exterior ``pos`` and mis-deposit along a wall it never crossed.  For
    in-domain sources (including ones sitting exactly on a face, like
    the default pencil) both clamps are no-ops.
    """
    pos, ivox = _clamp_to_domain(pos, shape)
    direc = jnp.asarray(direc, jnp.float32)
    n = pos.shape[0]
    if jnp.ndim(labels) == 0:
        label = jnp.broadcast_to(labels, (n,))
    else:
        label, _ = _lookup_label(labels, shape, ivox)
    return PhotonState(
        pos=pos,
        dir=direc,
        ivox=ivox,
        label=label.astype(jnp.int32),
        w=jnp.where(active, w0, 0.0).astype(jnp.float32),
        s_left=jnp.zeros((n,), jnp.float32),
        t=jnp.zeros((n,), jnp.float32),
        rng=rng,
        alive=active,
    )


def exitance_bins(esc_pos, esc_w, shape):
    """Bin z=0-face escapes into the flat (nx*ny) exitance image.

    Returns ``(flat_xy, w)``: a flat 2-D bin index per lane and the
    weight to deposit there (0 for lanes that did not exit through the
    illuminated face).  Shared by the engine, the pure-jnp oracle and
    the Pallas kernel so all three bin identically.
    """
    nx, ny, _ = shape
    z_exit = esc_pos[:, 2] < Z_EXIT_FACE_VOX
    hit = (esc_w > 0) & z_exit
    ex = jnp.clip(jnp.floor(esc_pos[:, 0]).astype(jnp.int32), 0, nx - 1)
    ey = jnp.clip(jnp.floor(esc_pos[:, 1]).astype(jnp.int32), 0, ny - 1)
    return ex * ny + ey, jnp.where(hit, esc_w, 0.0)


def time_gate_bins(dep_t, tmax_ns, n_time_gates):
    """Time-gate index for a deposit at photon time ``dep_t`` (ns).

    Gates split ``[0, tmax_ns]`` into ``n_time_gates`` equal bins; the
    index is computed *at deposit time* from the photon's elapsed
    time-of-flight at the end of the segment, so the 4-D accumulator can
    be scattered in the same pass as the CW grid (DESIGN.md
    §time-resolved).  Deposits from the partial segment that crosses
    ``tmax_ns`` clip into the last gate (the ungated engine keeps that
    energy, and ``n_time_gates=1`` must stay bit-identical to it).

    Shared by the engine, the pure-jnp oracle and the Pallas kernel so
    all three bin identically.
    """
    inv_gate = float(n_time_gates) / float(tmax_ns)
    g = jnp.floor(dep_t * jnp.float32(inv_gate)).astype(jnp.int32)
    return jnp.clip(g, 0, n_time_gates - 1)


def normalize3(v):
    """Scale ``(..., 3)`` vectors to unit length.

    The squared norm is summed component by component, in a fixed order.
    ``jnp.sum`` over the size-3 axis leaves the order to the compiler,
    and on a TPU it follows the array layout: a vmapped batch
    (``repro.scenarios``) and a single run then rounded the norm
    differently and their trajectories drifted apart.
    """
    x, y, z = v[..., 0], v[..., 1], v[..., 2]
    norm = jnp.sqrt(x * x + y * y + z * z)[..., None]
    return v / jnp.maximum(norm, 1e-12)


def _lookup_label(labels_flat, shape, ivox):
    nx, ny, nz = shape
    ix = jnp.clip(ivox[..., 0], 0, nx - 1)
    iy = jnp.clip(ivox[..., 1], 0, ny - 1)
    iz = jnp.clip(ivox[..., 2], 0, nz - 1)
    flat = (ix * ny + iy) * nz + iz
    return jnp.take(labels_flat, flat, axis=0), flat


def _boundary_distance(pos, direc, ivox):
    """Distance (voxel units) to the voxel wall along each axis + crossing axis."""
    fvox = ivox.astype(jnp.float32)
    d_pos = (fvox + 1.0 - pos) / jnp.where(direc > _DIR_EPS, direc, 1.0)
    d_neg = (fvox - pos) / jnp.where(direc < -_DIR_EPS, direc, 1.0)
    dists = jnp.where(
        direc > _DIR_EPS, d_pos, jnp.where(direc < -_DIR_EPS, d_neg, _INF)
    )
    dists = jnp.maximum(dists, 0.0)
    d_min = jnp.min(dists, axis=-1)
    axis = jnp.argmin(dists, axis=-1).astype(jnp.int32)
    return d_min, axis


def _hg_scatter(direc, g, u_cos, u_phi):
    """Henyey-Greenstein direction update (MCML rotation formulas)."""
    g = g.astype(jnp.float32)
    small_g = jnp.abs(g) < 1e-5
    g_safe = jnp.where(small_g, 1.0, g)
    frac = (1.0 - g_safe * g_safe) / (1.0 - g_safe + 2.0 * g_safe * u_cos)
    cost_hg = (1.0 + g_safe * g_safe - frac * frac) / (2.0 * g_safe)
    cost = jnp.where(small_g, 2.0 * u_cos - 1.0, cost_hg)
    cost = jnp.clip(cost, -1.0, 1.0)
    sint = jnp.sqrt(jnp.maximum(1.0 - cost * cost, 0.0))
    phi = (2.0 * jnp.pi) * u_phi
    cosp = jnp.cos(phi)
    sinp = jnp.sin(phi)

    ux, uy, uz = direc[..., 0], direc[..., 1], direc[..., 2]
    near_pole = jnp.abs(uz) > 0.99999
    # general rotation
    tmp = jnp.sqrt(jnp.maximum(1.0 - uz * uz, 1e-12))
    nx = sint * (ux * uz * cosp - uy * sinp) / tmp + ux * cost
    ny = sint * (uy * uz * cosp + ux * sinp) / tmp + uy * cost
    nz = -sint * cosp * tmp + uz * cost
    # polar special case
    px = sint * cosp
    py = sint * sinp
    pz = cost * jnp.sign(uz)
    out = jnp.stack(
        [
            jnp.where(near_pole, px, nx),
            jnp.where(near_pole, py, ny),
            jnp.where(near_pole, pz, nz),
        ],
        axis=-1,
    )
    # renormalize to fight fp drift
    return normalize3(out)


def _fresnel(n_i, n_t, cos_i):
    """Unpolarized Fresnel reflectance + transmitted cosine.

    Returns (R, cos_t, tir_mask).  cos_i must be in [0, 1].
    """
    cos_i = jnp.clip(cos_i, 0.0, 1.0)
    eta = n_i / jnp.maximum(n_t, 1e-6)
    sin2_t = eta * eta * jnp.maximum(1.0 - cos_i * cos_i, 0.0)
    tir = sin2_t >= 1.0
    cos_t = jnp.sqrt(jnp.maximum(1.0 - sin2_t, 0.0))
    rs_num = n_i * cos_i - n_t * cos_t
    rs_den = n_i * cos_i + n_t * cos_t
    rp_num = n_i * cos_t - n_t * cos_i
    rp_den = n_i * cos_t + n_t * cos_i
    rs = (rs_num / jnp.where(jnp.abs(rs_den) < 1e-12, 1.0, rs_den)) ** 2
    rp = (rp_num / jnp.where(jnp.abs(rp_den) < 1e-12, 1.0, rp_den)) ** 2
    r = jnp.where(tir, 1.0, 0.5 * (rs + rp))
    return jnp.clip(r, 0.0, 1.0), cos_t, tir


@jax.named_scope("step")
def step(state, labels_flat, media, shape, unitinmm, cfg: SimConfig) -> StepResult:
    """Advance every lane by one segment.

    With ``cfg.specialize`` (Opt3) the kernel is specialized at trace
    time to the benchmark's physics config; otherwise the general kernel
    (reflection machinery always live, driven by traced flags) is
    compiled, mirroring the paper's unsimplified baseline kernel.
    """
    pos, direc, ivox, label, w, s_left, t, rstate, alive = state
    unitinmm = jnp.float32(unitinmm)
    nx, ny, nz = shape

    with jax.named_scope("step.lookup"):
        # the current voxel's label is carried in the state; the label
        # grid is read once a segment, at the next voxel (step.boundary)
        props = jnp.take(media, label, axis=0)  # (N, 4)
    mua = props[:, 0] * unitinmm
    mus = props[:, 1] * unitinmm
    g = props[:, 2]
    n_cur = props[:, 3]

    # --- draw the per-step uniforms (fixed count: reproducibility) ---
    rstate, u_path = xrng.next_uniform(rstate)
    rstate, u_cos = xrng.next_uniform(rstate)
    rstate, u_phi = xrng.next_uniform(rstate)
    rstate, u_fres = xrng.next_uniform(rstate)
    rstate, u_roul = xrng.next_uniform(rstate)

    # --- HOP: distance to scattering site vs voxel wall ---
    need_draw = s_left <= 0.0
    s_left = jnp.where(need_draw, -jnp.log(u_path), s_left)

    d_wall, cross_axis = _boundary_distance(pos, direc, ivox)
    mus_safe = jnp.maximum(mus, 1e-9)
    d_scat = s_left / mus_safe
    ballistic = mus <= 1e-9  # non-scattering medium: fly to the wall
    d_scat = jnp.where(ballistic, _INF, d_scat)

    hits_wall = d_wall < d_scat
    seg = jnp.where(hits_wall, d_wall, d_scat)
    seg = jnp.maximum(seg, _EPS_STEP * 0.01)

    new_pos = pos + direc * seg[:, None]
    s_left = jnp.where(hits_wall, s_left - seg * mus, 0.0)
    t_new = t + seg * unitinmm * n_cur / C_MM_PER_NS

    # --- DROP: Beer-Lambert deposition into the current voxel ---
    tau = mua * seg
    if cfg.specialize:
        # Opt3: trace-time choice — only one math path in the graph.
        if cfg.deposit_mode == "taylor":
            dep = w * jnp.minimum(tau, 1.0)   # Opt1: first-order, no exp()
            w_after = w - dep
        else:
            w_after = w * jnp.exp(-tau)
            dep = w - w_after
    else:
        # General kernel: both paths compiled, selected by a traced flag.
        use_taylor = jnp.bool_(cfg.deposit_mode == "taylor")
        dep_taylor = w * jnp.minimum(tau, 1.0)
        w_exact = w * jnp.exp(-tau)
        dep = jnp.where(use_taylor, dep_taylor, w - w_exact)
        w_after = w - dep

    dep_flat = (
        jnp.clip(ivox[:, 0], 0, nx - 1) * ny + jnp.clip(ivox[:, 1], 0, ny - 1)
    ) * nz + jnp.clip(ivox[:, 2], 0, nz - 1)
    dep_w = jnp.where(alive, dep, 0.0)

    # --- SPIN: HG scatter for lanes that reached their scattering site ---
    scat_dir = _hg_scatter(direc, g, u_cos, u_phi)
    is_scatter = alive & ~hits_wall

    # --- BOUNDARY: next voxel, Fresnel, escape ---
    with jax.named_scope("step.boundary"):
        axis_onehot = jnp.eye(3, dtype=jnp.int32)[cross_axis]  # (N, 3)
        axis_f = axis_onehot.astype(jnp.float32)
        dir_axis = jnp.sum(direc * axis_f, axis=-1)
        sgn = jnp.sign(dir_axis).astype(jnp.int32)
        next_vox = ivox + axis_onehot * sgn[:, None]
        oob = (
            (next_vox[:, 0] < 0) | (next_vox[:, 0] >= nx)
            | (next_vox[:, 1] < 0) | (next_vox[:, 1] >= ny)
            | (next_vox[:, 2] < 0) | (next_vox[:, 2] >= nz)
        )
        next_label, _ = _lookup_label(labels_flat, shape, next_vox)
        next_label = jnp.where(oob, 0, next_label)
        n_next = jnp.take(media, next_label.astype(jnp.int32), axis=0)[:, 3]
        mismatch = jnp.abs(n_next - n_cur) > 1e-6
        cos_i = jnp.abs(dir_axis)

        if cfg.specialize and not cfg.do_reflect:
            # B1-style specialized kernel: no Fresnel/refraction in the graph.
            reflects = jnp.zeros_like(hits_wall)
            new_dir_boundary = direc
        else:
            refl_r, cos_t, _tir = _fresnel(n_cur, n_next, cos_i)
            do_reflect_flag = (
                True if (cfg.specialize and cfg.do_reflect)
                else jnp.bool_(cfg.do_reflect)
            )
            reflects = (hits_wall & mismatch & (u_fres < refl_r)
                        & do_reflect_flag)
            # reflected direction: flip the crossing-axis component
            refl_dir = direc * (1.0 - 2.0 * axis_f)
            # transmitted (refracted): scale tangentials, set normal cosine
            eta = n_cur / jnp.maximum(n_next, 1e-6)
            trans_tan = direc * (1.0 - axis_f) * eta[:, None]
            trans_nrm = axis_f * (sgn.astype(jnp.float32) * cos_t)[:, None]
            trans_dir = trans_tan + trans_nrm
            trans_dir = normalize3(trans_dir)
            bend = mismatch & do_reflect_flag
            trans_dir = jnp.where(bend[:, None], trans_dir, direc)
            new_dir_boundary = jnp.where(reflects[:, None], refl_dir,
                                         trans_dir)

        crossing = alive & hits_wall
        new_dir = jnp.where(
            is_scatter[:, None],
            scat_dir,
            jnp.where(crossing[:, None], new_dir_boundary, direc),
        )

        escapes = crossing & ~reflects & (oob | (next_label == 0))
        esc_w = jnp.where(escapes, w_after, 0.0)
        esc_pos = new_pos

        # advance the authoritative voxel index on transmitting crossings
        advances = crossing & ~reflects & ~escapes
        new_ivox = jnp.where(advances[:, None], next_vox, ivox)
        # ``advances`` excludes ``oob``, so ``next_label`` is the grid's
        # label at ``next_vox`` wherever it is taken
        new_label = jnp.where(advances, next_label.astype(jnp.int32),
                              label)

    # --- ROULETTE + time gate ---
    alive_after = alive & ~escapes
    low_w = alive_after & (w_after < cfg.w_threshold)
    survives = u_roul < (1.0 / cfg.roulette_m)
    w_final = jnp.where(
        low_w, jnp.where(survives, w_after * cfg.roulette_m, 0.0), w_after
    )
    alive_after = alive_after & ~(low_w & ~survives)
    # weight retired by the tmax_ns gate is a deterministic loss, not a
    # statistical roulette residue — report it separately so the energy
    # balance can distinguish the two (analysis.energy_balance)
    gate_kill = alive_after & (t_new > cfg.tmax_ns)
    alive_after = alive_after & ~gate_kill
    timed_w = jnp.where(gate_kill, w_final, 0.0)
    w_final = jnp.where(escapes, 0.0, w_final)

    new_state = PhotonState(
        pos=jnp.where(alive[:, None], new_pos, pos),
        dir=jnp.where(alive[:, None], new_dir, direc),
        ivox=jnp.where(alive[:, None], new_ivox, ivox),
        label=new_label,
        w=jnp.where(alive, w_final, w),
        s_left=jnp.where(alive, s_left, state.s_left),
        t=jnp.where(alive, t_new, t),
        rng=rstate,
        alive=alive_after,
    )
    return StepResult(
        state=new_state,
        dep_idx=dep_flat.astype(jnp.int32),
        dep_w=dep_w,
        esc_w=jnp.where(alive, esc_w, 0.0),
        esc_pos=esc_pos,
        dep_t=t_new,
        seg_med=label,
        seg_len=jnp.where(alive, seg * unitinmm, 0.0),
        timed_w=jnp.where(alive, timed_w, 0.0),
    )
