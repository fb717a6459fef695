"""Pallas TPU kernel for the photon transport hot loop.

TPU adaptation of the paper's OpenCL simulation kernel (DESIGN.md
§kernel):

  * The voxel volume (uint8 labels, 216 KB at the paper's 60^3) and the
    optical-property table live in VMEM for the whole kernel — the
    analogue of the paper keeping the volume in texture/constant memory.
  * Photon state is SoA, blocked over lanes: each grid step processes
    one block of photons entirely in VMEM/VREGs, advancing ``n_steps``
    segments per invocation (the "simulation loop" of Fig. 1).
  * Fluence / exitance accumulation: the paper needs atomic float adds
    (its B2a benchmark measures their cost).  TPU Pallas has no atomics
    and needs none: the grid is sequential on a core, so each block
    scatter-adds into fluence / exitance output blocks that are
    REVISITED by every grid step — race-free accumulation by
    construction.  Cross-device accumulation is one psum in the caller
    (multidevice.py).
  * In-kernel bookkeeping (DESIGN.md §rounds, §time-resolved):
    deposition (gate-major ``nvox * cfg.n_time_gates`` when
    time-resolved), the 2-D z=0-face exitance image, per-lane
    escaped / timed-out weight, and — when detectors are configured —
    the per-(detector, gate) TPSF histogram with per-medium partial
    pathlengths are all accumulated *inside* the kernel across the
    fused ``n_steps`` segments, so the host flushes each global grid
    once per round — the deferred-accumulation structure the paper uses
    to amortize global memory traffic over many transport steps.
    The gate index is computed at deposit time from the photon's
    time-of-flight (``photon.time_gate_bins``), so time-resolved
    recording adds zero state to the photon and one integer op to the
    scatter.  Note the VMEM budget: the revisited fluence block is
    ``nvox * ntg * 4`` bytes (a 60^3 volume supports ntg <= ~16 within
    a 16 MB VMEM core; larger gate counts need an HBM-resident
    accumulator, see DESIGN.md §time-resolved).
  * RNG: same counter-seeded xorshift128 as the engine (32-bit ops only;
    TPUs have no 64-bit vector units — the paper's xorshift128+ is
    64-bit, see DESIGN.md §rng).

The physics body is shared with the engine (repro.core.photon.step), so
kernel trajectories are bit-identical to the oracle by construction; the
kernel's contribution is the memory/layout architecture.

Validated with interpret=True on CPU (tests/test_kernels_photon.py)
only.  The kernel does NOT lower for a TPU yet.  Compiled for a TPU v5e
(jax 0.9.0, libtpu 0.0.34; tests/test_tpu_compile.py, a strict xfail),
Mosaic refuses it three times over:

  1. the default ``block_lanes=256`` for the rank-1 int8 ``alive``
     block: "rank 1 block shapes ... [must be] a multiple of the tiling
     size (512 = 128 * (32 // 8))";
  2. at 512 lanes, the 1-D ``jnp.take`` over the uint8 label volume and
     the media table (``photon._lookup_label``, ``photon.step``): "Only
     2D gather is supported";
  3. the in-kernel scatter-adds into the revisited fluence, exitance,
     TPSF and Jacobian blocks: "Unimplemented primitive in Pallas TPU
     lowering ...: scatter-add".

A ``jnp.where`` chain over the few media rows lowers; the label gather
and the deposit scatters need a different design (HBM-resident labels
and fluence moved by DMA, ROADMAP B2).  Until then ``engine="pallas"``
fails at compile time on a TPU, out loud, and the jnp engine is the
one that runs there.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import photon as ph
from repro.core.volume import SimConfig
from repro.detectors import accumulate_capture, update_capture
from repro.kernels.photon_step import spec as kspec


def default_interpret() -> bool:
    """Auto-detect the Pallas execution mode.

    Mosaic lowering only exists on TPU backends; everywhere else
    (CPU/GPU test rigs) the kernel must run under the Pallas
    interpreter.  Callers may still force either mode explicitly — the
    auto-detect only replaces ``interpret=None`` — so real-TPU runs get
    the compiled kernel instead of silently falling back to the
    interpreter (the old hard default).
    """
    return jax.default_backend() != "tpu"


def resolve_block_lanes(n_lanes: int, block_lanes: int) -> int:
    """Clamp ``block_lanes`` to a divisor of ``n_lanes``.

    The kernel grid needs ``block_lanes | n_lanes``; fall back to the
    largest divisor <= the requested block so any lane count works
    through the public APIs (schedulers don't expose block_lanes).
    Shared by every executor that dispatches the kernel (the forward
    engine and both replay passes) so the fallback policy cannot
    diverge.
    """
    requested = block_lanes = min(block_lanes, n_lanes)
    while n_lanes % block_lanes:
        block_lanes -= 1
    if block_lanes < requested:
        warnings.warn(
            f"n_lanes={n_lanes} is not divisible by "
            f"block_lanes={requested}; falling back to "
            f"block_lanes={block_lanes} — small blocks serialize the "
            f"Pallas grid (prefer a lane count with a divisor near "
            f"{requested})", stacklevel=3)
    return block_lanes


def _kernel(labels_ref, media_ref, *refs,
            shape, unitinmm, cfg: SimConfig, n_steps: int, n_det: int,
            record: bool, jac_cols: int, stats: bool):
    # unpack the variadic refs: 9 state inputs [+ ppath + det_geom]
    # [+ jac_w + jac_col], then 9 state outputs + fluence/exitance/esc/
    # timed [+ ppath + det_w + det_ppath] [+ cap_det + cap_gate]
    # [+ jac] [+ stats] — assembled to match photon_step_pallas's specs
    (pos_ref, dir_ref, ivox_ref, label_ref, w_ref, s_ref, t_ref, rng_ref,
     alive_ref) = refs[:9]
    cur = 9
    if n_det:
        ppath_ref, det_geom_ref = refs[cur:cur + 2]
        cur += 2
    if jac_cols:
        jac_w_ref, jac_col_ref = refs[cur:cur + 2]
        cur += 2
    outs = refs[cur:]
    (out_pos, out_dir, out_ivox, out_label, out_w, out_s, out_t, out_rng,
     out_alive, fluence_ref, exitance_ref, esc_ref, timed_ref) = outs[:13]
    cur = 13
    if n_det:
        out_ppath, det_w_ref, det_ppath_ref = outs[cur:cur + 3]
        cur += 3
    if record:
        cap_det_ref, cap_gate_ref = outs[cur:cur + 2]
        cur += 2
    if jac_cols:
        jac_ref = outs[cur]
        cur += 1
    if stats:
        stats_ref = outs[cur]

    ntg = int(cfg.n_time_gates)

    # zero the (revisited) accumulator blocks on the first grid step only
    @pl.when(pl.program_id(0) == 0)
    def _():
        fluence_ref[...] = jnp.zeros_like(fluence_ref)
        exitance_ref[...] = jnp.zeros_like(exitance_ref)
        if n_det:
            det_w_ref[...] = jnp.zeros_like(det_w_ref)
            det_ppath_ref[...] = jnp.zeros_like(det_ppath_ref)
        if jac_cols:
            jac_ref[...] = jnp.zeros_like(jac_ref)

    labels = labels_ref[...]
    media = media_ref[...]
    state = ph.PhotonState(
        pos=pos_ref[...], dir=dir_ref[...], ivox=ivox_ref[...],
        label=label_ref[...], w=w_ref[...], s_left=s_ref[...],
        t=t_ref[...], rng=rng_ref[...], alive=alive_ref[...] != 0,
    )
    n = state.w.shape[0]
    if n_det:
        det_geom = det_geom_ref[...]
    if jac_cols:
        jac_w = jac_w_ref[...]
        jac_col = jac_col_ref[...]

    def body(_, carry):
        st, flu, exi, esc, timed = carry[:5]
        cur = 5
        if n_det:
            pp, dw, dp = carry[cur:cur + 3]
            cur += 3
        if record:
            capd, capg = carry[cur:cur + 2]
            cur += 2
        if jac_cols:
            jac = carry[cur]
            cur += 1
        if stats:
            stbl = carry[cur]
        res = ph.step(st, labels, media, shape, unitinmm, cfg)
        gate = ph.time_gate_bins(res.dep_t, cfg.tmax_ns, ntg)
        flu = flu.at[res.dep_idx * ntg + gate].add(res.dep_w)
        xy, xw = ph.exitance_bins(res.esc_pos, res.esc_w, shape)
        exi = exi.at[xy].add(xw)
        esc = esc + res.esc_w
        timed = timed + res.timed_w
        out = (res.state, flu, exi, esc, timed)
        if n_det:
            pp, dw, dp = accumulate_capture(pp, dw, dp, res, gate,
                                            det_geom, ntg)
            out = out + (pp, dw, dp)
            if record:
                capd, capg = update_capture(capd, capg, res, gate, det_geom)
                out = out + (capd, capg)
        if jac_cols:
            # replay pass-B scatter (DESIGN.md §replay): each lane
            # deposits jac_w * seg_len into its fixed Jacobian column;
            # seg_len is 0 for dead lanes and jac_w is 0 for padding,
            # so masked lanes add exact zeros
            jac = jac.at[res.dep_idx * jac_cols + jac_col].add(
                jac_w * res.seg_len)
            out = out + (jac,)
        if stats:
            # telemetry counters (DESIGN.md §observability): col 0 counts
            # segments entered alive, col 1 sums deposited weight, cols 2
            # and 3 count segments with a deposit and with an exitance
            # weight > 0; pure extra reductions, never read back by any
            # physics value
            stbl = stbl + jnp.stack(
                [st.alive.astype(jnp.float32), res.dep_w,
                 (res.dep_w > 0).astype(jnp.float32),
                 (xw > 0).astype(jnp.float32)], axis=1)
            out = out + (stbl,)
        return out

    init = (state, jnp.zeros_like(fluence_ref),
            jnp.zeros_like(exitance_ref), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    if n_det:
        init = init + (ppath_ref[...], jnp.zeros_like(det_w_ref),
                       jnp.zeros_like(det_ppath_ref))
    if record:
        init = init + (jnp.full((n,), -1, jnp.int32),
                       jnp.zeros((n,), jnp.int32))
    if jac_cols:
        init = init + (jnp.zeros_like(jac_ref),)
    if stats:
        init = init + (jnp.zeros((n, 4), jnp.float32),)
    final = jax.lax.fori_loop(0, n_steps, body, init)
    state, flu_add, exi_add, esc, timed = final[:5]

    out_pos[...] = state.pos
    out_dir[...] = state.dir
    out_ivox[...] = state.ivox
    out_label[...] = state.label
    out_w[...] = state.w
    out_s[...] = state.s_left
    out_t[...] = state.t
    out_rng[...] = state.rng
    out_alive[...] = state.alive.astype(jnp.int8)
    esc_ref[...] = esc
    timed_ref[...] = timed
    # accumulate this block's deposition into the shared output blocks
    fluence_ref[...] += flu_add
    exitance_ref[...] += exi_add
    cur = 5
    if n_det:
        pp, dw_add, dp_add = final[cur:cur + 3]
        cur += 3
        out_ppath[...] = pp
        det_w_ref[...] += dw_add
        det_ppath_ref[...] += dp_add
    if record:
        cap_det_ref[...] = final[cur]
        cap_gate_ref[...] = final[cur + 1]
        cur += 2
    if jac_cols:
        jac_ref[...] += final[cur]
        cur += 1
    if stats:
        stats_ref[...] = final[cur]


def photon_step_pallas(labels_flat, media, state: ph.PhotonState,
                       shape, unitinmm, cfg: SimConfig, n_steps: int,
                       block_lanes: int = 256,
                       interpret: bool | None = None,
                       ppath=None, det_geom=None, record: bool = False,
                       jac_w=None, jac_col=None, jac_cols: int = 0,
                       stats: bool = False):
    """Advance all lanes ``n_steps`` segments; returns
    ``(new_state, fluence_flat, exitance_flat, escaped_per_lane,
    timed_per_lane)`` — plus ``(ppath, det_w_flat, det_ppath)`` when
    detectors are configured, plus per-lane ``(cap_det, cap_gate)``
    int32 capture records when ``record`` is set (DESIGN.md §replay:
    detector index of this round's capture, -1 for none, and its exit
    time gate — the caller owns the global photon ids and appends the
    records to the fixed-capacity id buffer), plus a trailing
    ``(nvox * jac_cols,)`` replay-Jacobian accumulator when
    ``jac_cols > 0``: each lane scatter-adds ``jac_w * seg_len`` of
    every transport segment into column ``jac_col`` of its deposition
    voxel (``jac_w``/``jac_col`` are per-lane (n,) f32/int32 inputs —
    the exit-weight scale and fixed Jacobian column of the record being
    replayed; DESIGN.md §replay).

    ``stats=True`` appends one more lane-blocked ``(n, 4)`` float32
    output (always last): column 0 counts segments each lane entered
    alive, column 1 sums the lane's deposited weight over the round,
    columns 2 and 3 count the lane's segments whose deposit and whose
    z=0-face exitance weight are > 0 —
    the in-kernel half of the ``SimConfig.collect_stats`` telemetry
    counters (DESIGN.md §observability).  The block is accumulated
    alongside the physics carries and written per lane block; it never
    feeds back into any physics value, so every other output is
    bit-identical with ``stats`` on or off.

    ``fluence_flat`` is gate-major ``(nvox * cfg.n_time_gates,)``
    (``(nvox,)`` for the CW case, bit-identical to the ungated kernel),
    ``exitance_flat`` is (nx*ny,) — the z=0-face exitance image
    accumulated in-kernel over all ``n_steps`` segments;
    ``timed_per_lane`` is the weight each lane retired at the tmax_ns
    gate.  ``ppath`` is the (n, n_media) per-medium partial-pathlength
    state (pass the previous round's output back in) and ``det_geom``
    the (n_det, 3) array from ``repro.detectors.det_geometry`` —
    detector capture accumulates the flat ``(n_det * ntg,)`` TPSF
    histogram and the (n_det, n_media) weighted pathlength sums
    in-kernel.  ``interpret=None`` auto-detects the backend
    (:func:`default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    if (ppath is None) != (det_geom is None):
        raise ValueError("ppath and det_geom must be given together")
    jac_cols = int(jac_cols)
    if (jac_cols > 0) != (jac_w is not None) or \
            (jac_w is None) != (jac_col is None):
        raise ValueError("jac_w, jac_col and jac_cols > 0 must be given "
                         "together")
    n = state.w.shape[0]
    if n % block_lanes:
        raise ValueError(f"lane count {n} not divisible by {block_lanes}")
    nblocks = n // block_lanes
    nvox = labels_flat.shape[0]
    ntg = int(cfg.n_time_gates)
    nxy = shape[0] * shape[1]
    n_media = media.shape[0]
    n_det = 0 if det_geom is None else det_geom.shape[0]
    if record and not n_det:
        raise ValueError("record=True requires detectors (det_geom)")
    if not interpret:
        # compiled mode only: the interpreter has no VMEM to overflow,
        # and the CPU benches legitimately run configs (60^3, ntg=32)
        # the hardware budget rejects
        kspec.check_vmem(nvox, nxy, ntg, block_lanes, n_media, n_det,
                         record, jac_cols, stats)

    def lane_spec(extra=()):
        return pl.BlockSpec((block_lanes,) + extra,
                            lambda i: (i,) + (0,) * len(extra))

    def full_spec(*dims):
        return pl.BlockSpec(dims, lambda i, _nd=len(dims): (0,) * _nd)

    full_vol = full_spec(nvox * ntg)                       # revisited
    full_img = full_spec(nxy)                              # revisited
    full_media = full_spec(n_media, 4)

    out_shapes = [
        jax.ShapeDtypeStruct((n, 3), jnp.float32),   # pos
        jax.ShapeDtypeStruct((n, 3), jnp.float32),   # dir
        jax.ShapeDtypeStruct((n, 3), jnp.int32),     # ivox
        jax.ShapeDtypeStruct((n,), jnp.int32),       # label
        jax.ShapeDtypeStruct((n,), jnp.float32),     # w
        jax.ShapeDtypeStruct((n,), jnp.float32),     # s_left
        jax.ShapeDtypeStruct((n,), jnp.float32),     # t
        jax.ShapeDtypeStruct((n, 4), jnp.uint32),    # rng
        jax.ShapeDtypeStruct((n,), jnp.int8),        # alive
        jax.ShapeDtypeStruct((nvox * ntg,), jnp.float32),  # fluence (accum)
        jax.ShapeDtypeStruct((nxy,), jnp.float32),   # exitance (accumulated)
        jax.ShapeDtypeStruct((n,), jnp.float32),     # escaped weight
        jax.ShapeDtypeStruct((n,), jnp.float32),     # timed-out weight
    ]
    out_specs = [
        lane_spec((3,)), lane_spec((3,)), lane_spec((3,)), lane_spec(),
        lane_spec(), lane_spec(), lane_spec(),
        lane_spec((4,)), lane_spec(),
        full_vol, full_img, lane_spec(), lane_spec(),
    ]
    in_specs = [
        full_spec(nvox), full_media,
        lane_spec((3,)), lane_spec((3,)), lane_spec((3,)), lane_spec(),
        lane_spec(), lane_spec(), lane_spec(),
        lane_spec((4,)), lane_spec(),
    ]
    operands = [labels_flat, media,
                state.pos, state.dir, state.ivox, state.label, state.w,
                state.s_left,
                state.t, state.rng, state.alive.astype(jnp.int8)]
    if n_det:
        in_specs += [lane_spec((n_media,)), full_spec(n_det, 3)]
        operands += [ppath, det_geom]
        out_shapes += [
            jax.ShapeDtypeStruct((n, n_media), jnp.float32),      # ppath
            jax.ShapeDtypeStruct((n_det * ntg,), jnp.float32),    # det TPSF
            jax.ShapeDtypeStruct((n_det, n_media), jnp.float32),  # det ppath
        ]
        out_specs += [lane_spec((n_media,)), full_spec(n_det * ntg),
                      full_spec(n_det, n_media)]
    if jac_cols:
        in_specs += [lane_spec(), lane_spec()]
        operands += [jac_w, jac_col]
    if record:
        out_shapes += [
            jax.ShapeDtypeStruct((n,), jnp.int32),   # cap_det (-1: none)
            jax.ShapeDtypeStruct((n,), jnp.int32),   # cap_gate
        ]
        out_specs += [lane_spec(), lane_spec()]
    if jac_cols:
        out_shapes += [
            jax.ShapeDtypeStruct((nvox * jac_cols,), jnp.float32),  # jac
        ]
        out_specs += [full_spec(nvox * jac_cols)]              # revisited
    if stats:
        out_shapes += [
            jax.ShapeDtypeStruct((n, 4), jnp.float32),   # telemetry block
        ]
        out_specs += [lane_spec((4,))]

    kernel = functools.partial(
        _kernel, shape=shape, unitinmm=unitinmm, cfg=cfg, n_steps=n_steps,
        n_det=n_det, record=record, jac_cols=jac_cols, stats=stats)
    outs = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        interpret=interpret,
    )(*operands)

    assert len(outs) == kspec.output_arity(
        n_det, record, jac_cols, stats, packed_state=False), \
        "pallas output list drifted from kernels/photon_step/spec.py"
    new_state = ph.PhotonState(
        pos=outs[0], dir=outs[1], ivox=outs[2], label=outs[3], w=outs[4],
        s_left=outs[5], t=outs[6], rng=outs[7], alive=outs[8] != 0,
    )
    return (new_state,) + tuple(outs[9:])
