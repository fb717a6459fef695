"""Pure-jnp oracle for the photon_step Pallas kernel.

Runs ``n_steps`` lock-step iterations of the hop-drop-spin physics over
all lanes, accumulating deposition into a (gate-major, time-resolved)
fluence grid, z=0-face exits into a flat exitance image, and escaped /
timed-out weight per lane — plus, when detectors are configured, the
per-(detector, gate) TPSF histogram and per-medium partial pathlengths —
exactly the computation the kernel performs, without any blocking/VMEM
structure.  The kernel test asserts allclose (and for matching RNG,
bit-equality of trajectories) against this.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import photon as ph
from repro.core.volume import SimConfig
from repro.detectors import accumulate_capture, update_capture


def photon_steps_ref(labels_flat, media, state: ph.PhotonState,
                     shape, unitinmm, cfg: SimConfig, n_steps: int,
                     ppath=None, det_geom=None, record=False,
                     jac_w=None, jac_col=None, jac_cols: int = 0,
                     stats: bool = False):
    """Returns ``(new_state, fluence_flat, exitance_flat,
    escaped_per_lane, timed_per_lane)`` — plus
    ``(ppath, det_w_flat, det_ppath)`` when detectors are configured,
    plus ``(cap_det, cap_gate)`` per-lane capture records when
    ``record`` is set, plus the ``(nvox * jac_cols,)`` replay-Jacobian
    accumulator when ``jac_cols > 0``, plus the trailing ``(n, 4)``
    telemetry counter block (segments entered alive, deposited weight,
    segments with a deposit, segments with an exitance weight) when
    ``stats`` is set (same contract as ``photon_step_pallas``)."""
    if (ppath is None) != (det_geom is None):
        raise ValueError("ppath and det_geom must be given together")
    jac_cols = int(jac_cols)
    if (jac_cols > 0) != (jac_w is not None) or \
            (jac_w is None) != (jac_col is None):
        raise ValueError("jac_w, jac_col and jac_cols > 0 must be given "
                         "together")
    nvox = labels_flat.shape[0]
    ntg = int(cfg.n_time_gates)
    nxy = shape[0] * shape[1]
    n = state.w.shape[0]
    n_media = media.shape[0]
    n_det = 0 if det_geom is None else det_geom.shape[0]
    if record and not n_det:
        raise ValueError("record=True requires detectors (det_geom)")

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
        res = ph.step(st, labels_flat, media, shape, unitinmm, cfg)
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
            jac = jac.at[res.dep_idx * jac_cols + jac_col].add(
                jac_w * res.seg_len)
            out = out + (jac,)
        if stats:
            stbl = stbl + jnp.stack(
                [st.alive.astype(jnp.float32), res.dep_w,
                 (res.dep_w > 0).astype(jnp.float32),
                 (xw > 0).astype(jnp.float32)], axis=1)
            out = out + (stbl,)
        return out

    init = (state, jnp.zeros((nvox * ntg,), jnp.float32),
            jnp.zeros((nxy,), jnp.float32), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    if n_det:
        init = init + (ppath, jnp.zeros((n_det * ntg,), jnp.float32),
                       jnp.zeros((n_det, n_media), jnp.float32))
    if record:
        init = init + (jnp.full((n,), -1, jnp.int32),
                       jnp.zeros((n,), jnp.int32))
    if jac_cols:
        init = init + (jnp.zeros((nvox * jac_cols,), jnp.float32),)
    if stats:
        init = init + (jnp.zeros((n, 4), jnp.float32),)
    return jax.lax.fori_loop(0, n_steps, body, init)
