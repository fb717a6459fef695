"""Detected-photon replay (DESIGN.md §replay).

Contracts under test:

  * The forward engines (jnp at any K, Pallas, and the ref oracle)
    record the *same* detected-photon id set — trajectories are
    id-keyed, so the records are engine-independent.
  * The fixed-capacity id buffer fills in capture order, never corrupts
    the aggregate detector outputs, and counts overflowing captures.
  * Replayed photons reproduce their forward trajectories bit-for-bit:
    recorded detector index and exit gate are reproduced exactly, and
    the per-detector replayed exit-weight sums match the forward TPSF
    totals to fp-accumulation tolerance.
  * The absorption Jacobian's per-medium row sums equal the forward
    run's weight-weighted partial pathlengths (``det_ppath``) — the
    identity that ties the replay to ``analysis.rescale_detected`` —
    and a finite-difference perturbed forward run on B2 confirms the
    first-order prediction.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import analysis as A
from repro.core import photon as ph
from repro.core import simulator as S
from repro.core import volume as V
from repro.detectors import Detector, det_geometry
from repro.replay import ReplayResult, detected_records, replay_jacobian
from repro.sources import Pencil

SHAPE = (16, 16, 16)
SRC = {"type": "pencil", "pos": (8.0, 8.0, 0.0)}
DETS = (Detector(11.0, 8.0, 3.0), Detector(5.0, 5.0, 2.5))
SEED = 7
N_PHOTONS = 2000
LANES = 256


def _forward(record=4096, engine="jnp", k=1, cfg=None, vol=None,
             n_photons=N_PHOTONS, **kw):
    vol = vol if vol is not None else V.benchmark_b1(SHAPE)
    cfg = cfg or V.SimConfig(do_reflect=False, steps_per_round=k)
    return S.simulate(vol, cfg, n_photons, LANES, SEED, source=SRC,
                      engine=engine, detectors=DETS, record_detected=record,
                      **kw), vol, cfg


def _sorted(rec):
    return np.asarray(sorted(map(tuple, np.asarray(rec))), np.uint32)


# ---------------------------------------------------------------------------
# record buffer semantics
# ---------------------------------------------------------------------------

def test_records_are_unique_and_consistent_with_det_w():
    res, _, _ = _forward()
    rec = detected_records(res)
    assert rec.shape[0] == int(res.det_rec_n) > 0
    assert int(res.det_rec_overflow) == 0
    # each capture is recorded once: ids unique
    ids = {(int(r[0]), int(r[1])) for r in rec}
    assert len(ids) == rec.shape[0]
    # detector/gate indices in range
    assert rec[:, 2].max() < len(DETS)
    assert rec[:, 3].max() < 1  # CW run: single gate
    # every detector with recorded captures has detected weight and
    # vice versa
    w = np.asarray(res.det_w).sum(axis=1)
    for d in range(len(DETS)):
        assert (w[d] > 0) == ((rec[:, 2] == d).any())


def test_record_overflow_keeps_aggregates_intact():
    full, _, _ = _forward(record=4096)
    n_cap = int(full.det_rec_n)
    assert n_cap > 8
    cap = 5
    small, _, _ = _forward(record=cap)
    assert int(small.det_rec_n) == cap
    assert int(small.det_rec_overflow) == n_cap - cap
    # the first `cap` records agree (captures append in engine order)
    np.testing.assert_array_equal(detected_records(small),
                                  detected_records(full)[:cap])
    # aggregate detector outputs are unaffected by the buffer size
    np.testing.assert_array_equal(np.asarray(small.det_w),
                                  np.asarray(full.det_w))
    np.testing.assert_array_equal(np.asarray(small.det_ppath),
                                  np.asarray(full.det_ppath))


def test_recording_does_not_perturb_physics():
    plain, _, _ = _forward(record=0)
    recd, _, _ = _forward(record=4096)
    np.testing.assert_array_equal(np.asarray(plain.energy),
                                  np.asarray(recd.energy))
    np.testing.assert_array_equal(np.asarray(plain.det_w),
                                  np.asarray(recd.det_w))
    assert int(plain.n_launched) == int(recd.n_launched)


def test_record_requires_detectors():
    vol = V.benchmark_b1(SHAPE)
    with pytest.raises(ValueError, match="requires detectors"):
        S.build_sim_fn(vol.shape, vol.unitinmm, V.SimConfig(), 128,
                       record_detected=16)


# ---------------------------------------------------------------------------
# engine parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine,k", [("jnp", 4), ("pallas", 1),
                                      ("pallas", 4)])
def test_records_engine_invariant(engine, k):
    """The recorded id set is identical across round executors and
    fused-round depths (capture *order* may differ between K values, so
    compare as sorted sets)."""
    ref, _, _ = _forward(engine="jnp", k=1)
    other, _, _ = _forward(engine=engine, k=k, block_lanes=64)
    np.testing.assert_array_equal(_sorted(detected_records(ref)),
                                  _sorted(detected_records(other)))


def test_kernel_capture_records_match_oracle():
    """Per-lane (cap_det, cap_gate) outputs: Pallas kernel vs the
    pure-jnp ref oracle, bit-for-bit."""
    from repro.kernels.photon_step.photon_step import photon_step_pallas
    from repro.kernels.photon_step.ref import photon_steps_ref

    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False, n_time_gates=4)
    n = 256
    src = Pencil(pos=(8.0, 8.0, 0.0))
    ids = jnp.arange(n, dtype=jnp.uint32)
    pos, direc, w0, rng = src.sample(ids, jnp.uint32(SEED))
    state = ph.launch(pos, direc, w0, rng, jnp.ones((n,), bool), vol.shape,
                      vol.labels.reshape(-1))
    dg = det_geometry(DETS)
    pp0 = jnp.zeros((n, vol.media.shape[0]), jnp.float32)
    args = (vol.labels.reshape(-1), vol.media, state, vol.shape,
            vol.unitinmm, cfg, 60)

    outs_k = photon_step_pallas(*args, block_lanes=64, interpret=True,
                                ppath=pp0, det_geom=dg, record=True)
    outs_r = photon_steps_ref(*args, ppath=pp0, det_geom=dg, record=True)
    capd_k, capg_k = outs_k[8:]
    capd_r, capg_r = outs_r[8:]
    np.testing.assert_array_equal(np.asarray(capd_k), np.asarray(capd_r))
    np.testing.assert_array_equal(np.asarray(capg_k), np.asarray(capg_r))
    # captures happened, and only captured lanes carry a gate
    assert int(jnp.sum(capd_k >= 0)) > 0


# ---------------------------------------------------------------------------
# replay: bit-exact trajectories + Jacobian validation
# ---------------------------------------------------------------------------

def _b2_forward():
    vol = V.benchmark_b2((20, 20, 20))
    cfg = V.SimConfig(do_reflect=True, steps_per_round=4)
    src = {"type": "pencil", "pos": (10.0, 10.0, 0.0)}
    dets = (Detector(14.0, 10.0, 3.0), Detector(6.0, 6.0, 2.0))
    res = S.simulate(vol, cfg, 3000, 512, SEED, source=src, detectors=dets,
                     record_detected=4096)
    return res, vol, cfg, src, dets


def test_replay_reproduces_forward_bit_for_bit():
    res, vol, cfg, src, dets = _b2_forward()
    rec = detected_records(res)
    assert rec.shape[0] > 100 and int(res.det_rec_overflow) == 0
    rep = replay_jacobian(vol, cfg, rec, dets, source=src, seed=SEED,
                          n_lanes=512)
    assert isinstance(rep, ReplayResult) and rep.n_records == rec.shape[0]
    # trajectory determinism: every replayed photon exits into the same
    # detector at the same time gate as the forward run recorded
    np.testing.assert_array_equal(rep.replayed_det, rep.det)
    np.testing.assert_array_equal(rep.gate, rec[:, 3].astype(np.int32))
    # per-detector replayed exit weight == forward TPSF totals
    per_det = np.zeros(len(dets))
    np.add.at(per_det, rep.det, rep.w_exit.astype(np.float64))
    fw = np.asarray(res.det_w, np.float64).sum(axis=1)
    np.testing.assert_allclose(per_det, fw, rtol=1e-5)


def test_jacobian_matches_ppath_rescale_and_finite_difference():
    res, vol, cfg, src, dets = _b2_forward()
    rep = replay_jacobian(vol, cfg, detected_records(res), dets, source=src,
                          seed=SEED, n_lanes=512)
    # 1) medium row sums == forward weight-weighted partial pathlengths
    M = A.jacobian_medium_sums(rep.jacobian, vol)
    np.testing.assert_allclose(M, np.asarray(res.det_ppath, np.float64),
                               rtol=1e-4, atol=1e-4)
    # 2) first-order consistency with the white-MC rescaling: for a
    #    small per-medium absorption change both predict
    #    dW_d = -sum_m det_ppath[d, m] * dmua_m
    d_mua = 0.005 * 0.05  # +5% of the background mua
    W0 = np.asarray(res.det_w, np.float64).sum(axis=1)
    new_mua = np.asarray(vol.media)[:, 0].copy()
    new_mua[1] += d_mua
    dw_rescale = A.rescale_detected(res, vol, new_mua) - W0
    dw_jac = -M[:, 1] * d_mua
    np.testing.assert_allclose(dw_jac, dw_rescale, rtol=5e-2)
    # 3) finite difference: a perturbed forward run (same seed — the
    #    trajectories only drift through roulette, second order here)
    media2 = np.asarray(vol.media).copy()
    media2[1, 0] += d_mua
    vol2 = dataclasses.replace(vol, media=jnp.asarray(media2))
    res2 = S.simulate(vol2, cfg, 3000, 512, SEED, source=src,
                      detectors=dets)
    dw_fd = np.asarray(res2.det_w, np.float64).sum(axis=1) - W0
    np.testing.assert_allclose(dw_jac, dw_fd, rtol=5e-2)
    # sanity: the Jacobian is nonnegative and concentrated where the
    # detected light actually travelled (source-detector plane)
    assert rep.jacobian.min() >= 0.0
    assert rep.jacobian.sum() > 0.0


def test_replay_input_validation():
    res, vol, cfg, src, dets = _b2_forward()
    with pytest.raises(ValueError, match="detectors"):
        replay_jacobian(vol, cfg, detected_records(res), ())
    bad = np.array([[1, 0, 99, 0]], np.uint32)  # detector 99 of 2
    with pytest.raises(ValueError, match="detector 99"):
        replay_jacobian(vol, cfg, bad, dets, source=src, seed=SEED)


# ---------------------------------------------------------------------------
# 64-bit photon ids through the engine
# ---------------------------------------------------------------------------

def test_photon_ids_straddle_2_32_through_the_engine():
    """Regression for the uint32 id-counter wraparound: a campaign
    window straddling 2**32 must (a) count its launches correctly and
    (b) simulate photons with *distinct* RNG streams from the sub-2**32
    ids sharing the same low word — under the old uint32 counter the
    post-wrap photons re-ran ids 0, 1, 2, ... bit-identically."""
    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False)
    fn = S.make_simulator(vol, cfg, LANES, source=SRC)
    labels, media = vol.labels.reshape(-1), vol.media
    n = 500
    # NB: offsets >= 2**31 must cross the jit boundary as np.uint32 —
    # rng.split_id64 does this for host-side 64-bit ids
    off_lo, off_hi = S.xrng.split_id64(2**32 - n // 2)
    straddle = fn(labels, media, n, SEED, off_lo, off_hi)
    assert int(straddle.n_launched) == n
    low = fn(labels, media, n, SEED, off_lo, off_hi + 1)  # same lo, hi+1
    assert int(low.n_launched) == n
    # distinct id windows -> distinct photon sets -> different grids
    assert not np.array_equal(np.asarray(straddle.energy),
                              np.asarray(low.energy))
    # the old wraparound made the post-wrap half replay ids 0..249: the
    # straddling window must differ from simulating ids 0..n-1 too
    zero = fn(labels, media, n, SEED, 0, 0)
    assert not np.array_equal(np.asarray(straddle.energy),
                              np.asarray(zero.energy))


def test_sub_2_32_ids_unchanged_by_id_offset_hi_plumbing():
    """id_offset_hi=0 (the default) is the historical engine: calling
    with and without the new argument is bit-identical."""
    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False)
    fn = S.make_simulator(vol, cfg, LANES, source=SRC)
    labels, media = vol.labels.reshape(-1), vol.media
    a = fn(labels, media, 800, SEED, 123)
    b = fn(labels, media, 800, SEED, 123, 0)
    np.testing.assert_array_equal(np.asarray(a.energy), np.asarray(b.energy))
    np.testing.assert_array_equal(np.asarray(a.exitance),
                                  np.asarray(b.exitance))
    assert int(a.n_launched) == int(b.n_launched) == 800


def test_detected_records_reassembles_sharded_buffers():
    """simulate_sharded concatenates per-shard fixed-capacity buffers
    with a rank-1 det_rec_n; detected_records must slice each shard's
    valid prefix (exercised host-side — the live 8-device path is
    covered by test_multidevice)."""
    cap = 4
    shard0 = [[1, 0, 0, 0], [2, 0, 1, 0], [0, 0, 0, 0], [0, 0, 0, 0]]
    shard1 = [[7, 1, 0, 2], [0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0]]
    res = S.SimResult(
        energy=np.zeros((2, 2, 2), np.float32),
        exitance=np.zeros((2, 2), np.float32),
        escaped_w=np.float32(0), n_launched=np.int32(0),
        launched_w=np.float32(0), steps=np.zeros((2,), np.int32),
        det_rec=np.asarray(shard0 + shard1, np.uint32),
        det_rec_n=np.asarray([2, 1], np.int32),
        det_rec_overflow=np.int32(0),
    )
    rec = detected_records(res)
    np.testing.assert_array_equal(
        rec, np.asarray([[1, 0, 0, 0], [2, 0, 1, 0], [7, 1, 0, 2]],
                        np.uint32))
    assert rec.shape[0] == 3 and cap == 4


# ---------------------------------------------------------------------------
# engine-pluggable, batched/sharded replay (DESIGN.md §replay)
# ---------------------------------------------------------------------------

def _b1_forward(seed=5, n_photons=400, cfg=None, lanes=128):
    vol = V.benchmark_b1(SHAPE)
    cfg = cfg or V.SimConfig(do_reflect=False)
    res = S.simulate(vol, cfg, n_photons, lanes, seed, source=SRC,
                     detectors=DETS, record_detected=2048)
    return res, vol, cfg


def test_replay_pallas_engine_matches_jnp():
    """The Pallas round executor replays bit-identical trajectories:
    per-record outputs equal the jnp engine exactly for any blocking,
    and the Jacobian is bit-equal when the grid is a single block (the
    in-kernel scatter then runs in the same order as the jnp rounds)."""
    res, vol, cfg, src, dets = _b2_forward()
    rec = detected_records(res)
    rj = replay_jacobian(vol, cfg, rec, dets, source=src, seed=SEED,
                         n_lanes=256, engine="jnp")
    rp = replay_jacobian(vol, cfg, rec, dets, source=src, seed=SEED,
                         n_lanes=256, engine="pallas", block_lanes=256)
    np.testing.assert_array_equal(rp.w_exit, rj.w_exit)
    np.testing.assert_array_equal(rp.gate, rj.gate)
    np.testing.assert_array_equal(rp.replayed_det, rj.replayed_det)
    np.testing.assert_array_equal(rp.jacobian, rj.jacobian)
    # multi-block grids reorder the in-kernel scatter across lane
    # blocks: per-record outputs stay bit-equal, the Jacobian agrees to
    # fp-accumulation order
    rp4 = replay_jacobian(vol, cfg, rec, dets, source=src, seed=SEED,
                          n_lanes=256, engine="pallas", block_lanes=64)
    np.testing.assert_array_equal(rp4.w_exit, rj.w_exit)
    np.testing.assert_array_equal(rp4.replayed_det, rj.replayed_det)
    np.testing.assert_allclose(rp4.jacobian, rj.jacobian,
                               rtol=1e-5, atol=1e-9)


def test_replay_gate_resolved_partitions_ungated():
    """gate_resolved=True widens the scatter to (nvox, n_det, ntg)
    keyed by each record's exit gate; the gates *partition* the
    scatter, so the gate-sum recovers the ungated Jacobian and the
    5-D medium sums keep the det_ppath identity."""
    cfg = V.SimConfig(do_reflect=False, steps_per_round=2, tmax_ns=0.5,
                      n_time_gates=4)
    res, vol, cfg = _b1_forward(seed=7, n_photons=1500, cfg=cfg, lanes=256)
    rec = detected_records(res)
    gates = np.unique(rec[:, 3])
    assert gates.size >= 2, "fixture must spread records over gates"
    rj = replay_jacobian(vol, cfg, rec, DETS, source=SRC, seed=7,
                         n_lanes=256)
    rg = replay_jacobian(vol, cfg, rec, DETS, source=SRC, seed=7,
                         n_lanes=256, gate_resolved=True)
    assert rg.jacobian.shape == SHAPE + (len(DETS), 4)
    np.testing.assert_array_equal(rg.w_exit, rj.w_exit)
    np.testing.assert_array_equal(rg.gate, rec[:, 3].astype(np.int32))
    np.testing.assert_allclose(rg.jacobian.sum(axis=-1), rj.jacobian,
                               rtol=2e-5, atol=1e-9)
    # gates with no records contribute empty slices
    for g in range(4):
        if g not in gates:
            assert np.abs(rg.jacobian[..., g]).max() == 0.0
    # 5-D medium sums: gate-summed identity vs the forward det_ppath,
    # and the per-gate variant partitions it
    M = A.jacobian_medium_sums(rg.jacobian, vol)
    np.testing.assert_allclose(M, np.asarray(res.det_ppath, np.float64),
                               rtol=1e-4, atol=1e-4)
    Mg = A.jacobian_medium_sums(rg.jacobian, vol, per_gate=True)
    assert Mg.shape == (len(DETS), 4, vol.media.shape[0])
    np.testing.assert_allclose(Mg.sum(axis=1), M)
    with pytest.raises(ValueError, match="per_gate"):
        A.jacobian_medium_sums(rj.jacobian, vol, per_gate=True)


def test_replay_gate_resolved_cw_is_bit_equal_ungated():
    """ntg=1 (CW): the gate-resolved scatter is the ungated scatter
    with a singleton gate axis — bit-for-bit."""
    res, vol, cfg = _b1_forward()
    rec = detected_records(res)
    rj = replay_jacobian(vol, cfg, rec, DETS, source=SRC, seed=5,
                         n_lanes=128)
    rg = replay_jacobian(vol, cfg, rec, DETS, source=SRC, seed=5,
                         n_lanes=128, gate_resolved=True)
    assert rg.jacobian.shape == SHAPE + (len(DETS), 1)
    np.testing.assert_array_equal(rg.jacobian[..., 0], rj.jacobian)


def test_replay_batch_padding_contributes_exactly_zero():
    """Regression for the batch-padding contract: padding lanes carry
    id (0, 0) with active=False and must contribute *exactly* zero —
    even when a real detected photon has id 0 (the padding id is not a
    sentinel; only the active mask separates them)."""
    res, vol, cfg = _b1_forward()  # seed 5: photon id 0 IS detected
    rec = detected_records(res)
    is_id0 = (rec[:, 0] == 0) & (rec[:, 1] == 0)
    assert is_id0.any(), \
        "fixture must detect photon id (0,0) — pick another seed"
    id0 = rec[is_id0]
    # 1 real lane + 7 padding lanes with the SAME id as the real one:
    # padding adds exact zeros, so the result is bit-equal to the
    # pad-free single-lane replay
    padded = replay_jacobian(vol, cfg, id0, DETS, source=SRC, seed=5,
                             n_lanes=8)
    alone = replay_jacobian(vol, cfg, id0, DETS, source=SRC, seed=5,
                            n_lanes=1)
    np.testing.assert_array_equal(padded.jacobian, alone.jacobian)
    np.testing.assert_array_equal(padded.w_exit, alone.w_exit)
    # a 5-record subset including id 0, padded to 8 lanes vs exact fit
    subset = rec[:5] if is_id0[:5].any() else np.concatenate(
        [id0[:1], rec[~is_id0][:4]])
    pad8 = replay_jacobian(vol, cfg, subset, DETS, source=SRC, seed=5,
                           n_lanes=8)
    fit5 = replay_jacobian(vol, cfg, subset, DETS, source=SRC, seed=5,
                           n_lanes=5)
    np.testing.assert_array_equal(pad8.jacobian, fit5.jacobian)


def test_replay_batch_size_invariance():
    """Replay is batched over fixed-size lane blocks; the per-record
    outputs are bit-invariant across batch sizes (trajectories depend
    only on the photon id) and the Jacobian agrees to fp-accumulation
    order."""
    res, vol, cfg = _b1_forward()
    rec = detected_records(res)
    assert rec.shape[0] > 64  # several batches at n_lanes=8
    r8 = replay_jacobian(vol, cfg, rec, DETS, source=SRC, seed=5,
                         n_lanes=8)
    r64 = replay_jacobian(vol, cfg, rec, DETS, source=SRC, seed=5,
                          n_lanes=64)
    np.testing.assert_array_equal(r8.w_exit, r64.w_exit)
    np.testing.assert_array_equal(r8.gate, r64.gate)
    np.testing.assert_array_equal(r8.replayed_det, r64.replayed_det)
    np.testing.assert_allclose(r8.jacobian, r64.jacobian,
                               rtol=1e-5, atol=1e-9)


def test_truncated_records_replay_matches_ppath_subset():
    """det_rec_overflow semantics under replay: a deliberately tiny id
    buffer truncates the record list but not the aggregates; replaying
    the truncated records yields exactly the det_ppath share of those
    records (truncated + dropped = the full forward det_ppath)."""
    full, vol, cfg = _b1_forward()
    n_cap = int(full.det_rec_n)
    assert n_cap > 12
    cap = 8
    small = S.simulate(vol, cfg, 400, 128, 5, source=SRC, detectors=DETS,
                       record_detected=cap)
    assert int(small.det_rec_n) == cap
    assert int(small.det_rec_overflow) == n_cap - cap
    # aggregates are untouched by the truncation
    np.testing.assert_array_equal(np.asarray(small.det_w),
                                  np.asarray(full.det_w))
    np.testing.assert_array_equal(np.asarray(small.det_ppath),
                                  np.asarray(full.det_ppath))
    rec_full = detected_records(full)
    rec_small = detected_records(small)
    np.testing.assert_array_equal(rec_small, rec_full[:cap])
    # the truncated replay covers exactly its records' det_ppath share
    M_trunc = A.jacobian_medium_sums(
        replay_jacobian(vol, cfg, rec_small, DETS, source=SRC,
                        seed=5, n_lanes=64).jacobian, vol)
    M_rest = A.jacobian_medium_sums(
        replay_jacobian(vol, cfg, rec_full[cap:], DETS, source=SRC,
                        seed=5, n_lanes=64).jacobian, vol)
    ppath = np.asarray(full.det_ppath, np.float64)
    np.testing.assert_allclose(M_trunc + M_rest, ppath,
                               rtol=1e-4, atol=1e-4)
    assert (M_trunc <= ppath + 1e-6).all()


def test_replay_engine_and_gate_validation():
    res, vol, cfg = _b1_forward()
    rec = detected_records(res)
    with pytest.raises(ValueError, match="unknown engine"):
        replay_jacobian(vol, cfg, rec, DETS, source=SRC, seed=5,
                        engine="bogus")
    # gate-resolved replay refuses records whose gates exceed the cfg's
    # gate count (records from a different forward gate layout)
    bad = rec.copy()
    bad[0, 3] = 7
    with pytest.raises(ValueError, match="time gate 7"):
        replay_jacobian(vol, cfg, bad, DETS, source=SRC, seed=5,
                        gate_resolved=True)
