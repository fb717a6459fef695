"""Time-resolved recording (DESIGN.md §time-resolved).

Contracts under test:

  * ``n_time_gates=1`` (the CW default) is **bit-identical** to the
    pre-PR (PR-2 fused) engine at K=1 and K=4 — the ungated round
    executor is embedded verbatim below as the reference.  The gated
    scatter index ``voxel * ntg + gate`` degenerates to ``voxel`` at
    ntg=1, so this holds exactly, not just to tolerance.
  * Summing ``fluence_td`` over gates reproduces ``fluence_cw``
    bit-for-bit on the same result (jnp engine, any K, any gate count)
    — the gate axis partitions deposition, it never rescales it — and
    the gate-summed energy of an ntg>1 run matches the CW run of the
    same photon set to fp-accumulation tolerance (for both engines).
  * Detector TPSF capture: detected weight is a subset of the z=0-face
    exitance, is identical across schedulers (chunked vs one-shot), and
    the analysis helpers (tpsf / detector_mean_ppath / rescale_detected)
    are consistent with the raw histograms.
"""

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import analysis as A
from repro.core import photon as ph
from repro.core import simulator as S
from repro.core import volume as V
from repro.detectors import Detector, as_detectors, det_geometry
from repro.sources import as_source


# ---------------------------------------------------------------------------
# Verbatim copy of the PR-2 fused engine: K fused segments per round, ONE
# ungated (nvox,) energy scatter per round — the "current engine" the
# ntg=1 path must reproduce bit-for-bit at any K.
# ---------------------------------------------------------------------------

class _Pr2Carry(NamedTuple):
    state: ph.PhotonState
    energy: jnp.ndarray
    exitance: jnp.ndarray
    escaped_w: jnp.ndarray
    remaining: jnp.ndarray
    launched_per_lane: jnp.ndarray
    next_id: jnp.ndarray
    launched_w: jnp.ndarray
    steps: jnp.ndarray


def _pr2_fused_sim_fn(shape, unitinmm, cfg, n_lanes, mode="dynamic",
                      source=None):
    source = as_source(source)
    nx, ny, nz = shape
    nvox = nx * ny * nz
    nxy = nx * ny
    K = int(cfg.steps_per_round)

    def sim_fn(labels_flat, media, n_photons, seed, id_offset=0):
        n_photons = jnp.asarray(n_photons, jnp.int32)
        seed = jnp.asarray(seed, jnp.uint32)
        id_offset = jnp.asarray(id_offset, jnp.int32)
        lane_idx = jnp.arange(n_lanes, dtype=jnp.int32)
        quota = n_photons // n_lanes + (lane_idx < n_photons % n_lanes)
        state0 = ph.PhotonState(
            pos=jnp.zeros((n_lanes, 3), jnp.float32),
            dir=jnp.tile(jnp.asarray([0.0, 0.0, 1.0], jnp.float32),
                         (n_lanes, 1)),
            ivox=jnp.zeros((n_lanes, 3), jnp.int32),
            label=jnp.full((n_lanes,), labels_flat[0], jnp.int32),
            w=jnp.zeros((n_lanes,), jnp.float32),
            s_left=jnp.zeros((n_lanes,), jnp.float32),
            t=jnp.zeros((n_lanes,), jnp.float32),
            rng=jnp.zeros((n_lanes, 4), jnp.uint32),
            alive=jnp.zeros((n_lanes,), bool),
        )
        # _maybe_regenerate now carries the id counter as a 64-bit
        # (lo, hi) uint32 pair; hi=0 is bit-identical to the PR-2 int32
        # counter, so the verbatim copy keeps its contract
        carry0 = _Pr2Carry(
            state0, jnp.zeros((nvox,), jnp.float32),
            jnp.zeros((nxy,), jnp.float32), jnp.float32(0.0), n_photons,
            jnp.zeros((n_lanes,), jnp.int32),
            (id_offset.astype(jnp.uint32), jnp.uint32(0)),
            jnp.float32(0.0), jnp.int32(0),
        )

        def cond(c):
            has_work = jnp.any(c.state.alive)
            if mode == "dynamic":
                has_work = has_work | (c.remaining > 0)
            else:
                has_work = has_work | jnp.any(c.launched_per_lane < quota)
            return has_work & (c.steps < cfg.max_steps)

        def round_jnp(state):
            def seg(k, rc):
                st, dep_i, dep_w, ex_i, ex_w, esc = rc
                res = ph.step(st, labels_flat, media, shape, unitinmm, cfg)
                dep_i = dep_i.at[k].set(res.dep_idx)
                dep_w = dep_w.at[k].set(res.dep_w)
                xy, xw = ph.exitance_bins(res.esc_pos, res.esc_w, shape)
                ex_i = ex_i.at[k].set(xy)
                ex_w = ex_w.at[k].set(xw)
                # the engine sums lanes in a fixed order (S.lane_sum)
                esc = esc + S.lane_sum(res.esc_w)
                return (res.state, dep_i, dep_w, ex_i, ex_w, esc)

            init = (
                state,
                jnp.zeros((K, n_lanes), jnp.int32),
                jnp.zeros((K, n_lanes), jnp.float32),
                jnp.zeros((K, n_lanes), jnp.int32),
                jnp.zeros((K, n_lanes), jnp.float32),
                jnp.float32(0.0),
            )
            return jax.lax.fori_loop(0, K, seg, init)

        def body(c):
            state, remaining, launched, next_id, w_new = S._maybe_regenerate(
                c.state, c.remaining, c.launched_per_lane, c.next_id,
                quota, source, seed, mode, shape, labels_flat,
            )
            state, dep_i, dep_w, ex_i, ex_w, esc = round_jnp(state)
            energy = c.energy.at[dep_i.reshape(-1)].add(dep_w.reshape(-1))
            exitance = c.exitance.at[ex_i.reshape(-1)].add(ex_w.reshape(-1))
            return _Pr2Carry(state, energy, exitance, c.escaped_w + esc,
                             remaining, launched, next_id,
                             c.launched_w + w_new, c.steps + K)

        final = jax.lax.while_loop(cond, body, carry0)
        return S.SimResult(
            energy=final.energy.reshape(shape),
            exitance=final.exitance.reshape((nx, ny)),
            escaped_w=final.escaped_w,
            n_launched=(final.next_id[0]
                        - id_offset.astype(jnp.uint32)).astype(jnp.int32),
            launched_w=final.launched_w,
            steps=final.steps,
        )

    return sim_fn


SHAPE = (16, 16, 16)
N_PHOTONS = 2500
LANES = 512
SEED = 17


def _bench(reflect=False):
    vol = V.benchmark_b2(SHAPE) if reflect else V.benchmark_b1(SHAPE)
    return vol, V.SimConfig(do_reflect=reflect)


def _run(vol, cfg, engine="jnp", lanes=LANES, detectors=None,
         n_photons=N_PHOTONS):
    fn = jax.jit(S.build_sim_fn(vol.shape, vol.unitinmm, cfg, lanes,
                                engine=engine, detectors=detectors))
    return fn(vol.labels.reshape(-1), vol.media, n_photons, SEED, 0)


# ---------------------------------------------------------------------------
# ntg=1 — bit-identical to the pre-PR engine at K=1 and K=4
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("reflect", [False, True])
def test_ntg1_bit_identical_to_ungated_engine(k, reflect):
    vol, cfg = _bench(reflect)
    cfg = dataclasses.replace(cfg, steps_per_round=k)
    assert cfg.n_time_gates == 1
    ref_fn = jax.jit(_pr2_fused_sim_fn(vol.shape, vol.unitinmm, cfg, LANES))
    ref = ref_fn(vol.labels.reshape(-1), vol.media, N_PHOTONS, SEED)
    res = _run(vol, cfg)
    np.testing.assert_array_equal(np.asarray(ref.energy),
                                  np.asarray(res.energy))
    np.testing.assert_array_equal(np.asarray(ref.exitance),
                                  np.asarray(res.exitance))
    assert float(ref.escaped_w) == float(res.escaped_w)
    assert int(ref.n_launched) == int(res.n_launched)
    assert float(ref.launched_w) == float(res.launched_w)
    assert int(ref.steps) == int(res.steps)


# ---------------------------------------------------------------------------
# gate-sum properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ntg", [1, 3, 8])
@pytest.mark.parametrize("k", [1, 4])
def test_fluence_td_gate_sum_is_fluence_cw_bitwise(ntg, k):
    """Summing fluence_td over gates IS fluence_cw, bit for bit, for any
    (K, gate count) on the jnp engine — the normalization is shared."""
    vol, cfg = _bench(False)
    cfg = dataclasses.replace(cfg, steps_per_round=k, n_time_gates=ntg)
    res = _run(vol, cfg)
    td = np.asarray(A.fluence_td(res, vol))
    assert td.shape == vol.shape + (ntg,)
    cw = np.asarray(A.fluence_cw(res, vol))
    np.testing.assert_array_equal(td.sum(axis=-1), cw)


@pytest.mark.parametrize("ntg", [2, 5, 16])
def test_gated_energy_sums_to_cw_run(ntg):
    """An ntg>1 run simulates the identical photon set as the CW run;
    its gate-summed energy matches to fp-accumulation tolerance and the
    overall accounting is exact."""
    vol, cfg = _bench(False)
    res_cw = _run(vol, cfg)
    res_td = _run(vol, dataclasses.replace(cfg, n_time_gates=ntg))
    assert res_td.energy.shape == vol.shape + (ntg,)
    assert int(res_cw.n_launched) == int(res_td.n_launched)
    assert float(res_cw.launched_w) == float(res_td.launched_w)
    assert int(res_cw.steps) == int(res_td.steps)
    np.testing.assert_allclose(np.asarray(res_td.energy).sum(axis=-1),
                               np.asarray(res_cw.energy),
                               rtol=5e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(res_td.exitance),
                                  np.asarray(res_cw.exitance))
    # early gates fill first for a source on the z=0 face
    per_gate = np.asarray(res_td.energy).sum(axis=(0, 1, 2))
    assert per_gate[0] > 0


@pytest.mark.parametrize("k", [4])
def test_pallas_engine_gated_matches_jnp(k):
    vol, cfg = _bench(False)
    cfg = dataclasses.replace(cfg, steps_per_round=k, n_time_gates=6)
    res_j = _run(vol, cfg, engine="jnp", lanes=256)
    res_p = _run(vol, cfg, engine="pallas", lanes=256)
    assert int(res_j.n_launched) == int(res_p.n_launched)
    np.testing.assert_allclose(np.asarray(res_j.energy),
                               np.asarray(res_p.energy),
                               rtol=5e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res_j.energy).sum(-1),
                               np.asarray(_run(vol, dataclasses.replace(
                                   cfg, n_time_gates=1), lanes=256).energy),
                               rtol=5e-5, atol=1e-5)


def test_timed_out_accounting_short_gate():
    """With a tight tmax the balance closes through timed_out, for both
    engines and any gate count."""
    vol, cfg = _bench(False)
    cfg = dataclasses.replace(cfg, tmax_ns=0.08, n_time_gates=4,
                              steps_per_round=4)
    for engine in ("jnp", "pallas"):
        res = _run(vol, cfg, engine=engine, lanes=256)
        bal = A.energy_balance(res)
        assert bal["timed_out"] > 0
        assert abs(bal["residue_frac"]) < 1e-5, (engine, bal)


# ---------------------------------------------------------------------------
# detector TPSF capture
# ---------------------------------------------------------------------------

_DETS = (Detector(8.0, 8.0, 5.0), Detector(2.0, 2.0, 2.0))


def _pencil_center():
    from repro import sources as SRC

    return SRC.Pencil(pos=(8.0, 8.0, 0.0))


def _run_det(cfg, engine="jnp", lanes=256):
    vol, _ = _bench(False)
    fn = jax.jit(S.build_sim_fn(vol.shape, vol.unitinmm, cfg, lanes,
                                source=_pencil_center(), engine=engine,
                                detectors=_DETS))
    return vol, fn(vol.labels.reshape(-1), vol.media, N_PHOTONS, SEED, 0)


def test_detected_weight_subset_of_exitance():
    cfg = dataclasses.replace(V.SimConfig(), n_time_gates=8,
                              steps_per_round=2)
    _, res = _run_det(cfg)
    assert res.det_w.shape == (2, 8)
    assert res.det_ppath.shape == (2, 2)
    tot = float(np.asarray(res.det_w).sum())
    assert 0 < tot <= float(np.asarray(res.exitance).sum()) + 1e-4
    # the central detector sits under the beam: it must catch more
    assert float(np.asarray(res.det_w)[0].sum()) > \
        float(np.asarray(res.det_w)[1].sum())


def test_detectors_do_not_perturb_physics():
    """Detector capture is pure observation: energy/exitance/accounting
    are bit-identical with and without detectors."""
    cfg = dataclasses.replace(V.SimConfig(), n_time_gates=4)
    vol, res_det = _run_det(cfg)
    fn = jax.jit(S.build_sim_fn(vol.shape, vol.unitinmm, cfg, 256,
                                source=_pencil_center()))
    res_plain = fn(vol.labels.reshape(-1), vol.media, N_PHOTONS, SEED, 0)
    np.testing.assert_array_equal(np.asarray(res_det.energy),
                                  np.asarray(res_plain.energy))
    np.testing.assert_array_equal(np.asarray(res_det.exitance),
                                  np.asarray(res_plain.exitance))
    assert float(res_det.escaped_w) == float(res_plain.escaped_w)
    assert int(res_det.steps) == int(res_plain.steps)


def test_detector_capture_engine_parity():
    cfg = dataclasses.replace(V.SimConfig(), n_time_gates=8,
                              steps_per_round=4)
    _, res_j = _run_det(cfg, engine="jnp")
    _, res_p = _run_det(cfg, engine="pallas")
    np.testing.assert_allclose(np.asarray(res_j.det_w),
                               np.asarray(res_p.det_w),
                               rtol=5e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(res_j.det_ppath),
                               np.asarray(res_p.det_ppath),
                               rtol=5e-5, atol=1e-5)


def test_tpsf_and_ppath_helpers():
    cfg = dataclasses.replace(V.SimConfig(), n_time_gates=8)
    vol, res = _run_det(cfg)
    times, curves = A.tpsf(res, cfg)
    assert times.shape == (8,) and curves.shape == (2, 8)
    # un-normalizing recovers the raw histogram
    np.testing.assert_allclose(
        curves * float(res.launched_w) * cfg.gate_width_ns,
        np.asarray(res.det_w), rtol=1e-6)
    # early-photon peak: the TPSF must peak before the last gate for a
    # detector adjacent to the source
    assert int(np.argmax(curves[0])) < 7
    mean_l = A.detector_mean_ppath(res)
    assert mean_l.shape == (2, 2)
    assert mean_l[0, 0] == 0.0  # medium 0 is exterior air: no pathlength
    assert mean_l[0, 1] > 0.0
    # rescaling to the SAME mua returns the detected weight unchanged;
    # higher absorption must attenuate it
    base = A.rescale_detected(res, vol, np.asarray(vol.media)[:, 0])
    np.testing.assert_allclose(base, np.asarray(res.det_w).sum(axis=1),
                               rtol=1e-6)
    up = np.asarray(vol.media)[:, 0] + np.asarray([0.0, 0.01])
    assert (A.rescale_detected(res, vol, up) < base + 1e-12).all()
    with pytest.raises(ValueError, match="gates"):
        A.tpsf(res, dataclasses.replace(cfg, n_time_gates=4))


def test_detector_results_match_across_chunked_run():
    """TPSF accumulators obey the same id-keyed determinism contract as
    the fluence grids: a chunked run over the same photon ids merges to
    the one-shot result to fp tolerance."""
    from repro.core.multidevice import ElasticSimulator

    vol, _ = _bench(False)
    cfg = dataclasses.replace(V.SimConfig(), n_time_gates=4)
    es = ElasticSimulator(vol, cfg, N_PHOTONS, 500, n_lanes=256, seed=SEED,
                          source=_pencil_center(), detectors=_DETS)
    res_chunked = es.run_to_completion()
    fn = jax.jit(S.build_sim_fn(vol.shape, vol.unitinmm, cfg, 256,
                                source=_pencil_center(), detectors=_DETS))
    res_one = fn(vol.labels.reshape(-1), vol.media, N_PHOTONS, SEED, 0)
    np.testing.assert_allclose(np.asarray(res_chunked.det_w),
                               np.asarray(res_one.det_w),
                               rtol=5e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(res_chunked.det_ppath),
                               np.asarray(res_one.det_ppath),
                               rtol=5e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res_chunked.energy),
                               np.asarray(res_one.energy),
                               rtol=5e-5, atol=1e-5)
    # checkpoint round-trip preserves the new accumulators
    state = es.state_dict()
    es2 = ElasticSimulator(vol, cfg, N_PHOTONS, 500, n_lanes=256, seed=SEED,
                           source=_pencil_center(), detectors=_DETS)
    es2.load_state_dict(state)
    np.testing.assert_array_equal(np.asarray(es2.result().det_w),
                                  np.asarray(res_chunked.det_w))
    assert es2.result().timed_out_w == res_chunked.timed_out_w
    # a mismatched detector set must refuse the checkpoint
    es3 = ElasticSimulator(vol, cfg, N_PHOTONS, 500, n_lanes=256, seed=SEED,
                           source=_pencil_center(),
                           detectors=(Detector(4.0, 4.0, 1.0),))
    with pytest.raises(AssertionError, match="detector mismatch"):
        es3.load_state_dict(state)
    # and a gate-count mismatch is caught by the grid-shape check
    es4 = ElasticSimulator(vol, dataclasses.replace(cfg, n_time_gates=8),
                           N_PHOTONS, 500, n_lanes=256, seed=SEED,
                           source=_pencil_center(), detectors=_DETS)
    with pytest.raises(AssertionError, match="energy grid mismatch"):
        es4.load_state_dict(state)


def test_detector_validation():
    with pytest.raises(ValueError, match="radius"):
        Detector(1.0, 1.0, 0.0)
    assert as_detectors(None) == ()
    dets = as_detectors([(1, 2, 3), {"x": 4, "y": 5, "radius": 6}])
    assert dets == (Detector(1.0, 2.0, 3.0), Detector(4.0, 5.0, 6.0))
    geom = np.asarray(det_geometry(dets))
    np.testing.assert_allclose(geom, [[1, 2, 9], [4, 5, 36]])
    with pytest.raises(ValueError, match="n_time_gates"):
        S.build_sim_fn((8, 8, 8), 1.0,
                       dataclasses.replace(V.SimConfig(), n_time_gates=0),
                       128)


# ---------------------------------------------------------------------------
# time_gate_bins edge contract (PR 4): the replay exit-gate index reuses
# this helper, so its clip-into-last-gate behavior is pinned here
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ntg", [1, 4, 32])
def test_gate_bins_edge_times(ntg):
    tmax = 5.0
    gw = tmax / ntg
    # exact edges: t=0 -> first gate; t=tmax (and beyond) clips into the
    # last gate — deposits of the partial segment crossing tmax belong
    # to the final gate, never out of range
    t = jnp.asarray([0.0, gw * 0.5, tmax - 1e-4, tmax, tmax + 1e-3,
                     10.0 * tmax], jnp.float32)
    g = np.asarray(ph.time_gate_bins(t, tmax, ntg))
    assert g[0] == 0
    assert g[-3] == ntg - 1   # t == tmax clips, not overflows
    assert g[-2] == ntg - 1   # t > tmax clips into the last gate
    assert g[-1] == ntg - 1
    assert g.min() >= 0 and g.max() < ntg
    # interior times land in their analytic gate
    assert g[1] == 0
    assert g[2] == ntg - 1


def test_gate_bins_cover_every_gate():
    ntg, tmax = 8, 4.0
    centers = (np.arange(ntg) + 0.5) * tmax / ntg
    g = np.asarray(ph.time_gate_bins(jnp.asarray(centers, jnp.float32),
                                     tmax, ntg))
    np.testing.assert_array_equal(g, np.arange(ntg))


try:  # property test: hypothesis is optional locally, pinned in CI
    from hypothesis import given, settings, strategies as hst
    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=100, deadline=None)
    @given(
        ntg=hst.integers(1, 64),
        tmax=hst.floats(1e-2, 100.0, allow_nan=False),
        ts=hst.lists(hst.floats(0.0, 1000.0, allow_nan=False), min_size=1,
                     max_size=32),
    )
    def test_property_gate_bins_in_range_and_monotone(ntg, tmax, ts):
        t = jnp.asarray(np.asarray(sorted(ts), np.float32))
        g = np.asarray(ph.time_gate_bins(t, tmax, ntg))
        assert g.min() >= 0 and g.max() <= ntg - 1
        assert (np.diff(g) >= 0).all()  # nondecreasing in time


# ---------------------------------------------------------------------------
# detector geometry validation (PR 4): disks that miss the volume
# footprint fail at make_simulator time with an actionable error
# ---------------------------------------------------------------------------

def test_detector_outside_footprint_rejected():
    vol = V.benchmark_b1((16, 16, 16))
    cfg = V.SimConfig()
    # fully outside the (nx, ny) footprint — e.g. mm coordinates used on
    # a voxel-unit API
    with pytest.raises(ValueError, match="entirely outside the z=0 face"):
        S.make_simulator(vol, cfg, 128, detectors=[Detector(40.0, 8.0, 2.0)])
    # beyond a corner, radius too small to reach the face
    with pytest.raises(ValueError, match="entirely outside"):
        S.make_simulator(vol, cfg, 128,
                         detectors=[Detector(20.0, 20.0, 3.0)])
    # tangent disks (closest approach == radius) capture nothing: reject
    with pytest.raises(ValueError, match="entirely outside"):
        S.make_simulator(vol, cfg, 128,
                         detectors=[Detector(18.0, 8.0, 2.0)])
    # the error names the offending detector index
    with pytest.raises(ValueError, match="detector 1 "):
        S.make_simulator(vol, cfg, 128,
                         detectors=[Detector(8.0, 8.0, 2.0),
                                    Detector(-9.0, 8.0, 2.0)])


def test_detector_overhanging_edge_accepted():
    """A disk overhanging the footprint edge still captures on the
    overlap — it must pass validation, and a centered one obviously
    does."""
    vol = V.benchmark_b1((16, 16, 16))
    cfg = V.SimConfig()
    for det in (Detector(0.0, 0.0, 2.0),      # corner, center on the rim
                Detector(17.0, 8.0, 2.0),     # center outside, overlaps
                Detector(8.0, 8.0, 30.0)):    # disk swallows the face
        fn = S.make_simulator(vol, cfg, 128, detectors=[det])
        res = fn(vol.labels.reshape(-1), vol.media, 200, 3)
        assert np.asarray(res.det_w).shape[0] == 1
