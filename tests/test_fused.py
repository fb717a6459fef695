"""Fused multi-segment rounds (DESIGN.md §rounds).

Contracts under test:

  * ``steps_per_round=1`` with ``engine="jnp"`` reproduces the pre-PR
    (seed) engine **bit-for-bit** — energy, exitance, escaped_w,
    n_launched, launched_w, steps.  The seed loop (one regeneration +
    one per-segment scatter per outer iteration) is embedded verbatim
    below as the reference.
  * K>1 changes only fp accumulation order: trajectories/RNG are
    id-keyed, so energy/exitance/escaped agree with K=1 to
    fp-accumulation tolerance and the photon accounting is exact.
  * ``engine="pallas"`` matches ``engine="jnp"`` on the same round
    config to fp-accumulation tolerance (blocked in-kernel scatters).
"""

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import photon as ph
from repro.core import simulator as S
from repro.core import volume as V
from repro.sources import as_source


# ---------------------------------------------------------------------------
# Verbatim copy of the pre-PR engine loop: regeneration + ONE segment +
# per-segment global scatters on every while_loop iteration.
# ---------------------------------------------------------------------------

class _SeedCarry(NamedTuple):
    state: ph.PhotonState
    energy: jnp.ndarray
    exitance: jnp.ndarray
    escaped_w: jnp.ndarray
    remaining: jnp.ndarray
    launched_per_lane: jnp.ndarray
    next_id: jnp.ndarray
    launched_w: jnp.ndarray
    steps: jnp.ndarray


def _seed_sim_fn(shape, unitinmm, cfg, n_lanes, mode="dynamic", source=None):
    source = as_source(source)
    nx, ny, nz = shape
    nvox = nx * ny * nz

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
        carry0 = _SeedCarry(
            state0, jnp.zeros((nvox,), jnp.float32),
            jnp.zeros((nx, ny), jnp.float32), jnp.float32(0.0), n_photons,
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

        def body(c):
            # _regenerate now carries the id counter as a 64-bit
            # (lo, hi) uint32 pair; hi=0 is bit-identical to the seed
            # engine's int32 counter, so the copy keeps its contract
            state, remaining, launched, next_id, w_new = S._regenerate(
                c.state, c.remaining, c.launched_per_lane, c.next_id,
                quota, source, seed, mode, shape, labels_flat)
            res = ph.step(state, labels_flat, media, shape, unitinmm, cfg)
            energy = c.energy.at[res.dep_idx].add(res.dep_w)
            # the engine sums lanes in a fixed order (S.lane_sum)
            escaped_w = c.escaped_w + S.lane_sum(res.esc_w)
            z_exit = res.esc_pos[:, 2] < ph.Z_EXIT_FACE_VOX
            hit = (res.esc_w > 0) & z_exit
            ex = jnp.clip(jnp.floor(res.esc_pos[:, 0]).astype(jnp.int32),
                          0, nx - 1)
            ey = jnp.clip(jnp.floor(res.esc_pos[:, 1]).astype(jnp.int32),
                          0, ny - 1)
            exitance = c.exitance.at[ex, ey].add(
                jnp.where(hit, res.esc_w, 0.0))
            return _SeedCarry(res.state, energy, exitance, escaped_w,
                              remaining, launched, next_id,
                              c.launched_w + w_new, c.steps + 1)

        final = jax.lax.while_loop(cond, body, carry0)
        return S.SimResult(
            energy=final.energy.reshape(shape),
            exitance=final.exitance,
            escaped_w=final.escaped_w,
            n_launched=(final.next_id[0]
                        - id_offset.astype(jnp.uint32)).astype(jnp.int32),
            launched_w=final.launched_w,
            steps=final.steps,
        )

    return sim_fn


SHAPE = (16, 16, 16)
N_PHOTONS = 3000
LANES = 512
SEED = 42


def _bench(reflect):
    vol = V.benchmark_b2(SHAPE) if reflect else V.benchmark_b1(SHAPE)
    return vol, V.SimConfig(do_reflect=reflect)


def _run(vol, cfg, mode="dynamic", engine="jnp", lanes=LANES,
         id_offset=0):
    fn = jax.jit(S.build_sim_fn(vol.shape, vol.unitinmm, cfg, lanes, mode,
                                engine=engine))
    return fn(vol.labels.reshape(-1), vol.media, N_PHOTONS, SEED, id_offset)


# ---------------------------------------------------------------------------
# K=1 — bit-identical to the seed engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("reflect,mode", [
    (False, "dynamic"),   # B1, the pencil-beam benchmark config
    (True, "dynamic"),    # B2
    (False, "static"),
])
def test_k1_bit_identical_to_seed_engine(reflect, mode):
    vol, cfg = _bench(reflect)
    assert cfg.steps_per_round == 1
    seed_fn = jax.jit(_seed_sim_fn(vol.shape, vol.unitinmm, cfg, LANES, mode))
    ref = seed_fn(vol.labels.reshape(-1), vol.media, N_PHOTONS, SEED)
    res = _run(vol, cfg, mode)
    np.testing.assert_array_equal(np.asarray(ref.energy),
                                  np.asarray(res.energy))
    np.testing.assert_array_equal(np.asarray(ref.exitance),
                                  np.asarray(res.exitance))
    assert float(ref.escaped_w) == float(res.escaped_w)
    assert int(ref.n_launched) == int(res.n_launched)
    assert float(ref.launched_w) == float(res.launched_w)
    assert int(ref.steps) == int(res.steps)


# ---------------------------------------------------------------------------
# K>1 — same physics, fp-accumulation-order changes only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [4, 16])
@pytest.mark.parametrize("reflect", [False, True])
def test_fused_rounds_match_k1(k, reflect):
    vol, cfg1 = _bench(reflect)
    res1 = _run(vol, cfg1, "dynamic")
    cfgk = dataclasses.replace(cfg1, steps_per_round=k)
    resk = _run(vol, cfgk, "dynamic")
    # photon accounting is exact: same id-keyed photon set launches
    assert int(res1.n_launched) == int(resk.n_launched) == N_PHOTONS
    assert float(res1.launched_w) == float(resk.launched_w)
    np.testing.assert_allclose(np.asarray(res1.energy),
                               np.asarray(resk.energy),
                               rtol=5e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res1.exitance),
                               np.asarray(resk.exitance),
                               rtol=5e-5, atol=1e-5)
    np.testing.assert_allclose(float(res1.escaped_w), float(resk.escaped_w),
                               rtol=1e-5)
    # a round only ever runs whole: steps is a multiple of K
    assert int(resk.steps) % k == 0


@pytest.mark.parametrize("k", [4])
def test_fused_static_mode(k):
    vol, cfg1 = _bench(False)
    res1 = _run(vol, cfg1, "static")
    resk = _run(vol, dataclasses.replace(cfg1, steps_per_round=k), "static")
    assert int(res1.n_launched) == int(resk.n_launched) == N_PHOTONS
    np.testing.assert_allclose(np.asarray(res1.energy),
                               np.asarray(resk.energy),
                               rtol=5e-5, atol=1e-5)


def test_fused_id_offset_determinism():
    """Fused rounds keep the §determinism contract: a shard simulating
    ids [offset, offset+n) is unaffected by K."""
    vol, cfg1 = _bench(False)
    cfg8 = dataclasses.replace(cfg1, steps_per_round=8)
    a = _run(vol, cfg1, id_offset=7777)
    b = _run(vol, cfg8, id_offset=7777)
    assert int(a.n_launched) == int(b.n_launched)
    np.testing.assert_allclose(np.asarray(a.energy), np.asarray(b.energy),
                               rtol=5e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# engine="pallas" — parity with the jnp engine on the same round config
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,reflect", [(4, False), (8, True)])
def test_pallas_engine_parity(k, reflect):
    vol, cfg = _bench(reflect)
    cfg = dataclasses.replace(cfg, steps_per_round=k)
    res_j = _run(vol, cfg, engine="jnp", lanes=256)
    res_p = _run(vol, cfg, engine="pallas", lanes=256)
    assert int(res_j.n_launched) == int(res_p.n_launched) == N_PHOTONS
    assert int(res_j.steps) == int(res_p.steps)
    np.testing.assert_allclose(np.asarray(res_j.energy),
                               np.asarray(res_p.energy),
                               rtol=5e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res_j.exitance),
                               np.asarray(res_p.exitance),
                               rtol=5e-5, atol=1e-5)
    np.testing.assert_allclose(float(res_j.escaped_w),
                               float(res_p.escaped_w), rtol=1e-5)


def test_pallas_engine_simulate_api():
    """simulate(engine="pallas") end to end, energy balance closed."""
    from repro.core import analysis as A

    vol, cfg = _bench(False)
    cfg = dataclasses.replace(cfg, steps_per_round=8)
    res = S.simulate(vol, cfg, 1500, n_lanes=256, seed=3, engine="pallas")
    bal = A.energy_balance(res)
    assert abs(bal["residue_frac"]) < 1e-4
    assert int(res.n_launched) == 1500


def test_engine_validation():
    vol, cfg = _bench(False)
    with pytest.raises(ValueError, match="unknown engine"):
        S.build_sim_fn(vol.shape, vol.unitinmm, cfg, 128, engine="cuda")
    with pytest.raises(ValueError, match="steps_per_round"):
        S.build_sim_fn(vol.shape, vol.unitinmm,
                       dataclasses.replace(cfg, steps_per_round=0), 128)


def test_autotune_rounds_2d():
    """The 2-D Opt2 sweep returns a (lanes, K) grid of timings."""
    vol, cfg = _bench(False)
    (lanes, k), timings = S.autotune_rounds(
        vol, cfg, n_pilot=400, lane_candidates=(128, 256),
        round_candidates=(1, 4), repeats=1)
    assert set(timings) == {(128, 1), (128, 4), (256, 1), (256, 4)}
    assert (lanes, k) in timings
    assert timings[(lanes, k)] == min(timings.values())
    # the legacy 1-D interface still works on top of the 2-D sweep
    best, t1d = S.autotune_lanes(vol, cfg, n_pilot=400,
                                 candidates=(128, 256), repeats=1)
    assert best in (128, 256) and set(t1d) == {128, 256}
