"""Source subsystem tests: registry, determinism contract, physics.

Covers the DESIGN.md §sources guarantees: pure counter-seeded sampling
(photon id, not lane/device, determines the launch state), pencil-beam
bit-compatibility with the historical hard-coded launch, per-type weight
conservation through a full simulation, and id_offset-sharded launches
reproducing the single-device photon set for a non-pencil source.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sources as SRC
from repro.core import rng as xrng
from repro.core import simulator as S
from repro.core import volume as V

SHAPE = (16, 16, 16)
CENTER_FACE = (8.0, 8.0, 0.0)
CENTER = (8.0, 8.0, 8.0)

ALL_SOURCES = {
    "pencil": SRC.Pencil(pos=CENTER_FACE),
    "isotropic": SRC.IsotropicPoint(pos=CENTER),
    "cone": SRC.Cone(pos=CENTER_FACE, half_angle_deg=20.0),
    "gaussian": SRC.GaussianBeam(pos=CENTER_FACE, waist=2.0),
    "disk": SRC.Disk(pos=CENTER_FACE, radius=3.0),
    "planar": SRC.Planar(pos=(4.0, 4.0, 0.0), v1=(8.0, 0.0, 0.0),
                         v2=(0.0, 8.0, 0.0),
                         pattern=((1.0, 0.5), (0.25, 1.0))),
    "line_slit": SRC.Line(start=(4.0, 8.0, 0.0), end=(12.0, 8.0, 0.0)),
    "line_iso": SRC.Line(start=(4.0, 8.0, 8.0), end=(12.0, 8.0, 8.0),
                         dir=None),
}


# ---------------------------------------------------------------------------
# registry + serialization
# ---------------------------------------------------------------------------

def test_registry_lists_all_types():
    assert set(SRC.available_sources()) == {
        "pencil", "isotropic", "cone", "gaussian", "disk", "planar", "line",
    }


@pytest.mark.parametrize("name", sorted(ALL_SOURCES))
def test_config_roundtrip(name):
    src = ALL_SOURCES[name]
    d = SRC.to_dict(src)
    assert d["type"] == src.type_name
    # JSON-friendly: only lists/scalars/None in the payload
    import json
    json.dumps(d)
    assert SRC.from_dict(d) == src


def test_as_source_coercions():
    assert SRC.as_source(None) == SRC.Pencil()
    legacy = V.Source(pos=(5.0, 6.0, 0.0), dir=(0.0, 0.0, 1.0))
    assert SRC.as_source(legacy) == SRC.Pencil(pos=(5.0, 6.0, 0.0))
    disk = ALL_SOURCES["disk"]
    assert SRC.as_source(disk) is disk
    assert SRC.as_source(SRC.to_dict(disk)) == disk
    with pytest.raises(KeyError):
        SRC.from_dict({"type": "warp-drive"})
    with pytest.raises(TypeError):
        SRC.as_source(42)
    # list-typed fields are normalized to tuples so sources stay hashable
    # (jit caches in ChunkScheduler key on the source instance)
    listy = SRC.Planar(pos=[4.0, 4.0, 0.0], v1=[8.0, 0.0, 0.0],
                       v2=[0.0, 8.0, 0.0], pattern=[[1.0, 0.5], [0.5, 1.0]])
    norm = SRC.as_source(listy)
    hash(norm)
    assert norm == SRC.Planar(pos=(4.0, 4.0, 0.0), v1=(8.0, 0.0, 0.0),
                              v2=(0.0, 8.0, 0.0),
                              pattern=((1.0, 0.5), (0.5, 1.0)))


# ---------------------------------------------------------------------------
# determinism contract
# ---------------------------------------------------------------------------

def test_pencil_matches_historical_launch():
    """Pencil sampling is bit-identical to the pre-subsystem hard-coded
    launch: broadcast pos/dir, unit weights, unsalted counter RNG."""
    src = ALL_SOURCES["pencil"]
    ids = jnp.arange(64, dtype=jnp.uint32)
    pos, direc, w0, rng = src.sample(ids, jnp.uint32(99))
    np.testing.assert_array_equal(
        np.asarray(pos), np.full((64, 3), CENTER_FACE, np.float32))
    np.testing.assert_array_equal(
        np.asarray(direc), np.broadcast_to([0.0, 0.0, 1.0], (64, 3)))
    np.testing.assert_array_equal(np.asarray(w0), np.ones(64, np.float32))
    np.testing.assert_array_equal(
        np.asarray(rng), np.asarray(xrng.seed_state(jnp.uint32(99), ids)))


@pytest.mark.parametrize("name", sorted(ALL_SOURCES))
def test_sample_is_pure_in_photon_id(name):
    """Row k of sample(ids) depends only on ids[k] — lane order, batch
    size, and shard boundaries cannot change any photon's launch state."""
    src = ALL_SOURCES[name]
    seed = jnp.uint32(7)
    ids = jnp.arange(40, dtype=jnp.uint32)
    perm = np.random.default_rng(0).permutation(40)
    ref = src.sample(ids, seed)
    shuffled = src.sample(ids[perm], seed)
    for a, b in zip(ref, shuffled):
        np.testing.assert_array_equal(np.asarray(a)[perm], np.asarray(b))
    # and a disjoint id window sampled separately matches the full window
    tail = src.sample(ids[25:], seed)
    for a, b in zip(ref, tail):
        np.testing.assert_array_equal(np.asarray(a)[25:], np.asarray(b))


@pytest.mark.parametrize("name", sorted(ALL_SOURCES))
def test_sample_geometry(name):
    src = ALL_SOURCES[name]
    pos, direc, w0, _ = src.sample(jnp.arange(500, dtype=jnp.uint32),
                                   jnp.uint32(3))
    norms = np.linalg.norm(np.asarray(direc), axis=-1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-5)
    w = np.asarray(w0)
    assert np.all(w >= 0.0) and np.all(w <= 1.0)
    p = np.asarray(pos)
    if name == "cone":
        cost = np.asarray(direc)[:, 2]  # axis is +z
        assert np.all(cost >= np.cos(np.radians(20.0)) - 1e-5)
    if name == "disk":
        r = np.linalg.norm(p - np.asarray(CENTER_FACE), axis=-1)
        assert np.all(r <= 3.0 + 1e-5)
    if name == "planar":
        assert np.all(p[:, 0] >= 4.0 - 1e-5) and np.all(p[:, 0] <= 12.0 + 1e-5)
        assert np.all(p[:, 1] >= 4.0 - 1e-5) and np.all(p[:, 1] <= 12.0 + 1e-5)
        assert len(np.unique(w)) > 1  # pattern actually modulates weights


# ---------------------------------------------------------------------------
# full-simulation physics
# ---------------------------------------------------------------------------

def _launched_weight(src, n, seed):
    ids = jnp.arange(n, dtype=jnp.uint32)
    return float(jnp.sum(src.sample(ids, jnp.uint32(seed))[2]))


@pytest.mark.parametrize("name", sorted(ALL_SOURCES))
def test_weight_conservation(name):
    """deposited + escaped ≈ launched weight once every photon terminates
    (roulette is unbiased; residue is statistical only)."""
    src = ALL_SOURCES[name]
    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False)
    n = 1500
    res = S.simulate(vol, cfg, n, 512, 11, source=src)
    jax.block_until_ready(res)
    assert int(res.n_launched) == n
    launched = _launched_weight(src, n, 11)
    # the engine's launched-weight accumulator matches the analytic sum
    np.testing.assert_allclose(float(res.launched_w), launched, rtol=1e-6)
    total = float(jnp.sum(res.energy)) + float(res.escaped_w)
    assert abs(total - launched) / launched < 5e-3, (total, launched)


def test_sharded_id_offset_reproduces_single_run():
    """Two id_offset-sharded launches of a non-pencil source reproduce the
    single-device photon set (DESIGN.md §determinism + §sources)."""
    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False)
    src = ALL_SOURCES["disk"]
    labels, media = vol.labels.reshape(-1), vol.media
    n = 2000
    fn = jax.jit(S.build_sim_fn(SHAPE, vol.unitinmm, cfg, 512,
                                source=src))
    full = fn(labels, media, n, 5)
    half_a = fn(labels, media, n // 2, 5, 0)
    half_b = fn(labels, media, n // 2, 5, n // 2)
    jax.block_until_ready((full, half_a, half_b))
    assert int(half_a.n_launched) + int(half_b.n_launched) == n
    merged = np.asarray(half_a.energy) + np.asarray(half_b.energy)
    ref = np.asarray(full.energy)
    rel = np.abs(merged - ref).max() / ref.max()
    assert rel < 1e-3, rel
    esc = float(half_a.escaped_w) + float(half_b.escaped_w)
    np.testing.assert_allclose(esc, float(full.escaped_w), rtol=1e-4)


def test_out_of_domain_launches_are_clamped():
    """Launch positions sampled outside the volume are clamped onto the
    boundary (photon.launch): the run still terminates and conserves
    weight instead of mis-depositing from inconsistent pos/ivox lanes."""
    from repro.core import photon as ph

    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False)
    # disk overhanging the x=0 face + wide Gaussian tails
    for src in (SRC.Disk(pos=(1.0, 8.0, 0.0), radius=5.0),
                SRC.GaussianBeam(pos=(8.0, 8.0, 0.0), waist=10.0)):
        ids = jnp.arange(400, dtype=jnp.uint32)
        pos, direc, w0, rng = src.sample(ids, jnp.uint32(2))
        state = ph.launch(pos, direc, w0, rng, jnp.ones((400,), bool), SHAPE,
                          vol.labels.reshape(-1))
        p = np.asarray(state.pos)
        assert p.min() >= 0.0 and np.all(p <= np.asarray(SHAPE, np.float32))
        np.testing.assert_array_equal(
            np.asarray(state.ivox),
            np.clip(np.floor(p).astype(np.int32), 0,
                    np.asarray(SHAPE, np.int32) - 1))
        res = S.simulate(vol, cfg, 800, 256, 2, source=src)
        jax.block_until_ready(res)
        launched = _launched_weight(src, 800, 2)
        total = float(jnp.sum(res.energy)) + float(res.escaped_w)
        assert abs(total - launched) / launched < 5e-3


def test_energy_balance_uses_launched_weight():
    """energy_balance must balance against launched *weight*, not photon
    count — a Planar pattern source launches well below 1.0 per photon."""
    from repro.core import analysis as A

    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False)
    src = ALL_SOURCES["planar"]
    res = S.simulate(vol, cfg, 1500, 512, 11, source=src)
    jax.block_until_ready(res)
    bal = A.energy_balance(res)
    assert bal["launched"] < 1500 * 0.95  # pattern weights pull it down
    assert abs(bal["residue_frac"]) < 5e-3, bal


def test_elastic_checkpoint_rejects_source_mismatch():
    from repro.core.multidevice import ElasticSimulator

    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False)
    es = ElasticSimulator(vol, cfg, 800, 400, n_lanes=256, seed=3,
                          source=ALL_SOURCES["disk"])
    es.run_round(max_chunks=1)
    state = es.state_dict()
    es2 = ElasticSimulator(vol, cfg, 800, 400, n_lanes=256, seed=3)  # pencil
    with pytest.raises(AssertionError, match="source mismatch"):
        es2.load_state_dict(state)
    es3 = ElasticSimulator(vol, cfg, 800, 400, n_lanes=256, seed=3,
                           source=ALL_SOURCES["disk"])
    es3.load_state_dict(state)
    res = es3.run_to_completion()
    assert int(res.n_launched) == 800


def test_elastic_checkpoint_roundtrips_through_checkpointer(tmp_path):
    """Every state_dict leaf must stay a numeric array the project
    Checkpointer can write to npz — including the encoded source key."""
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.core.multidevice import ElasticSimulator

    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False)
    es = ElasticSimulator(vol, cfg, 800, 400, n_lanes=256, seed=3,
                          source=ALL_SOURCES["disk"])
    es.run_round(max_chunks=1)
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(1, es.state_dict())
    step, restored = ckpt.restore(es.state_dict())
    assert step == 1
    es2 = ElasticSimulator(vol, cfg, 800, 400, n_lanes=256, seed=3,
                           source=ALL_SOURCES["disk"])
    es2.load_state_dict(restored)
    res = es2.run_to_completion()
    assert int(res.n_launched) == 800
    np.testing.assert_allclose(
        float(res.launched_w) - float(jnp.sum(res.energy))
        - float(res.escaped_w), 0.0, atol=5.0)


def test_non_pencil_source_changes_fluence():
    """Different sources must actually produce different light fields."""
    vol = V.benchmark_b1(SHAPE)
    cfg = V.SimConfig(do_reflect=False)
    a = S.simulate(vol, cfg, 800, 256, 3, source=ALL_SOURCES["pencil"])
    b = S.simulate(vol, cfg, 800, 256, 3, source=ALL_SOURCES["isotropic"])
    assert not np.allclose(np.asarray(a.energy), np.asarray(b.energy))
