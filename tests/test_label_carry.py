"""The photon state carries the medium label of its voxel (DESIGN.md
§rounds).

Contracts under test:

  * Invariant: every segment starts with
    ``state.label == labels_flat[flat(clip(state.ivox))]`` in every
    lane, dead or alive — across both engines, both workload modes,
    one and four segments per round, a point and an area source, on
    B2's reflecting sphere and on a random four-label volume whose
    refractive indices mismatch at most faces.
  * Structure: the round loop reads the label grid with one per-lane
    gather per segment (the next voxel, in ``step.boundary``).  Regen
    reads it per lane only for an area source; a point source's fresh
    photons take one lookup at the source's voxel, also when the
    source position is a per-scenario value under ``vmap``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sources as SRC
from repro.core import photon as ph
from repro.core import simulator as S
from repro.core import volume as V

SHAPE = (12, 12, 12)
LANES = 128
MIN_STEPS = 300


def _random_volume(seed=5):
    """Four labels: air holes (0) and three tissues whose refractive
    indices differ, so most faces reflect or refract."""
    rs = np.random.RandomState(seed)
    labels = rs.choice(4, size=SHAPE, p=(0.04, 0.32, 0.32, 0.32))
    media = V.pack_media([
        V.AIR,
        V.Medium(mua=0.01, mus=2.0, g=0.8, n=1.37),
        V.Medium(mua=0.005, mus=4.0, g=0.5, n=1.5),
        V.Medium(mua=0.02, mus=1.0, g=0.0, n=1.2),
    ])
    return V.Volume(labels=jnp.asarray(labels, jnp.uint8), media=media)


VOLUMES = {"b2_sphere": lambda: V.benchmark_b2(SHAPE),
           "random4": _random_volume}
SOURCES = {"pencil": SRC.Pencil(pos=(6.0, 6.0, 0.0)),
           "disk": SRC.Disk(pos=(6.0, 6.0, 0.0), radius=4.0)}


def _checked_step(monkeypatch):
    """Wrap ``photon.step`` so each traced segment reports how many
    lanes enter it with a label that is not their voxel's."""
    real = ph.step
    bad = []

    def step(state, labels_flat, media, shape, *args, **kw):
        want, _ = ph._lookup_label(labels_flat, shape, state.ivox)
        n_bad = jnp.sum(state.label != want.astype(jnp.int32))
        jax.debug.callback(lambda b: bad.append(int(b)), n_bad)
        return real(state, labels_flat, media, shape, *args, **kw)

    monkeypatch.setattr(ph, "step", step)
    return bad


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("mode", ["dynamic", "static"])
@pytest.mark.parametrize("source", sorted(SOURCES))
@pytest.mark.parametrize("volume", sorted(VOLUMES))
def test_label_matches_voxel_every_segment(monkeypatch, volume, source,
                                           mode, k, engine):
    vol = VOLUMES[volume]()
    cfg = V.SimConfig(do_reflect=True, steps_per_round=k)
    bad = _checked_step(monkeypatch)
    fn = jax.jit(S.build_sim_fn(vol.shape, vol.unitinmm, cfg, LANES, mode,
                                SOURCES[source], engine=engine,
                                block_lanes=64, interpret=True))
    res = fn(vol.labels.reshape(-1), vol.media, 600, 17)
    jax.block_until_ready(res)
    assert int(res.steps) >= MIN_STEPS
    # the Pallas engine runs the step once per lane block
    n_blocks = LANES // 64 if engine == "pallas" else 1
    assert len(bad) == int(res.steps) * n_blocks
    assert sum(bad) == 0, f"{sum(bad)} lane-segments with a stale label"


# ---------------------------------------------------------------------------
# structure: where the traced round loop reads the label grid
# ---------------------------------------------------------------------------

_REGIONS = ("while", "scan", "cond")


def _label_reads(jaxpr, nvox, path=()):
    """``(path, n_indices)`` for each gather from the ``(nvox,)`` uint8
    label grid; ``path`` names the enclosing loops and branches."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            src = eqn.invars[0].aval
            if src.shape == (nvox,) and src.dtype == jnp.uint8:
                out.append((path, int(np.prod(eqn.outvars[0].aval.shape))))
        name = eqn.primitive.name
        sub_path = path + (name,) if name in _REGIONS else path
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _label_reads(sub, nvox, sub_path)
    return out


@pytest.mark.parametrize("source", sorted(SOURCES))
@pytest.mark.parametrize("bench", ["B1", "B2"])
def test_round_loop_reads_label_grid_once_a_segment(bench, source):
    vol = V.benchmark_b1(SHAPE) if bench == "B1" else V.benchmark_b2(SHAPE)
    cfg = V.b1_config() if bench == "B1" else V.b2_config()
    cfg = dataclasses.replace(cfg, steps_per_round=4)
    fn = S.build_sim_fn(vol.shape, vol.unitinmm, cfg, LANES,
                        source=SOURCES[source])
    closed = jax.make_jaxpr(fn)(vol.labels.reshape(-1), vol.media, 1000, 3)
    reads = _label_reads(closed.jaxpr, vol.labels.size)
    # the K segments of a round are one scan body: one gather a segment
    segment = [m for p, m in reads if p == ("while", "scan")]
    regen = [m for p, m in reads if p == ("while", "cond")]
    before_loop = [m for p, m in reads if p == ()]
    assert segment == [LANES], reads
    if source == "pencil":
        # one lookup at the source's voxel, outside the round loop
        assert (regen, before_loop) == ([], [1]), reads
    else:
        assert (regen, before_loop) == ([LANES], []), reads
    assert len(reads) == 2, reads


def test_vmapped_point_source_looks_up_one_voxel_per_scenario():
    vol = V.benchmark_b2(SHAPE)
    cfg = V.SimConfig(do_reflect=True)
    cls, staged = SRC.stage_source(SOURCES["pencil"])
    batch = 3
    stacked = {key: jnp.stack([v] * batch) for key, v in staged.items()}

    def one(labels_flat, media, staged, seed):
        fn = S.build_sim_fn(vol.shape, vol.unitinmm, cfg, LANES,
                            source=SRC.StagedSource(cls, staged))
        return fn(labels_flat, media, 1000, seed)

    # labels shared by the batch, as the campaign runner passes them
    closed = jax.make_jaxpr(jax.vmap(one, in_axes=(None, 0, 0, 0)))(
        vol.labels.reshape(-1), jnp.stack([vol.media] * batch), stacked,
        jnp.arange(batch, dtype=jnp.uint32))
    reads = _label_reads(closed.jaxpr, vol.labels.size)
    # vmap turns regen's cond into a select whose branches both run in
    # every round: a launch that gathered per lane would run there too
    assert reads == [((), batch), (("while", "scan"), batch * LANES)], reads
