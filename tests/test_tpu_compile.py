"""Compile rehearsals for a TPU v5e that is described, not attached.

Every program the chip smoke run (``chip_smoke.py``) drives is compiled
here at its real shapes (the paper's 60^3 B1/B2 domains, 4096 lanes)
by the TPU compiler that ships with libtpu, for one chip of a v5e:2x2
topology and, for the mesh runner, for all four.  Nothing runs, so
these tests say nothing about results or times: they catch what the
chip's compiler refuses (tiling, gathers, memory) before any chip time
is spent.

The topology is described inside a module fixture, never at import:
only one process may hold libtpu, and under pytest-xdist every worker
imports this file.  The Pallas photon-step kernel does not lower yet;
its test is a strict xfail that names the refusals, so the PR that
makes it lower has to remove the marker.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import scenarios as SC
from repro.core import photon as ph
from repro.core import simulator as S
from repro.core import volume as V
from repro.core.multidevice import collective_kinds, sharded_sim_fn
from repro.detectors import as_detectors, det_geometry
from repro.replay import _build_replay_fn

SIZE = 60
LANES = 4096
RECORD_CAP = 65536
GATES = 16
SOURCE = {"type": "pencil", "pos": [30.0, 30.0, 0.0]}
DETS = as_detectors([{"x": 40.0, "y": 30.0, "radius": 2.0}])  # 10 mm away


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without the chip: keep the cache off
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR",
                      os.environ.get("TPU_LOG_DIR", "disabled"))
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no libtpu, or another process holds it
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _vol_specs(vol, sharding):
    return (_spec(vol.labels.reshape(-1).shape, jnp.uint8, sharding),
            _spec(vol.media.shape, jnp.float32, sharding))


def _b2_cfg():
    vol, cfg = V.benchmark_b2((SIZE,) * 3), V.b2_config()
    return vol, dataclasses.replace(cfg, n_time_gates=GATES)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()  # XLA code only
    return compiled


ENGINE_SCOPES = {"regen", "step", "step.lookup", "step.boundary",
                 "flush.fluence", "flush.exitance"}


def _scopes(compiled) -> set[str]:
    """The named scopes in the op_name metadata of compiled HLO."""
    return {part for op in re.findall(r'op_name="([^"]*)"',
                                      compiled.as_text())
            for part in op.split("/")}


def test_b1_engine_compiles(one_chip):
    vol, cfg = V.benchmark_b1((SIZE,) * 3), V.b1_config()
    raw = S.build_sim_fn(vol.shape, vol.unitinmm, cfg, LANES,
                         source=SOURCE)
    c = _compile(raw, *_vol_specs(vol, one_chip),
                 _spec((), jnp.int32, one_chip),
                 _spec((), jnp.uint32, one_chip))
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 2**30
    assert ENGINE_SCOPES <= _scopes(c)


def test_b2_detector_gates_record_compiles(one_chip):
    vol, cfg = _b2_cfg()
    raw = S.build_sim_fn(vol.shape, vol.unitinmm, cfg, LANES,
                         source=SOURCE, detectors=DETS,
                         record_detected=RECORD_CAP)
    c = _compile(raw, *_vol_specs(vol, one_chip),
                 _spec((), jnp.int32, one_chip),
                 _spec((), jnp.uint32, one_chip))
    # the record buffer (capacity x 4 uint32) and the gated fluence are
    # outputs; both fit one chip's 16 GB many times over
    assert c.memory_analysis().output_size_in_bytes >= \
        SIZE**3 * GATES * 4 + RECORD_CAP * 16
    assert ENGINE_SCOPES | {"capture", "record"} <= _scopes(c)


def test_simulate_many_batched_closure_compiles(one_chip):
    vol, cfg = V.benchmark_b1((SIZE,) * 3), V.b1_config()
    scs = [SC.Scenario(vol, cfg, 100_000, seed=s, source=SOURCE)
           for s in (1, 2, 3)]
    fn, args = SC.make_batched(scs, n_lanes=LANES)
    specs = jax.tree_util.tree_map(
        lambda a: _spec(np.shape(a), a.dtype, one_chip), args)
    # the vmapped campaign loop inherits the engine's scopes
    assert ENGINE_SCOPES <= _scopes(_compile(fn, *specs))


def test_replay_two_pass_program_compiles(one_chip):
    vol, cfg = _b2_cfg()
    raw = _build_replay_fn(vol.shape, vol.unitinmm, cfg, LANES, len(DETS),
                           SOURCE, det_geometry(DETS), len(DETS))
    lanes = [_spec((LANES,), dt, one_chip)
             for dt in (jnp.uint32, jnp.uint32, jnp.int32, jnp.bool_)]
    _compile(raw, *_vol_specs(vol, one_chip), *lanes,
             _spec((), jnp.uint32, one_chip))


def test_sharded_b2_compiles_for_four_chips_with_one_collective_kind(topo):
    vol, cfg = _b2_cfg()
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    assert mesh.size == 4
    fn = sharded_sim_fn(vol, cfg, LANES, mesh, source=SOURCE,
                        detectors=DETS, record_detected=RECORD_CAP)
    repl, lanes = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    compiled = fn.lower(*_vol_specs(vol, repl),
                        _spec((4,), jnp.int32, lanes),
                        _spec((4,), jnp.uint32, lanes),
                        _spec((4,), jnp.uint32, lanes),
                        _spec((), jnp.uint32, repl)).compile()
    kinds = collective_kinds(compiled.as_text())
    assert set(kinds) == {"all-reduce"}, kinds
    assert ENGINE_SCOPES | {"psum"} <= _scopes(compiled)


PALLAS_REFUSALS = (
    "Mosaic refuses the photon-step kernel for the TPU: (1) the rank-1 "
    "int8 `alive` block at block_lanes=256 (rank-1 blocks need a "
    "multiple of 512 = 128 * (32 // 8)); (2) at 512 lanes, the 1-D "
    "`jnp.take` over the uint8 labels and the media table: 'Only 2D "
    "gather is supported'; (3) the in-kernel fluence scatter: "
    "'Unimplemented primitive in Pallas TPU lowering ...: scatter-add'. "
    "Needs HBM-resident labels and fluence moved by DMA (ROADMAP B1/B2)")


@pytest.mark.xfail(strict=True, raises=(ValueError, NotImplementedError),
                   reason=PALLAS_REFUSALS)
@pytest.mark.parametrize("block_lanes", [256, 512])
def test_pallas_photon_step_lowers_for_tpu(one_chip, block_lanes):
    from repro.kernels.photon_step.photon_step import photon_step_pallas

    vol, cfg = V.benchmark_b1((SIZE,) * 3), V.b1_config()

    def lane(*minor, dt=jnp.float32):
        return _spec((LANES,) + minor, dt, one_chip)

    state = ph.PhotonState(pos=lane(3), dir=lane(3),
                           ivox=lane(3, dt=jnp.int32),
                           label=lane(dt=jnp.int32), w=lane(),
                           s_left=lane(), t=lane(),
                           rng=lane(4, dt=jnp.uint32),
                           alive=lane(dt=jnp.bool_))

    def step(labels, media, st):
        return photon_step_pallas(labels, media, st, vol.shape,
                                  vol.unitinmm, cfg, 1, block_lanes,
                                  interpret=False)

    jax.jit(step).lower(*_vol_specs(vol, one_chip), state).compile()
