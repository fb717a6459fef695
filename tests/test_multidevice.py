"""Multi-device distribution tests.

These run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the flag must be set before jax initializes, and the main test process
must keep seeing exactly 1 device, per the dry-run isolation rule).
"""

import os
import subprocess
import sys

import pytest

# every test here spawns a fresh interpreter (8 fake XLA devices) and
# re-runs compilation from scratch — the expensive tail of tier-1.  CI
# keeps a fast `-m "not slow"` lane ahead of the full suite.
pytestmark = pytest.mark.slow

_ENV = dict(os.environ)
_ENV["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
_ENV["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str) -> str:
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env=_ENV,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


_PRELUDE = """
import jax, numpy as np, jax.numpy as jnp
from repro.core import volume as V, simulator as S, analysis as A
from repro.core.multidevice import (simulate_sharded, ChunkScheduler,
                                    ElasticSimulator)
vol = V.benchmark_b1((30, 30, 30)); cfg = V.b1_config()
ref = S.simulate(vol, cfg, 6000, 2048, 5)
"""


def test_sharded_equals_single_device():
    out = _run(_PRELUDE + """
mesh = jax.make_mesh((8,), ("data",))
res = simulate_sharded(vol, cfg, 6000, mesh, n_lanes=256, seed=5)
assert int(res.n_launched) == 6000
from repro.telemetry import default_tracer
events = list(default_tracer().events)
sim = events[-1]
assert (sim.name, sim.device, sim.parent_id) == ("simulate", "mesh", None)
kids = [e.name for e in events if e.parent_id == sim.span_id]
assert kids == ["simulate.compile", "simulate.execute"], kids
bal = A.energy_balance(res)
assert abs(bal["residue_frac"]) < 1e-4, bal
diff = np.abs(np.asarray(res.energy) - np.asarray(ref.energy)).max()
rel = diff / np.asarray(ref.energy).max()
assert rel < 1e-3, (diff, rel)
print("OK", rel)
""")
    assert "OK" in out


def test_sharded_uneven_partition():
    out = _run(_PRELUDE + """
mesh = jax.make_mesh((8,), ("data",))
part = [1500, 1500, 750, 750, 375, 375, 375, 375]
res = simulate_sharded(vol, cfg, 6000, mesh, partition=part, n_lanes=256, seed=5)
diff = np.abs(np.asarray(res.energy) - np.asarray(ref.energy)).max()
rel = diff / np.asarray(ref.energy).max()
assert rel < 1e-3, rel
print("OK", rel)
""")
    assert "OK" in out


def test_multipod_axes_lower_and_run():
    """2x4 mesh with ('pod', 'data') axes — the multi-pod photon sharding."""
    out = _run(_PRELUDE + """
mesh = jax.make_mesh((2, 4), ("pod", "data"))
res = simulate_sharded(vol, cfg, 6000, mesh, axis_names=("pod", "data"),
                       n_lanes=256, seed=5)
assert int(res.n_launched) == 6000
diff = np.abs(np.asarray(res.energy) - np.asarray(ref.energy)).max()
rel = diff / np.asarray(ref.energy).max()
assert rel < 1e-3, rel
print("OK", rel)
""")
    assert "OK" in out


def test_chunk_scheduler_covers_and_matches():
    out = _run(_PRELUDE + """
sched = ChunkScheduler(vol, cfg, n_lanes=256)
tot, stats = sched.run(6000, 400, seed=5)
assert int(tot.n_launched) == 6000
assert sum(stats.values()) == 6000
assert len([d for d, n in stats.items() if n > 0]) >= 2  # used >1 device
diff = np.abs(np.asarray(tot.energy) - np.asarray(ref.energy)).max()
rel = diff / np.asarray(ref.energy).max()
assert rel < 1e-3, rel
print("OK", stats)
""")
    assert "OK" in out


def test_elastic_failure_and_restart_deterministic():
    out = _run(_PRELUDE + """
es = ElasticSimulator(vol, cfg, 6000, 400, n_lanes=256, seed=5)
killed = [True]
es.run_round(fail=lambda ch, dev: ch.start_id == 0 and killed
             and (killed.pop(), True)[1])
state = es.state_dict()
# restart from checkpoint in a fresh instance (simulates process loss)
es2 = ElasticSimulator(vol, cfg, 6000, 400, n_lanes=256, seed=5)
es2.load_state_dict(state)
res = es2.run_to_completion()
assert int(res.n_launched) == 6000
diff = np.abs(np.asarray(res.energy) - np.asarray(ref.energy)).max()
rel = diff / np.asarray(ref.energy).max()
assert rel < 1e-3, rel
print("OK", rel)
""")
    assert "OK" in out


def test_time_gated_sharded_and_chunked():
    """Gate-aware merges (DESIGN.md §time-resolved): the 4-D time-gated
    energy grid, detector TPSF histograms and timed-out accounting
    survive the psum'd shard_map path and the host-side ChunkScheduler
    merge, agreeing with the single-device run of the same photon set."""
    out = _run("""
import dataclasses
import jax, numpy as np
from repro.core import volume as V, simulator as S, analysis as A
from repro.core.multidevice import simulate_sharded, ChunkScheduler
from repro.detectors import Detector
vol = V.benchmark_b1((16, 16, 16))
cfg = dataclasses.replace(V.b1_config(), n_time_gates=6, steps_per_round=2)
dets = (Detector(8.0, 8.0, 5.0),)
from repro import sources as SRC
src = SRC.Pencil(pos=(8.0, 8.0, 0.0))
ref = S.simulate(vol, cfg, 2400, 256, 5, source=src, detectors=dets)
assert ref.energy.shape == (16, 16, 16, 6)

mesh = jax.make_mesh((8,), ("data",))
res = simulate_sharded(vol, cfg, 2400, mesh, n_lanes=128, seed=5,
                       source=src, detectors=dets)
assert res.energy.shape == (16, 16, 16, 6)
assert int(res.n_launched) == 2400
assert abs(A.energy_balance(res)["residue_frac"]) < 1e-5
rel = (np.abs(np.asarray(res.energy) - np.asarray(ref.energy)).max()
       / np.asarray(ref.energy).max())
assert rel < 1e-3, rel
dw = np.abs(np.asarray(res.det_w) - np.asarray(ref.det_w)).max()
assert dw < 1e-3 * max(np.asarray(ref.det_w).max(), 1.0), dw

sched = ChunkScheduler(vol, cfg, n_lanes=128, source=src, detectors=dets)
tot, stats = sched.run(2400, 600, seed=5)
assert int(tot.n_launched) == 2400
rel = (np.abs(np.asarray(tot.energy) - np.asarray(ref.energy)).max()
       / np.asarray(ref.energy).max())
assert rel < 1e-3, rel
assert np.abs(np.asarray(tot.det_ppath) - np.asarray(ref.det_ppath)).max() \
    < 1e-2
print("OK")
""")
    assert "OK" in out


def test_fused_pallas_engine_sharded_and_chunked():
    """The fused Pallas round executor runs under every scheduler
    (DESIGN.md §rounds): shard_map'd, chunked, and elastic runs agree
    with the single-device jnp reference on the same photon set."""
    out = _run("""
import dataclasses
import jax, numpy as np
from repro.core import volume as V, simulator as S, analysis as A
from repro.core.multidevice import (simulate_sharded, ChunkScheduler,
                                    ElasticSimulator)
vol = V.benchmark_b1((16, 16, 16)); cfg = V.b1_config()
cfg = dataclasses.replace(cfg, steps_per_round=4)
ref = S.simulate(vol, cfg, 1200, 256, 5)

mesh = jax.make_mesh((8,), ("data",))
res = simulate_sharded(vol, cfg, 1200, mesh, n_lanes=128, seed=5,
                       engine="pallas")
assert int(res.n_launched) == 1200
assert abs(A.energy_balance(res)["residue_frac"]) < 1e-4
rel = (np.abs(np.asarray(res.energy) - np.asarray(ref.energy)).max()
       / np.asarray(ref.energy).max())
assert rel < 1e-3, rel

sched = ChunkScheduler(vol, cfg, n_lanes=128, engine="pallas")
tot, stats = sched.run(1200, 300, seed=5)
assert int(tot.n_launched) == 1200 and sum(stats.values()) == 1200
rel = (np.abs(np.asarray(tot.energy) - np.asarray(ref.energy)).max()
       / np.asarray(ref.energy).max())
assert rel < 1e-3, rel

es = ElasticSimulator(vol, cfg, 1200, 300, n_lanes=128, seed=5,
                      engine="pallas")
er = es.run_to_completion()
assert int(er.n_launched) == 1200
rel = (np.abs(np.asarray(er.energy) - np.asarray(ref.energy)).max()
       / np.asarray(ref.energy).max())
assert rel < 1e-3, rel
print("OK")
""")
    assert "OK" in out


def test_detected_records_sharded_chunked_elastic():
    """Detected-photon id records (DESIGN.md §replay) thread through
    every scheduler: the sharded concatenated buffers, the chunked and
    elastic host-side merges, and an elastic checkpoint/restart all
    reproduce the single-device record *set* exactly (order is
    scheduler-dependent), with 64-bit-safe chunk id offsets."""
    out = _run("""
import jax, numpy as np
from repro.core import volume as V, simulator as S
from repro.core.multidevice import (simulate_sharded, ChunkScheduler,
                                    ElasticSimulator)
from repro.detectors import Detector
from repro.replay import detected_records
from repro import sources as SRC
vol = V.benchmark_b1((16, 16, 16)); cfg = V.b1_config()
dets = (Detector(11.0, 8.0, 3.0),)
src = SRC.Pencil(pos=(8.0, 8.0, 0.0))

def row_sorted(rec):
    # lexicographic ROW sort — np.sort(axis=0) would sort each column
    # independently and could equate genuinely different record sets
    return np.asarray(sorted(map(tuple, rec)), np.uint32).reshape(-1, 4)

ref = S.simulate(vol, cfg, 4000, 512, 5, source=src, detectors=dets,
                 record_detected=2048)
recs_ref = row_sorted(detected_records(ref))
assert recs_ref.shape[0] > 0 and int(ref.det_rec_overflow) == 0

mesh = jax.make_mesh((8,), ("data",))
res = simulate_sharded(vol, cfg, 4000, mesh, n_lanes=128, seed=5,
                       source=src, detectors=dets, record_detected=512)
assert np.asarray(res.det_rec_n).shape == (8,)
assert np.array_equal(row_sorted(detected_records(res)), recs_ref)

sched = ChunkScheduler(vol, cfg, n_lanes=128, source=src, detectors=dets,
                       record_detected=512)
tot, stats = sched.run(4000, 500, seed=5)
assert np.array_equal(row_sorted(detected_records(tot)), recs_ref)

es = ElasticSimulator(vol, cfg, 4000, 500, n_lanes=128, seed=5,
                      source=src, detectors=dets, record_detected=512)
es.run_round()
sd = es.state_dict()
es2 = ElasticSimulator(vol, cfg, 4000, 500, n_lanes=128, seed=5,
                       source=src, detectors=dets, record_detected=512)
es2.load_state_dict(sd)
er = es2.run_to_completion()
assert np.array_equal(row_sorted(detected_records(er)), recs_ref)
print("OK")
""")
    assert "OK" in out


def test_sharded_replay_matches_single_device():
    """shard_map'd replay (DESIGN.md §replay): record batches fan out
    over 8 fake devices through multidevice.sharded_replay_fn; the
    per-record outputs are bit-equal to the single-device replay
    (trajectories are id-keyed) and the psum'd Jacobian agrees to
    fp-accumulation order — for both round executors and the
    gate-resolved scatter."""
    out = _run("""
import dataclasses
import jax, numpy as np
from repro.core import volume as V, simulator as S, analysis as A
from repro.detectors import Detector
from repro.replay import detected_records, replay_jacobian
vol = V.benchmark_b1((16, 16, 16))
cfg = dataclasses.replace(V.b1_config(), steps_per_round=2,
                          tmax_ns=0.5, n_time_gates=4)
dets = (Detector(11.0, 8.0, 3.0),)
src = {"type": "pencil", "pos": (8.0, 8.0, 0.0)}
res = S.simulate(vol, cfg, 1500, 256, 7, source=src, detectors=dets,
                 record_detected=4096)
rec = detected_records(res)
assert rec.shape[0] > 50

single = replay_jacobian(vol, cfg, rec, dets, source=src, seed=7,
                         n_lanes=64)
mesh = jax.make_mesh((8,), ("data",))
shard = replay_jacobian(vol, cfg, rec, dets, source=src, seed=7,
                        n_lanes=64, mesh=mesh)
assert np.array_equal(single.w_exit, shard.w_exit)
assert np.array_equal(single.gate, shard.gate)
assert np.array_equal(single.replayed_det, shard.replayed_det)
np.testing.assert_allclose(shard.jacobian, single.jacobian,
                           rtol=1e-5, atol=1e-9)

# pallas executor + gate-resolved scatter through the same fan-out
sg = replay_jacobian(vol, cfg, rec, dets, source=src, seed=7,
                     n_lanes=64, mesh=mesh, engine="pallas",
                     gate_resolved=True)
assert sg.jacobian.shape == (16, 16, 16, 1, 4)
assert np.array_equal(sg.w_exit, single.w_exit)
np.testing.assert_allclose(sg.jacobian.sum(axis=-1), single.jacobian,
                           rtol=1e-5, atol=1e-9)
M = A.jacobian_medium_sums(sg.jacobian, vol)
np.testing.assert_allclose(M, np.asarray(res.det_ppath, np.float64),
                           rtol=1e-4, atol=1e-4)
print("OK")
""")
    assert "OK" in out


def test_sharded_collect_stats_bit_identity_and_psum():
    """collect_stats under shard_map: the RoundStats pytree psums across
    shards without touching any physics bit, and the merged counters
    keep exact photon accounting (DESIGN.md §observability)."""
    out = _run(_PRELUDE + """
import dataclasses
mesh = jax.make_mesh((8,), ("data",))
off = simulate_sharded(vol, cfg, 6000, mesh, n_lanes=256, seed=5)
cfg_on = dataclasses.replace(cfg, collect_stats=True)
on = simulate_sharded(vol, cfg_on, 6000, mesh, n_lanes=256, seed=5)
assert off.stats is None and on.stats is not None
assert np.array_equal(np.asarray(off.energy), np.asarray(on.energy))
assert np.array_equal(np.asarray(off.exitance), np.asarray(on.exitance))
assert float(off.escaped_w) == float(on.escaped_w)
assert int(off.n_launched) == int(on.n_launched)
st = on.stats
assert int(st.relaunched) == int(on.n_launched) == 6000
assert float(st.escaped_w) == float(on.escaped_w)
occ = st.lane_occupancy()
assert 0.0 < occ <= 1.0, occ
bal = A.energy_balance(on)
rel = abs(float(st.deposited_w) - bal["absorbed"]) / max(bal["absorbed"], 1e-9)
assert rel < 1e-5, rel
print("OK", occ)
""")
    assert "OK" in out


def test_chaos_anchor_mixed_fleet_bit_identity():
    """The DESIGN.md §resilience acceptance anchor at fleet scale: a
    heterogeneous DevicePool over 8 fake devices — mixed jnp/pallas
    specs, one throttled straggler — under a seeded fault schedule
    (dispatch failures, NaN corruption, delays, a scheduled dropout,
    deadline-triggered speculation) produces a SimResult bit-identical
    to the fault-free run of the same fleet, with no chunk merged
    twice and the quarantine/retry accounting adding up."""
    out = _run("""
import jax, numpy as np
from repro.core import volume as V
from repro.resilience import DevicePool, DeviceSpec, FaultInjector, RetryPolicy
vol = V.benchmark_b1((16, 16, 16)); cfg = V.SimConfig(do_reflect=False)
N, CHUNK, SEED = 6000, 500, 11
devs = jax.devices()
assert len(devs) == 8
specs = [DeviceSpec(device=devs[i], engine="jnp", n_lanes=256,
                    label=f"jnp{i}") for i in range(6)]
specs += [DeviceSpec(device=devs[6], engine="pallas", n_lanes=256,
                     label="pal6"),
          DeviceSpec(device=devs[7], engine="jnp", n_lanes=256,
                     label="lag7", throttle_s=0.4)]

clean = DevicePool(vol, cfg, specs, chunk_timeout_s=0.2)
ref, rep_ref = clean.run(N, CHUNK, seed=SEED, deadline_s=600)
assert rep_ref.merged == rep_ref.n_chunks == 12

inj = FaultInjector(seed=4, p_fail=0.25, p_nan=0.15, p_delay=0.25,
                    delay_s=0.05, dropout={"jnp3": 1})
chaos = DevicePool(vol, cfg, specs, chunk_timeout_s=0.2,
                   fault_injector=inj,
                   retry_policy=RetryPolicy(max_attempts=12,
                                            quarantine_after=50))
res, rep = chaos.run(N, CHUNK, seed=SEED, deadline_s=600)
for f in ("energy", "exitance", "escaped_w", "timed_out_w", "det_w",
          "det_ppath", "launched_w", "n_launched"):
    a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(res, f))
    assert np.array_equal(a, b), f
assert rep.merged == rep.n_chunks == 12 and not rep.quarantined_chunks
assert int(res.n_launched) == N
assert rep.injected_faults > 0 and rep.retries > 0
assert rep.workers_quarantined >= 1          # the scheduled dropout
assert rep.rebound == 0                      # jnp class never extinct...
assert rep_ref.rebound == 0
# ...so bit-identity held the strong way, not via engine parity
print("OK", rep.counters())
""")
    assert "OK" in out
