"""Simulation telemetry (DESIGN.md §observability).

Contracts under test:

  * ``collect_stats=True`` changes NO physics bit: energy, exitance,
    escaped_w, timed_out_w, n_launched, launched_w and steps are
    bit-identical to the stats-off run — for both round executors and
    for K in {1, 4}.
  * ``SimResult.stats`` reconciles with the energy-balance identity:
    relaunched == n_launched, escaped_w / timed_out_w are bit-equal to
    the SimResult fields, deposited_w matches sum(energy) to fp
    accumulation order, detected_w matches sum(det_w), and
    lane_segments == steps * n_lanes.
  * The flush counters count the updates that carry weight: none
    beyond the lane-segments run, no more exitance updates than
    photons, the same counts from both engines, none from a volume that
    absorbs every photon.
  * The Tracer's span timeline round-trips through Chrome trace JSON
    and feeds ``loadbalance.fit_pilot`` as measured-throughput samples
    (the dispatch -> measure -> refit -> re-partition loop).
  * The entry points record a span tree on ``time.time_ns()``'s clock
    into the process tracer: a CLI call and a ``simulate_many`` call,
    compile spans ending before execution starts.
  * The CLI surfaces the silent-loss warnings (timed-out weight,
    detector id-buffer overflow) and writes trace/metrics files that
    parse back into device models.
"""

import dataclasses
import json
import time

import jax
import numpy as np
import pytest

from repro.core import analysis as A
from repro.core import loadbalance as LB
from repro.core import simulator as S
from repro.core import volume as V
from repro.core.multidevice import ChunkScheduler, ElasticSimulator
from repro.detectors import Detector
from repro.launch import simulate as CLI
from repro.scenarios import CompileCache, Scenario, simulate_many
from repro.telemetry import (InMemorySink, JsonlSink, RoundStats, SpanEvent,
                             Tracer, chrome_trace, default_tracer,
                             device_label, fit_device_models,
                             load_chrome_trace)

SHAPE = (16, 16, 16)
N_PHOTONS = 2000
LANES = 256
SEED = 9


def _bench(reflect=False):
    vol = V.benchmark_b2(SHAPE) if reflect else V.benchmark_b1(SHAPE)
    return vol, V.SimConfig(do_reflect=reflect)


def _run(vol, cfg, engine="jnp", **kw):
    return S.simulate(vol, cfg, N_PHOTONS, LANES, SEED, engine=engine, **kw)


# ---------------------------------------------------------------------------
# RoundStats: bit-identical physics + counter reconciliation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["jnp", "pallas"])
@pytest.mark.parametrize("k", [1, 4])
def test_collect_stats_changes_no_physics_bit(engine, k):
    vol, cfg = _bench()
    cfg = dataclasses.replace(cfg, steps_per_round=k)
    off = _run(vol, cfg, engine)
    on = _run(vol, dataclasses.replace(cfg, collect_stats=True), engine)
    assert off.stats is None and on.stats is not None
    np.testing.assert_array_equal(np.asarray(off.energy),
                                  np.asarray(on.energy))
    np.testing.assert_array_equal(np.asarray(off.exitance),
                                  np.asarray(on.exitance))
    for field in ("escaped_w", "timed_out_w", "launched_w"):
        assert float(getattr(off, field)) == float(getattr(on, field)), field
    assert int(off.n_launched) == int(on.n_launched)
    assert int(off.steps) == int(on.steps)


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_round_stats_reconcile_with_energy_balance(engine):
    vol, cfg = _bench()
    cfg = dataclasses.replace(cfg, steps_per_round=4, collect_stats=True)
    res = _run(vol, cfg, engine)
    st = res.stats
    bal = A.energy_balance(res)
    # exact photon accounting: every launch goes through regeneration
    assert int(st.relaunched) == int(res.n_launched) == N_PHOTONS
    assert int(st.rounds) == int(res.steps) // 4
    assert 0 < int(st.regen_rounds) <= int(st.rounds)
    # retired-weight counters mirror the physics accumulators bit-exactly
    assert float(st.escaped_w) == float(res.escaped_w) == bal["escaped"]
    assert float(st.timed_out_w) == float(res.timed_out_w)
    # deposited weight re-sums the same per-segment deposits the energy
    # grid scatters, so it agrees to fp accumulation order
    np.testing.assert_allclose(float(st.deposited_w), bal["absorbed"],
                               rtol=1e-5)
    # the counters close the balance like the grids do, up to the
    # statistical Russian-roulette residue
    total = (float(st.deposited_w) + float(st.escaped_w)
             + float(st.timed_out_w))
    np.testing.assert_allclose(total, bal["launched"], rtol=5e-4)
    # occupancy bookkeeping: denominator is exactly steps * n_lanes
    assert float(st.lane_segments) == float(int(res.steps) * LANES)
    assert 0.0 < st.lane_occupancy() <= 1.0


def test_round_stats_engine_and_k_invariant_live_segments():
    """live_segments counts id-keyed trajectory segments, so it is
    invariant across engines and K (trajectories are identical)."""
    vol, cfg0 = _bench()
    vals = set()
    for engine in ("jnp", "pallas"):
        for k in (1, 4):
            cfg = dataclasses.replace(cfg0, steps_per_round=k,
                                      collect_stats=True)
            vals.add(float(_run(vol, cfg, engine).stats.live_segments))
    assert len(vals) == 1, vals


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_flush_counters_count_weighted_updates(engine):
    vol, cfg = _bench(reflect=True)
    cfg = dataclasses.replace(cfg, steps_per_round=4, collect_stats=True)
    res = _run(vol, cfg, engine)
    st = res.stats
    assert 0 < float(st.dep_live) <= float(st.live_segments)
    assert float(st.live_segments) <= float(st.lane_segments)
    # a photon leaves the z=0 face at most once
    assert 0 < float(st.exit_live) <= int(res.n_launched)


def test_flush_counters_engine_and_k_invariant():
    """Trajectories are identical across engines and K, so are the
    segments that deposit and that leave through the z=0 face."""
    vol, cfg0 = _bench(reflect=True)
    vals = set()
    for engine in ("jnp", "pallas"):
        for k in (1, 4):
            cfg = dataclasses.replace(cfg0, steps_per_round=k,
                                      collect_stats=True)
            st = _run(vol, cfg, engine).stats
            vals.add((float(st.dep_live), float(st.exit_live)))
    assert len(vals) == 1, vals


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_flush_counters_all_absorbing(engine):
    """No scattering and a high absorption: every photon flies along +z
    and its weight is absorbed on the way, so nothing leaves the z=0
    face."""
    vol = V.homogeneous_cube(SHAPE, V.Medium(mua=10.0, mus=0.0, g=0.0,
                                             n=1.0), 1.0)
    cfg = V.SimConfig(do_reflect=False, collect_stats=True)
    res = _run(vol, cfg, engine, source={"type": "pencil",
                                         "pos": [8.0, 8.0, 0.0]})
    bal = A.energy_balance(res)
    # absorbed up to the Russian-roulette residue; nothing escapes
    np.testing.assert_allclose(bal["absorbed"], bal["launched"], rtol=1e-3)
    assert bal["escaped"] == 0.0
    assert float(res.stats.exit_live) == 0.0
    assert float(np.asarray(res.exitance).sum()) == 0.0
    assert 0 < float(res.stats.dep_live) <= float(res.stats.live_segments)


def test_round_stats_detected_w_reconciles():
    vol, cfg = _bench()
    cfg = dataclasses.replace(cfg, collect_stats=True)
    dets = (Detector(SHAPE[0] / 2.0, SHAPE[1] / 2.0, SHAPE[0] / 2.0),)
    res = _run(vol, cfg, detectors=dets)
    assert float(np.asarray(res.det_w).sum()) > 0
    np.testing.assert_allclose(float(res.stats.detected_w),
                               float(np.asarray(res.det_w).sum()), rtol=1e-5)


def test_round_stats_host_merge_helpers():
    a = RoundStats.zeros()
    assert a.lane_occupancy() == 0.0
    b = RoundStats.from_vector([2, 1, 100, 50.0, 200.0, 1.5, 2.5, 0.5, 0.25])
    m = a.add(b).add(b)
    assert int(m.rounds) == 4 and int(m.relaunched) == 200
    assert float(m.live_segments) == 100.0
    assert m.lane_occupancy() == pytest.approx(0.25)
    d = m.to_dict()
    assert isinstance(d["rounds"], int)
    assert d["lane_occupancy"] == pytest.approx(0.25)
    # round-trips through the checkpoint vector form
    rt = RoundStats.from_vector([float(v) for v in m])
    assert rt == m


# ---------------------------------------------------------------------------
# Tracer, sinks, Chrome trace round-trip, device-model fitting
# ---------------------------------------------------------------------------

def test_tracer_spans_and_sinks(tmp_path):
    mem = InMemorySink()
    jsonl = JsonlSink(tmp_path / "m.jsonl")
    tr = Tracer(sinks=[mem, jsonl])
    with tr.span("chunk", device="cpu:0", engine="jnp", photons=100):
        pass
    sp = tr.span("chunk", device="cpu:1", engine="jnp", photons=50)
    sp.end(overflow=3)
    tr.counter("photons_per_s", 123.0, bench="B1")
    jsonl.close()
    assert len(tr.events) == 2
    assert tr.events[1].args["overflow"] == 3
    assert [e["type"] for e in mem.events] == ["span", "span", "counter"]
    lines = [json.loads(line)
             for line in (tmp_path / "m.jsonl").read_text().splitlines()]
    assert len(lines) == 3 and lines[2]["value"] == 123.0
    # throughput is derived from the photons arg and the measured span
    assert tr.events[0].photons_per_s > 0


def test_chrome_trace_round_trip(tmp_path):
    events = [
        SpanEvent("chunk", "cpu:0", t0=1.0, dur=0.5, engine="jnp",
                  args={"photons": 1000}),
        SpanEvent("chunk", "cpu:1", t0=1.2, dur=0.25, engine="jnp",
                  args={"photons": 500}),
    ]
    obj = chrome_trace(events)
    # one viewer thread per device, named via metadata rows
    names = {r["args"]["name"] for r in obj["traceEvents"]
             if r.get("ph") == "M" and r["name"] == "thread_name"}
    assert names == {"cpu:0", "cpu:1"}
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(obj))
    back = load_chrome_trace(path)
    assert {(e.name, e.device, e.engine) for e in back} == \
        {("chunk", "cpu:0", "jnp"), ("chunk", "cpu:1", "jnp")}
    by_dev = {e.device: e for e in back}
    assert by_dev["cpu:0"].args["photons"] == 1000
    assert by_dev["cpu:0"].dur == pytest.approx(0.5, rel=1e-6)


def test_fit_device_models_pilot_and_throughput_fallback():
    # two distinct chunk sizes -> the full T = a*n + T0 pilot fit
    ev = [SpanEvent("chunk", "tpu:0", 0.0, 0.1 + 1e-4 * n,
                    args={"photons": n}) for n in (1000, 4000, 8000)]
    # equal chunk sizes -> aggregate-throughput fallback (t0 = 0)
    ev += [SpanEvent("chunk", "tpu:1", 0.0, 0.5, args={"photons": 1000}),
           SpanEvent("chunk", "tpu:1", 1.0, 0.5, args={"photons": 1000})]
    models = fit_device_models(ev, name="chunk")
    assert set(models) == {"tpu:0", "tpu:1"}
    assert models["tpu:0"].a == pytest.approx(1e-4, rel=1e-3)
    assert models["tpu:0"].t0 == pytest.approx(0.1, rel=1e-3)
    assert models["tpu:1"].t0 == 0.0
    assert models["tpu:1"].a == pytest.approx(1.0 / 1000 * 0.5 * 2 / 2)
    # the fits plug straight into the paper's partitioners
    part = LB.partition_s2(10_000, list(models.values()))
    assert sum(part) == 10_000 and all(p >= 0 for p in part)


def test_device_label():
    assert device_label(None) == "host"
    assert device_label("mesh") == "mesh"
    d = jax.devices()[0]
    assert device_label(d) == f"{d.platform}:{d.id}"


# ---------------------------------------------------------------------------
# Schedulers: chunk spans + merged stats
# ---------------------------------------------------------------------------

def test_chunk_scheduler_trace_and_stats_merge():
    vol, cfg = _bench()
    cfg = dataclasses.replace(cfg, collect_stats=True)
    tr = Tracer(sinks=[InMemorySink()])
    sched = ChunkScheduler(vol, cfg, n_lanes=LANES, tracer=tr)
    res, per_dev = sched.run(N_PHOTONS, chunk_size=500, seed=SEED)
    spans = [e for e in tr.events if e.name == "chunk"]
    assert len(spans) == 4
    assert {e.device for e in spans} <= \
        {device_label(d) for d in jax.devices()}
    assert sum(e.args["photons"] for e in spans) == N_PHOTONS
    # merged counters keep exact photon accounting across chunks
    assert int(res.stats.relaunched) == int(res.n_launched) == N_PHOTONS
    # chunked run matches the single-shot physics (id-keyed photons)
    ref = _run(vol, dataclasses.replace(cfg, collect_stats=False))
    np.testing.assert_allclose(np.asarray(res.energy), np.asarray(ref.energy),
                               rtol=5e-5, atol=1e-5)
    # ...and its spans fit device models the partitioners accept
    models = fit_device_models(tr.events, name="chunk")
    assert models
    part = LB.partition_s2(N_PHOTONS, list(models.values()))
    assert sum(part) == N_PHOTONS


def test_elastic_simulator_stats_checkpoint_roundtrip():
    vol, cfg = _bench()
    cfg = dataclasses.replace(cfg, collect_stats=True)
    tr = Tracer()
    sim = ElasticSimulator(vol, cfg, N_PHOTONS, chunk_size=500,
                           n_lanes=LANES, seed=SEED, tracer=tr)
    sim.run_round()
    state = sim.state_dict()
    assert "stats" in state
    res = sim.run_to_completion()
    assert int(res.stats.relaunched) == N_PHOTONS
    assert len([e for e in tr.events if e.name == "chunk"]) == 4
    # restart from the checkpoint: stats resume mid-campaign
    sim2 = ElasticSimulator(vol, cfg, N_PHOTONS, chunk_size=500,
                            n_lanes=LANES, seed=SEED)
    sim2.load_state_dict(state)
    res2 = sim2.run_to_completion()
    assert int(res2.stats.relaunched) == N_PHOTONS
    np.testing.assert_array_equal(np.asarray(res.energy),
                                  np.asarray(res2.energy))
    for a, b in zip(res.stats, res2.stats):
        assert float(a) == float(b)


# ---------------------------------------------------------------------------
# CLI: loss warnings + trace/metrics files (the end-to-end loop)
# ---------------------------------------------------------------------------

_CLI_BASE = ["--bench", "B1", "--size", "16", "--photons", "800",
             "--lanes", "128", "--seed", "3"]


def test_cli_warns_on_timed_out_weight(capsys):
    CLI.main(_CLI_BASE + ["--tmax-ns", "0.02"])
    out = capsys.readouterr().out
    assert "WARNING" in out and "tmax" in out
    assert "retired" in out


def test_cli_no_timeout_warning_by_default(capsys):
    CLI.main(_CLI_BASE)
    out = capsys.readouterr().out
    assert "WARNING" not in out


def test_cli_warns_on_detector_record_overflow(capsys):
    # B2 (mismatched boundary, reflection on) backscatters enough weight
    # into the z=0 disk to overrun a tiny id buffer
    det = json.dumps([{"x": 8, "y": 8, "radius": 8}])
    CLI.main(["--bench", "B2", "--size", "16", "--photons", "800",
              "--lanes", "128", "--seed", "3",
              "--detectors", det, "--save-detected", "8"])
    out = capsys.readouterr().out
    assert "WARNING" in out and "overflow" in out
    assert "raise --save-detected" in out


def test_cli_trace_metrics_feed_load_balancer(tmp_path, capsys):
    """The acceptance loop: a chunked CLI run's --trace-out spans
    round-trip into loadbalance device models."""
    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.jsonl"
    CLI.main(_CLI_BASE + ["--chunk", "200", "--collect-stats",
                          "--trace-out", str(trace),
                          "--metrics-out", str(metrics)])
    out = capsys.readouterr().out
    assert "round stats:" in out and "lane occupancy" in out
    events = load_chrome_trace(trace)
    spans = [e for e in events if e.name == "chunk"]
    assert len(spans) == 4
    for d in jax.devices():
        assert any(e.device == device_label(d) for e in spans)
    models = fit_device_models(events, name="chunk")
    assert models
    part = LB.partition_s2(4000, list(models.values()))
    assert sum(part) == 4000
    recs = [json.loads(line)
            for line in metrics.read_text().splitlines()]
    spans = [r for r in recs if r["type"] == "span"]
    root = next(r for r in spans if r["name"] == "cli.main")
    assert root["parent_id"] is None
    # every span of the call, chunks included, belongs to its root
    assert all(r["root_id"] == root["span_id"] for r in spans)
    assert {"cli.prepare", "chunk", "cli.summarize"} <= \
        {r["name"] for r in spans}
    names = {r["name"] for r in recs if r["type"] == "counter"}
    assert "round_stats.lane_occupancy" in names
    # an unchunked call runs the program once, in simulate.execute
    one = tmp_path / "one.jsonl"
    CLI.main(_CLI_BASE + ["--metrics-out", str(one)])
    by_name = {r["name"]: r for r in map(json.loads,
                                         one.read_text().splitlines())
               if r["type"] == "span"}
    execute = by_name["simulate.execute"]
    assert execute["parent_id"] == by_name["simulate"]["span_id"]
    assert execute["root_id"] == by_name["cli.main"]["span_id"]
    assert execute["photons"] == 800


# ---------------------------------------------------------------------------
# The span tree of the entry points, in the process tracer
# ---------------------------------------------------------------------------

def _tree_of_last_call(t_start_ns, t_end_ns):
    """The spans of the process tracer's last root call, checked for a
    well-formed tree inside the call's wall interval; name -> spans."""
    events = list(default_tracer().events)
    root = events[-1]
    assert root.parent_id is None and root.root_id == root.span_id
    spans = [e for e in events if e.root_id == root.span_id]
    by_id = {e.span_id: e for e in spans}
    for e in spans:
        assert t_start_ns <= e.t0 * 1e9 and \
            (e.t0 + e.dur) * 1e9 <= t_end_ns + 1e3, e.name
        if e is root:
            continue
        parent = by_id[e.parent_id]
        # children lie inside their parents (1 us of float rounding)
        assert parent.t0 - 1e-6 <= e.t0, e.name
        assert e.t0 + e.dur <= parent.t0 + parent.dur + 1e-6, e.name
    out = {}
    for e in spans:
        out.setdefault(e.name, []).append(e)
    return root, out


def test_cli_call_span_tree():
    t0 = time.time_ns()
    CLI.main(_CLI_BASE + ["--collect-stats"])
    root, spans = _tree_of_last_call(t0, time.time_ns())
    assert root.name == "cli.main" and root.args["photons"] == 800
    children = {e.name for es in spans.values() for e in es
                if e.parent_id == root.span_id}
    assert children == {"cli.prepare", "simulate", "cli.summarize"}
    (sim,) = spans["simulate"]
    (compile_,) = spans["simulate.compile"]
    (execute,) = spans["simulate.execute"]
    assert compile_.parent_id == execute.parent_id == sim.span_id
    assert compile_.t0 + compile_.dur <= execute.t0 + 1e-6
    assert execute.args["photons"] == 800


def test_simulate_many_span_tree():
    vol = V.benchmark_b1((8, 8, 8))
    scs = [Scenario(vol, V.SimConfig(), 300, seed=s) for s in range(3)]
    cache = CompileCache()
    for _ in range(2):   # a cache miss, then a hit
        t0 = time.time_ns()
        simulate_many(scs, n_lanes=64, cache=cache)
        root, spans = _tree_of_last_call(t0, time.time_ns())
        assert root.name == "scenarios.simulate_many"
        assert root.args["scenarios"] == 3
        children = [e.name for es in spans.values() for e in es
                    if e.parent_id == root.span_id]
        assert sorted(children) == ["scenarios.batch", "scenarios.prepare",
                                    "scenarios.split"]
        (batch,) = spans["scenarios.batch"]
        (execute,) = spans["scenarios.execute"]
        assert execute.parent_id == batch.span_id
        if batch.args["cache_hit"]:
            assert "scenarios.compile" not in spans
        else:
            (compile_,) = spans["scenarios.compile"]
            assert compile_.parent_id == batch.span_id
            # compilation alone: it ends before the execution starts
            assert compile_.t0 + compile_.dur <= execute.t0 + 1e-6
    assert cache.hits == cache.misses == 1
