"""Photon-simulation launcher (the paper's workload).

  PYTHONPATH=src python -m repro.launch.simulate --bench B1 \
      --photons 100000 --lanes 4096 [--autotune] [--devices all]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro import detectors as D
from repro import scenarios as SC
from repro import telemetry as T
from repro.core import analysis as A
from repro.core import simulator as S
from repro.core import volume as V
from repro.core.multidevice import ChunkScheduler, simulate_sharded
from repro.launch.compile_cache import enable_compile_cache


def get_bench(name: str, size: int):
    shape = (size, size, size)
    if name == "B1":
        return V.benchmark_b1(shape), V.SimConfig(do_reflect=False)
    if name in ("B2", "B2a"):
        return V.benchmark_b2(shape), V.SimConfig(do_reflect=True)
    raise ValueError(name)


def _run_scenarios(args, ap, tracer, t_start_ns):
    """--scenarios: batched multi-scenario execution (DESIGN.md §batching)."""
    with tracer.span("cli.prepare", start_ns=t_start_ns):
        spec = args.scenarios
        if spec.startswith("@"):
            with open(spec[1:]) as f:
                spec = f.read()
        entries = json.loads(spec)
        if not isinstance(entries, list) or not entries:
            ap.error("--scenarios expects a non-empty JSON list of "
                     "scenario dicts (or @file.json holding one)")
        scenarios = [SC.Scenario.from_dict(e) for e in entries]
        mesh = None
        if args.devices == "all" and len(jax.devices()) > 1:
            mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    cache = SC.default_cache()
    t0 = time.time()
    results = SC.simulate_many(scenarios, n_lanes=args.lanes,
                               engine=args.engine, mesh=mesh, cache=cache,
                               tracer=tracer)
    dt = time.time() - t0

    with tracer.span("cli.summarize"):
        total_photons = sum(sc.n_photons for sc in scenarios)
        keys = {SC.group_key(sc, args.lanes, engine=args.engine)
                for sc in scenarios}
        sharded = f" over {mesh.size} devices" if mesh is not None else ""
        print(f"scenarios: {len(scenarios)} in {dt:.2f}s "
              f"({len(scenarios)/dt:.2f} scenarios/s, "
              f"{total_photons/dt/1e3:.2f} photons/ms total), "
              f"{len(keys)} config shape(s){sharded}")
        st = cache.stats()
        print(f"compile cache: {st['hits']} hits / {st['misses']} misses "
              f"(hit rate {st['hit_rate']:.2f}), {st['entries']} entries, "
              f"{st['evictions']} evictions")
        for i, (sc, res) in enumerate(zip(scenarios, results)):
            bal = A.energy_balance(res)
            line = (f"  scenario {i}: {sc.n_photons} photons seed={sc.seed} "
                    f"absorbed={bal['absorbed']:.1f} "
                    f"escaped={bal['escaped']:.1f} "
                    f"residue={bal['residue_frac']:.2e}")
            if sc.detectors:
                line += f" det_w={np.asarray(res.det_w).sum():.3f}"
            print(line)
    return results


def _parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default="B1", choices=["B1", "B2", "B2a"])
    ap.add_argument("--photons", type=int, default=100_000)
    ap.add_argument("--lanes", type=int, default=4096)
    ap.add_argument("--size", type=int, default=60)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--engine", default="jnp", choices=list(S.ENGINES),
                    help="round executor: in-graph jnp loop or the Pallas "
                         "photon-step kernel (DESIGN.md §rounds)")
    ap.add_argument("--steps-per-round", type=int, default=1,
                    help="K: fused transport segments per regeneration/"
                         "flush round")
    ap.add_argument("--autotune", action="store_true",
                    help="Opt2: pilot-sweep the lane count (at the chosen "
                         "steps-per-round)")
    ap.add_argument("--devices", default="one", choices=["one", "all"])
    ap.add_argument("--chunk", type=int, default=0,
                    help=">0: dynamic chunk scheduling (straggler-safe)")
    ap.add_argument("--chaos", default=None, metavar="JSON",
                    help="seeded fault-injection drill for the --chunk "
                         "scheduler (DESIGN.md §resilience): JSON "
                         "FaultInjector config, e.g. '{\"seed\": 1, "
                         "\"p_fail\": 0.2, \"p_nan\": 0.1, \"p_delay\": "
                         "0.2, \"delay_s\": 0.1, \"poison_chunks\": [0], "
                         "\"dropout\": {\"w0:cpu:0\": 2}}'; results stay "
                         "bit-identical to the fault-free run")
    ap.add_argument("--max-retries", type=int, default=None, metavar="N",
                    help="attempt cap per chunk before it is quarantined "
                         "(default: RetryPolicy's 5); requires --chunk")
    ap.add_argument("--chunk-timeout-s", type=float, default=None,
                    metavar="S",
                    help="hard per-chunk deadline: a chunk inflight "
                         "longer re-dispatches speculatively (on top of "
                         "the fitted DeviceModel deadlines); requires "
                         "--chunk")
    ap.add_argument("--deadline-s", type=float, default=None, metavar="S",
                    help="overall wall-clock bound for the chunked run "
                         "(TimeoutError past it instead of waiting "
                         "forever); requires --chunk")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                    help="auto-checkpoint the chunked campaign every N "
                         "merged chunks (atomic Checkpointer); requires "
                         "--chunk and --checkpoint-dir")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="checkpoint directory for --checkpoint-every; "
                         "if it already holds a matching campaign "
                         "checkpoint the run resumes from it")
    ap.add_argument("--source", default=None,
                    help="JSON source spec (repro.sources), e.g. "
                         '\'{"type": "disk", "pos": [30, 30, 0], '
                         '"radius": 5}\'; default: pencil beam')
    ap.add_argument("--time-gates", type=int, default=1,
                    help="bin deposited energy over this many time-of-"
                         "flight gates spanning [0, tmax_ns] (DESIGN.md "
                         "§time-resolved); 1 = CW (default)")
    ap.add_argument("--detectors", default=None,
                    help="JSON detector disks on the z=0 face "
                         "(repro.detectors), e.g. "
                         '\'[{"x": 40, "y": 30, "radius": 2}]\'; records '
                         "per-detector TPSF + mean partial pathlengths")
    ap.add_argument("--save-detected", type=int, default=0, metavar="CAP",
                    help="record detected-photon ids (global photon id, "
                         "detector, exit gate) for replay (DESIGN.md "
                         "§replay); requires --detectors.  CAP is the id-"
                         "buffer capacity PER SIMULATION UNIT: the whole "
                         "run on one device, per shard with --devices "
                         "all, per chunk with --chunk (buffers are "
                         "concatenated host-side) — check the reported "
                         "overflow either way")
    ap.add_argument("--replay", action="store_true",
                    help="after the forward run, replay the recorded "
                         "detected photons into per-detector absorption "
                         "Jacobian volumes (requires --save-detected)")
    ap.add_argument("--replay-engine", default="jnp",
                    choices=list(S.ENGINES),
                    help="round executor for the two replay transport "
                         "passes (DESIGN.md §replay): in-graph jnp loop "
                         "or the Pallas photon-step kernel; with "
                         "--devices all the record batches are "
                         "additionally shard_map'd over every device")
    ap.add_argument("--replay-gate-resolved", action="store_true",
                    help="widen the replay scatter to a time-gate-"
                         "resolved (nvox, n_det, n_time_gates) Jacobian "
                         "keyed by each record's exit gate (requires "
                         "--replay)")
    ap.add_argument("--tmax-ns", type=float, default=None,
                    help="time-of-flight cutoff in ns (default: the "
                         "benchmark config's 5.0); weight still in "
                         "flight at the cutoff is retired as timed-out")
    ap.add_argument("--collect-stats", action="store_true",
                    help="accumulate round-level telemetry counters "
                         "(lane occupancy, relaunches, retired weight) "
                         "onto SimResult.stats (DESIGN.md "
                         "§observability); physics outputs stay "
                         "bit-identical")
    ap.add_argument("--scenarios", default=None, metavar="JSON",
                    help="batched multi-scenario run (repro.scenarios): a "
                         "JSON list of scenario dicts (or @file.json), "
                         "each with keys bench/size/photons/seed/source/"
                         "detectors/time_gates/steps_per_round/tmax_ns/"
                         "do_reflect/id_offset.  Scenarios sharing a "
                         "config shape are vmapped into one executable "
                         "via the compile cache; with --devices all the "
                         "scenario axis is sharded over the mesh.  "
                         "Results are bit-identical to sequential runs")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="stream structured telemetry events (spans, "
                         "counters) as JSON lines to PATH")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the host-side span timeline as Chrome "
                         "trace_event JSON to PATH (open in "
                         "chrome://tracing or Perfetto; per-device "
                         "photons/s feeds telemetry.fit_device_models)")
    args = ap.parse_args(argv)
    if args.save_detected and not args.detectors:
        ap.error("--save-detected requires --detectors")
    if args.replay and not args.save_detected:
        ap.error("--replay requires --save-detected")
    if args.replay_gate_resolved and not args.replay:
        ap.error("--replay-gate-resolved requires --replay")
    for flag in ("chaos", "max_retries", "chunk_timeout_s", "deadline_s"):
        if getattr(args, flag) is not None and not args.chunk:
            ap.error(f"--{flag.replace('_', '-')} requires --chunk")
    if args.checkpoint_every and not (args.chunk and args.checkpoint_dir):
        ap.error("--checkpoint-every requires --chunk and --checkpoint-dir")
    if args.scenarios:
        for flag in ("chunk", "autotune", "save_detected", "replay",
                     "source", "detectors", "collect_stats"):
            if getattr(args, flag):
                ap.error(f"--scenarios is incompatible with "
                         f"--{flag.replace('_', '-')} (scenario dicts "
                         f"carry their own per-scenario config)")

    return ap, args


def main(argv=None):
    """One call of the command line.  Its spans, in ``--trace-out``'s
    tracer or else the process's ``telemetry.default_tracer``: a
    ``cli.main`` root with ``cli.prepare`` (argument parsing, volume,
    source, detectors), the entry point's own spans (``simulate`` with
    ``simulate.compile`` and ``simulate.execute``, or the ``chunk`` and
    ``scenarios.*`` spans), ``cli.summarize`` (energy balance, fluence,
    gates, detector and record summaries) and the replay's spans."""
    t_start_ns = time.time_ns()
    ap, args = _parse(argv)
    sinks = []
    if args.metrics_out:
        sinks.append(T.JsonlSink(args.metrics_out))
    tracer = (T.Tracer(sinks=sinks) if (args.trace_out or sinks)
              else T.default_tracer())
    with tracer.span("cli.main", start_ns=t_start_ns, bench=args.bench,
                     photons=args.photons):
        if args.scenarios:
            res = _run_scenarios(args, ap, tracer, t_start_ns)
        else:
            res = _run(args, tracer, t_start_ns)
    if args.trace_out:
        path = tracer.save_chrome_trace(args.trace_out)
        print(f"trace timeline: {path} "
              f"({len(tracer.events)} spans; open in chrome://tracing)")
    for sink in sinks:
        sink.close()
    return res


def _run(args, tracer, t_start_ns):
    """The forward run (one device, the mesh, or chunked), its
    summaries and the optional replay."""
    with tracer.span("cli.prepare", start_ns=t_start_ns):
        source = json.loads(args.source) if args.source else None
        detectors = D.as_detectors(
            json.loads(args.detectors)) if args.detectors else None
        vol, cfg = get_bench(args.bench, args.size)
        if args.steps_per_round != 1:
            cfg = dataclasses.replace(cfg,
                                      steps_per_round=args.steps_per_round)
        if args.time_gates != 1:
            cfg = dataclasses.replace(cfg, n_time_gates=args.time_gates)
        if args.tmax_ns is not None:
            cfg = dataclasses.replace(cfg, tmax_ns=args.tmax_ns)
        if args.collect_stats:
            cfg = dataclasses.replace(cfg, collect_stats=True)
    lanes = args.lanes
    if args.autotune:
        lanes, timings = S.autotune_lanes(vol, cfg, n_pilot=args.photons // 10,
                                          source=source, engine=args.engine)
        print("autotune:", {k: round(v, 3) for k, v in timings.items()},
              "-> lanes =", lanes)

    t0 = time.time()
    mesh = None
    if args.chunk:
        from repro.resilience import FaultInjector, RetryPolicy

        injector = (FaultInjector(**json.loads(args.chaos))
                    if args.chaos else None)
        policy = (RetryPolicy(max_attempts=args.max_retries)
                  if args.max_retries is not None else None)
        checkpointer = None
        resume = False
        if args.checkpoint_every:
            from repro.checkpoint import Checkpointer

            checkpointer = Checkpointer(args.checkpoint_dir)
            resume = checkpointer.latest_step() is not None
            if resume:
                print(f"resuming from checkpoint step "
                      f"{checkpointer.latest_step()} in "
                      f"{args.checkpoint_dir}")
        sched = ChunkScheduler(vol, cfg, n_lanes=lanes, source=source,
                               engine=args.engine, detectors=detectors,
                               record_detected=args.save_detected,
                               tracer=tracer, fault_injector=injector,
                               retry_policy=policy,
                               chunk_timeout_s=args.chunk_timeout_s,
                               checkpointer=checkpointer,
                               checkpoint_every=args.checkpoint_every)
        res, stats = sched.run(args.photons, args.chunk, seed=args.seed,
                               deadline_s=args.deadline_s, resume=resume)
        print("per-device photons:", stats)
        rep = sched.last_report
        if injector is not None or rep.retries or rep.quarantine_events:
            c = rep.counters()
            print(f"resilience: {c['merged']}/{c['chunks']} chunks merged, "
                  f"{c['retries']} retries, {c['speculative']} speculative, "
                  f"{c['validation_failures']} rejected by merge guard, "
                  f"{c['quarantine_events']} quarantine events, "
                  f"{c['checkpoints']} checkpoints")
    elif args.devices == "all" and len(jax.devices()) > 1:
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        res = simulate_sharded(vol, cfg, args.photons, mesh,
                               n_lanes=lanes, seed=args.seed, source=source,
                               engine=args.engine, detectors=detectors,
                               record_detected=args.save_detected,
                               tracer=tracer)
    else:
        res = S.simulate(vol, cfg, args.photons, lanes, args.seed,
                         source=source, engine=args.engine,
                         detectors=detectors,
                         record_detected=args.save_detected, tracer=tracer)
    jax.block_until_ready(res)
    dt = time.time() - t0

    with tracer.span("cli.summarize"):
        recs = _summarize(args, tracer, res, vol, cfg, detectors, dt)
    if args.replay and recs.shape[0]:
        from repro.replay import replay_jacobian

        t0 = time.time()
        rep = replay_jacobian(vol, cfg, recs, detectors, source=source,
                              seed=args.seed, n_lanes=lanes,
                              engine=args.replay_engine,
                              gate_resolved=args.replay_gate_resolved,
                              mesh=mesh, tracer=tracer)
        dt = time.time() - t0
        ok = int((rep.replayed_det == rep.det).sum())
        sharded = f" over {mesh.size} devices" if mesh is not None else ""
        print(f"replay[{args.replay_engine}]: {rep.n_records} photons "
              f"in {dt:.2f}s ({rep.n_records/dt/1e3:.2f} photons/ms)"
              f"{sharded}, {ok}/{rep.n_records} detector-exact")
        jac = rep.jacobian
        med = A.jacobian_medium_sums(jac, vol)
        gated = jac if jac.ndim == 4 else jac.sum(axis=-1)
        for i, d in enumerate(detectors):
            nz = int(np.sum(gated[..., i] > 0))
            print(f"  J[det {i}]: sum={gated[..., i].sum():.3e} "
                  f"(weight*mm), nonzero voxels={nz}, per-medium "
                  f"{np.array_str(med[i], precision=3)}")
        if jac.ndim == 5:
            per_gate = jac.sum(axis=(0, 1, 2, 3))
            print(f"  gate-resolved: {jac.shape[-1]} gates, "
                  f"peak gate {int(per_gate.argmax())}")
    return res


def _summarize(args, tracer, res, vol, cfg, detectors, dt):
    """Print the forward run's summaries; returns its detected-photon
    records (empty unless ``--save-detected``)."""
    bal = A.energy_balance(res)
    print(f"{args.bench}: {args.photons} photons in {dt:.2f}s "
          f"({args.photons/dt/1e3:.2f} photons/ms)")
    print(f"energy balance: absorbed={bal['absorbed']:.1f} "
          f"escaped={bal['escaped']:.1f} timed_out={bal['timed_out']:.2e} "
          f"residue={bal['residue_frac']:.2e}")
    timed_frac = bal["timed_out"] / max(bal["launched"], 1e-30)
    if timed_frac > 0.01:
        print(f"WARNING: {timed_frac:.1%} of launched weight "
              f"({bal['timed_out']:.3f}) was retired by the "
              f"tmax_ns={cfg.tmax_ns} time gate / max_steps cap — "
              f"fluence and detector readings are truncated; raise "
              f"--tmax-ns if unintended")
    if res.stats is not None:
        sd = res.stats.to_dict()
        print(f"round stats: {sd['rounds']} rounds "
              f"({sd['regen_rounds']} regenerating, "
              f"{sd['relaunched']} relaunches), lane occupancy "
              f"{sd['lane_occupancy']:.1%} "
              f"({sd['live_segments']:.3g}/{sd['lane_segments']:.3g} "
              f"lane-segments live)")
        for k, v in sd.items():
            tracer.counter(f"round_stats.{k}", v, bench=args.bench,
                           engine=args.engine)
    phi = A.fluence_cw(res, vol)
    print(f"fluence: max={float(np.max(np.asarray(phi))):.3e} "
          f"nonzero voxels={int(np.sum(np.asarray(phi) > 0))}")
    if cfg.n_time_gates > 1:
        td = np.asarray(A.fluence_td(res, vol))
        per_gate = td.sum(axis=(0, 1, 2))
        print(f"time gates: {cfg.n_time_gates} x {cfg.gate_width_ns:.3f} ns, "
              f"peak gate {int(per_gate.argmax())}")
    if detectors:
        times, curves = A.tpsf(res, cfg)
        tot = np.asarray(res.det_w).sum(axis=1)
        for i, d in enumerate(detectors):
            peak = float(times[int(curves[i].argmax())]) if tot[i] else 0.0
            print(f"detector {i} ({d.x:.0f},{d.y:.0f},r={d.radius:.0f}): "
                  f"weight={tot[i]:.3f} tpsf-peak@{peak:.3f} ns")
        print("mean partial pathlengths (mm/medium):")
        print(np.array_str(A.detector_mean_ppath(res), precision=2))
    if not args.save_detected:
        return np.zeros((0, 4), np.uint32)
    from repro.replay import detected_records

    recs = detected_records(res)
    overflow = int(np.asarray(res.det_rec_overflow))
    print(f"detected-photon records: {recs.shape[0]} "
          f"(overflow: {overflow})")
    if overflow > 0:
        print(f"WARNING: {overflow} detector captures were dropped "
              f"from the id buffer (capacity {args.save_detected} per "
              f"simulation unit) — det_w still counts them, but "
              f"replay will miss them; raise --save-detected")
    return recs


if __name__ == "__main__":
    # the command line gets the persistent compile cache; in-process
    # callers of main() (tests, chip_smoke.py) keep their own setting
    enable_compile_cache()
    main()
