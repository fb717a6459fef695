#!/usr/bin/env python3
"""Smoke run of the photon-transport main path on a TPU.

    python chip_smoke.py                # one chip (the default)
    python chip_smoke.py --four-chips   # the four-chip mesh phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --four-chips

One process drives the chip through the entry points a user calls, at
the paper's full size (Yu et al. 2017, Fig. 2: B1 and B2 at 60^3):

  device        fail unless JAX's first device is a TPU;
  b1_cli        ``repro.launch.simulate.main`` on B1 with the jnp
                engine: energy residue <= 1e-4, every photon launched;
  b2_cli        B2 through the CLI with one detector 10 mm from the
                source, 16 time gates, recording and ``--replay``:
                every replayed photon reaches its recorded detector,
                and the Jacobian's per-medium sums equal detected
                weight x mean partial pathlength;
  many          ``simulate_many`` of 3 same-shape B1 scenarios: one
                compile, results bit-identical to sequential runs;
  tpu_vs_cpu    B1 on the TPU and on the host CPU over several seeds:
                absorbed and escaped fractions within 5 standard errors.

``--four-chips`` runs only ``simulate_sharded`` of B2 over four chips
against one chip, the sharded ``replay_jacobian``, and a check that the
psum'd accumulators are the program's only collective.

Each phase prints its compile, trace and run seconds and photons/s on
a line of its own.  They are smoke numbers from one run, not benchmark
numbers.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and is printed only when every check passed.  Without a TPU the script
exits non-zero unless ``--rehearse`` is given, which allows the CPU and
cuts every size down (a rehearsal, never a result).  The Pallas engine
is not on this path: it does not lower for the TPU yet (ROADMAP B1).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SMOKE = "(smoke numbers from one run, not a benchmark)"


@dataclasses.dataclass(frozen=True)
class Sizes:
    size: int           # cube edge, voxels (1 mm each)
    b1_photons: int
    b2_photons: int
    many_photons: int   # per scenario
    ref_photons: int    # per seed, per backend
    ref_seeds: int
    mesh_photons: int   # four-chip phase
    record_cap: int = 65536
    lanes: int = 4096


CHIP = Sizes(size=60, b1_photons=1_000_000, b2_photons=1_000_000,
             many_photons=100_000, ref_photons=100_000, ref_seeds=4,
             mesh_photons=1_000_000)
REHEARSAL = Sizes(size=30, b1_photons=20_000, b2_photons=20_000,
                  many_photons=5_000, ref_photons=5_000, ref_seeds=4,
                  mesh_photons=20_000, record_cap=4096, lanes=1024)


class SmokeFailure(Exception):
    """A phase's check failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  check ok: {what}", flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading
    from the persistent cache), read from ``jax.monitoring`` events."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __init__(self, jax):
        self.totals = {k: 0.0 for k in self.EVENTS.values()}
        self.counts = {"compiles": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        key = self.EVENTS.get(event)
        if key is not None:
            self.totals[key] += secs
            if key == "compile_s":
                self.counts["compiles"] += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    @contextlib.contextmanager
    def phase(self, name: str, photons: int):
        before = dict(self.totals), dict(self.counts)
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        d = {k: v - before[0][k] for k, v in self.totals.items()}
        c = {k: v - before[1][k] for k, v in self.counts.items()}
        run = max(wall - sum(d.values()), 1e-9)
        print(f"[smoke] {name}: compile_s={d['compile_s']:.3f} "
              f"trace_s={d['trace_s']:.3f} lower_s={d['lower_s']:.3f} "
              f"run_s={run:.3f} wall_s={wall:.3f} "
              f"compiles={c['compiles']} cache_hits={c['cache_hits']} "
              f"photons={photons} photons_per_s={photons / run:.6g} "
              f"{SMOKE}", flush=True)


def run_cli(cli, argv):
    """Run ``repro.launch.simulate.main`` and return (result, stdout);
    the CLI's own report is echoed as it would be to a user."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        res = cli.main(argv)
    out = buf.getvalue()
    print("  | " + out.rstrip().replace("\n", "\n  | "), flush=True)
    return res, out


def check_balance(A, res, n_photons):
    bal = A.energy_balance(res)
    check(abs(bal["residue_frac"]) <= 1e-4,
          f"energy residue_frac {bal['residue_frac']:.3e} <= 1e-4")
    check(int(res.n_launched) == n_photons,
          f"n_launched {int(res.n_launched)} == {n_photons}")


def check_replay_identity(np, A, rep, res, vol, what):
    """The replay identity tests/test_replay.py pins: per-medium sums
    of J equal detected weight x mean partial pathlength."""
    med = A.jacobian_medium_sums(rep.jacobian, vol)
    tot_w = np.asarray(res.det_w, np.float64).sum(axis=1, keepdims=True)
    want = tot_w * A.detector_mean_ppath(res)
    err = float(np.max(np.abs(med - want) / (1e-4 + 1e-4 * np.abs(want))))
    check(err <= 1.0, f"{what}: per-medium J sums {med.ravel().tolist()} "
          f"match weight x mean ppath {want.ravel().tolist()} "
          f"(rtol 1e-4, atol 1e-4)")


def source_and_detector(sz: Sizes):
    c = sz.size / 2
    src = {"type": "pencil", "pos": [c, c, 0.0]}
    det = [{"x": c + 10.0, "y": c, "radius": 2.0}]   # 10 mm away
    return src, det


def phase_b1_cli(m, sz, clock):
    src, _ = source_and_detector(sz)
    with clock.phase("b1_cli", sz.b1_photons):
        res, _ = run_cli(m.CLI, [
            "--bench", "B1", "--size", str(sz.size),
            "--photons", str(sz.b1_photons), "--lanes", str(sz.lanes),
            "--source", json.dumps(src)])
    m.jax.block_until_ready(res)
    check_balance(m.A, res, sz.b1_photons)


def phase_b2_cli(m, sz, clock):
    np, A = m.np, m.A
    src, det = source_and_detector(sz)
    with clock.phase("b2_cli", sz.b2_photons):
        res, out = run_cli(m.CLI, [
            "--bench", "B2", "--size", str(sz.size),
            "--photons", str(sz.b2_photons), "--lanes", str(sz.lanes),
            "--source", json.dumps(src), "--detectors", json.dumps(det),
            "--time-gates", "16", "--save-detected", str(sz.record_cap),
            "--replay"])
    check_balance(A, res, sz.b2_photons)
    check(int(np.asarray(res.det_rec_overflow)) == 0,
          "no detector record overflow")
    hit = re.search(r"(\d+)/(\d+) detector-exact", out)
    check(hit is not None and int(hit.group(2)) > 0
          and hit.group(1) == hit.group(2),
          f"CLI replay {hit.group(0) if hit else 'missing'}")
    vol, cfg = m.CLI.get_bench("B2", sz.size)
    cfg = dataclasses.replace(cfg, n_time_gates=16)
    recs = m.detected_records(res)
    with clock.phase("b2_replay", len(recs)):
        rep = m.replay_jacobian(vol, cfg, recs, det, source=src,
                                seed=1234, n_lanes=sz.lanes)
    check(int((rep.replayed_det == rep.det).sum()) == rep.n_records,
          f"library replay {rep.n_records}/{rep.n_records} detector-exact")
    check_replay_identity(np, A, rep, res, vol, "replay identity")


def phase_many(m, sz, clock):
    np, S = m.np, m.S
    vol, cfg = m.CLI.get_bench("B1", sz.size)
    src, _ = source_and_detector(sz)
    scs = [m.SC.Scenario(vol, cfg, sz.many_photons, seed=s, source=src)
           for s in (1, 2, 3)]
    cache = m.SC.CompileCache()
    with clock.phase("many", 3 * sz.many_photons):
        got = m.SC.simulate_many(scs, n_lanes=sz.lanes, cache=cache)
    check(cache.misses == 1 and cache.hits == 0,
          f"simulate_many compiled once ({cache.misses} miss, "
          f"{cache.hits} hits)")
    with clock.phase("many_sequential", 3 * sz.many_photons):
        want = [S.simulate(vol, cfg, sc.n_photons, sz.lanes, sc.seed,
                           source=src) for sc in scs]
        m.jax.block_until_ready(want)
    for i, (g, w) in enumerate(zip(got, want)):
        differ = [f for f in w._fields if getattr(w, f) is not None
                  and not np.array_equal(np.asarray(getattr(g, f)),
                                         np.asarray(getattr(w, f)))]
        check(not differ, f"scenario {i} bit-identical to sequential "
              f"simulate (fields differing: {differ})")


def phase_tpu_vs_cpu(m, sz, clock):
    np, S, A, jax = m.np, m.S, m.A, m.jax
    vol, cfg = m.CLI.get_bench("B1", sz.size)
    src, _ = source_and_detector(sz)
    seeds = range(1, sz.ref_seeds + 1)
    fracs = {}
    for name, dev in (("tpu", jax.devices()[0]),
                      ("cpu", jax.devices("cpu")[0])):
        rows = []
        with clock.phase(f"b1_{name}_reference", sz.ref_photons * len(seeds)):
            with jax.default_device(dev):
                for s in seeds:
                    res = S.simulate(vol, cfg, sz.ref_photons, sz.lanes, s,
                                     source=src)
                    check(res.energy.devices() == {dev},
                          f"seed {s} ran on {dev.platform}")
                    bal = A.energy_balance(res)
                    rows.append((bal["absorbed"] / bal["launched"],
                                 bal["escaped"] / bal["launched"]))
        fracs[name] = np.asarray(rows, np.float64)
    for j, what in enumerate(("absorbed", "escaped")):
        t, c = fracs["tpu"][:, j], fracs["cpu"][:, j]
        se = np.sqrt(t.var(ddof=1) / len(t) + c.var(ddof=1) / len(c))
        diff = abs(t.mean() - c.mean())
        check(diff <= 5 * se,
              f"{what} fraction tpu {t.mean():.6f} vs cpu {c.mean():.6f}: "
              f"|diff| {diff:.3g} <= 5 SE ({5 * se:.3g}, "
              f"{len(t)} seeds x {sz.ref_photons} photons)")


def phase_four_chips(m, sz, clock):
    np, S, A, jax = m.np, m.S, m.A, m.jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.multidevice import (collective_kinds, sharded_sim_fn,
                                        simulate_sharded)

    devs = jax.devices()
    check(len(devs) == 4, f"{len(devs)} devices == 4")
    mesh = jax.make_mesh((4,), ("data",))   # as the CLI's --devices all
    vol, cfg = m.CLI.get_bench("B2", sz.size)
    src, det = source_and_detector(sz)
    dets = m.D.as_detectors(det)
    kw = dict(source=src, detectors=dets, record_detected=sz.record_cap)
    n = sz.mesh_photons
    with clock.phase("b2_sharded_4", n):
        sh = simulate_sharded(vol, cfg, n, mesh, n_lanes=sz.lanes,
                              seed=1234, **kw)
        jax.block_until_ready(sh)
    with clock.phase("b2_single_chip", n):
        one = S.simulate(vol, cfg, n, sz.lanes, 1234, **kw)
        jax.block_until_ready(one)
    for f in ("energy", "det_w", "det_rec"):
        placed = getattr(sh, f).sharding.device_set
        check(placed == set(devs),
              f"sharded {f} lives on all 4 devices, not device 0 alone")
    check(int(sh.n_launched) == int(one.n_launched) == n,
          f"n_launched {int(sh.n_launched)} == {int(one.n_launched)} == {n}")
    for f in ("energy", "det_w"):
        a = float(np.asarray(getattr(sh, f), np.float64).sum())
        b = float(np.asarray(getattr(one, f), np.float64).sum())
        rel = abs(a - b) / max(abs(b), 1e-30)
        check(rel <= 1e-3, f"total {f} sharded {a:.6f} vs one chip {b:.6f}: "
              f"rel {rel:.3g} <= 1e-3")
    check_balance(A, sh, n)
    recs = m.detected_records(sh)
    check(int(np.asarray(sh.det_rec_overflow)) == 0 and len(recs) > 0,
          f"{len(recs)} detector records, no overflow")
    with clock.phase("b2_replay_sharded_4", len(recs)):
        rep = m.replay_jacobian(vol, cfg, recs, dets, source=src,
                                seed=1234, n_lanes=sz.lanes, mesh=mesh)
    check(int((rep.replayed_det == rep.det).sum()) == rep.n_records,
          f"sharded replay {rep.n_records}/{rep.n_records} detector-exact")
    check_replay_identity(np, A, rep, sh, vol, "sharded replay identity")

    fn = sharded_sim_fn(vol, cfg, sz.lanes, mesh, **kw)
    rep_sh, lane_sh = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    args = (jax.ShapeDtypeStruct((vol.labels.size,), vol.labels.dtype,
                                 sharding=rep_sh),
            jax.ShapeDtypeStruct(vol.media.shape, vol.media.dtype,
                                 sharding=rep_sh),
            jax.ShapeDtypeStruct((4,), np.int32, sharding=lane_sh),
            jax.ShapeDtypeStruct((4,), np.uint32, sharding=lane_sh),
            jax.ShapeDtypeStruct((4,), np.uint32, sharding=lane_sh),
            jax.ShapeDtypeStruct((), np.uint32, sharding=rep_sh))
    hlo = fn.lower(*args).compile().as_text()
    kinds = collective_kinds(hlo)
    check(set(kinds) == {"all-reduce"},
          f"only all-reduce collectives in the sharded program: {kinds}")


def load_modules():
    """Import JAX and the program; None when this is not a checkout."""
    # the tpu_vs_cpu phase needs the host CPU backend beside the TPU
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT} is not a checkout of the program (no "
              f"src/repro beside this script)", file=sys.stderr)
        return None
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import jax
        import numpy as np

        from repro import detectors as D
        from repro import scenarios as SC
        from repro.core import analysis as A
        from repro.core import simulator as S
        from repro.launch import simulate as CLI
        from repro.launch.compile_cache import enable_compile_cache
        from repro.replay import detected_records, replay_jacobian
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              f"the root of a checkout", file=sys.stderr)
        return None
    return argparse.Namespace(
        jax=jax, np=np, D=D, SC=SC, A=A, S=S, CLI=CLI,
        enable_compile_cache=enable_compile_cache,
        detected_records=detected_records, replay_jacobian=replay_jacobian)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a CPU backend and cut every size down "
                         "(rehearsal only; prints no chip result)")
    args = ap.parse_args(argv)
    m = load_modules()
    if m is None:
        return 2
    jax = m.jax
    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{platform}); nothing was run", file=sys.stderr)
        return 3
    sz = REHEARSAL if args.rehearse else CHIP
    cache_dir = m.enable_compile_cache()
    clock = CompileClock(jax)
    print(f"[smoke] device: platform={platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"compile_cache={cache_dir}"
          f"{' REHEARSAL' if args.rehearse else ''}", flush=True)
    if args.four_chips:
        phases = [phase_four_chips]
    else:
        phases = [phase_b1_cli, phase_b2_cli, phase_many, phase_tpu_vs_cpu]
    for ph in phases:
        print(f"[smoke] --- {ph.__name__[len('phase_'):]} ---", flush=True)
        try:
            ph(m, sz, clock)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED in {ph.__name__}: {e}",
                  file=sys.stderr)
            return 1
    result = {"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
