#!/usr/bin/env python3
"""Chip benchmark of the photon-transport engine: one run of one cell.

    python3 benchmarks/chip/run.py --workload b1.cw --seed 7 \\
        --seconds 30 --trace 0

Set-up turns on JAX's persistent compilation cache in ``.jax_cache`` at
the root of the checkout (a fixed path, whatever the environment says,
so that two checkouts share nothing), opens the cell's entry point and
warms its program with one call of 0 photons (the photon count is
traced, so that call loads the executable the timed ones run).  The
window is a closed loop: one caller sends jobs back to back,
each the cell's photon count with a seed drawn from ``--seed`` and the
job's index, each ending in ``block_until_ready``; no job starts after
``--seconds`` and the one in flight finishes and counts.

``--trace 0`` reports the end-to-end metrics, ``photons_per_s`` (photons
of every job over the seconds from the window's opening to the last
job's end) and ``setup_s`` (process start to the window's opening).
``--trace 1`` runs with the program's round counters on, profiles a
slice of a second in the middle of the window's second job, where its
round loop runs, and reports every per-layer metric (``metrics/``) that
finds something to read.

After the window, one job drawn from the seed is compared with the
plain reference (``reference.py``, ``compare.py``); the numbers compared
are printed beside their limits as the last lines of standard error and
under ``checks`` in the result.  The last line of standard output is the
result's JSON object.  Without a TPU, or with fewer chips than the cell
asks for, the run prints no result and exits non-zero.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src"))
                if p not in sys.path]

from benchmarks.chip import (  # noqa: E402
    compare, harness, programs, reference, tracereduce)
from benchmarks.chip.clock import CompileClock  # noqa: E402


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _devices(jax, chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def enable_cache(jax, root: Path = HERE) -> Path:
    """The persistent compilation cache of the checkout that holds the
    benchmark directory ``root``; the program's own entry points are
    driven in process and set none."""
    path = Path(root).resolve().parents[1] / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _host_counts(jax, answers):
    """The small per-answer fields the metric readers read."""
    out = []
    for a in answers:
        r = a["result"]
        out.append(jax.device_get({
            "steps": r.steps, "stats": r.stats, "n_launched": r.n_launched,
            "det_rec_n": r.det_rec_n, "overflow": r.det_rec_overflow,
            "photons": a["photons"]}))
    return out


def check(cell, entry, jobs, host_answers, seed, device=None):
    """Compare one job drawn from the seed with the reference; return
    the values of every number the cell's limits name."""
    import numpy as np

    cfg = cell.config
    spec = cell.spec["reference"]
    n_det = len(cfg.get("detectors", []))
    gates = int(cfg.get("time_gates", 1))
    values = {"launched_gap": max(abs(int(a["n_launched"]) - a["photons"])
                                  for answers in host_answers
                                  for a in answers)}
    if cfg.get("record_capacity"):
        values["overflow"] = max(int(a["overflow"]) for answers
                                 in host_answers for a in answers)
    pick = int(np.random.default_rng(int(seed) % 2 ** 63).integers(
        len(jobs)))
    answers = entry.answers(jobs[pick].result)
    observed = [(compare.observe(a["result"], spec["blocks"], n_det, gates),
                 a["media"]) for a in answers]
    # the program's state is freed before the reference runs
    for j in jobs:
        j.result = None
    del answers
    gc.collect()
    readings = []
    bad = 0
    for k, (obs, media) in enumerate(observed):
        ref = reference.simulate(
            cfg, spec["photons"], harness.job_seed(~int(seed), k),
            n_batches=spec["batches"], blocks=spec["blocks"],
            media=reference.media_from_config(cfg, media), device=device)
        if int(ref["capped"]):
            raise RuntimeError(f"the reference hit its iteration cap with "
                               f"{int(ref['capped'])} photons alive")
        n_prog = host_answers[pick][k]["photons"]
        readings.append(compare.numbers(obs, ref, n_prog, spec["photons"]))
        if n_det:
            bad += compare.records_bad(obs["records"], n_prog, n_det, gates)
    values.update(compare.worst(readings))
    if n_det:
        values["records_bad"] = bad
    return values


def measure(workload: str, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True, root: Path = HERE,
            t_start: float = T_PROCESS, entry_wrap=None) -> dict:
    """One run of one cell; returns the result object.

    ``require_tpu=False`` and ``entry_wrap`` (a function of the opened
    entry that returns the entry the window drives) are for the tests,
    which run the rest of a run on the CPU, with and without faults.
    """
    cell = harness.load_cell(workload, root)
    import jax

    devs = _devices(jax, cell.chips, require_tpu)
    enable_cache(jax, root)
    clock = CompileClock(jax)
    entry_mod = harness.load_module("entries", cell.traffic["entry"], root)
    entry = entry_mod.Entry(cell.config, cell.traffic, collect_stats=trace)
    if entry_wrap is not None:
        entry = entry_wrap(entry)
    hlo_texts = {}
    with programs.capture(jax) if trace else contextlib.nullcontext() as cap:
        jax.block_until_ready(entry.job(harness.job_seed(seed, -1), 0))
    if trace:
        hlo_texts = cap.hlo_texts()
    setup_s = time.perf_counter() - t_start

    spans: list = []
    before = clock.snapshot()
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        slice_ = None
        if trace:
            slice_ = harness.Slice(jax, Path(tdir),
                                   cell.spec["trace"]["slice_s"])
        try:
            jobs, window_s = harness.run_window(jax, entry, seed, seconds,
                                                spans, slice_)
        finally:
            if slice_ is not None:
                slice_.finish()
        window_compile = clock.since(before, clock.snapshot())
        clock.close()
        spans += [harness.Span("entry.trace_compile", a, b)
                  for a, b in clock.intervals]
        summary = None
        if slice_ is not None and slice_.taken and slice_.xplane():
            summary = tracereduce.summarize(
                slice_.xplane(), slice_.t_start, slice_.t_stop, spans,
                n_devices=cell.chips, hlo_texts=hlo_texts)
    stats = [d.memory_stats() or {} for d in devs]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    host_answers = [_host_counts(jax, entry.answers(j.result))
                    for j in jobs]
    run = harness.Run(cell=cell, jobs=jobs, window_s=window_s,
                      compile=window_compile, answers=host_answers,
                      trace=summary, device_kind=devs[0].device_kind)
    photons = sum(j.photons for j in jobs)
    if trace:
        metrics = {}
        for name in harness.metric_names(root):
            mod = harness.load_module("metrics", name, root)
            value = mod.read(run)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": mod.UNIT}
    else:
        metrics = {
            "photons_per_s": {"value": photons / window_s,
                              "unit": "photons/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.slice_s

    values = check(cell, entry, jobs, host_answers, seed, device=devs[0])
    correct, table = compare.verdict(values, cell.spec["limits"])
    result = {"correct": correct, "attempted": len(jobs), "failed": 0,
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = table
    info = {"window_s": window_s, "jobs": len(jobs), "photons": photons,
            "window_compiles": window_compile["compiles"],
            "window_compile_s": window_compile["seconds"]}
    if summary is not None:
        info.update(summary.info())
    result["_info"] = info
    return result


def main(argv=None) -> int:
    args = _parse(argv)
    try:
        result = measure(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    info = result.pop("_info")
    print("run: " + " ".join(f"{k}={v}" for k, v in info.items()),
          flush=True)
    for name, row in result["checks"].items():
        ok = row["value"] is not None and row["value"] <= row["limit"]
        print(f"check {name}: {row['value']!r} limit {row['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
