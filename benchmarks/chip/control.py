#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 benchmarks/chip/control.py --workload b1.cw \\
        --seeds 11 12 13 --control-seeds 21 22 23

In one process, on the cell's chips and at the cell's sizes:

* for each of ``--seeds``, one job of the cell's entry point, compared
  with the reference exactly as a run compares its drawn job: the
  program's readings, whose largest is a limit's lower reading;
* for each of ``--control-seeds``, the reference in bfloat16 put in the
  program's place and compared with the float32 reference: the control,
  whose smallest reading is a limit's upper reading;
* for each of ``--faults`` (``faults.FAULTS``) and each of
  ``--fault-seeds``, one job of the entry with that fault planted under
  it, compared as a run compares its drawn job.

Each reading is one JSON line on standard output.  The benchmark's runs
never run this; it is how the limits in ``workloads/*.json`` were set,
and how a later change to a cell sets them again.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def control_numbers(cell, seed: int, device=None) -> dict:
    """The reference in bfloat16, in the program's place, against the
    float32 reference."""
    import jax.numpy as jnp

    from benchmarks.chip import compare, harness, reference

    spec = cell.spec["reference"]
    cfg = cell.config
    n = spec["photons"]
    kw = dict(n_batches=spec["batches"], blocks=spec["blocks"],
              device=device)
    low = reference.simulate(cfg, n, harness.job_seed(seed, 0),
                             dtype=jnp.bfloat16, **kw)
    ref = reference.simulate(cfg, n, harness.job_seed(~int(seed), 0), **kw)
    obs = compare.observe_reference(low, len(cfg.get("detectors", [])))
    return compare.numbers(obs, ref, n, n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from benchmarks.chip import faults, harness, run

    cell = harness.load_cell(args.workload)
    devs = run._devices(jax, cell.chips, require_tpu=True)
    run.enable_cache(jax)
    entry = harness.load_module("entries", cell.traffic["entry"]).Entry(
        cell.config, cell.traffic, collect_stats=False)
    jax.block_until_ready(entry.job(1, 0))

    def reading(kind, entry, seed):
        res = entry.job(harness.job_seed(seed, 0))
        jax.block_until_ready(res)
        job = harness.Job(0, seed, 0.0, 0.0, entry.photons, res)
        host = [run._host_counts(jax, entry.answers(res))]
        values = run.check(cell, entry, [job], host, seed, device=devs[0])
        print(json.dumps({"kind": kind, "seed": seed, **values}),
              flush=True)

    for seed in args.seeds:
        reading("program", entry, seed)
    for fault in args.faults:
        for seed in args.fault_seeds:
            reading(f"fault:{fault}", faults.Faulty(entry, fault), seed)
    for seed in args.control_seeds:
        values = control_numbers(cell, seed, device=devs[0])
        print(json.dumps({"kind": "control", "seed": seed, **values}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
