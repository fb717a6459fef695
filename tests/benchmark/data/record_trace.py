#!/usr/bin/env python3
"""Record the small TPU trace that ``test_chipbench_trace.py`` reduces.

    python3 tests/benchmark/data/record_trace.py   # on a TPU, from the root

Two calls of the command line on B1 at 8^3 with 32 photons on 128
lanes, with the harness's host spans around them, profiled as a run's
slice is.  Writes ``tpu_slice.xplane.pb.gz``, ``tpu_slice_hlo.json.gz``
(the HLO text of the programs, captured as a traced run captures them)
and ``tpu_slice.json`` (the slice's bounds and spans) beside itself.
"""

import contextlib
import gzip
import io
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    import jax

    from benchmarks.chip import programs
    from benchmarks.chip.clock import CompileClock
    from repro.launch import simulate

    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    argv = ["--bench", "B1", "--size", "8", "--photons", "32", "--lanes",
            "128", "--source", '{"type": "pencil", "pos": [4, 4, 0]}']

    def call(seed):
        with contextlib.redirect_stdout(io.StringIO()):
            return jax.block_until_ready(
                simulate.main(argv + ["--seed", str(seed)]))

    with programs.capture(jax) as cap:
        call(1)
    hlo = cap.hlo_texts()
    clock = CompileClock(jax)
    spans = []
    with tempfile.TemporaryDirectory() as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        t_open = time.time_ns()
        for seed in (2, 3):
            a = time.time()
            call(seed)
            spans.append(("entry.call", a, time.time()))
        time.sleep(0.01)
        t_close = time.time_ns()
        jax.profiler.stop_trace()
        clock.close()
        spans += [("entry.trace_compile", a, b) for a, b in clock.intervals]
        src = sorted(Path(tdir).glob("plugins/profile/*/*.xplane.pb"))[-1]
        with open(src, "rb") as f, \
                gzip.open(HERE / "tpu_slice.xplane.pb.gz", "wb") as g:
            shutil.copyfileobj(f, g)
    with gzip.open(HERE / "tpu_slice_hlo.json.gz", "wt") as g:
        json.dump(hlo, g)
    (HERE / "tpu_slice.json").write_text(json.dumps(
        {"t_open_ns": t_open, "t_close_ns": t_close, "spans": spans,
         "device_kind": jax.devices()[0].device_kind}, indent=1))
    print("recorded", [p.stat().st_size for p in sorted(HERE.glob(
        "tpu_slice*"))])


if __name__ == "__main__":
    main()
