"""The per-layer metrics that read what the program records itself, on
the CPU at 8^3: the host's share of the entry's calls outside the
program's execution (spans of the process tracer) and the share of the
round flush's updates that carry weight (``RoundStats``)."""

import time

import pytest

from benchmarks.chip import run


@pytest.mark.parametrize("workload", ["tiny.b1", "tiny.b2", "tiny.sweep"])
def test_program_spans_and_flush_counters_are_read(tiny_root, cpu_cache,
                                                   workload):
    result = run.measure(workload, 2 ** 31 + 777, 0.01, True,
                         require_tpu=False, root=tiny_root,
                         t_start=time.perf_counter())
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in ("entry_host_share", "flush_live_share"):
        assert 0 < m[name] <= 1, (name, m)
    # the compile path lies inside the root calls and outside execution
    assert m["entry_host_share"] >= m["host_compile_share"]
    # two updates per lane-segment, of which only the live ones can
    # carry weight
    assert m["flush_live_share"] <= m["lane_occupancy"]
    assert result["correct"], result["checks"]
