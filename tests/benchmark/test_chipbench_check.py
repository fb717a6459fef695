"""The window loop and the comparison with the reference, on the CPU at
8^3: a sound run is correct, and the lower-precision control and every
fault a cell can have come out as not correct."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import compare, control, harness, reference, run
from benchmarks.chip.faults import Faulty

SEED = 2 ** 31 + 12345     # larger than 32 signed bits hold


def _measure(root, workload, entry_wrap=None, trace=False):
    return run.measure(workload, SEED, 0.01, trace, require_tpu=False,
                       root=root, t_start=time.perf_counter(),
                       entry_wrap=entry_wrap)


class Wrapped:
    def __init__(self, entry):
        self._entry = entry

    def __getattr__(self, name):
        return getattr(self._entry, name)


def test_sound_run_is_correct(tiny_root, cpu_cache):
    result = _measure(tiny_root, "tiny.b1")
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-2:] == ["checks", "_info"]
    assert set(result["metrics"]) == {"photons_per_s", "setup_s"}
    assert set(result["checks"]) == {"launched_gap", "totals_z",
                                     "fluence_z"}


def test_sweep_scenarios_each_checked_against_their_own_media(
        tiny_root, cpu_cache):
    result = _measure(tiny_root, "tiny.sweep")
    assert result["correct"], result["checks"]

    class Shuffled(Wrapped):
        """Scenarios' results handed back one place out of order."""

        def answers(self, res, photons=None):
            out = self._entry.answers(res, photons)
            return [dict(a, result=b["result"])
                    for a, b in zip(out, out[1:] + out[:1])]

    assert not _measure(tiny_root, "tiny.sweep", Shuffled)["correct"]


@pytest.mark.parametrize("fault", ["nothing_moves", "half_left_out",
                                   "answer_altered"])
def test_fault_in_the_timed_path_is_not_correct(tiny_root, cpu_cache,
                                                fault):
    result = _measure(tiny_root, "tiny.b1",
                      lambda entry: Faulty(entry, fault))
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("workload", ["tiny.sweep", "tiny.b2"])
@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
def test_fault_fails_sweeps_and_detector_cells_too(tiny_root, cpu_cache,
                                                   workload, fault):
    result = _measure(tiny_root, workload,
                      lambda entry: Faulty(entry, fault))
    assert not result["correct"], result["checks"]


def test_unknown_fault_is_refused():
    with pytest.raises(ValueError):
        Faulty(object(), "a_fault_no_cell_has")


def test_lower_precision_control_is_not_correct(tiny_root):
    cell = harness.load_cell("tiny.b1", tiny_root)
    values = control.control_numbers(cell, SEED)
    stats = {k: v for k, v in cell.spec["limits"].items()
             if k.endswith("_z")}
    ok, table = compare.verdict(values, stats)
    assert not ok, table


def test_reference_agrees_with_itself_and_not_with_bfloat16(tiny_root):
    cell = harness.load_cell("tiny.b2", tiny_root)
    cfg = cell.config
    a = reference.simulate(cfg, 4096, 1, blocks=2)
    b = reference.simulate(cfg, 4096, 2, blocks=2)
    low = reference.simulate(cfg, 4096, 3, blocks=2, dtype=jnp.bfloat16)
    assert int(a["capped"]) == 0 and int(b["capped"]) == 0
    n_det = len(cfg["detectors"])
    same = compare.numbers(compare.observe_reference(b, n_det), a,
                           4096, 4096)
    lower = compare.numbers(compare.observe_reference(low, n_det), a,
                            4096, 4096)
    assert max(same["totals_z"], same["fluence_z"]) < 5
    assert lower["totals_z"] > 10
    # every photon ends absorbed, escaped, timed out or lost to roulette
    total = a["absorbed"].sum() + a["escaped"].sum() + a["timed"].sum()
    assert abs(total / 4096 - 1) < 0.02
    assert np.all(a["exit0"] <= a["escaped"] + 1e-6)
