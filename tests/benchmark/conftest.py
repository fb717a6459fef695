"""Shared set-up of the chip benchmark's CPU tests.

``tiny_root`` is a copy of ``benchmarks/chip`` with tiny cells added as
new files (8^3 volumes, a few thousand photons), the way a later change
adds a cell; its runs keep their compilation cache beside the copy.
``cpu_cache`` restores the process's cache setting after a module's
tests, since ``run.measure`` turns the cache on.
"""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def add_tiny_cells(root: Path) -> None:
    """Tiny configurations, traffic and cells, written as new files."""
    b1 = json.loads((root / "configs" / "b1_cube60.json").read_text())
    b1.update(shape=[8, 8, 8],
              beam={"type": "pencil", "pos": [4.0, 4.0, 0.0],
                    "dir": [0.0, 0.0, 1.0]})
    b2 = json.loads((root / "configs" / "b2_cube60_sphere.json").read_text())
    b2.update(shape=[8, 8, 8],
              beam={"type": "pencil", "pos": [4.0, 4.0, 0.0],
                    "dir": [0.0, 0.0, 1.0]},
              sphere={"center_mm": [4.0, 4.0, 4.0], "radius_mm": 2.0,
                      "label": 2},
              time_gates=2, detectors=[{"x": 4.0, "y": 4.0, "radius": 3.0}],
              record_capacity=4096)
    files = {
        "configs/tiny_b1.json": b1,
        "configs/tiny_b2.json": b2,
        "traffic/tiny_cw.json": {"entry": "cli", "photons": 2048,
                                 "devices": "one"},
        "traffic/tiny_sweep.json": {
            "entry": "simulate_many", "photons": 512,
            "sweep": {"label": 1, "mua": [0.005, 0.05], "mus": [0.5, 2.0]}},
    }
    limits = {"launched_gap": 0, "totals_z": 6.0, "fluence_z": 7.0}
    b2_limits = dict(limits, overflow=0, records_bad=0, detector_z=7.0)
    for name, cfg, traffic, lim in (
            ("tiny.b1", "tiny_b1", "tiny_cw", limits),
            ("tiny.b2", "tiny_b2", "tiny_cw", b2_limits),
            ("tiny.sweep", "tiny_b1", "tiny_sweep", limits)):
        files[f"workloads/{name}.json"] = {
            "config": cfg, "traffic": traffic, "chips": 1,
            "reference": {"photons": 4096, "batches": 64, "blocks": 2},
            "limits": lim, "trace": {"slice_s": 0.5}}
    for rel, obj in files.items():
        path = root / rel
        assert not path.exists(), f"{rel} would overwrite a file"
        path.write_text(json.dumps(obj, indent=1))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout") / "benchmarks" / "chip"
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    add_tiny_cells(root)
    return root


@pytest.fixture(scope="module")
def cpu_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
