"""Files found by name, the closed-loop window, and the run record that
the per-layer metric readers read.

Layout under the benchmark's directory (``root``):

  configs/<config>.json     a deployment: volume formula, media, beam,
                            detectors, gates, guarantees and its source;
  traffic/<traffic>.json    a traffic mix: the entry point, photons per
                            job and any sweep;
  workloads/<cell>.json     a cell: its config, traffic and chips, the
                            reference's size, the limits of the check,
                            and the length of the traced slice;
  entries/<entry>.py        an ``Entry(config, traffic, collect_stats)``
                            with ``job(seed, photons)`` and
                            ``answers(result, photons)``;
  metrics/<metric>.py       ``read(run) -> float | None``: one per-layer
                            metric, ``None`` where the run holds nothing
                            for it to read.

A new deployment, traffic mix, entry point or metric is a new file
there; nothing that exists is edited.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load_json(kind: str, name: str, root: Path = HERE) -> dict:
    path = Path(root) / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: Path = HERE):
    path = Path(root) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(path)
    ident = "".join(c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{ident}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_names(root: Path = HERE) -> list[str]:
    return sorted(p.stem for p in (Path(root) / "metrics").glob("*.py")
                  if not p.stem.startswith("_"))


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict
    config: dict
    traffic: dict

    @property
    def chips(self) -> int:
        return int(self.spec["chips"])


def load_cell(name: str, root: Path = HERE) -> Cell:
    spec = load_json("workloads", name, root)
    return Cell(name=name, spec=spec,
                config=load_json("configs", spec["config"], root),
                traffic=load_json("traffic", spec["traffic"], root))


def job_seed(seed: int, index: int) -> int:
    """A seed below 2**31 for job ``index`` of a run with ``--seed seed``
    (splitmix64 of the pair), the same on every machine.  The program's
    entry points take a Python int that JAX parses as int32."""
    x = (int(seed) * 0x9E3779B97F4A7C15 + index + 1) % 2 ** 64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) % 2 ** 64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) % 2 ** 64
    return (x ^ (x >> 31)) % 2 ** 31


@dataclasses.dataclass
class Job:
    index: int
    seed: int
    start_s: float          # from the window's opening
    end_s: float
    photons: int
    result: object          # what the entry returned, still on device


@dataclasses.dataclass
class Span:
    name: str
    start_s: float          # time.time() clock
    end_s: float


class Slice:
    """Profiles a slice of ``slice_s`` seconds in the middle of the
    window's second job: it opens ``slice_s / 2`` before half the first
    job's length has passed since the second job started.  The slice
    then holds the operations of the job's round loop alone, not the
    retrace and cache load at a call's start (``host_compile_share``
    reads those) nor the host's work at its end, so the op-class and
    idle shares read from it are the loop's."""

    def __init__(self, jax, log_dir: Path, slice_s: float):
        self.jax = jax
        self.log_dir = Path(log_dir)
        self.slice_s = float(slice_s)
        self.t_start = self.t_stop = None      # time.time_ns()
        self.taken = False
        self._thread = None
        self._stop = threading.Event()

    def job_started(self, index: int, first_job_s: float | None):
        if index != 1 or first_job_s is None or self._thread is not None:
            return
        delay = max(self.delay(first_job_s), 0.0)
        self._thread = threading.Thread(target=self._run, args=(delay,),
                                        daemon=True)
        self._thread.start()

    def delay(self, first_job_s: float) -> float:
        """Seconds from the second job's start to the slice's opening."""
        return (first_job_s - self.slice_s) / 2

    def _run(self, delay):
        if self._stop.wait(delay):
            return
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(str(self.log_dir),
                                      profiler_options=opts)
        # the device records from start_trace's return to stop_trace's
        # call: those bound the slice
        self.t_start = time.time_ns()
        try:
            self._stop.wait(self.slice_s)
        finally:
            self.t_stop = time.time_ns()
            self.jax.profiler.stop_trace()
            self.taken = True

    def finish(self):
        """Wait for the slice to close.  A slice that has not opened when
        the window ends is not taken."""
        if self._thread is None:
            return
        if self.t_start is None:
            self._stop.set()
        self._thread.join()

    def xplane(self) -> Path | None:
        found = sorted(self.log_dir.glob("plugins/profile/*/*.xplane.pb"))
        return found[-1] if found else None


def run_window(jax, entry, seed: int, seconds: float, spans: list,
               slice_: Slice | None = None) -> tuple[list[Job], float]:
    """The closed loop: jobs back to back until ``seconds`` have passed;
    the job in flight then finishes and counts.  Returns the jobs and the
    window's length, from its opening to the last job's end."""
    jobs: list[Job] = []
    t0 = time.perf_counter()
    w0 = time.time()
    while True:
        i = len(jobs)
        s = job_seed(seed, i)
        if slice_ is not None:
            slice_.job_started(i, jobs[0].end_s - jobs[0].start_s
                               if jobs else None)
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation("entry.call"):
            res = entry.job(s)
            jax.block_until_ready(res)
        b = time.perf_counter()
        spans.append(Span("entry.call", w0 + (a - t0), w0 + (b - t0)))
        jobs.append(Job(i, s, a - t0, b - t0, entry.photons, res))
        if b - t0 >= seconds:
            return jobs, b - t0


@dataclasses.dataclass
class Run:
    """What one run measured, for the per-layer metric readers."""

    cell: Cell
    jobs: list[Job]
    window_s: float
    compile: dict            # CompileClock.since over the window
    answers: list            # per job, the entry's answers (host side)
    trace: object = None     # tracereduce.Summary of the slice, or None
    device_kind: str = ""
