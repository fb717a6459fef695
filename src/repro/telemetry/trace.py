"""Host-side span recording + Chrome trace export (DESIGN.md
§observability).

The paper's heterogeneous-execution result rests on *measuring*
per-device throughput and feeding it back into work assignment
(Sec. 2.4); cross-vendor portability studies (PAPERS.md) likewise lean
on per-kernel event timing.  This module gives the schedulers that
instrument: a :class:`Tracer` wraps every chunk / batch dispatch in a
span tagged with device, engine and photon count, and
the recorded timeline exports as Chrome ``trace_event`` JSON
(chrome://tracing, Perfetto) or streams to any
:class:`repro.telemetry.MetricsSink`.

Spans form a tree: a span opened while another is open as a ``with``
block records it as its parent, and every span records the id of its
root call.  Times are on ``time.time_ns()``'s clock, the clock the JAX
profiler's trace is aligned to, and every span is also a
``jax.profiler.TraceAnnotation``, so in a profile the program's spans
land in the host plane beside the device's operations.  The entry
points record into the tracer they are given, or else into the
process-wide :func:`default_tracer`, which is always on and keeps a
bounded ring of recent spans.

The span records double as *measured throughput samples*:
:func:`fit_device_models` turns a recorded (or re-loaded) timeline into
per-device ``loadbalance.DeviceModel`` fits — chunks of two or more
distinct sizes give the paper's full ``T = a*n + T0`` pilot fit via
``fit_pilot``; equal-size chunks fall back to a throughput-only model
(``t0 = 0``).  That closes the loop the ROADMAP's "true heterogeneous
execution" item is blocked on: dispatch, measure, refit, re-partition.
"""

from __future__ import annotations

import collections
import contextvars
import dataclasses
import itertools
import json
import time
from pathlib import Path
from typing import Sequence

from jax.profiler import TraceAnnotation

from repro.telemetry.sinks import MetricsSink

DEFAULT_RING = 4096  # spans the process tracer keeps

_span_ids = itertools.count(1)
# the innermost span open as a ``with`` block in this thread / context
_open_span: contextvars.ContextVar = contextvars.ContextVar(
    "repro_open_span", default=None)


def device_label(device) -> str:
    """Stable string id of a jax.Device (or pass a string through)."""
    if device is None:
        return "host"
    if isinstance(device, str):
        return device
    return f"{device.platform}:{device.id}"


@dataclasses.dataclass
class SpanEvent:
    """One completed span on the host timeline."""

    name: str
    device: str               # device_label() string
    t0: float                 # start, seconds on time.time_ns()'s clock
    dur: float                # duration, seconds
    engine: str | None = None
    args: dict = dataclasses.field(default_factory=dict)
    span_id: int = 0
    parent_id: int | None = None  # None: a root call
    root_id: int = 0              # span_id of the root call

    @property
    def photons_per_s(self) -> float | None:
        n = self.args.get("photons", self.args.get("records"))
        if n is None or self.dur <= 0:
            return None
        return float(n) / self.dur

    def to_dict(self) -> dict:
        out = {"type": "span", "name": self.name, "device": self.device,
               "t0": self.t0, "dur_s": self.dur, "engine": self.engine,
               "span_id": self.span_id, "parent_id": self.parent_id,
               "root_id": self.root_id, **self.args}
        pps = self.photons_per_s
        if pps is not None:
            out["photons_per_s"] = pps
        return out


class _Span:
    """Open span handle; ``end()`` (or exiting the ``with`` block) seals
    it into the tracer's event list and sinks.  Only a span entered as a
    ``with`` block becomes the parent of spans opened inside it; an
    explicitly ended handle (an async dispatch) is a leaf."""

    def __init__(self, tracer: "Tracer", name: str, device: str,
                 engine: str | None, args: dict, start_ns: int | None):
        self._tracer = tracer
        parent = _open_span.get()
        span_id = next(_span_ids)
        self.event = SpanEvent(
            name=name, device=device, t0=0.0, dur=0.0, engine=engine,
            args=args, span_id=span_id,
            parent_id=None if parent is None else parent.span_id,
            root_id=span_id if parent is None else parent.root_id)
        self._token = None
        self._annotation = TraceAnnotation(name)
        self._annotation.__enter__()
        self._start_ns = (time.time_ns() if start_ns is None  # reprolint: disable=REP201 - span timing is this module's job
                          else int(start_ns))

    def end(self, **extra_args) -> SpanEvent:
        end_ns = time.time_ns()  # reprolint: disable=REP201 - span timing is this module's job
        self._close_annotation()
        self.event.t0 = self._start_ns / 1e9
        self.event.dur = (end_ns - self._start_ns) / 1e9
        self.event.args.update(extra_args)
        self._tracer._record(self.event)
        return self.event

    def _close_annotation(self):
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

    def __enter__(self):
        self._token = _open_span.set(self.event)
        return self

    def __exit__(self, exc_type, *exc):
        _open_span.reset(self._token)
        if exc_type is None:
            self.end()
        else:  # pragma: no cover - error path
            self._close_annotation()


class Tracer:
    """Collect host-side spans + counters; fan out to metrics sinks.

    ``span(...)`` returns an open handle for explicit ``begin``/``end``
    bracketing of async dispatches (begin at dispatch, end when the
    result array is ready); it is also a context manager for the
    synchronous case.  All completed events are kept in ``events`` (for
    in-process consumers like :func:`fit_device_models`) and forwarded
    to every sink as flat dicts.  ``max_events`` bounds ``events`` to a
    ring of the most recent ones (``None``: keep all).
    """

    def __init__(self, sinks: Sequence[MetricsSink] = (),
                 max_events: int | None = None):
        self.sinks = list(sinks)
        self.events: collections.deque[SpanEvent] = collections.deque(
            maxlen=max_events)

    # -- spans -------------------------------------------------------------

    def span(self, name: str, device=None, engine: str | None = None,
             start_ns: int | None = None, **args) -> _Span:
        """Open a span now, or at ``start_ns`` (``time.time_ns()``) for
        work that began before the tracer was chosen."""
        return _Span(self, name, device_label(device), engine, dict(args),
                     start_ns)

    def _record(self, event: SpanEvent) -> None:
        self.events.append(event)
        self._emit(event.to_dict())

    # -- scalar metrics ----------------------------------------------------

    def counter(self, name: str, value, **labels) -> None:
        """Emit one scalar sample (run summaries, RoundStats fields)."""
        self._emit({"type": "counter", "name": name,
                    "value": value, **labels})

    def _emit(self, record: dict) -> None:
        for sink in self.sinks:
            sink.emit(record)

    # -- chrome trace export ----------------------------------------------

    def chrome_trace(self) -> dict:
        return chrome_trace(self.events)

    def save_chrome_trace(self, path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.chrome_trace(), indent=1) + "\n")
        return path


_DEFAULT_TRACER = Tracer(max_events=DEFAULT_RING)


def default_tracer() -> Tracer:
    """The process-wide tracer the entry points record into when no
    ``tracer`` is passed: always on, no sinks, the last
    :data:`DEFAULT_RING` spans."""
    return _DEFAULT_TRACER


# ---------------------------------------------------------------------------
# Chrome trace_event JSON (chrome://tracing / Perfetto / speedscope)
# ---------------------------------------------------------------------------

_PID = 0  # one process: the simulation host


def chrome_trace(events: Sequence[SpanEvent]) -> dict:
    """Render span events as a Chrome ``trace_event`` JSON object.

    One trace-viewer *thread* (tid) per device, named via ``M``
    metadata events; each span is a complete ``X`` event with
    microsecond timestamps and the span's args (photon count, engine,
    photons/s) attached for inspection in the viewer.
    """
    tids: dict[str, int] = {}
    trace: list[dict] = [{
        "ph": "M", "pid": _PID, "tid": 0, "name": "process_name",
        "args": {"name": "repro photon transport"},
    }]
    span_rows: list[dict] = []
    for ev in sorted(events, key=lambda e: e.t0):
        tid = tids.setdefault(ev.device, len(tids))
        args = dict(ev.args)
        if ev.engine is not None:
            args["engine"] = ev.engine
        pps = ev.photons_per_s
        if pps is not None:
            args["photons_per_s"] = pps
        span_rows.append({
            "ph": "X", "pid": _PID, "tid": tid, "name": ev.name,
            "cat": "dispatch", "ts": ev.t0 * 1e6, "dur": ev.dur * 1e6,
            "args": args,
        })
    for device, tid in tids.items():
        trace.append({"ph": "M", "pid": _PID, "tid": tid,
                      "name": "thread_name", "args": {"name": device}})
    trace.extend(span_rows)
    return {"traceEvents": trace, "displayTimeUnit": "ms"}


def load_chrome_trace(path_or_obj) -> list[SpanEvent]:
    """Parse a Chrome trace JSON back into :class:`SpanEvent` rows.

    Accepts a path or an already-parsed trace dict.  The inverse of
    :func:`chrome_trace` up to float rounding — the round-trip is what
    lets a saved ``--trace-out`` file feed :func:`fit_device_models`
    (and therefore ``loadbalance.fit_pilot``) in a later process.
    """
    if isinstance(path_or_obj, (str, Path)):
        obj = json.loads(Path(path_or_obj).read_text())
    else:
        obj = path_or_obj
    rows = obj.get("traceEvents", obj) if isinstance(obj, dict) else obj
    tid_names: dict[tuple, str] = {}
    for row in rows:
        if row.get("ph") == "M" and row.get("name") == "thread_name":
            tid_names[(row.get("pid"), row.get("tid"))] = \
                row.get("args", {}).get("name", "")
    events = []
    for row in rows:
        if row.get("ph") != "X":
            continue
        args = dict(row.get("args", {}))
        engine = args.pop("engine", None)
        args.pop("photons_per_s", None)  # derived; recomputed on demand
        device = tid_names.get((row.get("pid"), row.get("tid")),
                               str(row.get("tid")))
        events.append(SpanEvent(
            name=row.get("name", ""), device=device,
            t0=float(row.get("ts", 0.0)) / 1e6,
            dur=float(row.get("dur", 0.0)) / 1e6,
            engine=engine, args=args))
    return events


# ---------------------------------------------------------------------------
# measured-throughput samples -> loadbalance device models
# ---------------------------------------------------------------------------

def device_samples(events: Sequence[SpanEvent],
                   name: str | None = None) -> dict[str, list[tuple]]:
    """Group span events into per-device ``(photons, seconds)`` samples.

    ``name`` filters by span name (``None``: every span carrying a
    ``photons`` or ``records`` arg counts).  The samples are exactly the
    pilot measurements ``loadbalance.fit_pilot`` consumes.
    """
    out: dict[str, list[tuple]] = {}
    for ev in events:
        if name is not None and ev.name != name:
            continue
        n = ev.args.get("photons", ev.args.get("records"))
        if n is None or ev.dur <= 0:
            continue
        out.setdefault(ev.device, []).append((float(n), float(ev.dur)))
    return out


def fit_device_models(events_or_trace, name: str | None = None) -> dict:
    """Fit a ``loadbalance.DeviceModel`` per device from span records.

    ``events_or_trace`` is a list of :class:`SpanEvent` (a live
    ``Tracer.events``) or anything :func:`load_chrome_trace` accepts (a
    saved ``--trace-out`` path).  Fitting follows the shared rule in
    ``loadbalance.model_from_samples`` (full ``T = a*n + T0`` fit when
    the samples span >= 2 distinct photon counts, aggregate-throughput
    fallback otherwise).  The result plugs straight into
    ``loadbalance.PARTITIONERS`` / ``heterogeneous_partition``.
    """
    from repro.core.loadbalance import DeviceModel, model_from_samples

    events = events_or_trace
    if isinstance(events, (str, Path, dict)) or not all(
            isinstance(e, SpanEvent) for e in events):
        events = load_chrome_trace(events_or_trace)
    models: dict[str, DeviceModel] = {}
    for device, samples in device_samples(events, name=name).items():
        model = model_from_samples(samples, name=device)
        if model is not None:
            models[device] = model
    return models
