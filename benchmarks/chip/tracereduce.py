"""Reduction of a profiled slice to device busy time, op classes and
named idle gaps.

The slice is the JAX profiler's ``.xplane.pb``, read with
``jax.profiler.ProfileData``.  Each TPU is a plane ``/device:TPU:<n>``;
its line ``XLA Ops`` holds one event per HLO operation that ran, named
by the instruction's text (``%fusion.106 = f32[216000]{...} fusion(...),
kind=kCustom, calls=%fused_computation.4``), and its line ``XLA
Modules`` one event per program execution (``jit_sim_fn(<id>)``).
Times are nanoseconds from the profile's start, the ``Task
Environment`` plane's ``profile_start_time``.

* The slice runs from ``start_trace``'s return to the call of
  ``stop_trace`` on the host's clock; the device records only between
  the two.  Busy time of a chip is the union of its operations'
  intervals within the slice.  ``busy_s`` is the mean over the chips the
  cell uses, ``busy_total_s`` their sum.
* An operation's class is its HLO opcode, or, for a fusion, the opcode
  of the fused computation's root, looked up in the HLO text of the
  module that ran it (``programs.py`` records those texts); where that
  text is missing, the opcode in the event's own text.  A fusion whose
  root is not one of the classes below but which holds a ``gather`` is
  classed ``gather``: the TPU compiler wraps a gather's result in a
  reshape, so its root does not show it.  The collectives
  are classed as ``multidevice.collective_kinds`` counts them, by their
  opcode.  Classes: ``scatter``, ``gather``, the collectives,
  ``fusion`` (other fused computations), and the opcode itself for
  anything else.
* An idle gap of the first chip is named by the harness span that was
  open on the host at its midpoint: ``entry.trace_compile`` (tracing,
  lowering, compiling or loading from the cache), else ``entry.call``
  (the rest of the entry's call: argument preparation, dispatch, the
  CLI's host-side summaries), else ``harness`` (the harness between
  jobs).
"""

from __future__ import annotations

import bisect
import dataclasses
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_ORDER = ("entry.trace_compile", "entry.call")

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _class_of(opcode: str) -> str:
    base = opcode[:-len("-start")] if opcode.endswith("-start") else opcode
    base = base[:-len("-done")] if base.endswith("-done") else base
    if base in COLLECTIVES:
        return base
    return opcode


_EVENT_OP = re.compile(r"[\]\})] ([a-z][\w\-]*)\(")
_KEEP = ("scatter", "gather") + COLLECTIVES


def hlo_classes(text: str) -> dict[str, str]:
    """Map each instruction name of one HLO module's text to its class:
    the opcode, or for a fusion the class of its root."""
    opcode: dict[str, str] = {}
    calls: dict[str, str] = {}
    roots: dict[str, str] = {}
    holds: dict[str, set] = {}
    comp = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, op = m.group(2), m.group(3)
        opcode[name] = op
        c = _CALLS.search(line)
        if op == "fusion" and c:
            calls[name] = c.group(1)
        if comp is not None:
            holds.setdefault(comp, set()).add(op)
            if m.group(1):
                roots[comp] = op
    out = {}
    for name, op in opcode.items():
        if op == "fusion":
            comp = calls.get(name, "")
            cls = _class_of(roots.get(comp, "fusion"))
            if cls not in _KEEP:
                cls = "gather" if "gather" in holds.get(comp, ()) \
                    else "fusion"
            out[name] = cls
        else:
            out[name] = _class_of(op)
    return out


def event_class(event_name: str) -> str:
    """Class from an op event's own text, where no HLO text is known."""
    m = _EVENT_OP.search(event_name)
    if not m:
        return "unknown"
    cls = _class_of(m.group(1))
    if cls == "fusion" or cls.endswith("fusion"):
        return "fusion"
    return cls


def _name_of(event_name: str) -> str:
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%").strip()


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


@dataclasses.dataclass
class Summary:
    slice_s: float
    busy_s: float                  # mean over the chips used
    busy_total_s: float            # summed over them
    class_s: dict                  # op class -> device seconds, all chips
    gaps: list                     # (name, seconds), first chip, longest first
    n_ops: int
    unmatched_s: float             # device seconds of ops of no known HLO

    def breakdown(self) -> dict:
        ops = sorted(self.class_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}

    def info(self) -> dict:
        return {"slice_s": self.slice_s, "busy_s": self.busy_s,
                "trace_ops": self.n_ops, "unmatched_s": self.unmatched_s}


def _device_planes(pd, n_devices):
    planes = [p for p in pd.planes
              if re.fullmatch(r"/device:TPU:\d+", p.name)]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return planes[:n_devices]


def _stat(holder, key):
    for k, v in holder.stats:
        if k == key:
            return v
    return None


def _lines(plane):
    return {line.name: line for line in plane.lines}


def summarize(path, t_open_ns: int, t_close_ns: int, spans,
              n_devices: int, hlo_texts: dict | None = None
              ) -> Summary | None:
    """Reduce the slice at ``path``; ``None`` when it holds no TPU.

    ``t_open_ns`` and ``t_close_ns`` bound the slice on ``time.time_ns()``
    (``start_trace`` returned, ``stop_trace`` called); ``spans`` are the
    harness's host spans (``harness.Span``, ``time.time()`` seconds);
    ``hlo_texts`` maps module names to the HLO text of the programs that
    ran.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = _device_planes(pd, n_devices)
    if not planes:
        return None
    origin_ns = t_open_ns
    for p in pd.planes:
        if p.name == "Task Environment":
            origin_ns = _stat(p, "profile_start_time") or origin_ns
    lo, hi = t_open_ns - origin_ns, t_close_ns - origin_ns
    classes = {name: hlo_classes(text)
               for name, text in (hlo_texts or {}).items()}
    class_s: dict[str, float] = {}
    busy = []
    gaps_first = []
    n_ops = 0
    unmatched = 0.0
    for i, plane in enumerate(planes):
        lines = _lines(plane)
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns,
             ev.name.split("(", 1)[0])
            for ev in (lines[MODULES_LINE].events
                       if MODULES_LINE in lines else ()))
        starts = [m[0] for m in modules]
        ivs = []
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            a = max(ev.start_ns, lo)
            b = min(ev.start_ns + ev.duration_ns, hi)
            if b <= a:
                continue
            ivs.append((a, b))
            k = bisect.bisect_right(starts, ev.start_ns) - 1
            table = classes.get(modules[k][2]) if k >= 0 else None
            cls = table.get(_name_of(ev.name)) if table else None
            if cls is None:
                unmatched += (b - a) / 1e9
                cls = event_class(ev.name)
            class_s[cls] = class_s.get(cls, 0.0) + (b - a) / 1e9
            n_ops += 1
        merged = _union(ivs)
        busy.append(sum(y - x for x, y in merged) / 1e9)
        if i == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps_first = [(x, y) for x, y in zip(edges[::2], edges[1::2])
                          if y > x]
    named = sorted(((_span_at(spans, (origin_ns + (x + y) / 2) / 1e9),
                     (y - x) / 1e9) for x, y in gaps_first),
                   key=lambda kv: -kv[1])
    return Summary(slice_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy),
                   busy_total_s=sum(busy), class_s=class_s, gaps=named,
                   n_ops=n_ops, unmatched_s=unmatched)


def _span_at(spans, t_s: float) -> str:
    open_now = {s.name for s in spans if s.start_s <= t_s <= s.end_s}
    for name in SPAN_ORDER:
        if name in open_now:
            return name
    return "harness"
