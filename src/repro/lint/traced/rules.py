"""REP8xx — the traced-tier rule registry.

Every rule walks closed jaxprs via :func:`repro.lint.traced.iter_eqns`
and yields :class:`~repro.lint.Finding`s anchored to the target's
entry file.  Adding a rule: subclass :class:`TracedRule` here, append
it to ``TRACED_RULES``, add positive + negative fixture tests to
tests/test_tracelint.py, and document it in DESIGN.md
§static-analysis.
"""

from __future__ import annotations

from typing import Iterator

from repro.lint import Finding
from repro.lint.traced import (TraceTarget, TracedRule, iter_eqns,
                               jaxpr_fingerprint)

# dtypes that must never appear in a traced program: the portability
# contract is float32/int32-class everywhere (DESIGN.md §dtype)
_WIDE_DTYPES = frozenset({"float64", "complex128", "complex64",
                          "int64", "uint64"})

# host-transfer primitives: inside the round loop each one is a
# device->host sync per iteration
_CALLBACK_PRIMS = frozenset({"pure_callback", "debug_callback",
                             "debug_print", "io_callback", "infeed",
                             "outfeed", "device_get", "host_callback"})

# scatter modes whose result depends on the order duplicate indices
# are applied in (add/min/max are order-dependent only under
# non-associative fp accumulation; plain scatter overwrites)
_SCATTER_PRIMS = frozenset({"scatter", "scatter-add", "scatter-sub",
                            "scatter-mul", "scatter-min", "scatter-max"})

# size-preserving unary reshapes the index-provenance analysis sees
# through
_PASSTHROUGH_PRIMS = frozenset({"copy", "convert_element_type",
                                "reshape", "squeeze", "expand_dims",
                                "rev"})


def _dtype_str(aval) -> str | None:
    d = getattr(aval, "dtype", None)
    return None if d is None else str(d)


class TracedDtypeRule(TracedRule):
    id = "REP801"
    name = "traced-dtype"
    severity = "error"
    description = ("no f64/i64/complex values or weak-typed float "
                   "promotion anywhere in a traced program")

    def check(self, targets: list[TraceTarget]) -> Iterator[Finding]:
        for t in targets:
            closed = t.jaxpr()
            seen: set[tuple] = set()

            def emit(kind, detail, target=t, seen=seen):
                if (kind, detail) in seen:
                    return None
                seen.add((kind, detail))
                return self.finding(target, detail)

            for i, av in enumerate(closed.out_avals):
                d = _dtype_str(av)
                if d is None:
                    continue
                if d in _WIDE_DTYPES:
                    f = emit("out-wide", f"entrypoint output {i} has wide "
                             f"dtype {d} — traced programs are "
                             f"f32/i32-class only")
                    if f:
                        yield f
                elif getattr(av, "weak_type", False) and \
                        d.startswith("float"):
                    f = emit("out-weak", f"entrypoint output {i} is "
                             f"weak-typed {d} — a Python scalar leaked "
                             f"into the outputs (promotion depends on "
                             f"the caller)")
                    if f:
                        yield f
            for var in closed.jaxpr.constvars:
                d = _dtype_str(getattr(var, "aval", None))
                if d in _WIDE_DTYPES:
                    f = emit("const-wide", f"closed-over constant has wide "
                             f"dtype {d}")
                    if f:
                        yield f
            for _jaxpr, eqn, _depth in iter_eqns(closed):
                prim = eqn.primitive.name
                for var in eqn.outvars:
                    av = getattr(var, "aval", None)
                    d = _dtype_str(av)
                    if d is None:
                        continue
                    if d in _WIDE_DTYPES:
                        f = emit("eqn-wide", f"`{prim}` produces wide dtype "
                                 f"{d} inside the trace")
                        if f:
                            yield f
                    elif getattr(av, "weak_type", False) and \
                            d.startswith("float"):
                        # weak *ints* are jax-internal loop counters
                        # (fori_loop lowers its bounds weakly); weak
                        # floats mean a bare Python float is steering
                        # promotion mid-trace
                        f = emit("eqn-weak", f"`{prim}` produces a "
                                 f"weak-typed {d} — a bare Python float "
                                 f"is steering promotion inside the "
                                 f"trace")
                        if f:
                            yield f


# ---------------------------------------------------------------------------
# REP802 — scatter-race / nondeterministic accumulation
# ---------------------------------------------------------------------------

def _const_scalar(atom, producers, depth=0):
    """Python scalar value of an atom, chasing broadcasts of literals."""
    import numpy as np
    val = getattr(atom, "val", None)
    if val is not None:  # Literal: scalar or nothing (may be unhashable)
        if np.ndim(val) == 0:
            return val.item() if hasattr(val, "item") else val
        return None
    if depth > 4:
        return None
    eqn = producers.get(atom)
    if eqn is not None and eqn.primitive.name in (
            "broadcast_in_dim", "convert_element_type", "copy"):
        return _const_scalar(eqn.invars[0], producers, depth + 1)
    return None


def _affine_of(var, producers, depth=0):
    """Prove ``var``'s elements form ``{scale*i + o : o in offsets}``
    over one iota — the shape every lane-disjoint accumulator index
    has.  Returns ``(root, scale, offsets, length)`` or None.
    """
    if depth > 16:
        return None
    if getattr(var, "val", None) is not None:
        return None  # Literal arrays are handled by the caller
    eqn = producers.get(var)
    if eqn is None:
        return None
    prim = eqn.primitive.name
    if prim == "iota":
        shape = var.aval.shape
        dim = eqn.params.get("dimension", 0)
        if not shape:
            return None
        return (var, 1, frozenset({0}), int(shape[dim]))
    if prim in _PASSTHROUGH_PRIMS and prim != "rev":
        return _affine_of(eqn.invars[0], producers, depth + 1)
    if prim == "broadcast_in_dim":
        import numpy as np
        src = eqn.invars[0]
        if np.prod(getattr(src.aval, "shape", (0,)), dtype=int) == \
                np.prod(var.aval.shape, dtype=int):
            return _affine_of(src, producers, depth + 1)
        return None  # true broadcast duplicates values: never injective
    if prim in ("add", "sub"):
        a, b = eqn.invars
        ca = _const_scalar(a, producers)
        cb = _const_scalar(b, producers)
        if cb is not None:
            base = _affine_of(a, producers, depth + 1)
            if base is None:
                return None
            root, s, offs, n = base
            d = cb if prim == "add" else -cb
            return (root, s, frozenset(o + d for o in offs), n)
        if ca is not None and prim == "add":
            base = _affine_of(b, producers, depth + 1)
            if base is None:
                return None
            root, s, offs, n = base
            return (root, s, frozenset(o + ca for o in offs), n)
        if ca is not None and prim == "sub":  # c - x: negate the map
            base = _affine_of(b, producers, depth + 1)
            if base is None:
                return None
            root, s, offs, n = base
            return (root, -s, frozenset(ca - o for o in offs), n)
        return None
    if prim == "mul":
        a, b = eqn.invars
        for x, c in ((a, _const_scalar(b, producers)),
                     (b, _const_scalar(a, producers))):
            if c is not None and c != 0:
                base = _affine_of(x, producers, depth + 1)
                if base is None:
                    return None
                root, s, offs, n = base
                return (root, s * c, frozenset(o * c for o in offs), n)
        return None
    if prim == "select_n":
        infos = [_affine_of(v, producers, depth + 1)
                 for v in eqn.invars[1:]]
        if any(i is None for i in infos):
            return None
        roots = {i[0] for i in infos}
        scales = {i[1] for i in infos}
        if len(roots) != 1 or len(scales) != 1:
            return None
        root = infos[0][0]
        scale = infos[0][1]
        length = infos[0][3]
        offs = frozenset().union(*(i[2] for i in infos))
        return (root, scale, offs, length)
    return None


def _indices_provably_disjoint(idx_var, producers) -> bool:
    """True when every element of the scatter-index operand is provably
    distinct (so duplicate-index accumulation order cannot matter)."""
    import numpy as np
    val = getattr(idx_var, "val", None)  # Literal indices: check directly
    if val is not None:
        arr = np.asarray(val).reshape(-1, np.asarray(val).shape[-1]) \
            if np.ndim(val) > 1 else np.asarray(val).reshape(-1, 1)
        return len(np.unique(arr, axis=0)) == arr.shape[0]
    info = _affine_of(idx_var, producers)
    if info is None:
        return False
    _root, scale, offsets, length = info
    if scale == 0:
        return False
    offs = sorted(offsets)
    gap = abs(scale) * length
    # distinct branches of the map never collide when their offset
    # bands (width |scale|*length) don't overlap
    return all(b - a >= gap for a, b in zip(offs, offs[1:]))


class ScatterRaceRule(TracedRule):
    id = "REP802"
    name = "scatter-race"
    severity = "error"
    description = ("scatter accumulations whose indices can alias "
                   "across lanes need a deterministic merge (symbolic "
                   "disjointness check)")

    def check(self, targets: list[TraceTarget]) -> Iterator[Finding]:
        for t in targets:
            for jaxpr, eqn, _depth in iter_eqns(t.jaxpr()):
                prim = eqn.primitive.name
                if prim not in _SCATTER_PRIMS:
                    continue
                if eqn.params.get("unique_indices"):
                    continue  # caller asserts disjointness
                producers = {}
                for e in jaxpr.eqns:
                    for v in e.outvars:
                        producers[v] = e
                idx = eqn.invars[1]
                if _indices_provably_disjoint(idx, producers):
                    continue
                out = eqn.outvars[0].aval
                yield self.finding(
                    t, f"`{prim}` onto {out.dtype}{list(out.shape)} with "
                       f"alias-capable indices — accumulation order is "
                       f"unordered on atomic backends; prove "
                       f"lane-disjointness, pre-sort/segment the "
                       f"indices, or allowlist with the serialization "
                       f"argument")


class HostSyncRule(TracedRule):
    id = "REP803"
    name = "host-sync"
    severity = "error"
    description = ("no host callbacks / transfers inside the traced "
                   "round loop (one device->host sync per iteration)")

    def check(self, targets: list[TraceTarget]) -> Iterator[Finding]:
        for t in targets:
            seen: set[str] = set()
            for _jaxpr, eqn, depth in iter_eqns(t.jaxpr()):
                prim = eqn.primitive.name
                if prim in _CALLBACK_PRIMS and depth >= 1 and \
                        prim not in seen:
                    seen.add(prim)
                    yield self.finding(
                        t, f"`{prim}` executes inside the round loop "
                           f"(loop depth {depth}) — that is a host "
                           f"sync per iteration; hoist it out of the "
                           f"loop or accumulate on-device")


class EngineParityRule(TracedRule):
    id = "REP804"
    name = "engine-parity"
    severity = "error"
    description = ("targets in one parity group (jnp vs pallas) must "
                   "produce identical output avals")

    def check(self, targets: list[TraceTarget]) -> Iterator[Finding]:
        groups: dict[str, list[TraceTarget]] = {}
        for t in targets:
            if t.group:
                groups.setdefault(t.group, []).append(t)
        for name in sorted(groups):
            members = groups[name]
            if len(members) < 2:
                continue
            ref = members[0]
            ra = list(ref.jaxpr().out_avals)
            for other in members[1:]:
                oa = list(other.jaxpr().out_avals)
                if len(oa) != len(ra):
                    yield self.finding(
                        other, f"parity group `{name}`: {len(oa)} "
                               f"outputs vs {len(ra)} from "
                               f"{ref.name} — the engines' output "
                               f"contracts diverged")
                    continue
                for i, (a, b) in enumerate(zip(ra, oa)):
                    sig_a = (getattr(a, "shape", None), _dtype_str(a),
                             getattr(a, "weak_type", False))
                    sig_b = (getattr(b, "shape", None), _dtype_str(b),
                             getattr(b, "weak_type", False))
                    if sig_a != sig_b:
                        yield self.finding(
                            other, f"parity group `{name}`: output {i} "
                                   f"is {sig_b[1]}{list(sig_b[0] or ())} "
                                   f"(weak={sig_b[2]}) vs "
                                   f"{sig_a[1]}{list(sig_a[0] or ())} "
                                   f"(weak={sig_a[2]}) from {ref.name}")


class RecompileChurnRule(TracedRule):
    id = "REP805"
    name = "recompile-churn"
    severity = "error"
    description = ("dynamic call arguments (photon count, seed, id "
                   "offset) must not change the traced program — the "
                   "compile-cache key depends on it")

    def check(self, targets: list[TraceTarget]) -> Iterator[Finding]:
        for t in targets:
            base = jaxpr_fingerprint(t.jaxpr())
            for vname in sorted(t.variants):
                overrides = t.variants[vname]
                try:
                    varied = t.make(overrides)
                except Exception as e:
                    yield self.finding(
                        t, f"perturbing dynamic field `{vname}` "
                           f"({overrides}) failed to trace "
                           f"({type(e).__name__}: {e}) — the field is "
                           f"concretized at trace time and forces a "
                           f"retrace per value")
                    continue
                if jaxpr_fingerprint(varied) != base:
                    yield self.finding(
                        t, f"perturbing dynamic field `{vname}` "
                           f"({overrides}) changed the jaxpr — the "
                           f"value is baked into the trace, so every "
                           f"new value recompiles (churns the "
                           f"simulate_many compile cache)")


TRACED_RULES = (
    TracedDtypeRule,     # REP801 traced dtype discipline
    ScatterRaceRule,     # REP802 nondeterministic accumulation
    HostSyncRule,        # REP803 host sync in the round loop
    EngineParityRule,    # REP804 jnp-vs-pallas output parity
    RecompileChurnRule,  # REP805 recompile-key churn
)

__all__ = ["TRACED_RULES", "TracedDtypeRule", "ScatterRaceRule",
           "HostSyncRule", "EngineParityRule", "RecompileChurnRule"]
