"""tracelint (REP8xx) tests: per-rule positive/negative jaxpr fixtures,
allowlist semantics, traced-baseline round-trip, and the live-tree
meta-test (the same gate the CI lint-traced job runs).

Fixture targets trace tiny throwaway jnp/pallas functions so each rule
is exercised in milliseconds; the live meta-test traces the real
entrypoint registry and is marked slow.
"""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.lint.baseline import load_baseline, save_baseline
from repro.lint.traced import (TraceTarget, allowlist_path, iter_eqns,
                               jaxpr_fingerprint, load_allowlist,
                               run_traced_lint, traced_baseline_path)

REPO = Path(__file__).resolve().parents[1]
ENTRY = "src/repro/fixture.py"


def _target(fn, *args, name="fx", group=None, variants=None, make=None):
    mk = make if make is not None else \
        (lambda ov, f=fn, a=args: jax.make_jaxpr(f)(*a))
    return TraceTarget(name=name, entry=ENTRY, make=mk, group=group,
                       variants=dict(variants or {}))


def _run(targets, *rule_ids, **kw):
    return run_traced_lint(REPO, targets=targets,
                           rule_ids=rule_ids or None, **kw)


# ---------------------------------------------------------------- REP801

def test_dtype_flags_f64_in_trace():
    def make(ov):
        with jax.enable_x64(True):
            return jax.make_jaxpr(
                lambda x: x.astype(jnp.float64).sum())(
                    jnp.ones(4, jnp.float32))
    rep = _run([_target(None, make=make)], "REP801")
    assert any("wide dtype float64" in f.message for f in rep.findings)


def test_dtype_flags_weak_float_output():
    # a bare Python scalar returned from the entrypoint: its dtype is
    # decided by whoever consumes it (weak f32 here, f64 under x64)
    t = _target(lambda x: (x.sum(), jnp.asarray(2.0)),
                jnp.ones(3, jnp.float32))
    rep = _run([t], "REP801")
    assert any("weak-typed" in f.message and "output 1" in f.message
               for f in rep.findings)


def test_dtype_flags_weak_float_eqn():
    t = _target(lambda: jnp.sin(2.0))
    rep = _run([t], "REP801")
    assert any("weak-typed float32" in f.message for f in rep.findings)


def test_dtype_quiet_on_f32_loop():
    # fori_loop lowers its bounds as weak int32 — jax-internal loop
    # counters must NOT be flagged
    def clean(x):
        return jax.lax.fori_loop(0, 3, lambda i, c: c + x, x)
    rep = _run([_target(clean, jnp.float32(0.0))], "REP801")
    assert rep.clean, [f.message for f in rep.findings]


# ---------------------------------------------------------------- REP802

def test_scatter_flags_alias_capable_indices():
    # indices arrive as a traced argument: nothing constrains them to
    # be lane-disjoint
    t = _target(lambda x, idx: x.at[idx].add(1.0),
                jnp.zeros(8, jnp.float32), jnp.zeros(4, jnp.int32))
    rep = _run([t], "REP802")
    assert len(rep.findings) == 1
    assert "alias-capable indices" in rep.findings[0].message


def test_scatter_flags_aliased_pallas_kernel():
    # a deliberately aliased in-kernel scatter: every lane hits the
    # same accumulator slots the traced indices choose
    from jax.experimental import pallas as pl

    def kernel(idx_ref, o_ref):
        o_ref[...] = jnp.zeros_like(o_ref[...]).at[idx_ref[...]].add(1.0)

    def racy(idx):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((8,), jnp.float32),
            interpret=True)(idx)

    rep = _run([_target(racy, jnp.zeros(4, jnp.int32))], "REP802")
    assert rep.findings, "in-kernel pallas scatter must be analyzed"
    assert all("alias-capable" in f.message for f in rep.findings)


def test_scatter_accepts_provably_disjoint_arange():
    # .at[arange].add — lane-disjoint by construction; the prover must
    # see through jax's negative-index wrap (iota -> select_n -> ...)
    t = _target(lambda x, v: x.at[jnp.arange(4)].add(v),
                jnp.zeros(8, jnp.float32), jnp.ones(4, jnp.float32))
    rep = _run([t], "REP802")
    assert rep.clean, [f.message for f in rep.findings]


def test_scatter_accepts_unique_indices_assertion():
    t = _target(lambda x, idx, v: x.at[idx].add(v, unique_indices=True),
                jnp.zeros(8, jnp.float32), jnp.zeros(4, jnp.int32),
                jnp.ones(4, jnp.float32))
    rep = _run([t], "REP802")
    assert rep.clean


# ---------------------------------------------------------------- REP803

def test_hostsync_flags_callback_in_loop():
    def loopy(x):
        def body(i, c):
            jax.debug.print("i = {i}", i=i)
            return c + 1.0
        return jax.lax.fori_loop(0, 3, body, x)
    rep = _run([_target(loopy, jnp.float32(0.0))], "REP803")
    assert len(rep.findings) == 1
    assert "inside the round loop" in rep.findings[0].message


def test_hostsync_accepts_callback_outside_loop():
    def flat(x):
        jax.debug.print("x = {x}", x=x)
        return x + 1.0
    rep = _run([_target(flat, jnp.float32(0.0))], "REP803")
    assert rep.clean


# ---------------------------------------------------------------- REP804

def test_parity_flags_dtype_mismatch():
    a = _target(lambda x: (x, x.sum()), jnp.ones(4, jnp.float32),
                name="eng-a", group="g")
    b = _target(lambda x: (x, x.sum().astype(jnp.int32)),
                jnp.ones(4, jnp.float32), name="eng-b", group="g")
    rep = _run([a, b], "REP804")
    assert len(rep.findings) == 1
    f = rep.findings[0]
    assert "parity group `g`" in f.message and "output 1" in f.message
    assert "[eng-b]" in f.message  # anchored to the diverging member


def test_parity_flags_output_count_drift():
    a = _target(lambda x: (x, x.sum()), jnp.ones(4, jnp.float32),
                name="eng-a", group="g")
    b = _target(lambda x: (x,), jnp.ones(4, jnp.float32),
                name="eng-b", group="g")
    rep = _run([a, b], "REP804")
    assert any("1 outputs vs 2" in f.message for f in rep.findings)


def test_parity_quiet_on_matching_groups():
    a = _target(lambda x: (x, x.sum()), jnp.ones(4, jnp.float32),
                name="eng-a", group="g")
    b = _target(lambda x: (x * 2.0, x.max()), jnp.ones(4, jnp.float32),
                name="eng-b", group="g")
    ungrouped = _target(lambda x: x.astype(jnp.int32),
                        jnp.ones(4, jnp.float32), name="other")
    rep = _run([a, b, ungrouped], "REP804")
    assert rep.clean


# ---------------------------------------------------------------- REP805

def test_churn_flags_value_baked_into_trace():
    # the fixture bakes a config field (w_threshold analogue) into the
    # traced program as a literal: every new value forces a retrace
    def make(ov):
        thresh = (ov or {}).get("w_threshold", 1e-4)
        return jax.make_jaxpr(lambda x: x * float(thresh))(
            jnp.ones(3, jnp.float32))
    t = _target(None, make=make,
                variants={"w_threshold": {"w_threshold": 1e-3}})
    rep = _run([t], "REP805")
    assert len(rep.findings) == 1
    assert "changed the jaxpr" in rep.findings[0].message
    assert "w_threshold" in rep.findings[0].message


def test_churn_flags_variant_trace_failure():
    def make(ov):
        n = (ov or {}).get("n", 4)
        if n > 10:
            raise ValueError("n indexes a static table of size 10")
        return jax.make_jaxpr(lambda x: x + 1.0)(jnp.ones(3, jnp.float32))
    t = _target(None, make=make, variants={"n": {"n": 100}})
    rep = _run([t], "REP805")
    assert len(rep.findings) == 1
    assert "failed to trace" in rep.findings[0].message


def test_churn_quiet_when_values_stay_traced():
    def make(ov):
        seed = (ov or {}).get("seed", 1)
        return jax.make_jaxpr(
            lambda x, s: x * s.astype(jnp.float32))(
                jnp.ones(3, jnp.float32), jnp.uint32(seed))
    t = _target(None, make=make, variants={"seed": {"seed": 99}})
    rep = _run([t], "REP805")
    assert rep.clean


def test_jaxpr_fingerprint_tracks_weak_type():
    strong = jax.make_jaxpr(lambda x: x)(jnp.float32(1.0))
    weak = jax.make_jaxpr(lambda x: x)(1.0)
    assert jaxpr_fingerprint(strong) != jaxpr_fingerprint(weak)


# ------------------------------------------------------------ engine

def test_trace_failure_becomes_rep800_finding():
    def boom(ov):
        raise RuntimeError("no such entrypoint")
    bad = _target(None, make=boom, name="broken")
    good = _target(lambda x: x + 1.0, jnp.ones(3, jnp.float32))
    rep = _run([bad, good])
    assert any(f.rule == "REP800" and "broken" in f.message
               for f in rep.findings)
    # the healthy target was still traced and linted
    assert rep.n_modules == 2


def test_iter_eqns_reaches_nested_loop_bodies():
    def nested(x):
        def outer(i, c):
            return jax.lax.fori_loop(0, 2, lambda j, d: d + 1.0, c)
        return jax.lax.fori_loop(0, 3, outer, x)
    closed = jax.make_jaxpr(nested)(jnp.float32(0.0))
    depths = [d for _, _, d in iter_eqns(closed)]
    assert max(depths) >= 2  # inner loop body sits two loops deep


# ---------------------------------------------------------- allowlist

def _racy_target():
    return _target(lambda x, idx: x.at[idx].add(1.0),
                   jnp.zeros(8, jnp.float32), jnp.zeros(4, jnp.int32))


def test_allowlist_suppresses_with_why():
    allow = [{"rule": "REP802", "target": "fx",
              "match": "alias-capable", "why": "fixture"}]
    rep = _run([_racy_target()], "REP802", allowlist=allow)
    assert rep.clean
    assert rep.suppressed_pragma == 1


def test_allowlist_max_caps_absorption():
    def two_scatters(x, idx):
        return x.at[idx].add(1.0), x.at[idx].add(2.0)
    t = _target(two_scatters, jnp.zeros(8, jnp.float32),
                jnp.zeros(4, jnp.int32))
    allow = [{"rule": "REP802", "target": "fx", "max": 1,
              "why": "only one grandfathered scatter"}]
    rep = _run([t], "REP802", allowlist=allow)
    assert len(rep.findings) == 1  # the second scatter still surfaces
    assert rep.suppressed_pragma == 1


def test_allowlist_requires_why(tmp_path):
    p = tmp_path / "allow.json"
    p.write_text(json.dumps(
        {"version": 1, "allow": [{"rule": "REP802", "why": "  "}]}))
    with pytest.raises(ValueError, match="why"):
        load_allowlist(p)


def test_allowlist_rejects_bad_version(tmp_path):
    p = tmp_path / "allow.json"
    p.write_text(json.dumps({"version": 99, "allow": []}))
    with pytest.raises(ValueError, match="version"):
        load_allowlist(p)


def test_allowlist_missing_file_is_empty(tmp_path):
    assert load_allowlist(tmp_path / "nope.json") == []


# ------------------------------------------------------------ baseline

def test_traced_baseline_round_trip(tmp_path):
    rep = _run([_racy_target()], "REP802")
    assert len(rep.findings) == 1
    bp = tmp_path / ".tracelint.json"
    save_baseline(bp, rep)
    rep2 = _run([_racy_target()], "REP802", baseline=load_baseline(bp))
    assert rep2.clean
    assert rep2.suppressed_baseline == 1


# ------------------------------------------------------ live-tree meta

@pytest.mark.slow
def test_live_tree_is_tracelint_clean():
    """The committed tree must stay tracelint-clean modulo the
    committed allowlist, with an EMPTY traced baseline — the gate the
    CI lint-traced job runs."""
    baseline = load_baseline(traced_baseline_path(REPO))
    assert baseline == {}, "policy: the traced baseline stays empty"
    allow = load_allowlist(allowlist_path(REPO))
    assert allow, "the live tree's scatter allowlist must be committed"
    rep = run_traced_lint(REPO, baseline=baseline, allowlist=allow)
    assert rep.clean, "\n".join(f.format() for f in rep.findings)
    assert rep.n_modules >= 6  # both engines x sim/replay/pool at least
    assert set(rep.rules_run) == {"REP801", "REP802", "REP803",
                                  "REP804", "REP805"}
    # the allowlist absorbed the documented scatter accumulators and
    # nothing else was needed
    assert rep.suppressed_pragma > 0
    assert rep.suppressed_baseline == 0


@pytest.mark.slow
def test_cli_tier_traced_json():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.lint", "--tier", "traced",
         "--format", "json"],
        cwd=REPO, capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert data["clean"] is True
    assert data["tier"] == "traced"
    assert set(data["rules"]) == {"REP801", "REP802", "REP803",
                                  "REP804", "REP805"}


def test_cli_list_rules_all_tiers():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.lint", "--tier", "all",
         "--list-rules"],
        cwd=REPO, capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "REP101" in proc.stdout and "REP805" in proc.stdout
