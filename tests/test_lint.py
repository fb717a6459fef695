"""reprolint rule tests: per-rule positive/negative fixtures, pragma
suppression, baseline round-trip, and the live-repo-clean meta-test.

Fixture trees are written under tmp_path with the real repo layout
(src/repro/..., benchmarks/, tests/) and linted with ``rule_ids``
isolation so one rule's fixture never trips another rule.
"""

import json
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

try:  # hypothesis is optional locally (pinned in CI); only the property
    # test needs it — the deterministic mutation tests always run
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    _HAVE_HYPOTHESIS = False

from repro.lint import normalize_line, run_lint
from repro.lint.baseline import (baseline_path, load_baseline,
                                 save_baseline)

REPO = Path(__file__).resolve().parents[1]
SPEC = REPO / "src" / "repro" / "kernels" / "photon_step" / "spec.py"


def _write(root: Path, rel: str, text: str) -> Path:
    p = root / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(text))
    return p


def _lint(root, *rule_ids, baseline=None):
    return run_lint(root, rule_ids=rule_ids or None, baseline=baseline)


# ---------------------------------------------------------------- REP101

_OPS_SRC = """\
    import functools

    import jax


    @functools.partial(jax.jit, static_argnames=(
        "shape", "unitinmm", "cfg", "n_steps", "block_lanes",
        "interpret", "record", "jac_cols", "stats"))
    def _photon_steps_jit(labels_flat, media, state, shape, unitinmm,
                          cfg, n_steps, block_lanes, interpret,
                          ppath=None, det_geom=None, record=False,
                          jac_w=None, jac_col=None, jac_cols=0,
                          stats=False):
        return None


    def photon_steps(labels_flat, media, state, shape, unitinmm, cfg,
                     n_steps, block_lanes=256, interpret=None,
                     ppath=None, det_geom=None, record=False,
                     jac_w=None, jac_col=None, jac_cols=0, stats=False):
        return _photon_steps_jit(labels_flat, media, state, shape,
                                 unitinmm, cfg, n_steps, block_lanes,
                                 interpret)
    """

_PALLAS_SRC = """\
    def photon_step_pallas(labels_flat, media, state, shape, unitinmm,
                           cfg, n_steps, block_lanes=256,
                           interpret=False, ppath=None, det_geom=None,
                           record=False, jac_w=None, jac_col=None,
                           jac_cols=0, stats=False):
        n_det = 0 if det_geom is None else 1
        out_shapes = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13]
        if n_det:
            out_shapes += [1, 2, 3]
        if record:
            out_shapes += [1, 2]
        if jac_cols:
            out_shapes += [1]
        if stats:
            out_shapes += [1]
        return out_shapes
    """

_REF_SRC = """\
    def photon_steps_ref(labels_flat, media, state, shape, unitinmm,
                         cfg, n_steps, ppath=None, det_geom=None,
                         record=False, jac_w=None, jac_col=None,
                         jac_cols=0, stats=False):
        n_det = 0 if det_geom is None else 1
        init = (state, 1, 2, 3, 4)
        if n_det:
            init = init + (1, 2, 3)
        if record:
            init = init + (1, 2)
        if jac_cols:
            init = init + (1,)
        if stats:
            init = init + (1,)
        return init
    """

_SIM_SRC = """\
    def build_sim_fn(engine, n_det, record, collect):
        def run(outs):
            state, flu, exi, esc, timed = outs[:5]
            cur = 5
            if n_det:
                ppath, dw, dp = outs[cur:cur + 3]
                cur += 3
            if record:
                capd, capg = outs[cur:cur + 2]
                cur += 2
            if collect:
                st_block = outs[cur]
            return state
        return run
    """


def _mirror_tree(root: Path) -> None:
    (root / "src/repro/kernels/photon_step").mkdir(parents=True,
                                                   exist_ok=True)
    shutil.copy(SPEC, root / "src/repro/kernels/photon_step/spec.py")
    _write(root, "src/repro/kernels/photon_step/ops.py", _OPS_SRC)
    _write(root, "src/repro/kernels/photon_step/photon_step.py",
           _PALLAS_SRC)
    _write(root, "src/repro/kernels/photon_step/ref.py", _REF_SRC)
    _write(root, "src/repro/core/simulator.py", _SIM_SRC)


def test_mirror_clean_tree(tmp_path):
    _mirror_tree(tmp_path)
    rep = _lint(tmp_path, "REP101")
    assert rep.clean, [f.format() for f in rep.findings]


def test_mirror_catches_demirrored_ref(tmp_path):
    _mirror_tree(tmp_path)
    _write(tmp_path, "src/repro/kernels/photon_step/ref.py",
           _REF_SRC.replace("init = init + (1, 2, 3)",
                            "init = init + (1, 2)"))
    rep = _lint(tmp_path, "REP101")
    assert len(rep.findings) == 1
    msg = rep.findings[0].message
    assert "ref.py init appends" in msg and "n_det" in msg


def test_mirror_catches_base_arity_drift(tmp_path):
    _mirror_tree(tmp_path)
    _write(tmp_path, "src/repro/kernels/photon_step/photon_step.py",
           _PALLAS_SRC.replace(
               "out_shapes = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13]",
               "out_shapes = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]"))
    rep = _lint(tmp_path, "REP101")
    assert any("out_shapes` has 12 entries" in f.message
               for f in rep.findings)


def test_mirror_catches_reordered_simulator_groups(tmp_path):
    _mirror_tree(tmp_path)
    _write(tmp_path, "src/repro/core/simulator.py", """\
        def build_sim_fn(engine, n_det, record, collect):
            def run(outs):
                state, flu, exi, esc, timed = outs[:5]
                cur = 5
                if record:
                    capd, capg = outs[cur:cur + 2]
                    cur += 2
                if n_det:
                    ppath, dw, dp = outs[cur:cur + 3]
                return state
            return run
        """)
    rep = _lint(tmp_path, "REP101")
    assert any("out of order" in f.message for f in rep.findings)


def test_mirror_catches_missing_static_flag(tmp_path):
    _mirror_tree(tmp_path)
    _write(tmp_path, "src/repro/kernels/photon_step/ops.py",
           _OPS_SRC.replace('"record", "jac_cols", "stats"',
                            '"record", "jac_cols"'))
    rep = _lint(tmp_path, "REP101")
    assert any("static_argnames is missing" in f.message and
               "stats" in f.message for f in rep.findings)


def test_mirror_silent_without_spec(tmp_path):
    # rule-isolated fixture trees for other rules must not trip REP101
    _write(tmp_path, "src/repro/core/simulator.py", "X = 1\n")
    assert _lint(tmp_path, "REP101").clean


# ---------------------------------------------------------------- REP201

def test_determinism_flags_host_rng_in_traced_module(tmp_path):
    _write(tmp_path, "src/repro/core/simulator.py", """\
        import numpy as np


        def sample(n):
            return np.random.rand(n)
        """)
    rep = _lint(tmp_path, "REP201")
    assert len(rep.findings) == 1  # outermost chain only, no dup
    assert "numpy.random" in rep.findings[0].message


def test_determinism_flags_set_iteration(tmp_path):
    _write(tmp_path, "src/repro/core/simulator.py", """\
        def order():
            return [x for x in {3, 1, 2}]
        """)
    rep = _lint(tmp_path, "REP201")
    assert len(rep.findings) == 1
    assert "hash order" in rep.findings[0].message


def test_determinism_ignores_untraced_modules(tmp_path):
    # helpers is not imported (at module level) from any traced
    # entrypoint, so host RNG there is fine
    _write(tmp_path, "src/repro/core/simulator.py", """\
        def run():
            from repro import helpers  # lazy: stays off the trace path
            return helpers.jitter()
        """)
    _write(tmp_path, "src/repro/helpers.py", """\
        import random


        def jitter():
            return random.random()
        """)
    assert _lint(tmp_path, "REP201").clean


def test_determinism_follows_module_level_imports(tmp_path):
    _write(tmp_path, "src/repro/core/simulator.py",
           "from repro import helpers\n")
    _write(tmp_path, "src/repro/helpers.py", """\
        import random


        def jitter():
            return random.random()
        """)
    rep = _lint(tmp_path, "REP201")
    assert len(rep.findings) == 1
    assert rep.findings[0].path.endswith("helpers.py")


# ---------------------------------------------------------------- REP301

def test_dtype_flags_float64_and_bare_float(tmp_path):
    _write(tmp_path, "src/repro/core/util.py", """\
        import numpy as np


        def bad(y):
            a = np.asarray(y, np.float64)
            b = np.zeros(3, dtype=float)
            c = np.asarray(y, float)
            return a, b, c
        """)
    rep = _lint(tmp_path, "REP301")
    assert len(rep.findings) == 3


def test_dtype_accepts_float32(tmp_path):
    _write(tmp_path, "src/repro/core/util.py", """\
        import numpy as np


        def good(y):
            return np.asarray(y, np.float32)
        """)
    assert _lint(tmp_path, "REP301").clean


def test_pragma_suppresses_finding(tmp_path):
    _write(tmp_path, "src/repro/core/util.py", """\
        import numpy as np


        def ok(y):
            return np.asarray(y, np.float64)  # reprolint: disable=REP301 - host-side test
        """)
    rep = _lint(tmp_path, "REP301")
    assert rep.clean
    assert rep.suppressed_pragma == 1


def test_pragma_disable_all(tmp_path):
    _write(tmp_path, "src/repro/core/util.py", """\
        import numpy as np


        def ok(y):
            return np.asarray(y, np.float64)  # reprolint: disable=all
        """)
    assert _lint(tmp_path, "REP301").clean


# ---------------------------------------------------------------- REP401

def test_jit_flags_host_sync_in_lax_body(tmp_path):
    _write(tmp_path, "src/repro/core/loop.py", """\
        import jax


        def step(c):
            return float(c) + 1


        def run(x):
            return jax.lax.while_loop(lambda c: c < 3, step, x)
        """)
    rep = _lint(tmp_path, "REP401")
    assert len(rep.findings) == 1
    assert "float" in rep.findings[0].message


def test_jit_flags_item_in_traced_body(tmp_path):
    _write(tmp_path, "src/repro/core/loop.py", """\
        import jax


        def body(i, c):
            return c + c.item()


        def run(x):
            return jax.lax.fori_loop(0, 3, body, x)
        """)
    rep = _lint(tmp_path, "REP401")
    assert any(".item()" in f.message for f in rep.findings)


def test_jit_ignores_host_calls_outside_traced_bodies(tmp_path):
    _write(tmp_path, "src/repro/core/loop.py", """\
        def host(x):
            return float(x)
        """)
    assert _lint(tmp_path, "REP401").clean


def test_jit_flags_bogus_static_argname(tmp_path):
    _write(tmp_path, "src/repro/core/wrap.py", """\
        import functools

        import jax


        @functools.partial(jax.jit, static_argnames=("n", "nope"))
        def f(x, n):
            return x
        """)
    rep = _lint(tmp_path, "REP401")
    assert len(rep.findings) == 1
    assert "`nope`" in rep.findings[0].message


def test_jit_accepts_valid_static_argnames(tmp_path):
    _write(tmp_path, "src/repro/core/wrap.py", """\
        import functools

        import jax


        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            return x
        """)
    assert _lint(tmp_path, "REP401").clean


# ---------------------------------------------------------------- REP501

_VMEM_CALL = """\
    from repro.core.volume import SimConfig
    from repro.kernels.photon_step.photon_step import photon_step_pallas


    def run(labels, media, state):
        shape = {shape}
        cfg = SimConfig(n_time_gates={ntg})
        return photon_step_pallas(labels, media, state, shape, 1.0,
                                  cfg, 10, block_lanes=256,
                                  interpret={interpret})
    """


def test_vmem_rejects_over_budget_config(tmp_path):
    # 60^3 x 32 gates: the gate-major fluence block alone (~27 MB)
    # blows the 16 MiB core budget — exactly the config the runtime's
    # spec.check_vmem refuses
    _write(tmp_path, "src/repro/core/driver.py", _VMEM_CALL.format(
        shape="(60, 60, 60)", ntg=32, interpret=False))
    rep = _lint(tmp_path, "REP501")
    assert len(rep.findings) == 1
    assert "VMEM budget" in rep.findings[0].message


def test_vmem_skips_interpret_mode(tmp_path):
    # the interpreter has no VMEM: the CPU benches legitimately sweep
    # this exact config
    _write(tmp_path, "src/repro/core/driver.py", _VMEM_CALL.format(
        shape="(60, 60, 60)", ntg=32, interpret=True))
    assert _lint(tmp_path, "REP501").clean


def test_vmem_accepts_in_budget_config(tmp_path):
    _write(tmp_path, "src/repro/core/driver.py", _VMEM_CALL.format(
        shape="(32, 32, 32)", ntg=4, interpret=False))
    assert _lint(tmp_path, "REP501").clean


def test_vmem_chases_local_alias(tmp_path):
    # regression: grid/cfg args flowing through a simple local alias
    # (cfg2 = cfg) used to defeat resolution entirely
    _write(tmp_path, "src/repro/core/driver.py", """\
        from repro.core.volume import SimConfig
        from repro.kernels.photon_step.photon_step import photon_step_pallas


        def run(labels, media, state):
            shape = (60, 60, 60)
            shp = shape
            cfg = SimConfig(n_time_gates=32)
            cfg2 = cfg
            return photon_step_pallas(labels, media, state, shp, 1.0,
                                      cfg2, 10, block_lanes=256,
                                      interpret=False)
        """)
    rep = _lint(tmp_path, "REP501")
    assert len(rep.findings) == 1
    assert "VMEM budget" in rep.findings[0].message


def test_vmem_resolves_module_level_constants(tmp_path):
    # regression: SHAPE/NTG living at module scope were invisible to
    # the function-local literal env
    _write(tmp_path, "src/repro/core/driver.py", """\
        from repro.core.volume import SimConfig
        from repro.kernels.photon_step.photon_step import photon_step_pallas

        SHAPE = (60, 60, 60)
        NTG = 32


        def run(labels, media, state):
            cfg = SimConfig(n_time_gates=NTG)
            return photon_step_pallas(labels, media, state, SHAPE, 1.0,
                                      cfg, 10, block_lanes=256,
                                      interpret=False)
        """)
    rep = _lint(tmp_path, "REP501")
    assert len(rep.findings) == 1
    assert "VMEM budget" in rep.findings[0].message


def test_vmem_alias_of_in_budget_config_stays_clean(tmp_path):
    _write(tmp_path, "src/repro/core/driver.py", """\
        from repro.core.volume import SimConfig
        from repro.kernels.photon_step.photon_step import photon_step_pallas

        SHAPE = (32, 32, 32)


        def run(labels, media, state):
            cfg = SimConfig(n_time_gates=4)
            cfg2 = cfg
            return photon_step_pallas(labels, media, state, SHAPE, 1.0,
                                      cfg2, 10, block_lanes=256,
                                      interpret=False)
        """)
    assert _lint(tmp_path, "REP501").clean


def test_vmem_drops_ambiguously_rebound_alias(tmp_path):
    # a name rebound twice is ambiguous at the call site: the rule
    # must skip (runtime check covers it), never guess
    _write(tmp_path, "src/repro/core/driver.py", """\
        from repro.core.volume import SimConfig
        from repro.kernels.photon_step.photon_step import photon_step_pallas


        def run(labels, media, state, flag):
            shape = (60, 60, 60)
            if flag:
                shape = (8, 8, 8)
            cfg = SimConfig(n_time_gates=32)
            return photon_step_pallas(labels, media, state, shape, 1.0,
                                      cfg, 10, block_lanes=256,
                                      interpret=False)
        """)
    assert _lint(tmp_path, "REP501").clean


def test_vmem_skips_unresolvable_shape(tmp_path):
    _write(tmp_path, "src/repro/core/driver.py", """\
        from repro.kernels.photon_step.ops import photon_steps


        def run(labels, media, state, shape, cfg):
            return photon_steps(labels, media, state, shape, 1.0, cfg,
                                10)
        """)
    assert _lint(tmp_path, "REP501").clean


def test_vmem_threshold_matches_runtime():
    """The lint threshold IS the runtime threshold: same function."""
    from repro.kernels.photon_step import spec
    try:
        spec.check_vmem(60 * 60 * 60, 60 * 60, ntg=32, block_lanes=256)
    except ValueError as e:
        assert "MiB" in str(e)
    else:
        raise AssertionError("60^3 x 32 gates must exceed the budget")
    # and the boundary the benches document as safe stays accepted
    spec.check_vmem(32 * 32 * 32, 32 * 32, ntg=4, block_lanes=256)


# ---------------------------------------------------------------- REP601

def test_reach_flags_orphan_module(tmp_path):
    _write(tmp_path, "src/repro/launch/run.py",
           "from repro.core import engine\n")
    _write(tmp_path, "src/repro/core/engine.py", "X = 1\n")
    _write(tmp_path, "src/repro/orphan.py", "Y = 2\n")
    rep = _lint(tmp_path, "REP601")
    assert len(rep.findings) == 1
    assert "`repro.orphan`" in rep.findings[0].message


def test_reach_counts_test_imports_as_roots(tmp_path):
    _write(tmp_path, "src/repro/launch/run.py", "X = 1\n")
    _write(tmp_path, "src/repro/oracle.py", "Y = 2\n")
    _write(tmp_path, "tests/test_oracle.py",
           "from repro import oracle\n")
    assert _lint(tmp_path, "REP601").clean


def test_reach_follows_lazy_imports(tmp_path):
    # reachability (unlike the traced closure) follows function-level
    # imports: lazy importing is the repo's idiom, not a sign of death
    _write(tmp_path, "src/repro/launch/run.py", """\
        def main():
            from repro import heavy
            return heavy.go()
        """)
    _write(tmp_path, "src/repro/heavy.py", "def go(): return 1\n")
    assert _lint(tmp_path, "REP601").clean


# ---------------------------------------------------------------- REP701

_BENCH_WRITER = """\
    import json

    {extra_import}

    def run():
        out = {{"meta": {meta}, "result": 1}}
        with open("BENCH_figx.json", "w") as f:
            json.dump(out, f)
    """


def test_bench_flags_missing_schema_stamp(tmp_path):
    _write(tmp_path, "benchmarks/figx.py", _BENCH_WRITER.format(
        extra_import="", meta="{}"))
    rep = _lint(tmp_path, "REP701")
    assert len(rep.findings) == 1
    assert "never stamps" in rep.findings[0].message


def test_bench_flags_hardcoded_schema_version(tmp_path):
    _write(tmp_path, "benchmarks/figx.py", _BENCH_WRITER.format(
        extra_import="", meta='{"schema_version": 3}'))
    rep = _lint(tmp_path, "REP701")
    assert len(rep.findings) == 1
    assert "hardcoded" in rep.findings[0].message


def test_bench_accepts_shared_constant(tmp_path):
    _write(tmp_path, "benchmarks/figx.py", _BENCH_WRITER.format(
        extra_import="from benchmarks.common import SCHEMA_VERSION",
        meta='{"schema_version": SCHEMA_VERSION}'))
    assert _lint(tmp_path, "REP701").clean


def test_bench_ignores_non_writers(tmp_path):
    _write(tmp_path, "benchmarks/plot.py", """\
        def load(path):
            return open(path).read()  # reads BENCH_ files, writes none
        """)
    assert _lint(tmp_path, "REP701").clean


# ------------------------------------------------------------ baseline

def test_baseline_round_trip(tmp_path):
    _write(tmp_path, "src/repro/core/util.py", """\
        import numpy as np


        def bad(y):
            return np.asarray(y, np.float64)
        """)
    rep = _lint(tmp_path, "REP301")
    assert len(rep.findings) == 1

    bp = baseline_path(tmp_path)
    save_baseline(bp, rep)
    data = json.loads(bp.read_text())
    assert data["version"] == 1 and len(data["findings"]) == 1

    rep2 = _lint(tmp_path, "REP301", baseline=load_baseline(bp))
    assert rep2.clean
    assert rep2.suppressed_baseline == 1

    # a *new* finding on top of the grandfathered one still fails
    _write(tmp_path, "src/repro/core/util.py", """\
        import numpy as np


        def bad(y):
            return np.asarray(y, np.float64)


        def worse(y):
            return np.zeros(3, dtype=float)
        """)
    rep3 = _lint(tmp_path, "REP301", baseline=load_baseline(bp))
    assert len(rep3.findings) == 1
    assert rep3.suppressed_baseline == 1


def test_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == {}


# ------------------------------------- fingerprint stability (baseline)

_FPRINT_TEMPLATE = """\
import numpy as np


def bad(y):
    {indent}a{s1}={s2}np.asarray(y,{s3}np.float64){comment}
    return a
"""


def _fingerprint_of(tmp_path, body: str) -> str:
    _write(tmp_path, "src/repro/core/util.py", body)
    rep = _lint(tmp_path, "REP301")
    assert len(rep.findings) == 1, body
    return rep.findings[0].fingerprint


if _HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(
        s1=st.text(alphabet=" ", max_size=3),
        s2=st.text(alphabet=" ", max_size=3),
        s3=st.text(alphabet=" ", max_size=3),
        comment=st.one_of(
            st.just(""),
            st.builds(lambda t: "  # " + t,
                      st.text(alphabet="abcdefghij xyz", max_size=20))),
    )
    def test_fingerprint_survives_whitespace_and_comment_edits(
            tmp_path_factory, s1, s2, s3, comment):
        """Whitespace/comment-only edits must not invalidate committed
        .reprolint.json fingerprints (the baseline would silently stop
        matching)."""
        canonical = _FPRINT_TEMPLATE.format(indent="", s1=" ", s2=" ",
                                            s3=" ", comment="")
        mutated = _FPRINT_TEMPLATE.format(indent="", s1=s1, s2=s2,
                                          s3=s3, comment=comment)
        tmp = tmp_path_factory.mktemp("fp")
        ref = _fingerprint_of(tmp, canonical)
        assert _fingerprint_of(tmp, mutated) == ref


def test_fingerprint_survives_canonical_mutations(tmp_path_factory):
    # deterministic subset of the property above: always runs, even
    # without hypothesis installed
    canonical = _FPRINT_TEMPLATE.format(indent="", s1=" ", s2=" ",
                                        s3=" ", comment="")
    ref = _fingerprint_of(tmp_path_factory.mktemp("fp"), canonical)
    for s1, s2, s3, comment in [
            ("", "", "", ""),
            ("   ", "  ", " ", ""),
            (" ", " ", " ", "  # host-side conversion"),
            ("", " ", "", "  # xyz"),
    ]:
        mutated = _FPRINT_TEMPLATE.format(indent="", s1=s1, s2=s2,
                                          s3=s3, comment=comment)
        assert _fingerprint_of(tmp_path_factory.mktemp("fp"),
                               mutated) == ref


def test_fingerprint_changes_on_content_edit(tmp_path):
    canonical = _FPRINT_TEMPLATE.format(indent="", s1=" ", s2=" ",
                                        s3=" ", comment="")
    edited = canonical.replace("np.float64", "np.float64.type")
    assert _fingerprint_of(tmp_path, canonical) != \
        _fingerprint_of(tmp_path, edited)


def test_normalize_line_is_quote_aware():
    # '#' inside a string literal is content, not a comment
    assert normalize_line('x = "a#b"  # note') == 'x="a#b"'
    assert normalize_line("y  =  1   # c") == "y=1"


# ------------------------------------------------------ live-repo meta

def test_live_repo_is_lint_clean():
    """The committed tree must stay clean modulo the committed
    baseline — the same gate CI runs."""
    rep = run_lint(REPO, baseline=load_baseline(baseline_path(REPO)))
    assert rep.clean, "\n".join(f.format() for f in rep.findings)
    assert rep.n_modules > 30  # sanity: the real tree was discovered
    assert set(rep.rules_run) >= {"REP101", "REP201", "REP301",
                                  "REP401", "REP501", "REP601",
                                  "REP701"}


def test_cli_github_format_emits_annotations(tmp_path, capsys):
    from repro.lint.__main__ import main
    _write(tmp_path, "src/repro/core/util.py", """\
        import numpy as np


        def bad(y):
            return np.asarray(y, np.float64)
        """)
    rc = main(["--root", str(tmp_path), "--format", "github",
               "--rules", "REP301"])
    out = capsys.readouterr().out
    assert rc == 1
    line = next(ln for ln in out.splitlines() if ln.startswith("::error"))
    assert "file=src/repro/core/util.py" in line
    assert "line=5" in line and "title=REP301[dtype]" in line
    assert "::" in line.rpartition("title=")[2]  # message after the ::


def test_cli_github_format_clean_tree(tmp_path, capsys):
    from repro.lint.__main__ import main
    _write(tmp_path, "src/repro/core/util.py", "X = 1\n")
    rc = main(["--root", str(tmp_path), "--format", "github",
               "--rules", "REP301"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "::error" not in out and "clean" in out


def test_cli_json_output():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.lint", "--format", "json"],
        cwd=REPO, capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert data["clean"] is True
