"""The jit-discipline analyzer, tested from both sides.

Positive side: every AST rule (JD001-JD005) fires on a minimal seeded
violation with the right rule id AND line number; the jaxpr audit
(JX101-JX103) fires on seeded-bad programs (an F=1 vs F=2 flatness
mismatch, a weak-typed output, a ``jax.debug.print`` in the loop).

Negative side: the current tree is clean — the self-scan pins every
satellite fix (CRN markers, shared excludes, gated jax import) and the
flatness audit independently reproduces the F-invariance contract of
``tests/test_compile_flatness.py`` through the shared walker. The CLI
round-trips its ``--json`` report and exits 0/1 by findings.
"""
import json
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import analysis
from repro.analysis import astlint, check as check_cli, jaxpr_audit
from repro.analysis.config import AnalysisConfig, load_config
from repro.analysis.findings import Finding, from_json_dict, load_json

REPO_ROOT = analysis.find_repo_root()


# --------------------------------------------------------------------------
# Fixture scaffolding: a throwaway repo tree with one bad file
# --------------------------------------------------------------------------

def _mini_repo(tmp_path, rel, source):
    """A minimal scannable tree: pyproject + one file at ``rel``."""
    (tmp_path / "pyproject.toml").write_text(
        "[tool.repro.analysis]\nexclude = []\n")
    target = tmp_path / rel
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(textwrap.dedent(source))
    return AnalysisConfig(root=str(tmp_path), exclude=())


def _rules_at(findings, rule):
    return [(f.path, f.line) for f in findings if f.rule == rule]


# --------------------------------------------------------------------------
# JD001 registry-frozen
# --------------------------------------------------------------------------

def test_jd001_unfrozen_registered_class(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", """\
        import dataclasses

        def register(name, item):
            pass

        @dataclasses.dataclass
        class MutablePolicy:
            alpha: float = 1.0

        register("mutable", MutablePolicy())
        """)
    findings = astlint.RegistryFrozenCheck().run(cfg)
    assert _rules_at(findings, "JD001") == [("src/repro/core/bad.py", 7)]


def test_jd001_unhashable_field(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", """\
        import dataclasses
        from typing import List

        def register(name, item):
            pass

        @dataclasses.dataclass(frozen=True)
        class ListPolicy:
            weights: List[float] = None

        register("listy", ListPolicy())
        """)
    findings = astlint.RegistryFrozenCheck().run(cfg)
    assert _rules_at(findings, "JD001") == [("src/repro/core/bad.py", 9)]
    assert "unhashable" in findings[0].message


def test_jd001_loop_registration_idiom_resolved(tmp_path):
    """The repo's ``for _n, _x in [...]: register(_n, _x)`` idiom and
    nested component constructors are both traced to their classes."""
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", """\
        import dataclasses

        def register(name, item):
            pass

        @dataclasses.dataclass(frozen=True)
        class Outer:
            inner: object = None

        @dataclasses.dataclass
        class Inner:
            x: float = 0.0

        for _n, _x in [("outer", Outer(Inner()))]:
            register(_n, _x)
        """)
    findings = astlint.RegistryFrozenCheck().run(cfg)
    assert _rules_at(findings, "JD001") == [("src/repro/core/bad.py", 11)]


# --------------------------------------------------------------------------
# JD002 crn-discipline
# --------------------------------------------------------------------------

_JD002_SRC = """\
    import jax

    def make_noise():
        key = jax.random.PRNGKey(0)
        return jax.random.uniform(key, ())
    """


def test_jd002_stray_prngkey(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", _JD002_SRC)
    findings = astlint.CrnDisciplineCheck().run(cfg)
    assert _rules_at(findings, "JD002") == [("src/repro/core/bad.py", 4)]


def test_jd002_marker_suppresses(tmp_path):
    src = _JD002_SRC.replace(
        "key = jax.random.PRNGKey(0)",
        "key = jax.random.PRNGKey(0)  "
        "# repro: allow-prng[test fixture reason]")
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", src)
    assert astlint.CrnDisciplineCheck().run(cfg) == []


def test_jd002_marker_without_reason_is_a_finding(tmp_path):
    src = _JD002_SRC.replace(
        "key = jax.random.PRNGKey(0)",
        "key = jax.random.PRNGKey(0)  # repro: allow-prng")
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", src)
    findings = astlint.CrnDisciplineCheck().run(cfg)
    assert len(findings) == 1
    assert "without a [reason]" in findings[0].message


# --------------------------------------------------------------------------
# JD003 host-effects
# --------------------------------------------------------------------------

def test_jd003_host_call_in_stage(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", """\
        import time

        def _stage_admit(st, trace):
            t0 = time.perf_counter()
            return st, t0
        """)
    findings = astlint.HostEffectsCheck().run(cfg)
    assert _rules_at(findings, "JD003") == [("src/repro/core/bad.py", 4)]


def test_jd003_host_call_outside_jit_body_ok(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/ok.py", """\
        import time

        def benchmark_harness(st):
            return time.perf_counter()
        """)
    assert astlint.HostEffectsCheck().run(cfg) == []


def test_jd003_jit_body_marker_opts_in(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", """\
        import time

        # repro: jit-body
        def helper_called_from_stage(st):
            return time.perf_counter()
        """)
    findings = astlint.HostEffectsCheck().run(cfg)
    assert _rules_at(findings, "JD003") == [("src/repro/core/bad.py", 5)]


# --------------------------------------------------------------------------
# JD004 traced-branch
# --------------------------------------------------------------------------

def test_jd004_python_if_on_traced_value(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", """\
        import jax.numpy as jnp

        def _stage_map(st, trace):
            load = jnp.sum(st.queue)
            if load > 3:
                st = st._replace(now=st.now + 1)
            return st
        """)
    findings = astlint.TracedBranchCheck().run(cfg)
    assert _rules_at(findings, "JD004") == [("src/repro/core/bad.py", 5)]


def test_jd004_bool_coercion(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/bad.py", """\
        def _stage_start(st):
            flag = bool(st.halted)
            return flag
        """)
    findings = astlint.TracedBranchCheck().run(cfg)
    assert _rules_at(findings, "JD004") == [("src/repro/core/bad.py", 2)]


def test_jd004_static_branches_stay_legal(tmp_path):
    """Config ifs (static closure args, shape tests, `is None`) are the
    engine's idiom and must not be flagged."""
    cfg = _mini_repo(tmp_path, "src/repro/core/ok.py", """\
        def _stage_dispatch(st, n_sites=1, halted=None):
            if n_sites == 1:
                return st
            if halted is not None:
                return st
            if st.queue.shape[0] > 4:
                return st
            return st
        """)
    assert astlint.TracedBranchCheck().run(cfg) == []


# --------------------------------------------------------------------------
# JD005 oracle-f32
# --------------------------------------------------------------------------

def test_jd005_bare_float_literal(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/pyengine.py", """\
        import numpy as np

        F = np.float32

        def _nominate_min_energy(dl, val):
            return F(dl) + 1e-6 * val
        """)
    findings = astlint.OracleF32Check(
        oracle_rel="src/repro/core/pyengine.py").run(cfg)
    assert _rules_at(findings, "JD005") == [("src/repro/core/pyengine.py", 6)]


def test_jd005_float64_reference(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/pyengine.py", """\
        import numpy as np

        def _key_urgency(dl):
            return np.float64(dl)
        """)
    findings = astlint.OracleF32Check(
        oracle_rel="src/repro/core/pyengine.py").run(cfg)
    assert _rules_at(findings, "JD005") == [("src/repro/core/pyengine.py", 4)]


def test_jd005_wrapped_literals_clean(tmp_path):
    cfg = _mini_repo(tmp_path, "src/repro/core/pyengine.py", """\
        import numpy as np

        F = np.float32

        def _nominate_min_energy(dl, val):
            return F(F(dl) + F(F(1e-6) * F(val)))
        """)
    assert astlint.OracleF32Check(
        oracle_rel="src/repro/core/pyengine.py").run(cfg) == []


# --------------------------------------------------------------------------
# Self-scan: the tree is clean, and stays clean
# --------------------------------------------------------------------------

def test_layer1_self_scan_clean():
    """All five AST rules pass on the real tree — pins the CRN markers,
    the shared excludes, and every future core/scenarios edit."""
    findings, errors = analysis.run_checks(root=REPO_ROOT, layers=(1,))
    assert errors == []
    assert findings == [], analysis.format_findings(findings)


def test_excludes_shared_with_ruff():
    """pyproject is the single source of truth: the analyzer exclude list
    exists, covers the legacy snapshots, and equals ruff's."""
    cfg = load_config(REPO_ROOT)
    legacy = ("tests/_legacy_heuristics.py", "tests/_legacy_siteloop.py",
              "tests/_legacy_workload.py")
    for rel in legacy:
        assert cfg.is_excluded(rel), rel
    from repro.analysis.config import _parse_toml
    with open(f"{REPO_ROOT}/pyproject.toml") as fh:
        data = _parse_toml(fh.read())
    assert data["tool"]["ruff"]["extend-exclude"] == list(cfg.exclude)


# --------------------------------------------------------------------------
# Layer 2: jaxpr audit
# --------------------------------------------------------------------------

def test_jx101_flatness_clean_f2_vs_f8():
    """F is data, not program: paper_x2 and paper_x8 trace identically
    (the reusable form of the F=2 vs F=32 compile-flatness pin)."""
    cfg = load_config(REPO_ROOT)
    findings = jaxpr_audit.FlatnessCheck(
        fleets=("paper_x2", "paper_x8")).run(cfg)
    assert findings == [], analysis.format_findings(findings)


def test_jx101_flatness_flags_f1_vs_f2():
    """Seeded-bad pair: the single-site program IS structurally different
    from the federated one, and the audit must say so."""
    cfg = load_config(REPO_ROOT)
    findings = jaxpr_audit.FlatnessCheck(
        fleets=("paper", "paper_x2")).run(cfg)
    assert findings, "F=1 vs F=2 should differ structurally"
    assert all(f.rule == "JX101" for f in findings)


def test_jx102_weak_type_output_flagged(monkeypatch):
    """A python-scalar-derived (weak-typed) output is caught."""
    def weak_program():
        def fn(x):
            return x.sum(), jnp.exp(1.0)  # second output is weak f32
        return fn, (jnp.zeros((4,), jnp.float32),)

    monkeypatch.setattr(jaxpr_audit, "DEFAULT_PROGRAMS",
                        (("weak-fixture", weak_program),))
    findings = jaxpr_audit.DtypeAuditCheck().run(load_config(REPO_ROOT))
    assert any(f.rule == "JX102" and "weak-typed" in f.message
               for f in findings), findings


def test_jx103_debug_print_flagged(monkeypatch):
    def noisy_program():
        def fn(x):
            jax.debug.print("x = {}", x)
            return x * 2
        return fn, (jnp.zeros((4,), jnp.float32),)

    monkeypatch.setattr(jaxpr_audit, "DEFAULT_PROGRAMS",
                        (("noisy-fixture", noisy_program),))
    findings = jaxpr_audit.EffectsAuditCheck().run(load_config(REPO_ROOT))
    assert [f.rule for f in findings] == ["JX103"]
    assert "debug_print" in findings[0].message


@pytest.mark.slow
def test_jx102_jx103_clean_on_default_programs():
    """The default audit matrix (ELARE/FELARE + full aux stack) carries
    no float64, no weak outputs, no effect primitives."""
    cfg = load_config(REPO_ROOT)
    for check in (jaxpr_audit.DtypeAuditCheck(),
                  jaxpr_audit.EffectsAuditCheck()):
        findings = check.run(cfg)
        assert findings == [], analysis.format_findings(findings)


@pytest.mark.slow
def test_jx104_retrace_replay_clean():
    findings = jaxpr_audit.RetraceAuditCheck(n_tasks=16).run(
        load_config(REPO_ROOT))
    assert findings == [], analysis.format_findings(findings)


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def test_cli_list_checks(capsys):
    assert check_cli.main(["--list-checks"]) == 0
    out = capsys.readouterr().out
    for rule in ("JD001", "JD002", "JD003", "JD004", "JD005",
                 "JX101", "JX102", "JX103", "JX104"):
        assert rule in out


def test_cli_layer1_clean_exit0(capsys):
    assert check_cli.main(["--layer", "1", "--root", REPO_ROOT]) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_json_round_trip(tmp_path, capsys):
    """Findings survive the --json report byte-exactly, and a dirty tree
    exits non-zero with rule ids in the report."""
    bad = tmp_path / "src" / "repro" / "core"
    bad.mkdir(parents=True)
    (tmp_path / "pyproject.toml").write_text(
        "[tool.repro.analysis]\nexclude = []\n")
    (bad / "bad.py").write_text(textwrap.dedent("""\
        import jax

        def _stage_admit(st):
            key = jax.random.PRNGKey(0)
            print("tracing")
            return st
        """))
    out_json = tmp_path / "analysis.json"
    rc = check_cli.main([
        "--layer", "1", "--root", str(tmp_path), "--json", str(out_json),
        "--checks", "crn-discipline,host-effects"])
    assert rc == 1
    report = json.loads(out_json.read_text())
    assert report["ok"] is False
    assert report["findings_by_rule"] == {"JD002": 1, "JD003": 1}
    loaded = load_json(out_json)
    assert loaded == sorted(
        from_json_dict(d) for d in report["findings"])
    assert {f.rule for f in loaded} == {"JD002", "JD003"}
    assert all(isinstance(f, Finding) and f.line for f in loaded)


def test_cli_crashed_check_fails_gate(tmp_path, monkeypatch):
    """A check that raises must fail the gate, not silently pass."""
    import dataclasses as _dc

    @_dc.dataclass(frozen=True)
    class Exploding:
        name: str = "exploding"
        rule: str = "JD999"
        layer: int = 1

        def run(self, cfg):
            raise RuntimeError("boom")

    analysis.register("exploding", Exploding())
    try:
        out_json = tmp_path / "r.json"
        rc = check_cli.main(["--checks", "exploding", "--root", REPO_ROOT,
                             "--json", str(out_json)])
        assert rc == 1
        report = json.loads(out_json.read_text())
        assert report["ok"] is False and report["errors"]
    finally:
        analysis.CHECKS.unregister("exploding")
