"""The trace-safety analysis suite itself: every rule fires exactly
once on its fixture violation, stays silent on clean code, and the
suppression file round-trips (with mandatory justifications)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (apply_suppressions, check_cache_key,
                            check_deprecated, check_facade,
                            check_facade_source, check_traced_purity,
                            parse_suppressions, run_ast_rules)
from repro.analysis.findings import Finding

REPO_ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


def mini_repo(tmp_path, files):
    """A synthetic repo root: {relpath: source} -> tmp dir."""
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(tmp_path)


# ---------------------------------------------------------------------------
# R001: purity of @traced_closure functions
# ---------------------------------------------------------------------------

_R001_VIOLATION = {
    "src/repro/core/fix_r001.py": """
        import numpy as np
        from .tracing import traced_closure

        @traced_closure
        def score(genomes):
            return np.sqrt(genomes)  # the one violation
    """,
}

_R001_CLEAN = {
    "src/repro/core/fix_clean.py": """
        import numpy as np
        import jax.numpy as jnp
        from .tracing import traced_closure

        TABLE = np.cumprod([2, 3, 4])  # build-time numpy is fine

        @traced_closure
        def score(genomes):
            return jnp.sqrt(genomes * jnp.asarray(TABLE))

        def host_helper(x):
            return float(np.sqrt(x))  # unmarked: not audited
    """,
}


def test_r001_fires_exactly_once(tmp_path):
    findings = check_traced_purity(mini_repo(tmp_path, _R001_VIOLATION))
    assert len(findings) == 1
    f = findings[0]
    assert (f.rule, f.symbol) == ("R001", "score")
    assert "numpy" in f.message


def test_r001_silent_on_clean_fixture(tmp_path):
    assert check_traced_purity(mini_repo(tmp_path, _R001_CLEAN)) == []


@pytest.mark.parametrize("body,needle", [
    ("return x.item()", ".item()"),
    ("return float(x)", "float()"),
    ("print(x)\n    return x", "print"),
    ("global _N\n    _N += 1\n    return x", "global"),
    ("import time\n    return time.perf_counter()", "time"),
])
def test_r001_construct_catalog(tmp_path, body, needle):
    src = ("from .tracing import traced_closure\n\n"
           "@traced_closure\ndef f(x):\n    " + body + "\n")
    root = mini_repo(tmp_path, {"src/repro/core/one.py": src})
    findings = check_traced_purity(root)
    assert len(findings) == 1 and needle in findings[0].message


@pytest.mark.parametrize("imports,call", [
    ("from .tracing import span", "span"),
    ("from repro.core.tracing import span as sp", "sp"),
    ("from . import tracing", "tracing.span"),
])
def test_r001_flags_host_span_in_traced_closure(tmp_path, imports, call):
    src = f"""
        {imports}
        from .tracing import traced_closure

        @traced_closure
        def score(genomes):
            with {call}("score"):  # the one violation
                return genomes

        def host_step(x):
            with {call}("host_step"):  # unmarked: a span belongs here
                return x
    """
    root = mini_repo(tmp_path, {"src/repro/core/spans.py": src})
    findings = check_traced_purity(root)
    assert len(findings) == 1
    f = findings[0]
    assert (f.rule, f.symbol) == ("R001", "score")
    assert "host span" in f.message


def test_r001_mutable_default_but_not_frozen_dataclass(tmp_path):
    src = """
        from .tracing import traced_closure

        @traced_closure
        def f(x, acc=[], consts=SomeFrozenThing()):
            return x
    """
    root = mini_repo(tmp_path, {"src/repro/core/two.py": src})
    findings = check_traced_purity(root)
    # the list default fires; the (frozen-style) constructor does not
    assert len(findings) == 1
    assert "mutable default" in findings[0].message


# ---------------------------------------------------------------------------
# R002: cache-key completeness
# ---------------------------------------------------------------------------

def _r002_repo(tmp_path, key_body):
    return mini_repo(tmp_path, {
        "src/repro/experiments/scenarios.py": """
            import dataclasses

            @dataclasses.dataclass(frozen=True)
            class Budget:
                p_ga: int = 8

            @dataclasses.dataclass(frozen=True)
            class Scenario:
                name: str
                mem: str
                seed: int = 0
        """,
        "src/repro/core/scoring.py": """
            import dataclasses

            @dataclasses.dataclass(frozen=True)
            class Calib:
                n_calib: int = 32
        """,
        "src/repro/experiments/runner.py": """
            import dataclasses

            CACHE_KEY_EXEMPT_FIELDS = frozenset({"name"})

            def cache_key_fields(scenario, seed, n_seeds):
                return """ + key_body + "\n",
    })


def test_r002_fires_exactly_once_on_missing_field(tmp_path):
    # 'mem' is neither read nor exempt -> exactly one error finding
    root = _r002_repo(tmp_path, """{
                "seed": scenario.seed,
                "budget": dataclasses.asdict(scenario.budget),
                "n_calib": scenario.n_calib,
            }""")
    errors = [f for f in check_cache_key(root) if f.severity == "error"]
    assert len(errors) == 1
    assert errors[0].rule == "R002" and "'mem'" in errors[0].message


def test_r002_silent_when_complete(tmp_path):
    root = _r002_repo(tmp_path, """{
                "mem": scenario.mem,
                "seed": scenario.seed,
                "budget": dataclasses.asdict(scenario.budget),
                "n_calib": scenario.n_calib,
            }""")
    assert check_cache_key(root) == []


def test_r002_real_repo_key_is_complete():
    """The actual runner keys every Scenario/Budget/Calib field."""
    assert check_cache_key(REPO_ROOT) == []


# ---------------------------------------------------------------------------
# R003: facade enforcement (the rule itself; test_api.py gates the repo)
# ---------------------------------------------------------------------------

def test_r003_fires_exactly_once(tmp_path):
    root = mini_repo(tmp_path, {"examples/demo.py": """
        import repro.api
        from repro.core import build_scorer  # the one violation
    """})
    findings = check_facade(root)
    assert len(findings) == 1
    assert findings[0].rule == "R003"
    assert "repro.core" in findings[0].message


def test_r003_source_helper_resolves_relative_imports():
    findings = check_facade_source(
        "from ..experiments import run_scenario\n",
        "src/repro/launch/job.py")
    assert len(findings) == 1
    assert "repro.experiments" in findings[0].message


# ---------------------------------------------------------------------------
# R004: deprecated ImportError stubs
# ---------------------------------------------------------------------------

def test_r004_fires_exactly_once(tmp_path):
    root = mini_repo(tmp_path, {"src/repro/fresh.py": """
        from repro.experiments import make_scorer

        def build(sp, wa, obj):
            return make_scorer(sp, wa, obj)
    """})
    findings = check_deprecated(root)
    assert len(findings) == 1
    assert findings[0].rule == "R004"
    assert "make_scorer" in findings[0].message


def test_r004_silent_on_the_replacement(tmp_path):
    root = mini_repo(tmp_path, {"src/repro/fresh.py": """
        from repro.api import build_scorer
    """})
    assert check_deprecated(root) == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_suppression_round_trip():
    sups, problems = parse_suppressions(
        "# comment\n"
        "\n"
        "R001 src/repro/core/foo.py:build.score  # pinned host table\n"
        "R003 benchmarks/bench.py  # measures internals\n",
        source="analysis/suppressions.txt")
    assert problems == []
    assert len(sups) == 2

    hit = Finding(rule="R001", path="src/repro/core/foo.py", line=3,
                  symbol="build.score.inner", message="m")
    miss = Finding(rule="R001", path="src/repro/core/bar.py", line=3,
                   symbol="build.score", message="m")
    kept, suppressed, stale = apply_suppressions([hit, miss], sups)
    assert kept == [miss]
    assert suppressed == [hit]
    # the R003 entry matched nothing -> exactly one stale warning
    assert len(stale) == 1 and stale[0].severity == "warning"
    assert "benchmarks/bench.py" in stale[0].message


def test_suppression_requires_justification():
    sups, problems = parse_suppressions(
        "R001 src/repro/core/foo.py\n"          # no justification
        "R001 too many parts here  # why\n")    # malformed
    assert sups == []
    assert len(problems) == 2
    assert all(p.rule == "R000" and p.severity == "error"
               for p in problems)


def test_repo_suppression_file_parses_clean():
    with open(os.path.join(REPO_ROOT, "analysis",
                           "suppressions.txt")) as f:
        _, problems = parse_suppressions(f.read())
    assert problems == []


# ---------------------------------------------------------------------------
# the repo itself + the CLI gate
# ---------------------------------------------------------------------------

def test_repo_ast_rules_all_suppressed_or_clean():
    """src/repro, examples/ and benchmarks/ carry no unsuppressed AST
    finding (same check the CI analysis job gates on)."""
    from repro.analysis import load_suppressions
    findings = run_ast_rules(REPO_ROOT)
    sups, problems = load_suppressions(REPO_ROOT)
    kept, _, _ = apply_suppressions(findings, sups)
    assert problems == []
    assert kept == [], "\n".join(f.format() for f in kept)


def test_cli_exit_codes(tmp_path):
    """--ast exits 0 on a clean synthetic repo, 1 when a violation is
    introduced, and 0 again once suppressed with a justification."""
    root = mini_repo(tmp_path, _R001_CLEAN)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(REPO_ROOT, "src")) + os.pathsep + env.get(
        "PYTHONPATH", "")

    def run():
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--ast",
             "--root", root, "--report", str(tmp_path / "rep.json")],
            capture_output=True, text=True, env=env, cwd=REPO_ROOT)

    assert run().returncode == 0

    bad = tmp_path / "src/repro/core/fix_r001.py"
    bad.write_text(textwrap.dedent(_R001_VIOLATION[
        "src/repro/core/fix_r001.py"]))
    r = run()
    assert r.returncode == 1 and "R001" in r.stdout
    report = json.loads((tmp_path / "rep.json").read_text())
    assert any(f["rule"] == "R001" for f in report["findings"])

    sup = tmp_path / "analysis" / "suppressions.txt"
    sup.parent.mkdir(exist_ok=True)
    sup.write_text("R001 src/repro/core/fix_r001.py:score"
                   "  # fixture: exercised by test_cli_exit_codes\n")
    assert run().returncode == 0


# ---------------------------------------------------------------------------
# jaxpr audit (unit level; the full lowering sweep is the CI job)
# ---------------------------------------------------------------------------

def test_jaxpr_callback_detection():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import (callback_primitives,
                                            count_primitives)

    def pure(x):
        return jnp.sin(x) * 2.0

    def impure(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    x = jnp.zeros((4,))
    assert callback_primitives(
        count_primitives(jax.make_jaxpr(pure)(x))) == {}
    bad = callback_primitives(count_primitives(
        jax.make_jaxpr(impure)(x)))
    assert bad and all("callback" in k for k in bad)


def test_jaxpr_counts_recurse_into_scan():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import count_primitives

    def scanned(x):
        def step(c, _):
            return jnp.tanh(c) + 1.0, c
        out, _ = jax.lax.scan(step, x, None, length=5)
        return out

    counts = count_primitives(jax.make_jaxpr(scanned)(jnp.zeros((3,))))
    assert counts.get("tanh", 0) >= 1  # found inside the scan body


def test_jaxpr_audit_rules_on_synthetic_entries():
    from repro.analysis.jaxpr_audit import KernelEntry, audit_entries

    def entry(kid, group, h, n, prims=None):
        return KernelEntry(kernel_id=kid, scenario=kid.split(":")[0],
                           label=kid.split("::")[1], group=group,
                           hash=h, n_primitives=n,
                           primitives=prims or {"add": n})

    entries = [
        entry("a::kernel", "g1", "h1", 100),
        entry("b::kernel", "g1", "h2", 100),   # J002: split group
        entry("c::kernel", "g2", "h3", 500),   # J003: bloat vs 100
        entry("d::kernel", "g3", "h4", 50,
              {"add": 49, "pure_callback": 1}),  # J001
    ]
    baseline = {"a::kernel": 100, "b::kernel": 100, "c::kernel": 100,
                "d::kernel": 50, "gone::kernel": 10}
    rules = sorted(f.rule for f in audit_entries(entries, baseline)
                   if f.severity == "error")
    assert rules == ["J001", "J002", "J003"]
    warn = [f for f in audit_entries(entries, baseline)
            if f.severity == "warning"]
    assert len(warn) == 1 and "gone::kernel" in warn[0].symbol


def test_jaxpr_baseline_round_trip(tmp_path):
    from repro.analysis.jaxpr_audit import (KernelEntry, load_baseline,
                                            write_baseline)

    e = KernelEntry(kernel_id="s::kernel", scenario="s", label="kernel",
                    group="g", hash="h", n_primitives=42,
                    primitives={"add": 42})
    write_baseline(str(tmp_path), [e])
    assert load_baseline(str(tmp_path)) == {"s::kernel": 42}


def test_repo_baseline_matches_registry():
    """analysis/baseline.json names only registered scenarios."""
    from repro.experiments import scenario_names
    with open(os.path.join(REPO_ROOT, "analysis",
                           "baseline.json")) as f:
        kernels = json.load(f)["kernels"]
    names = set(scenario_names())
    assert kernels, "baseline.json is empty"
    for kid in kernels:
        assert kid.split("::")[0] in names, kid


def test_one_scenario_lowers_callback_free():
    """End-to-end lowering of the smoke scenario (cheap single case;
    the full sweep is `python -m repro.analysis --jaxpr` in CI)."""
    from repro.analysis.jaxpr_audit import (callback_primitives,
                                            lower_scenario)
    from repro.experiments import get_scenario

    entries = lower_scenario(get_scenario("sram_smoke"))
    labels = sorted(e.label for e in entries)
    assert labels == ["kernel", "scorer"]
    for e in entries:
        assert callback_primitives(e.primitives) == {}, e.kernel_id
        assert e.n_primitives > 0
