"""Tests for the static-analysis suite (tools/analysis/).

Fixture-based: tests/fixtures/analysis/ is a miniature repo tree with one
known violation per rule plus clean counterparts, so both directions are
pinned — the rules fire where they must and stay silent where they must.
The twin-contract registry additionally gets a live run against the real
codebase and a seeded-drift run against a mutated copy of it (the
acceptance path: a kwarg added to one twin must fail the suite).

The suite is stdlib-only by design; none of these tests import jax.
"""

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))

from tools.analysis import CHECKERS, main  # noqa: E402
from tools.analysis import contracts  # noqa: E402
from tools.analysis.base import load_sources  # noqa: E402

FIXTURE_ROOT = REPO_ROOT / "tests" / "fixtures" / "analysis"


def run_checker(name, root):
    sources = {s.path: s for s in load_sources(root, ("src/repro",))}
    violations, notes = CHECKERS[name](root, sources)
    # apply waivers the way the runner does
    from tools.analysis.base import apply_waivers
    return apply_waivers(sources, violations), notes


def line_of(path: Path, needle: str) -> int:
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        if needle in line:
            return i
    raise AssertionError(f"{needle!r} not found in {path}")


# --------------------------------------------------------------- jit lint

class TestJitLint:
    @pytest.fixture(scope="class")
    def found(self):
        violations, _ = run_checker("jit", FIXTURE_ROOT)
        return violations

    def fixture_path(self):
        return FIXTURE_ROOT / "src" / "repro" / "bad_jit.py"

    def test_pyflow_on_traced_if(self, found):
        want = line_of(self.fixture_path(), "# jit-pyflow: `x` is traced")
        assert any(v.rule == "jit-pyflow" and v.line == want for v in found)

    def test_pyflow_in_scan_body(self, found):
        want = line_of(self.fixture_path(), "carry is traced in a scan body")
        assert any(v.rule == "jit-pyflow" and v.line == want for v in found)

    def test_pyflow_via_helper_taint(self, found):
        want = line_of(self.fixture_path(),
                       "jit-pyflow when a traced value reaches")
        assert any(v.rule == "jit-pyflow" and v.line == want for v in found)

    def test_coercions(self, found):
        path = self.fixture_path()
        for marker in ("# jit-coerce: concretizes a tracer",
                       "# jit-coerce: numpy on a traced value",
                       "# jit-coerce: device sync"):
            want = line_of(path, marker)
            assert any(v.rule == "jit-coerce" and v.line == want
                       for v in found), marker

    def test_mutable_default(self, found):
        want = line_of(self.fixture_path(), "# jit-mutable-default")
        assert any(v.rule == "jit-mutable-default" and v.line == want
                   for v in found)

    def test_hash64(self, found):
        want = line_of(self.fixture_path(), "module never enables wide ints")
        assert any(v.rule == "jit-hash64" and v.line == want for v in found)

    def test_clean_lines_stay_clean(self, found):
        text = self.fixture_path().read_text().splitlines()
        clean_lines = {i for i, line in enumerate(text, start=1)
                       if "clean" in line and "#" in line}
        hits = {v.line for v in found if v.path == self.fixture_path()}
        assert not (hits & clean_lines), sorted(hits & clean_lines)

    def test_static_args_not_tainted(self, found):
        # `for _ in range(n)` with static n, and _helper(x, mode) with a
        # static mode, must not be flagged
        path = self.fixture_path()
        for marker in ("`n` is static", "`flag` stays static"):
            line = line_of(path, marker)
            assert not any(v.line == line for v in found), marker

    def test_waiver_suppresses(self, found):
        line = line_of(self.fixture_path(), "exercising the waiver path")
        assert not any(v.line == line for v in found)


# ------------------------------------------------------------- units lint

class TestUnitsLint:
    @pytest.fixture(scope="class")
    def found(self):
        violations, _ = run_checker("units", FIXTURE_ROOT)
        return violations

    def fixture_path(self):
        return FIXTURE_ROOT / "src" / "repro" / "core" / "bad_units.py"

    @pytest.mark.parametrize("rule,marker", [
        ("units-mix", "# units-mix: ns minus us"),
        ("units-assign", "# units-assign: us into a _ns name"),
        ("units-mix", "# units-mix: compares ns to us"),
        ("units-mix", "# units-mix: min over mixed units"),
        ("units-mix", "# units-mix: ns value, us keyword"),
        ("units-mix", "# units-mix: time plus rate"),
    ])
    def test_violation_lines(self, found, rule, marker):
        want = line_of(self.fixture_path(), marker)
        assert any(v.rule == rule and v.line == want for v in found), marker

    def test_clean_lines_stay_clean(self, found):
        path = self.fixture_path()
        text = path.read_text().splitlines()
        clean = {i for i, line in enumerate(text, start=1)
                 if "clean" in line or "fine" in line}
        hits = {v.line for v in found if v.path == path}
        assert not (hits & clean), sorted(hits & clean)

    def test_waiver_suppresses(self, found):
        line = line_of(self.fixture_path(), "pre-scaled by the caller")
        assert not any(v.line in (line, line + 1) for v in found)


# ---------------------------------------------------------- import graph

class TestImportGraph:
    @pytest.fixture(scope="class")
    def result(self):
        return run_checker("imports", FIXTURE_ROOT)

    def test_cycle_detected(self, result):
        violations, _ = result
        cyc = [v for v in violations if v.rule == "imports-cycle"]
        assert len(cyc) == 1
        assert "cyc_a" in cyc[0].message and "cyc_b" in cyc[0].message

    def test_dead_import(self, result):
        violations, _ = result
        dead = [v for v in violations if v.rule == "imports-dead"]
        assert any("'os'" in v.message for v in dead)
        assert not any("'math'" in v.message for v in dead)

    def test_real_tree_has_no_cycles(self):
        violations, _ = run_checker("imports", REPO_ROOT)
        assert [v for v in violations if v.rule == "imports-cycle"] == []

    def test_real_tree_dormant_wings_reported(self):
        _, notes = run_checker("imports", REPO_ROOT)
        assert any("dormant" in n.text for n in notes)


# ------------------------------------------------------------- docs paths

class TestDocsPaths:
    def test_missing_path_flagged(self):
        violations, _ = run_checker("docs_paths", FIXTURE_ROOT)
        assert len(violations) == 1
        assert "does_not_exist.py" in violations[0].message


# --------------------------------------------------------------- obs lint

class TestObsLint:
    @pytest.fixture(scope="class")
    def found(self):
        violations, _ = run_checker("obs", FIXTURE_ROOT)
        return violations

    def fixture_path(self):
        return FIXTURE_ROOT / "src" / "repro" / "obs" / "bad_obs.py"

    def test_metric_name_without_suffix(self, found):
        want = line_of(self.fixture_path(),
                       "# obs-units: metric name without suffix")
        assert any(v.rule == "obs-units" and v.line == want for v in found)

    def test_time_like_schema_field(self, found):
        want = line_of(self.fixture_path(),
                       "# obs-units: time-like field without a unit")
        assert any(v.rule == "obs-units" and v.line == want for v in found)

    def test_nonstatic_trace_cap(self, found):
        want = line_of(self.fixture_path(), "def bad_ring")
        assert any(v.rule == "obs-ring-static" and v.line == want
                   for v in found)

    def test_estimator_field_without_unit(self, found):
        path = self.fixture_path()
        for marker in ("# obs-units: estimator field without a unit",
                       "# obs-units: EWMA field without a unit"):
            want = line_of(path, marker)
            assert any(v.rule == "obs-units" and v.line == want
                       for v in found), marker

    def test_nonstatic_sketch_window(self, found):
        want = line_of(self.fixture_path(), "def bad_sketch")
        hits = [v for v in found
                if v.rule == "obs-ring-static" and v.line == want]
        assert len(hits) == 1 and "window_us" in hits[0].message

    def test_span_count_without_suffix(self, found):
        path = self.fixture_path()
        for marker in ("# obs-units: span count without suffix",
                       "# obs-units: span count call without suffix"):
            want = line_of(path, marker)
            hits = [v for v in found
                    if v.rule == "obs-units" and v.line == want]
            assert len(hits) == 1 and "span count" in hits[0].message, marker

    def test_clean_lines_stay_clean(self, found):
        path = self.fixture_path()
        text = path.read_text().splitlines()
        clean = {i for i, line in enumerate(text, start=1)
                 if "clean" in line}
        clean.add(line_of(path, "def good_ring"))
        clean.add(line_of(path, "def good_sketch"))
        hits = {v.line for v in found if v.path == path}
        assert not (hits & clean), sorted(hits & clean)

    def test_real_tree_is_clean(self):
        violations, notes = run_checker("obs", REPO_ROOT)
        assert violations == []
        assert any("obs-lint" in n.text for n in notes)


# ---------------------------------------------------------- twin contracts

class TestTwinContracts:
    def resolver(self, root):
        return contracts._Resolver(root, {})

    def test_matched_pair_is_clean(self):
        pair = contracts.TwinPair(
            name="fixture-fn",
            fast="repro.twin_fast:fast_fn",
            oracle="repro.twin_oracle:oracle_fn",
            fast_only=("p_hits", "seeds"),
            oracle_only=("p_hit", "seed"),
        )
        assert contracts.check_pair(pair, self.resolver(FIXTURE_ROOT)) == []

    def test_class_init_resolution(self):
        pair = contracts.TwinPair(
            name="fixture-class",
            fast="repro.twin_fast:fast_fn",
            oracle="repro.twin_oracle:Oracle.__init__",
            fast_only=("p_hits", "seeds"),
            oracle_only=("p_hit", "seed"),
        )
        assert contracts.check_pair(pair, self.resolver(FIXTURE_ROOT)) == []

    def test_kwarg_drift_named(self):
        pair = contracts.TwinPair(
            name="fixture-drift",
            fast="repro.twin_fast:drifted_fast",
            oracle="repro.twin_oracle:drifted_oracle",
            fast_only=("p_hits",),
            oracle_only=("p_hit",),
        )
        found = contracts.check_pair(pair, self.resolver(FIXTURE_ROOT))
        rules = {v.rule for v in found}
        assert rules == {"twin-kwargs"}
        assert any("'fail_prob'" in v.message for v in found)
        assert any("'n_requests'" in v.message for v in found)

    def test_default_drift_named(self):
        pair = contracts.TwinPair(
            name="fixture-default",
            fast="repro.twin_fast:fast_fn",
            oracle="repro.twin_oracle:drifted_oracle",
            fast_only=("p_hits", "seeds", "coalesce_theta", "burst"),
            oracle_only=("p_hit",),
        )
        found = contracts.check_pair(pair, self.resolver(FIXTURE_ROOT))
        assert any(v.rule == "twin-default" and "'n_requests'" in v.message
                   for v in found)

    def test_stale_allowlist_flagged(self):
        pair = contracts.TwinPair(
            name="fixture-stale",
            fast="repro.twin_fast:fast_fn",
            oracle="repro.twin_oracle:oracle_fn",
            fast_only=("p_hits", "seeds", "not_a_param"),
            oracle_only=("p_hit", "seed"),
        )
        found = contracts.check_pair(pair, self.resolver(FIXTURE_ROOT))
        assert any(v.rule == "twin-allowlist" and "'not_a_param'" in v.message
                   for v in found)

    def test_missing_function_flagged(self):
        pair = contracts.TwinPair(
            name="fixture-missing",
            fast="repro.twin_fast:gone_fn",
            oracle="repro.twin_oracle:oracle_fn",
        )
        found = contracts.check_pair(pair, self.resolver(FIXTURE_ROOT))
        assert any(v.rule == "twin-missing" for v in found)

    def test_live_registry_is_clean(self):
        violations, notes = run_checker("contracts", REPO_ROOT)
        assert violations == []
        assert any("19 registered pairs" in n.text for n in notes)


# ----------------------------------------------- acceptance: seeded drift

class TestSeededDrift:
    @pytest.fixture()
    def mutated_tree(self, tmp_path):
        """Copy the real src/ tree and add a kwarg to one oracle only."""
        shutil.copytree(REPO_ROOT / "src", tmp_path / "src")
        py_sim = tmp_path / "src" / "repro" / "core" / "py_sim.py"
        text = py_sim.read_text()
        old = "def simulate_py(\n    net: ClosedNetwork,\n    p_hit: float,"
        assert old in text
        py_sim.write_text(text.replace(
            old, old + "\n    drift_knob: int = 7,", 1))
        return tmp_path

    def test_suite_exits_nonzero_on_drift(self, mutated_tree, capsys):
        rc = main(["--root", str(mutated_tree), "--only", "contracts",
                   "--quiet"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "twin-kwargs" in out and "drift_knob" in out

    def test_suite_exits_zero_on_repaired_tree(self, capsys):
        rc = main(["--root", str(REPO_ROOT), "--only", "contracts",
                   "--quiet"])
        assert rc == 0


# ------------------------------------------------------------ CLI surface

class TestCli:
    def test_fixture_tree_fails_with_waiver_reason(self, capsys):
        rc = main(["--root", str(FIXTURE_ROOT), "--quiet"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "waiver-reason" in out       # bad_waiver.py: no reason given
        assert "jit-pyflow" in out
        assert "units-mix" in out
        assert "imports-cycle" in out
        assert "docs-paths" in out

    def test_module_entry_point(self):
        # the exact invocation CI gates on (docs subset: fast, no jax)
        proc = subprocess.run(
            [sys.executable, "-m", "tools.analysis", "--only", "docs_paths"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_unknown_checker_rejected(self):
        with pytest.raises(SystemExit):
            main(["--only", "nope"])

    def test_list_rules(self, capsys):
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        assert "twin-kwargs" in out and "jit-pyflow" in out
