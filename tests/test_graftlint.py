"""graftlint (tools/graftlint): the static-analysis gate's own tests.

Three layers:

1. **Golden fixtures** (tools/graftlint/fixtures/): per rule, a positive
   snippet must produce findings (and a non-zero CLI exit), a negative
   snippet must be clean, and a pragma-suppressed snippet must be clean
   while COUNTING the suppression — pragmas are visible debt, not
   silence.
2. **Real-tree gate**: ``python -m tools.graftlint spark_examples_tpu/``
   exits 0 on this tree — the same blocking invocation CI runs.
3. **Schema-sharing meta-test**: the span/metric name sets graftlint
   extracts from the real tree must match ``scripts/validate_trace.py``
   exactly, and the rule must provably read the schema FROM that script
   (same module object), so the static and runtime gates can never
   drift apart.
"""

import os
import shutil
import subprocess
import sys

import pytest

from tools.graftlint.engine import Project, find_root, load_config, run_lint
from tools.graftlint.rules import ALL_RULES
from tools.graftlint.rules import span_contract as span_contract_mod

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tools", "graftlint", "fixtures")

ALL_RULE_NAMES = [r.name for r in ALL_RULES]

# (fixture stem, rule name, source suffix) for the single-file rules;
# flag-registry uses directory fixtures and is parametrized separately.
SINGLE_FILE_RULES = [
    ("gl001", "jit-purity", ".py"),
    ("gl002", "dtype-discipline", ".py"),
    ("gl003", "span-contract", ".py"),
    ("gl005", "resilience-routing", ".py"),
    ("gl006", "native-gil", ".cpp"),
    ("gl007", "lock-discipline", ".py"),
    ("gl008", "deadlock-order", ".py"),
    ("gl009", "guarded-fields", ".py"),
    ("gl010", "collective-congruence", ".py"),
    ("gl011", "donation-aliasing", ".py"),
    ("gl012", "retrace-discipline", ".py"),
    ("gl013", "atomic-commit", ".py"),
    ("gl014", "fencing-discipline", ".py"),
]


def _mini_project(tmp_path, rule_name, fixture_files, extra_rule_cfg=()):
    """A throwaway project enabling exactly one rule, scoped to '.'."""
    lines = ["[tool.graftlint]", "exclude = []"]
    for name in ALL_RULE_NAMES:
        lines.append(f'[tool.graftlint.rules."{name}"]')
        lines.append(f"enabled = {'true' if name == rule_name else 'false'}")
        if name == rule_name:
            lines.append('paths = ["."]')
            lines.extend(extra_rule_cfg)
    (tmp_path / "pyproject.toml").write_text("\n".join(lines) + "\n")
    for f in fixture_files:
        shutil.copy(os.path.join(FIXTURES, f), tmp_path)
    return str(tmp_path)


class TestGoldenFixtures:
    @pytest.mark.parametrize("stem,rule,ext", SINGLE_FILE_RULES)
    def test_positive_fixture_reports(self, tmp_path, stem, rule, ext):
        root = _mini_project(tmp_path, rule, [f"{stem}_positive{ext}"])
        findings, suppressed = run_lint(root, [])
        assert findings, f"{rule} found nothing in its golden positive"
        assert all(f.rule == rule for f in findings)
        assert not suppressed

    @pytest.mark.parametrize("stem,rule,ext", SINGLE_FILE_RULES)
    def test_negative_fixture_clean(self, tmp_path, stem, rule, ext):
        root = _mini_project(tmp_path, rule, [f"{stem}_negative{ext}"])
        findings, suppressed = run_lint(root, [])
        assert findings == []
        assert not suppressed

    @pytest.mark.parametrize("stem,rule,ext", SINGLE_FILE_RULES)
    def test_pragma_suppresses_and_counts(self, tmp_path, stem, rule, ext):
        root = _mini_project(tmp_path, rule, [f"{stem}_suppressed{ext}"])
        findings, suppressed = run_lint(root, [])
        assert findings == []
        assert suppressed.get(rule, 0) >= 1, (
            "suppression must be COUNTED, not silently dropped"
        )

    @pytest.mark.parametrize("kind,expect", [("positive", True), ("negative", False)])
    def test_flag_registry_fixture(self, tmp_path, kind, expect):
        src = os.path.join(FIXTURES, f"gl004_{kind}")
        for f in os.listdir(src):
            shutil.copy(os.path.join(src, f), tmp_path)
        _mini_project(
            tmp_path,
            "flag-registry",
            [],
            extra_rule_cfg=[
                'config_module = "config.py"',
                'cli_module = "main.py"',
                "script_paths = []",
                'doc_paths = ["README.md"]',
            ],
        )
        findings, _ = run_lint(str(tmp_path), [])
        assert bool(findings) == expect, [f.human() for f in findings]
        if expect:
            messages = "\n".join(f.message for f in findings)
            # All three sync directions must be represented:
            assert "dead flag" in messages
            assert "no CLI flag" in messages
            assert "stale documentation" in messages

    @pytest.mark.parametrize("stem,rule,ext", SINGLE_FILE_RULES)
    def test_cli_exits_nonzero_on_positive(self, tmp_path, stem, rule, ext):
        """The acceptance-criteria form: the CLI itself gates."""
        root = _mini_project(tmp_path, rule, [f"{stem}_positive{ext}"])
        proc = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", "--root", root],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "GL0" in proc.stdout

    def test_cli_exits_nonzero_on_flag_registry_positive(self, tmp_path):
        src = os.path.join(FIXTURES, "gl004_positive")
        for f in os.listdir(src):
            shutil.copy(os.path.join(src, f), tmp_path)
        _mini_project(
            tmp_path,
            "flag-registry",
            [],
            extra_rule_cfg=[
                'config_module = "config.py"',
                'cli_module = "main.py"',
                "script_paths = []",
                'doc_paths = ["README.md"]',
            ],
        )
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "tools.graftlint",
                "--root",
                str(tmp_path),
            ],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "GL004" in proc.stdout


class TestRealTreeGate:
    def test_tree_is_clean(self):
        """`python -m tools.graftlint spark_examples_tpu/` exits 0 —
        the exact blocking invocation CI runs."""
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "tools.graftlint",
                "spark_examples_tpu/",
            ],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_jsonl_output_is_machine_readable(self):
        import json

        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "tools.graftlint",
                "--format",
                "jsonl",
                "spark_examples_tpu/",
            ],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,
        )
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        objs = [json.loads(ln) for ln in lines]
        assert "summary" in objs[-1]
        # The deliberate session-root suppression is visible data:
        assert objs[-1]["summary"]["suppressed"].get("span-contract", 0) >= 1

    def test_list_rules_names_all_twelve(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", "--list-rules"],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,
        )
        assert proc.returncode == 0
        for code in (
            "GL001",
            "GL002",
            "GL003",
            "GL004",
            "GL005",
            "GL006",
            "GL007",
            "GL008",
            "GL009",
            "GL010",
            "GL011",
            "GL012",
        ):
            assert code in proc.stdout

    def test_self_lint_is_clean(self):
        """The analyzer holds itself to its own concurrency bar: the
        CI self-lint leg (`python -m tools.graftlint tools/graftlint`)
        exits 0."""
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "tools.graftlint",
                "tools/graftlint",
            ],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


class TestSchemaSharing:
    """The span/metric contract rule and scripts/validate_trace.py must
    read from ONE name-set source — asserted, not assumed."""

    @pytest.fixture()
    def project(self):
        return Project(REPO_ROOT, load_config(REPO_ROOT))

    def test_rule_loads_schema_from_validate_trace(self):
        schema = span_contract_mod.load_schema(REPO_ROOT)
        assert schema is not None
        # The rule's schema object IS the script: same file, same sets.
        assert schema.__file__ == os.path.join(
            REPO_ROOT, "scripts", "validate_trace.py"
        )
        assert hasattr(schema, "_INGEST_SPANS")

    def test_extracted_ingest_spans_match_schema_exactly(self, project):
        schema = span_contract_mod.load_schema(REPO_ROOT)
        extracted = {
            name
            for name in span_contract_mod.extract_span_names(project)
            if name.startswith("ingest.")
        }
        assert extracted == set(schema._INGEST_SPANS), (
            "emitted ingest.* span literals and the validate_trace "
            "schema diverged — change both sides in one PR"
        )

    def test_extracted_feed_spans_match_schema_exactly(self, project):
        schema = span_contract_mod.load_schema(REPO_ROOT)
        extracted = {
            name
            for name in span_contract_mod.extract_span_names(project)
            if name.startswith("feed.")
        }
        assert extracted == set(schema._FEED_SPANS), (
            "emitted feed.* span literals and the validate_trace "
            "schema diverged — change both sides in one PR"
        )

    def test_extracted_job_spans_match_schema_exactly(self, project):
        schema = span_contract_mod.load_schema(REPO_ROOT)
        extracted = {
            name
            for name in span_contract_mod.extract_span_names(project)
            if name.startswith("job.")
        }
        assert extracted == set(schema._JOB_SPANS), (
            "emitted job.* span literals and the validate_trace "
            "schema diverged — change both sides in one PR"
        )

    def test_extracted_sparse_spans_match_schema_exactly(self, project):
        schema = span_contract_mod.load_schema(REPO_ROOT)
        extracted = {
            name
            for name in span_contract_mod.extract_span_names(project)
            if name.startswith("gramian.sparse.")
        }
        assert extracted == set(schema._SPARSE_SPANS), (
            "emitted gramian.sparse.* span literals and the "
            "validate_trace schema diverged — change both sides in one "
            "PR"
        )

    def test_extracted_finish_spans_match_schema_exactly(self, project):
        schema = span_contract_mod.load_schema(REPO_ROOT)
        extracted = {
            name
            for name in span_contract_mod.extract_span_names(project)
            if name.startswith("finish.")
        }
        assert extracted == set(schema._FINISH_SPANS), (
            "emitted finish.* span literals and the validate_trace "
            "schema diverged — change both sides in one PR"
        )

    def test_extracted_pairhmm_spans_match_schema_exactly(self, project):
        schema = span_contract_mod.load_schema(REPO_ROOT)
        extracted = {
            name
            for name in span_contract_mod.extract_span_names(project)
            if name.startswith("pairhmm.")
        }
        assert extracted == set(schema._PAIRHMM_SPANS), (
            "emitted pairhmm.* span literals and the validate_trace "
            "schema diverged — change both sides in one PR"
        )

    def test_contract_metrics_registered_with_required_labels(self, project):
        schema = span_contract_mod.load_schema(REPO_ROOT)
        regs = span_contract_mod.extract_metric_registrations(project)
        for name in (*schema._WIRE_COUNTERS, schema._WIRE_HISTOGRAM):
            assert name in regs, f"wire metric {name} not registered"
            for _, _, _, labels in regs[name]:
                assert "transport" in labels
        for name in (*schema._INGEST_COUNTERS, schema._INGEST_HISTOGRAM):
            assert name in regs, f"ingest metric {name} not registered"
            for _, _, _, labels in regs[name]:
                assert "mode" in labels
        for name, label in schema._LABELED_COUNTERS.items():
            assert name in regs, f"labeled counter {name} not registered"
            for _, _, _, labels in regs[name]:
                assert label in labels, (
                    f"{name} registration missing .labels({label}=...)"
                )

    def test_schema_drift_is_detected(self, tmp_path):
        """End-to-end drift proof: a tree emitting an ingest span the
        schema doesn't know fails the rule in BOTH directions."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "feed.py").write_text(
            "from spark_examples_tpu import obs\n\n\n"
            "def stage():\n"
            "    with obs.span('ingest.typo'):\n"
            "        pass\n"
        )
        scripts = tmp_path / "scripts"
        scripts.mkdir()
        (scripts / "validate_trace.py").write_text(
            "_INGEST_SPANS = {'ingest.slice'}\n"
        )
        lines = ["[tool.graftlint]", "exclude = []"]
        for name in ALL_RULE_NAMES:
            lines.append(f'[tool.graftlint.rules."{name}"]')
            enabled = name == "span-contract"
            lines.append(f"enabled = {'true' if enabled else 'false'}")
            if enabled:
                lines.append('paths = ["pkg"]')
        (tmp_path / "pyproject.toml").write_text("\n".join(lines) + "\n")
        findings, _ = run_lint(str(tmp_path), [])
        messages = "\n".join(f.message for f in findings)
        assert "ingest.typo" in messages  # emitted-but-unknown direction
        assert "ingest.slice" in messages  # schema-but-unemitted direction

    def test_job_span_drift_is_detected(self, tmp_path):
        """The serving tier's job.* family gets the same two-way drift
        gate as the ingest sub-phases."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "tier.py").write_text(
            "from spark_examples_tpu import obs\n\n\n"
            "def run():\n"
            "    with obs.span('job.typo'):\n"
            "        pass\n"
        )
        scripts = tmp_path / "scripts"
        scripts.mkdir()
        (scripts / "validate_trace.py").write_text(
            "_JOB_SPANS = {'job.run'}\n"
        )
        lines = ["[tool.graftlint]", "exclude = []"]
        for name in ALL_RULE_NAMES:
            lines.append(f'[tool.graftlint.rules."{name}"]')
            enabled = name == "span-contract"
            lines.append(f"enabled = {'true' if enabled else 'false'}")
            if enabled:
                lines.append('paths = ["pkg"]')
        (tmp_path / "pyproject.toml").write_text("\n".join(lines) + "\n")
        findings, _ = run_lint(str(tmp_path), [])
        messages = "\n".join(f.message for f in findings)
        assert "job.typo" in messages  # emitted-but-unknown direction
        assert "job.run" in messages  # schema-but-unemitted direction

    def test_sparse_span_drift_is_detected(self, tmp_path):
        """The sparse Gramian's gramian.sparse.* family gets the same
        two-way drift gate as the ingest/job span sets."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "sparse.py").write_text(
            "from spark_examples_tpu import obs\n\n\n"
            "def accumulate():\n"
            "    with obs.span('gramian.sparse.typo'):\n"
            "        pass\n"
        )
        scripts = tmp_path / "scripts"
        scripts.mkdir()
        (scripts / "validate_trace.py").write_text(
            "_SPARSE_SPANS = {'gramian.sparse.window'}\n"
        )
        lines = ["[tool.graftlint]", "exclude = []"]
        for name in ALL_RULE_NAMES:
            lines.append(f'[tool.graftlint.rules."{name}"]')
            enabled = name == "span-contract"
            lines.append(f"enabled = {'true' if enabled else 'false'}")
            if enabled:
                lines.append('paths = ["pkg"]')
        (tmp_path / "pyproject.toml").write_text("\n".join(lines) + "\n")
        findings, _ = run_lint(str(tmp_path), [])
        messages = "\n".join(f.message for f in findings)
        assert "gramian.sparse.typo" in messages
        assert "gramian.sparse.window" in messages


    @pytest.mark.parametrize(
        "emitted,drift",
        [("feed.typo", True), ("feed.wait", False)],
        ids=["positive", "negative"],
    )
    def test_feed_span_drift(self, tmp_path, emitted, drift):
        """The device feed's feed.* family gets the same two-way drift
        gate; a tree that emits exactly the schema's set is clean."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "feed.py").write_text(
            "from spark_examples_tpu import obs\n\n\n"
            "def pull(q):\n"
            f"    with obs.span('{emitted}'):\n"
            "        return q.get()\n"
        )
        scripts = tmp_path / "scripts"
        scripts.mkdir()
        (scripts / "validate_trace.py").write_text(
            "_FEED_SPANS = {'feed.wait'}\n"
        )
        lines = ["[tool.graftlint]", "exclude = []"]
        for name in ALL_RULE_NAMES:
            lines.append(f'[tool.graftlint.rules."{name}"]')
            enabled = name == "span-contract"
            lines.append(f"enabled = {'true' if enabled else 'false'}")
            if enabled:
                lines.append('paths = ["pkg"]')
        (tmp_path / "pyproject.toml").write_text("\n".join(lines) + "\n")
        findings, _ = run_lint(str(tmp_path), [])
        messages = "\n".join(f.message for f in findings)
        if drift:
            assert "feed.typo" in messages  # emitted-but-unknown
            assert "feed.wait" in messages  # schema-but-unemitted
        else:
            assert findings == [], messages


class TestEngineBehavior:
    def test_find_root_walks_up(self, tmp_path):
        (tmp_path / "pyproject.toml").write_text("[tool.graftlint]\n")
        nested = tmp_path / "a" / "b"
        nested.mkdir(parents=True)
        assert find_root(str(nested)) == str(tmp_path)

    def test_path_narrowing_keeps_project_wide_rules(self, tmp_path):
        """CLI path scoping must not hide cross-file contract breaks."""
        src = os.path.join(FIXTURES, "gl004_positive")
        for f in os.listdir(src):
            shutil.copy(os.path.join(src, f), tmp_path)
        (tmp_path / "other").mkdir()
        _mini_project(
            tmp_path,
            "flag-registry",
            [],
            extra_rule_cfg=[
                'config_module = "config.py"',
                'cli_module = "main.py"',
                "script_paths = []",
                'doc_paths = ["README.md"]',
            ],
        )
        # Narrow to an unrelated subdir: flag-registry still reports.
        findings, _ = run_lint(str(tmp_path), ["other"])
        assert findings

    def test_syntax_error_fails_the_gate(self, tmp_path):
        """An unparseable file is skipped by every rule — so it must
        surface as its own (unsuppressible) finding, not a green exit."""
        root = _mini_project(tmp_path, "jit-purity", [])
        (tmp_path / "broken.py").write_text("def broken(:\n")
        findings, _ = run_lint(root, [])
        assert len(findings) == 1
        assert findings[0].code == "GL000"
        assert findings[0].path == "broken.py"

    def test_dot_prefixed_paths_survive_walk_and_exclude(self, tmp_path):
        """Regression: lstrip('./') stripped a charset, corrupting
        dot-prefixed names — hiding violations and deadening the
        shipped '.sanitize' exclude."""
        root = _mini_project(tmp_path, "native-gil", [])
        (tmp_path / ".hidden.cpp").write_text("PyObject* p;\n")
        findings, _ = run_lint(root, [])
        assert [f.path for f in findings] == [".hidden.cpp"]
        # And an exclude entry for the dot-dir actually excludes it:
        sub = tmp_path / ".sanitize"
        sub.mkdir()
        (sub / "gen.cpp").write_text("PyGILState_Ensure();\n")
        cfg = (tmp_path / "pyproject.toml").read_text()
        (tmp_path / "pyproject.toml").write_text(
            cfg.replace("exclude = []", 'exclude = [".sanitize"]')
        )
        findings, _ = run_lint(str(tmp_path), [])
        assert [f.path for f in findings] == [".hidden.cpp"]

    def test_cli_relative_paths_resolve_against_root(self, tmp_path):
        """Regression: positional paths resolved against cwd, so
        --root from elsewhere scoped every rule to nothing (false
        green)."""
        root = _mini_project(
            tmp_path, "native-gil", ["gl006_positive.cpp"]
        )
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "tools.graftlint",
                "--root",
                root,
                "gl006_positive.cpp",
            ],
            capture_output=True,
            text=True,
            cwd=REPO_ROOT,  # a cwd that is NOT the project root
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "GL006" in proc.stdout

    def test_jit_named_function_call_form_is_checked(self, tmp_path):
        """`jax.jit(named_fn)(x)` traces named_fn's body exactly like a
        decorator — the parallel/sharded.py idiom."""
        root = _mini_project(tmp_path, "jit-purity", [])
        (tmp_path / "mod.py").write_text(
            "import jax\n"
            "import numpy as np\n\n\n"
            "def _local(x):\n"
            "    return np.asarray(x)\n\n\n"
            "def run(x):\n"
            "    return jax.jit(_local)(x)\n"
        )
        findings, _ = run_lint(root, [])
        assert len(findings) == 1
        assert findings[0].rule == "jit-purity"

    def test_cpp_escaped_newline_keeps_line_numbers(self, tmp_path):
        """Regression: blanking a backslash-newline escape merged two
        source lines, shifting later findings (and pragma lookups)."""
        root = _mini_project(tmp_path, "native-gil", [])
        (tmp_path / "a.cpp").write_text(
            'const char* s = "a\\\n b";\n'  # escaped newline in literal
            "int x;\n"
            "PyObject* p;\n"  # line 4
        )
        findings, _ = run_lint(root, [])
        assert [(f.path, f.line) for f in findings] == [("a.cpp", 4)]
