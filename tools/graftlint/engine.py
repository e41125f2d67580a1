"""graftlint engine: file discovery, config, pragmas, rule runner, output.

The engine is rule-agnostic: a rule is any object with ``name``,
``code``, ``summary`` and a ``check(project) -> Iterable[Finding]``
method (see :mod:`tools.graftlint.rules`). The engine owns everything
rules should not re-implement — parsing files once, pragma suppression,
config scoping, and the two output formats (human lines and JSONL for
machine consumption in CI).

Design constraints baked in:

- **stdlib only** — must run on any dev box / CI image with no installs
  (the same bar scripts/validate_trace.py holds itself to);
- **suppressions are data** — every pragma hit is counted per rule and
  shown in the summary, so silencing debt stays visible.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "Finding",
    "FileCtx",
    "Project",
    "load_config",
    "run_lint",
    "sarif_document",
    "main",
]

_PRAGMA = re.compile(
    r"(?:#|//)\s*graftlint:\s*(disable|disable-file)\s*=\s*"
    r"([a-z0-9_,\- ]+)"
)
# Pragmas that suppress for the whole file must sit near the top, so a
# reviewer reading the file head sees the debt declaration.
_FILE_PRAGMA_WINDOW = 10


@dataclasses.dataclass
class Finding:
    """One rule violation at a source location."""

    rule: str  # rule name, e.g. "jit-purity"
    code: str  # stable id, e.g. "GL001"
    path: str  # repo-relative path
    line: int  # 1-based
    message: str

    def human(self) -> str:
        return f"{self.path}:{self.line}: {self.code}[{self.rule}] {self.message}"

    def jsonl(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)


class FileCtx:
    """One parsed source file: text, lines, AST (Python only), pragmas."""

    def __init__(self, root: str, relpath: str, text: str) -> None:
        self.root = root
        self.relpath = relpath
        self.text = text
        self.lines = text.splitlines()
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[str] = None
        self.parse_error_line = 1
        if relpath.endswith(".py"):
            try:
                self.tree = ast.parse(text, filename=relpath)
            except SyntaxError as e:
                self.parse_error = f"syntax error: {e.msg}"
                self.parse_error_line = e.lineno or 1
        # line -> set of rule names disabled on that line
        self.line_pragmas: Dict[int, set] = {}
        self.file_pragmas: set = set()
        for lineno, line in enumerate(self.lines, 1):
            m = _PRAGMA.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
            if m.group(1) == "disable-file":
                if lineno <= _FILE_PRAGMA_WINDOW:
                    self.file_pragmas |= rules
            else:
                self.line_pragmas[lineno] = (
                    self.line_pragmas.get(lineno, set()) | rules
                )

    def suppressed(self, rule: str, line: int) -> bool:
        """A finding is suppressed by a same-line pragma, a pragma on
        the line directly above (for lines where a trailing comment
        will not fit), or a file-level pragma."""
        if rule in self.file_pragmas:
            return True
        for at in (line, line - 1):
            if rule in self.line_pragmas.get(at, set()):
                return True
        return False


class Project:
    """The analyzed tree: config + lazily-parsed files keyed by relpath."""

    def __init__(self, root: str, config: Dict[str, Any]) -> None:
        self.root = os.path.abspath(root)
        self.config = config
        self._files: Dict[str, Optional[FileCtx]] = {}

    def file(self, relpath: str) -> Optional[FileCtx]:
        relpath = relpath.replace(os.sep, "/")
        if relpath not in self._files:
            abspath = os.path.join(self.root, relpath)
            try:
                with open(abspath, encoding="utf-8", errors="replace") as f:
                    text = f.read()
            except OSError:
                self._files[relpath] = None
            else:
                self._files[relpath] = FileCtx(self.root, relpath, text)
        return self._files[relpath]

    def walk(self, top: str, suffixes: Sequence[str] = (".py",)) -> List[str]:
        """Repo-relative paths under ``top`` with one of ``suffixes``,
        minus config-excluded subtrees, sorted for stable output."""
        exclude = tuple(self.config.get("exclude", ()))
        out: List[str] = []
        top_abs = os.path.join(self.root, top)
        if os.path.isfile(top_abs):
            rel = os.path.relpath(top_abs, self.root).replace(os.sep, "/")
            return [rel] if not _excluded(rel, exclude) else []
        for dirpath, dirnames, filenames in os.walk(top_abs):
            rel_dir = os.path.relpath(dirpath, self.root).replace(os.sep, "/")
            dirnames[:] = [
                d
                for d in sorted(dirnames)
                if not _excluded(_relnorm(f"{rel_dir}/{d}"), exclude)
            ]
            for fn in sorted(filenames):
                if not fn.endswith(tuple(suffixes)):
                    continue
                rel = _relnorm(f"{rel_dir}/{fn}")
                if not _excluded(rel, exclude):
                    out.append(rel)
        return out

    def rule_paths(self, rule_name: str, default: Sequence[str]) -> List[str]:
        rules_cfg = self.config.get("rules", {})
        cfg = rules_cfg.get(rule_name, {}) if isinstance(rules_cfg, dict) else {}
        return list(cfg.get("paths", default))

    def rule_enabled(self, rule_name: str) -> bool:
        rules_cfg = self.config.get("rules", {})
        cfg = rules_cfg.get(rule_name, {}) if isinstance(rules_cfg, dict) else {}
        return bool(cfg.get("enabled", True))


def _relnorm(rel: str) -> str:
    """Strip a leading ``./`` *prefix* (``str.lstrip`` strips a charset
    and would corrupt dot-prefixed names like ``.sanitize``)."""
    while rel.startswith("./"):
        rel = rel[2:]
    return rel


def _excluded(rel: str, exclude: Sequence[str]) -> bool:
    return any(
        rel == ex or rel.startswith(ex.rstrip("/") + "/") for ex in exclude
    )


# -- config ----------------------------------------------------------------


def load_config(root: str) -> Dict[str, Any]:
    """``[tool.graftlint]`` from ``<root>/pyproject.toml`` (or {})."""
    path = os.path.join(root, "pyproject.toml")
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return {}
    import tomllib

    return tomllib.loads(text).get("tool", {}).get("graftlint", {}) or {}


def find_root(start: str) -> str:
    """Nearest ancestor of ``start`` holding a pyproject.toml."""
    d = os.path.abspath(start)
    while True:
        if os.path.exists(os.path.join(d, "pyproject.toml")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            return os.path.abspath(start)
        d = parent


# -- runner ----------------------------------------------------------------


def run_lint(
    root: str,
    paths: Sequence[str],
    rules: Optional[Sequence[Any]] = None,
) -> Tuple[List["Finding"], Dict[str, int]]:
    """Run all (enabled) rules; returns (findings, suppressed_counts).

    ``paths`` narrows *per-file* scoping: a rule only reports findings in
    files under one of the given repo-relative paths. Project-wide
    cross-check rules (span-contract, flag-registry) always examine
    their full configured scope — a contract between N files cannot be
    checked through a keyhole — but their findings are still attributed
    to real files and reported regardless of ``paths``, because a broken
    cross-file contract is never out of scope.
    """
    if rules is None:
        from tools.graftlint.rules import ALL_RULES

        rules = ALL_RULES
    config = load_config(root)
    project = Project(root, config)
    findings: List[Finding] = []
    suppressed: Dict[str, int] = {}
    for rule in rules:
        if not project.rule_enabled(rule.name):
            continue
        for finding in rule.check(project):
            if not _in_scope(finding, rule, paths):
                continue
            ctx = project.file(finding.path)
            if ctx is not None and ctx.suppressed(rule.name, finding.line):
                suppressed[rule.name] = suppressed.get(rule.name, 0) + 1
                continue
            findings.append(finding)
    # A Python file in scope that does not parse must FAIL the gate,
    # not silently pass it: every rule skips `tree is None` files, so
    # without this the most broken files are the only ones ungated.
    # Not suppressible by design.
    for rel, ctx in sorted(project._files.items()):
        if ctx is None or not ctx.parse_error:
            continue
        finding = Finding(
            "parse-error",
            "GL000",
            rel,
            ctx.parse_error_line,
            f"{ctx.parse_error} — unparseable files cannot be analyzed, "
            "so no invariant is proven here; fix the syntax first",
        )
        if _in_scope(finding, _PARSE_ERROR_SCOPE, paths):
            findings.append(finding)
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    return findings, suppressed


class _ParseErrorScope:
    project_wide = False


_PARSE_ERROR_SCOPE = _ParseErrorScope()


def _in_scope(finding: Finding, rule: Any, paths: Sequence[str]) -> bool:
    if not paths or getattr(rule, "project_wide", False):
        return True
    norm = [p.replace(os.sep, "/").rstrip("/") for p in paths]
    return any(
        finding.path == p or finding.path.startswith(p + "/") for p in norm
    )


def sarif_document(findings: Sequence[Finding]) -> Dict[str, Any]:
    """Findings as one SARIF 2.1.0 run — the schema GitHub code
    scanning ingests for inline PR annotations. Rule metadata comes
    from the live registry so every GLxxx id resolves even on a clean
    run (an empty ``results`` array with full ``rules`` is how SARIF
    says "checked and found nothing", not "didn't check")."""
    from tools.graftlint.rules import ALL_RULES

    rules = [
        {
            "id": rule.code,
            "name": rule.name,
            "shortDescription": {"text": rule.summary},
        }
        for rule in ALL_RULES
    ]
    results = [
        {
            "ruleId": f.code,
            "level": "error",
            "message": {"text": f"[{f.rule}] {f.message}"},
            "locations": [
                {
                    "physicalLocation": {
                        "artifactLocation": {
                            "uri": f.path,
                            "uriBaseId": "SRCROOT",
                        },
                        "region": {"startLine": max(f.line, 1)},
                    }
                }
            ],
        }
        for f in findings
    ]
    return {
        "$schema": (
            "https://raw.githubusercontent.com/oasis-tcs/sarif-spec/"
            "master/Schemata/sarif-schema-2.1.0.json"
        ),
        "version": "2.1.0",
        "runs": [
            {
                "tool": {
                    "driver": {
                        "name": "graftlint",
                        "informationUri": (
                            "docs/STATIC_ANALYSIS.md"
                        ),
                        "rules": rules,
                    }
                },
                "results": results,
            }
        ],
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    from tools.graftlint.rules import ALL_RULES

    parser = argparse.ArgumentParser(
        prog="python -m tools.graftlint",
        description="Project-invariant static analysis for spark_examples_tpu",
    )
    parser.add_argument(
        "paths",
        nargs="*",
        help="Repo-relative files/directories to report on "
        "(default: everything in the configured scopes)",
    )
    parser.add_argument(
        "--root",
        default=None,
        help="Project root (default: nearest ancestor with pyproject.toml)",
    )
    parser.add_argument(
        "--format",
        choices=("human", "jsonl", "sarif"),
        default="human",
        help="Output format (jsonl: one finding object per line plus a "
        "trailing summary object; sarif: one SARIF 2.1.0 document for "
        "code-scanning upload)",
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="List rules and exit"
    )
    parser.add_argument(
        "--lock-graph",
        action="store_true",
        help="Print the GL008-derived lock-acquisition hierarchy as "
        "JSON and exit (the exact payload docs/CONCURRENCY.md embeds "
        "and the drift test pins)",
    )
    parser.add_argument(
        "--collective-order",
        action="store_true",
        help="Print the GL010-derived per-function lockstep collective "
        "sequences as JSON and exit (the exact payload "
        "docs/CONCURRENCY.md embeds and the drift test pins)",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule in ALL_RULES:
            print(f"{rule.code}  {rule.name:20s} {rule.summary}")
        return 0

    if args.lock_graph:
        from tools.graftlint.rules.deadlock_order import lock_graph

        root = args.root or find_root(os.getcwd())
        project = Project(root, load_config(root))
        print(json.dumps(lock_graph(project), indent=2, sort_keys=True))
        return 0

    if args.collective_order:
        from tools.graftlint.rules.collective_congruence import (
            collective_order,
        )

        root = args.root or find_root(os.getcwd())
        project = Project(root, load_config(root))
        print(
            json.dumps(collective_order(project), indent=2, sort_keys=True)
        )
        return 0

    root = args.root or find_root(os.getcwd())
    # Relative positional paths are ROOT-relative (as the help text
    # says): resolving them against a different cwd would silently
    # scope every rule to nothing and exit a false green 0.
    rel_paths = [
        os.path.relpath(
            p if os.path.isabs(p) else os.path.join(root, p), root
        )
        for p in args.paths
    ]
    findings, suppressed = run_lint(root, rel_paths)

    if args.format == "sarif":
        print(json.dumps(sarif_document(findings), sort_keys=True))
    elif args.format == "jsonl":
        for f in findings:
            print(f.jsonl())
        print(
            json.dumps(
                {
                    "summary": {
                        "findings": len(findings),
                        "suppressed": suppressed,
                    }
                },
                sort_keys=True,
            )
        )
    else:
        for f in findings:
            print(f.human())
        supp_total = sum(suppressed.values())
        detail = (
            " ("
            + ", ".join(f"{k}: {v}" for k, v in sorted(suppressed.items()))
            + ")"
            if suppressed
            else ""
        )
        print(
            f"graftlint: {len(findings)} finding(s), "
            f"{supp_total} suppressed by pragma{detail}",
            file=sys.stderr,
        )
    return 1 if findings else 0
