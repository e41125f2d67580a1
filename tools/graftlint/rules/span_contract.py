"""GL003 span-contract: telemetry emission and schema can never diverge.

``scripts/validate_trace.py`` is the runtime schema gate for telemetry
artifacts: it pins the closed ``ingest.*`` span set and the wire/ingest
metric contracts (transport/mode labels, histogram triplets). But the
gate only fires on artifacts a run happened to emit — rename a span at
the emission site and every artifact simply stops carrying it, forever
green. This rule closes the loop statically:

- every ``span(...)`` call must be used as a context manager (``with
  obs.span(...)``): a bare open/close pair leaks the span on any
  exception path and silently corrupts the trace nesting;
- the set of ``ingest.*`` span name literals in the tree must equal
  ``validate_trace._INGEST_SPANS`` **exactly** (both directions — an
  emitted name the schema does not know, or a schema name nothing emits,
  is a finding);
- every metric name in the wire/ingest contracts must be registered
  somewhere, and its registration must chain the label the schema
  requires (``transport=`` for wire, ``mode=`` for ingest).

The schema is imported from ``scripts/validate_trace.py`` itself — one
name-set source, shared, so the two sides provably cannot drift (the
meta-test in tests/test_graftlint.py asserts this sharing).
"""

from __future__ import annotations

import ast
import importlib.util
import os
from typing import Any, Dict, Iterable, List, Optional, Set, Tuple

from tools.graftlint.astutil import call_name, literal_str
from tools.graftlint.engine import Finding, Project

NAME = "span-contract"
CODE = "GL003"

DEFAULT_PATHS = ("spark_examples_tpu",)
SCHEMA_SCRIPT = "scripts/validate_trace.py"

_REGISTRATION_ATTRS = ("counter", "gauge", "histogram")


def load_schema(root: str) -> Optional[Any]:
    """Import scripts/validate_trace.py from the project root (stdlib-
    only module; None when absent, e.g. in fixture mini-projects)."""
    path = os.path.join(root, SCHEMA_SCRIPT)
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "graftlint_validate_trace", path
    )
    if spec is None or spec.loader is None:
        return None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span_calls(tree: ast.AST) -> List[Tuple[ast.Call, bool]]:
    """(call, used_as_context_manager) for every ``*.span(...)`` call."""
    with_items: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                with_items.add(id(item.context_expr))
    out: List[Tuple[ast.Call, bool]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        cname = call_name(node)
        if cname is None:
            # e.g. get_tracer().span(...): dotted_name can't flatten a
            # call in the chain; look at the raw attribute.
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "span"
            ):
                out.append((node, id(node) in with_items))
            continue
        if cname == "span" or cname.endswith(".span"):
            out.append((node, id(node) in with_items))
    return out


def extract_span_names(project: Project) -> Dict[str, List[Tuple[str, int]]]:
    """Literal span name -> [(path, line), ...] across the scope."""
    names: Dict[str, List[Tuple[str, int]]] = {}
    for top in project.rule_paths(NAME, DEFAULT_PATHS):
        for rel in project.walk(top):
            ctx = project.file(rel)
            if ctx is None or ctx.tree is None:
                continue
            for call, _ in _span_calls(ctx.tree):
                lit = literal_str(call.args[0]) if call.args else None
                if lit is not None:
                    names.setdefault(lit, []).append((rel, call.lineno))
    return names


def extract_instant_names(
    project: Project,
) -> Dict[str, List[Tuple[str, int]]]:
    """Literal instant name -> [(path, line), ...] across the scope.

    The ``pod.*`` family is emitted through ``instant(...)``, not
    ``span(...)`` — the timestamp pairs are points, not durations — so
    the closed-set cross-check needs its own call scan."""
    names: Dict[str, List[Tuple[str, int]]] = {}
    for top in project.rule_paths(NAME, DEFAULT_PATHS):
        for rel in project.walk(top):
            ctx = project.file(rel)
            if ctx is None or ctx.tree is None:
                continue
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                cname = call_name(node)
                is_instant = (
                    cname == "instant"
                    or (cname is not None and cname.endswith(".instant"))
                    or (
                        cname is None
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "instant"
                    )
                )
                if not is_instant:
                    continue
                lit = literal_str(node.args[0]) if node.args else None
                if lit is not None:
                    names.setdefault(lit, []).append((rel, node.lineno))
    return names


def extract_metric_registrations(
    project: Project,
) -> Dict[str, List[Tuple[str, int, str, Set[str]]]]:
    """Metric name -> [(path, line, kind, chained label kwargs)]."""
    regs: Dict[str, List[Tuple[str, int, str, Set[str]]]] = {}
    for top in project.rule_paths(NAME, DEFAULT_PATHS):
        for rel in project.walk(top):
            ctx = project.file(rel)
            if ctx is None or ctx.tree is None:
                continue
            # Registration call id -> labels kwargs chained onto it.
            labels_of: Dict[int, Set[str]] = {}
            for node in ast.walk(ctx.tree):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "labels"
                    and isinstance(node.func.value, ast.Call)
                ):
                    labels_of[id(node.func.value)] = {
                        kw.arg for kw in node.keywords if kw.arg
                    }
            for node in ast.walk(ctx.tree):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _REGISTRATION_ATTRS
                ):
                    continue
                lit = literal_str(node.args[0]) if node.args else None
                if lit is None:
                    continue
                regs.setdefault(lit, []).append(
                    (
                        rel,
                        node.lineno,
                        node.func.attr,
                        labels_of.get(id(node), set()),
                    )
                )
    return regs


def _schema_line(project: Project, needle: str) -> int:
    ctx = project.file(SCHEMA_SCRIPT)
    if ctx is not None:
        for lineno, line in enumerate(ctx.lines, 1):
            if needle in line:
                return lineno
    return 1


class SpanContractRule:
    name = NAME
    code = CODE
    summary = (
        "spans are context-managed; ingest.*/feed.*/job.*/gramian.sparse.*/"
        "gramian.sketch.*/pairhmm.* span names, pod.* instant names, "
        "and wire/ingest/serving/sparse/sketch metric registrations "
        "match scripts/validate_trace.py exactly"
    )
    project_wide = True

    def check(self, project: Project) -> Iterable[Finding]:
        findings: List[Finding] = []
        # 1. Context-manager discipline at every span call site.
        for top in project.rule_paths(NAME, DEFAULT_PATHS):
            for rel in project.walk(top):
                ctx = project.file(rel)
                if ctx is None or ctx.tree is None:
                    continue
                for call, managed in _span_calls(ctx.tree):
                    if not managed:
                        findings.append(
                            Finding(
                                NAME,
                                CODE,
                                rel,
                                call.lineno,
                                "span opened outside a `with` block: a "
                                "bare open/close pair leaks the span on "
                                "any exception path and corrupts trace "
                                "nesting",
                            )
                        )
        # 2-3. Name-set cross-check against the runtime schema — the
        # same closed-set discipline for each prefixed span family
        # (ingest sub-phases, device feed, serving job tier).
        schema = load_schema(project.root)
        if schema is None:
            return findings
        span_names = extract_span_names(project)
        for prefix, attr in (
            ("ingest.", "_INGEST_SPANS"),
            ("feed.", "_FEED_SPANS"),
            ("job.", "_JOB_SPANS"),
            ("gramian.sparse.", "_SPARSE_SPANS"),
            ("finish.", "_FINISH_SPANS"),
            ("gramian.sketch.", "_SKETCH_SPANS"),
            ("pairhmm.", "_PAIRHMM_SPANS"),
        ):
            emitted = {n for n in span_names if n.startswith(prefix)}
            schema_spans: Set[str] = set(getattr(schema, attr, set()))
            for name in sorted(emitted - schema_spans):
                rel, line = span_names[name][0]
                findings.append(
                    Finding(
                        NAME,
                        CODE,
                        rel,
                        line,
                        f"span {name!r} is not in validate_trace."
                        f"{attr} — artifacts carrying it fail the "
                        "runtime schema gate; add it to the schema in "
                        "the same change",
                    )
                )
            for name in sorted(schema_spans - emitted):
                findings.append(
                    Finding(
                        NAME,
                        CODE,
                        SCHEMA_SCRIPT,
                        _schema_line(project, f'"{name}"'),
                        f"schema span {name!r} is emitted nowhere in "
                        "the tree (literal scan) — dead schema entries "
                        "hide renames; remove it or restore the "
                        "emission",
                    )
                )
        # 3b. The pod.* instant family, both directions — same closed-
        # set discipline, over instant() calls instead of span() calls
        # (merge_pod_trace.py keys its clock alignment on these names).
        instant_names = extract_instant_names(project)
        pod_schema: Set[str] = set(getattr(schema, "_POD_INSTANTS", set()))
        pod_emitted = {
            n for n in instant_names if n.startswith("pod.")
        }
        for name in sorted(pod_emitted - pod_schema):
            rel, line = instant_names[name][0]
            findings.append(
                Finding(
                    NAME,
                    CODE,
                    rel,
                    line,
                    f"instant {name!r} is not in validate_trace."
                    "_POD_INSTANTS — artifacts carrying it fail the "
                    "runtime schema gate; add it to the schema in the "
                    "same change",
                )
            )
        for name in sorted(pod_schema - pod_emitted):
            findings.append(
                Finding(
                    NAME,
                    CODE,
                    SCHEMA_SCRIPT,
                    _schema_line(project, f'"{name}"'),
                    f"schema pod instant {name!r} is emitted nowhere "
                    "in the tree (literal scan) — dead schema entries "
                    "hide renames; remove it or restore the emission",
                )
            )
        # 4-5. Metric contract: required names registered, with the
        # labels the schema's sample checks demand.
        regs = extract_metric_registrations(project)
        required: Dict[str, Optional[str]] = {
            name: "transport"
            for name in getattr(schema, "_WIRE_COUNTERS", ())
        }
        wire_hist = getattr(schema, "_WIRE_HISTOGRAM", None)
        if wire_hist:
            required[wire_hist] = "transport"
        for name in getattr(schema, "_INGEST_COUNTERS", ()):
            required[name] = "mode"
        ingest_hist = getattr(schema, "_INGEST_HISTOGRAM", None)
        if ingest_hist:
            required[ingest_hist] = "mode"
        # Serving/resilience counters: the schema names the label each
        # sample must carry (breaker probes, job outcomes, sheds).
        required.update(getattr(schema, "_LABELED_COUNTERS", {}))
        # Plain serving histograms and gauges: registration required,
        # no label contract (None = skip the label check).
        for name in getattr(schema, "_SERVING_HISTOGRAMS", ()):
            required[name] = None
        for name in getattr(schema, "_SERVING_GAUGES", ()):
            required[name] = None
        for name, label in sorted(required.items()):
            sites = regs.get(name)
            if not sites:
                findings.append(
                    Finding(
                        NAME,
                        CODE,
                        SCHEMA_SCRIPT,
                        _schema_line(project, f'"{name}"'),
                        f"schema metric {name!r} is registered nowhere "
                        "in the tree — the runtime contract it encodes "
                        "is dead",
                    )
                )
                continue
            for rel, line, _kind, labels in sites:
                if label is None:
                    continue
                if label not in labels:
                    findings.append(
                        Finding(
                            NAME,
                            CODE,
                            rel,
                            line,
                            f"metric {name!r} registration does not "
                            f"chain .labels({label}=...) — "
                            "validate_trace rejects its samples "
                            f"without the {label!r} label",
                        )
                    )
        return findings


RULE = SpanContractRule()
