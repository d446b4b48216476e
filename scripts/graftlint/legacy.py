"""Ports of the four standalone ``scripts/check_*.py`` invariants.

Same semantics as the originals (which remain as thin shims over this
driver so their tier-1 subprocess tests keep passing), but emitting the
shared Finding format so one baseline file and one CLI cover everything:

  * ``bare-print``            — check_no_bare_print
  * ``metric-undocumented`` / ``metric-unknown`` / ``event-undocumented``
    / ``event-unknown`` / ``profiler-undocumented``
                              — check_metrics_documented
  * ``cli-mode-undocumented`` / ``cli-mode-unknown``
                              — check_cli_modes_documented
  * ``quant-uncovered``       — check_quant_coverage

The metrics analyzer imports the telemetry catalogs exactly as the
original did — telemetry is dependency-free by contract (no jax), and
importing is the only way to see computed names. Everything else works
from source text / AST, never importing jax-bearing modules.
"""

from __future__ import annotations

import ast
import importlib
import re
import sys
from typing import Dict, List, Optional, Set

from .core import Context, Finding, PKG_DIR

# --------------------------------------------------------------------------
# bare print
# --------------------------------------------------------------------------

CLI_ALLOWED_FUNC = "_emit"       # main.py's single sanctioned stdout funnel


def analyze_bare_print(ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    for mod in ctx.modules:
        allow = CLI_ALLOWED_FUNC if mod.path.name == "main.py" else None

        def walk(node, inside_allowed, qualname):
            for child in ast.iter_child_nodes(node):
                allowed, qn = inside_allowed, qualname
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qn = (f"{qualname}.{child.name}"
                          if qualname != "<module>" else child.name)
                    if child.name == allow:
                        allowed = True
                elif isinstance(child, ast.ClassDef):
                    qn = (f"{qualname}.{child.name}"
                          if qualname != "<module>" else child.name)
                if (isinstance(child, ast.Call)
                        and isinstance(child.func, ast.Name)
                        and child.func.id == "print"
                        and not allowed):
                    findings.append(Finding(
                        "bare-print", mod.rel, child.lineno, qualname,
                        f"bare print() in `{qualname}` — library code must "
                        "route diagnostics through logging (or _emit() in "
                        "main.py)"))
                walk(child, allowed, qn)

        walk(mod.tree, False, "<module>")
    return findings


# --------------------------------------------------------------------------
# metrics / events / profiler docs drift
# --------------------------------------------------------------------------

_DOC_METRIC_RE = re.compile(
    r"`((?:server|client|transport|scheduler|gateway)_[a-z0-9_]+"
    r"(?:_total|_seconds|_bytes|_ratio|_sessions|_hops|_depth|_rate))`"
)
_DOC_EVENT_RE = re.compile(r"^\|\s*`([a-z][a-z0-9_]+)`", re.MULTILINE)

_OBS_DOC = "docs/OBSERVABILITY.md"


def _telemetry(ctx: Context):
    """Import the (jax-free by contract) telemetry catalogs from ctx.repo."""
    root = str(ctx.repo)
    if root not in sys.path:
        sys.path.insert(0, root)
    cat = importlib.import_module(f"{PKG_DIR}.telemetry.catalog")
    ev = importlib.import_module(f"{PKG_DIR}.telemetry.events")
    prof = importlib.import_module(f"{PKG_DIR}.telemetry.profiling")
    return cat, ev, prof


def analyze_metrics_doc(ctx: Context) -> List[Finding]:
    text = ctx.docs_text.get(_OBS_DOC)
    if text is None:
        return [Finding("metric-undocumented", _OBS_DOC, 1, "<missing>",
                        f"missing {_OBS_DOC}")]
    cat, ev, prof = _telemetry(ctx)
    cat_rel = f"{PKG_DIR}/telemetry/catalog.py"
    ev_rel = f"{PKG_DIR}/telemetry/events.py"
    prof_rel = f"{PKG_DIR}/telemetry/profiling.py"
    findings: List[Finding] = []
    for n in cat.all_names():
        if f"`{n}`" not in text:
            findings.append(Finding(
                "metric-undocumented", cat_rel, 1, n,
                f"metric `{n}` in telemetry/catalog.py is missing from "
                f"{_OBS_DOC}"))
    for n in sorted({m for m in _DOC_METRIC_RE.findall(text)
                     if m not in cat.SPEC}):
        findings.append(Finding(
            "metric-unknown", _OBS_DOC, 1, n,
            f"metric `{n}` documented in {_OBS_DOC} is absent from "
            "telemetry/catalog.py"))
    for n in ev.all_event_names():
        if f"`{n}`" not in text:
            findings.append(Finding(
                "event-undocumented", ev_rel, 1, n,
                f"event `{n}` in telemetry/events.py is missing from "
                f"{_OBS_DOC}"))
    for n in sorted({m for m in _DOC_EVENT_RE.findall(text)
                     if m not in ev.EVENTS and m not in cat.SPEC
                     and m not in prof.PHASES
                     and m not in prof.DIGEST_FIELDS}):
        findings.append(Finding(
            "event-unknown", _OBS_DOC, 1, n,
            f"event `{n}` documented in {_OBS_DOC} is absent from "
            "telemetry/events.py"))
    for n in (*prof.PHASES, *prof.DIGEST_FIELDS):
        if f"`{n}`" not in text:
            findings.append(Finding(
                "profiler-undocumented", prof_rel, 1, n,
                f"profiler phase / digest field `{n}` is missing from "
                f"{_OBS_DOC}"))
    return findings


# --------------------------------------------------------------------------
# CLI mode docs drift
# --------------------------------------------------------------------------

def _parser_choices(src: str, flag: str) -> Optional[List[str]]:
    m = re.search(
        r'add_argument\(\s*"%s",\s*choices=\[(.*?)\]' % re.escape(flag),
        src, re.S)
    if not m:
        return None
    return re.findall(r'"([a-z0-9_-]+)"', m.group(1))


def analyze_cli_doc(ctx: Context) -> List[Finding]:
    main_mod = ctx.module("main.py")
    if main_mod is None:
        return []
    text = "\n".join(ctx.docs_text.values())
    findings: List[Finding] = []
    for flag in ("--mode", "--chaos_scenario"):
        choices = _parser_choices(main_mod.source, flag)
        if choices is None:
            findings.append(Finding(
                "cli-mode-undocumented", main_mod.rel, 1, flag,
                f"could not find {flag} choices in main.py — the argparse "
                "declaration moved; update scripts/graftlint/legacy.py"))
            continue
        used = set(re.findall(r"%s[ =]+([a-z0-9_-]+)" % re.escape(flag),
                              text))
        for c in choices:
            if c not in used:
                findings.append(Finding(
                    "cli-mode-undocumented", main_mod.rel, 1,
                    f"{flag}:{c}",
                    f"{flag} choice `{c}` is never shown in use in "
                    "README.md or docs/*.md"))
        for c in sorted(used - set(choices)):
            findings.append(Finding(
                "cli-mode-unknown", main_mod.rel, 1, f"{flag}:{c}",
                f"{flag} usage `{c}` in the docs is not a parser choice "
                "— renamed or removed mode lingering in prose"))
    return findings


# --------------------------------------------------------------------------
# quant coverage
# --------------------------------------------------------------------------

_CALL = r"(?:quantize_params|quantize_layers|_qp|_sqp)"
_ARGS = r"\((?:[^()]|\([^()]*\))*?"


def _quantize_calls(text: str, fmts) -> Set[str]:
    called = {f for f in fmts
              if re.search(_CALL + _ARGS + '"%s"' % re.escape(f), text)}
    if re.search(_CALL + r'\(\s*[a-zA-Z_][^,")]*\)', text):
        called.add("int8")      # mode omitted means int8 (signature default)
    return called


def analyze_quant_coverage(ctx: Context) -> List[Finding]:
    quant_mod = ctx.module("models/quant.py")
    if quant_mod is None:
        return []
    m = re.search(r"QUANT_BITS\s*=\s*\{(.*?)\}", quant_mod.source, re.S)
    if not m:
        return [Finding(
            "quant-uncovered", quant_mod.rel, 1, "QUANT_BITS",
            "could not find QUANT_BITS in models/quant.py — the format "
            "table moved; update scripts/graftlint/legacy.py")]
    fmts = [f for f in re.findall(r'"([a-z0-9_]+)"\s*:', m.group(1))
            if f != "none"]
    parity_cov: Set[str] = set()
    moe_cov: Set[str] = set()
    for rel, text in ctx.tests_text.items():
        if not rel.rsplit("/", 1)[-1].startswith("test_"):
            continue
        if not re.search(r"dequant|materializ", text):
            continue
        if not re.search(r"assert .*==|assert_array_equal", text):
            continue
        covered = _quantize_calls(text, fmts)
        parity_cov |= covered
        if re.search(r"mixtral|moe", text, re.I):
            moe_cov |= covered
    findings: List[Finding] = []
    for fmt in fmts:
        missing = []
        if fmt not in parity_cov:
            missing.append("parity test under tests/")
        if fmt not in moe_cov:
            missing.append("MoE-path parity test under tests/ "
                           "(mixtral/moe module)")
        if missing:
            findings.append(Finding(
                "quant-uncovered", quant_mod.rel, 1, fmt,
                f"quant format {fmt!r} (models/quant.py QUANT_BITS) "
                f"lacks: {', '.join(missing)}"))
    return findings
