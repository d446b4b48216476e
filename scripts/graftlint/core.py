"""graftlint driver: finding format, baseline policy, analyzer registry.

A Finding's `key` deliberately excludes the line number — baselines must
survive unrelated edits above a suppressed site. The anchor is the nearest
stable symbol (Class.method, attribute, verb, flag name), so a suppression
dies exactly when the code it excused changes shape.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import astutil

PKG_DIR = ("global_capstone_design_distributed_inference_of_llms"
           "_over_the_internet_tpu")
BASELINE_FILE = "graftlint_baseline.json"


@dataclasses.dataclass
class Finding:
    rule: str                  # e.g. "lock-unguarded-attr"
    path: str                  # repo-relative posix path
    line: int
    anchor: str                # stable symbol: "Class.method:attr", verb, ...
    message: str

    @property
    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.anchor}"

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "anchor": self.anchor, "key": self.key,
                "message": self.message}

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] {self.message}"
                f"\n    key: {self.key}")


@dataclasses.dataclass
class Context:
    """Everything an analyzer may look at. Built once, shared by all —
    parsing the ~60-module package once keeps the whole run subsecond."""

    repo: pathlib.Path
    modules: List[astutil.Module]          # the package under analysis
    protocol_text: str                     # docs/PROTOCOL.md ("" if absent)
    tests_text: Dict[str, str]             # tests/*.py rel-path -> source
    scripts_text: Dict[str, str]           # scripts/*.py rel-path -> source
    docs_text: Dict[str, str]              # README.md + docs/*.md

    def module(self, rel_suffix: str) -> Optional[astutil.Module]:
        for m in self.modules:
            if m.rel.endswith(rel_suffix):
                return m
        return None


def build_context(repo: pathlib.Path,
                  pkg: Optional[pathlib.Path] = None) -> Context:
    repo = pathlib.Path(repo).resolve()
    pkg = pkg if pkg is not None else repo / PKG_DIR
    modules = astutil.parse_tree(pkg, repo)
    proto = repo / "docs" / "PROTOCOL.md"

    def _texts(folder: pathlib.Path, pattern: str) -> Dict[str, str]:
        if not folder.is_dir():
            return {}
        return {p.relative_to(repo).as_posix(): p.read_text(encoding="utf-8")
                for p in sorted(folder.glob(pattern))}

    docs = _texts(repo / "docs", "*.md")
    readme = repo / "README.md"
    if readme.exists():
        docs["README.md"] = readme.read_text(encoding="utf-8")
    return Context(
        repo=repo,
        modules=modules,
        protocol_text=(proto.read_text(encoding="utf-8")
                       if proto.exists() else ""),
        tests_text=_texts(repo / "tests", "*.py"),
        scripts_text=_texts(repo / "scripts", "*.py"),
        docs_text=docs,
    )


# ---------------------------------------------------------------------------
# Baseline: suppression with mandatory justification
# ---------------------------------------------------------------------------

class BaselineError(ValueError):
    """The baseline file itself violates policy (missing reasons, bad
    shape) — a config error, reported distinctly from findings."""


class Baseline:
    """``graftlint_baseline.json``: ``{"findings": [{"key", "reason"}]}``.

    Policy (docs/STATIC_ANALYSIS.md): every entry carries a non-empty
    reason; entries that no longer match any finding are STALE and fail
    the run — fixed code must shed its suppression in the same change."""

    def __init__(self, entries: Dict[str, str]):
        self.entries = entries           # key -> reason

    @classmethod
    def load(cls, path: pathlib.Path) -> "Baseline":
        if not path.exists():
            return cls({})
        try:
            data = json.loads(path.read_text(encoding="utf-8"))
        except json.JSONDecodeError as exc:
            raise BaselineError(f"{path.name}: not valid JSON: {exc}")
        entries: Dict[str, str] = {}
        for i, row in enumerate(data.get("findings", [])):
            key = row.get("key")
            reason = row.get("reason")
            if not key:
                raise BaselineError(f"{path.name}: entry {i} has no key")
            if not (isinstance(reason, str) and reason.strip()):
                raise BaselineError(
                    f"{path.name}: entry {key!r} has no reason — every "
                    "suppression must say why it is intentional")
            if key in entries:
                raise BaselineError(f"{path.name}: duplicate key {key!r}")
            entries[key] = reason
        return cls(entries)

    def split(self, findings: Sequence[Finding]
              ) -> Tuple[List[Finding], List[Finding], List[str]]:
        """(new, suppressed, stale_keys)."""
        seen = {f.key for f in findings}
        new = [f for f in findings if f.key not in self.entries]
        suppressed = [f for f in findings if f.key in self.entries]
        stale = sorted(k for k in self.entries if k not in seen)
        return new, suppressed, stale


# ---------------------------------------------------------------------------
# Registry + driver
# ---------------------------------------------------------------------------

def _registry() -> Dict[str, Callable[[Context], List[Finding]]]:
    # Imported lazily so `import scripts.graftlint` stays cheap and a bug
    # in one analyzer module doesn't break the others' entry points.
    from . import (determinism, dispatch, env_flags, failures, jax_hygiene,
                   legacy, locks, recompile, spmd, wire_schema)

    return {
        "locks": locks.analyze,
        "jax": jax_hygiene.analyze,
        "dispatch": dispatch.analyze,
        "env_flags": env_flags.analyze,
        "failures": failures.analyze,
        "determinism": determinism.analyze,
        "spmd": spmd.analyze,
        "recompile": recompile.analyze,
        "wire_schema": wire_schema.analyze,
        "bare_print": legacy.analyze_bare_print,
        "metrics_doc": legacy.analyze_metrics_doc,
        "cli_doc": legacy.analyze_cli_doc,
        "quant_coverage": legacy.analyze_quant_coverage,
    }


ALL_ANALYZERS: Tuple[str, ...] = (
    "locks", "jax", "dispatch", "env_flags", "failures", "determinism",
    "spmd", "recompile", "wire_schema",
    "bare_print", "metrics_doc", "cli_doc", "quant_coverage",
)


def run_analyzers(ctx: Context,
                  names: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run the named analyzers (default: all) and return findings sorted
    by (path, line, rule). Duplicate keys within one run are collapsed to
    the first occurrence — one suppression covers one site, and a method
    touching the same unguarded attribute five times is one decision."""
    reg = _registry()
    names = list(names) if names is not None else list(ALL_ANALYZERS)
    unknown = [n for n in names if n not in reg]
    if unknown:
        raise KeyError(f"unknown analyzers: {unknown}; "
                       f"have {sorted(reg)}")
    findings: List[Finding] = []
    seen = set()
    for name in names:
        for f in reg[name](ctx):
            if f.key in seen:
                continue
            seen.add(f.key)
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.anchor))
    return findings
