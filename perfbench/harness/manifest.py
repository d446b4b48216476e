"""BENCHMARK.json and the data files it names, found by name.

A cell is an entry of ``workloads``: it names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``).
A per-layer metric is ``layer_metrics/<name>.json`` (+ an optional
``<name>.py`` reader). A configuration of a family the stock reference
does not know names its own module (``"reference": "<path>.py"``: the
plain reference, the seeded checkpoint and, optionally, the cost counts),
and one whose step is not one token a sequence names the file that drives
its engine in the check (``"check": {"drive": "<path>.py"}``).
Adding any of them adds files and edits none: every function here takes
the root directory, so the tests load throw-away examples from a temporary
one."""

from __future__ import annotations

import ast
import importlib.util
import json
import os
import re
from typing import Dict, List, Optional, Set

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# `reduced` may never name a width (the contract's list).
WIDTH_RE = re.compile(
    r"((hidden|intermediate|latent|state|proj)\w*_size$|_dim$|_rank$|"
    r"^head_|expan|experts_per_tok|^n_embd$|^n_inner$)")


# What a configuration's module has to define (``tick_cost`` is optional),
# and what the file that drives its engine has to (``rows_needed`` is).
REFERENCE_NEEDS = ("make_weights", "forward")
DRIVE_NEEDS = ("drive",)


class ManifestError(ValueError):
    pass


def load_module(path: str):
    """A Python file of the benchmark, loaded from its path: a per-layer
    metric's reader, a configuration's reference."""
    tag = re.sub(r"\W", "_", os.path.splitext(os.path.basename(path))[0])
    spec = importlib.util.spec_from_file_location("perfbench_file_" + tag,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def defined_names(path: str) -> Set[str]:
    """The names a Python file binds at its top level, read without
    running it (the benchmark's parent process stays free of JAX)."""
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), path)
    except FileNotFoundError:
        raise ManifestError(f"missing file {path}") from None
    except SyntaxError as exc:
        raise ManifestError(f"{path}: {exc}") from None
    names: Set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, ast.Assign):
            names.update(t.id for t in node.targets
                         if isinstance(t, ast.Name))
    return names


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing file {path}") from None
    except json.JSONDecodeError as exc:
        raise ManifestError(f"{path}: {exc}") from None


class Manifest:
    def __init__(self, root: str, bench_dir: str = "perfbench"):
        self.root = os.path.abspath(root)
        self.dir = os.path.join(self.root, bench_dir)
        self.data = _load_json(os.path.join(self.root, "BENCHMARK.json"))

    # -- lookups by name ---------------------------------------------------

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r}; BENCHMARK.json has "
            f"{[w['name'] for w in self.data['workloads']]}")

    def config_entry(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise ManifestError(f"no config {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return _load_json(os.path.join(self.root,
                                       self.config_entry(name)["file"]))

    def reference_file(self, config: dict) -> Optional[str]:
        """The module a configuration's file names, or None where it
        takes the stock ``harness/reference.py`` and ``roofline.py``."""
        rel = config.get("reference")
        return os.path.join(self.root, rel) if rel else None

    def traffic(self, name: str) -> dict:
        return _load_json(os.path.join(self.dir, "traffic", name + ".json"))

    def layer_metric(self, name: str) -> dict:
        return _load_json(os.path.join(self.dir, "layer_metrics",
                                       name + ".json"))

    def layer_reader_file(self, name: str) -> Optional[str]:
        path = os.path.join(self.dir, "layer_metrics", name + ".py")
        return path if os.path.exists(path) else None

    def metrics_for(self, workload: str, kind: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports.
        An end-to-end metric without ``workloads`` is every cell's; a
        per-layer metric without it is reported in every cell that reports
        the end-to-end metric it ``moves``."""
        e2e = [m for m in self.data["end_to_end"]
               if "workloads" not in m or workload in m["workloads"]]
        if kind == "end_to_end":
            return e2e
        reported = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]

    # -- validation (the contract's rules that a file can break) -----------

    def _validate_module(self, entry: dict, key: str, rel,
                         needs: tuple) -> None:
        """A Python file a configuration names (``key`` says where): under
        ``paths``, there, and defining what the harness will call."""
        if rel is None:
            return
        where = f"config {entry['name']}: {key} {rel!r}"
        if not (isinstance(rel, str) and rel.endswith(".py")
                and ".." not in rel.split("/") and any(
                    rel.startswith(p + "/") for p in self.data["paths"])):
            raise ManifestError(f"{where} is no Python file under paths "
                                f"{self.data['paths']}")
        path = os.path.join(self.root, rel)
        if not os.path.isfile(path):
            raise ManifestError(f"{where} is missing")
        names = defined_names(path)
        missing = [n for n in needs if n not in names]
        if missing:
            raise ManifestError(f"{where} does not define "
                                f"{' or '.join(missing)}")

    def _validate_check_sizes(self, entry: dict, body: dict) -> None:
        """The check may be smaller than the cell only in what the cell
        already lists as reduced, never in a width, and never under one
        whole period of the layer pattern."""
        chk = body.get("check", {})
        where = f"config {entry['name']}: check"
        for key in chk.get("reduced_to", {}):
            if WIDTH_RE.search(key):
                raise ManifestError(f"{where}.reduced_to names a width "
                                    f"{key!r}")
            if key not in entry["reduced"]:
                raise ManifestError(
                    f"{where}.reduced_to names {key!r}, which is not in "
                    f"the configuration's reduced {entry['reduced']}")
        args = chk.get("model_args", [])
        if not (isinstance(args, list)
                and all(isinstance(a, str) for a in args)):
            raise ManifestError(f"{where}.model_args is no list of strings")
        period = body.get("layer_period")
        if period is not None and int(chk.get("layers", period)) < period:
            raise ManifestError(
                f"{where}.layers {chk['layers']} is under one whole period "
                f"of the layer pattern (layer_period {period})")

    def validate(self) -> None:
        d = self.data
        want = {"command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"}
        if set(d) != want:
            raise ManifestError(f"keys {sorted(d)} != {sorted(want)}")
        if not (isinstance(d["run_seconds"], int)
                and 1 <= d["run_seconds"] <= 51):
            raise ManifestError("run_seconds outside 1..51")
        names: Dict[str, str] = {}

        def name_ok(n, what):
            if not (isinstance(n, str) and NAME_RE.match(n)):
                raise ManifestError(f"bad {what} name {n!r}")

        for c in d["configs"]:
            name_ok(c["name"], "config")
            if set(c) != {"name", "source", "file", "reduced", "why"}:
                raise ManifestError(f"config {c['name']}: keys {sorted(c)}")
            if not any(c["file"].startswith(p + "/") for p in d["paths"]):
                raise ManifestError(f"{c['file']} is outside paths")
            for key in c["reduced"]:
                name_ok(key, "reduced key")
                if WIDTH_RE.search(key):
                    raise ManifestError(
                        f"config {c['name']}: reduced names a width {key!r}")
            body = self.config(c["name"])
            if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                raise ManifestError(
                    f"config {c['name']}: its file lists reduced="
                    f"{body.get('reduced')}, BENCHMARK.json {c['reduced']}")
            self._validate_module(c, "reference", body.get("reference"),
                                  REFERENCE_NEEDS)
            self._validate_module(c, "check.drive",
                                  body.get("check", {}).get("drive"),
                                  DRIVE_NEEDS)
            self._validate_check_sizes(c, body)
        cfg_names = [c["name"] for c in d["configs"]]
        if len(set(cfg_names)) != len(cfg_names):
            raise ManifestError("two configs share a name")
        cells = []
        for w in d["workloads"]:
            name_ok(w["name"], "workload")
            name_ok(w["traffic"], "traffic")
            if set(w) != {"name", "config", "traffic", "chips", "why"}:
                raise ManifestError(f"workload {w['name']}: keys {sorted(w)}")
            if w["config"] not in cfg_names:
                raise ManifestError(f"{w['name']}: unknown config")
            if w["chips"] not in (1, 4):
                raise ManifestError(f"{w['name']}: chips {w['chips']}")
            if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
                raise ManifestError(f"{w['name']}: why is not one line "
                                    "of 1..200 characters")
            self.traffic(w["traffic"])
            cells.append((w["config"], w["traffic"]))
        if len(set(cells)) != len(cells):
            raise ManifestError("a (config, traffic) pair appears twice")
        wl_names = [w["name"] for w in d["workloads"]]
        if len(set(wl_names)) != len(wl_names):
            raise ManifestError("two workloads share a name")
        four = sum(1 for w in d["workloads"] if w["chips"] == 4)
        if four > max(1, len(wl_names) // 4):
            raise ManifestError(f"{four} four-chip cells of {len(wl_names)}")
        unused = set(cfg_names) - {w["config"] for w in d["workloads"]}
        if unused:
            raise ManifestError(f"configs used by no cell: {sorted(unused)}")
        for kind in ("end_to_end", "per_layer"):
            for m in d[kind]:
                name_ok(m["name"], "metric")
                if m["name"] in names:
                    raise ManifestError(f"metric {m['name']} twice")
                names[m["name"]] = kind
                if not UNIT_RE.match(m["unit"]):
                    raise ManifestError(f"{m['name']}: unit {m['unit']!r}")
                if m["better"] not in ("lower", "higher"):
                    raise ManifestError(f"{m['name']}: better")
                if m["source"] not in SOURCES:
                    raise ManifestError(f"{m['name']}: source")
                for w in m.get("workloads", []):
                    if w not in wl_names:
                        raise ManifestError(f"{m['name']}: workload {w}")
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if "setup_s" not in e2e:
            raise ManifestError("no setup_s")
        for m in d["end_to_end"]:
            if set(m) - {"workloads"} != {"name", "unit", "better", "bound",
                                         "source"}:
                raise ManifestError(f"{m['name']}: keys {sorted(m)}")
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"{m['name']}: end-to-end source")
            if not 0 < m["bound"] <= 0.1:
                raise ManifestError(f"{m['name']}: bound {m['bound']}")
        for m in d["per_layer"]:
            if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                         "layer", "moves"}:
                raise ManifestError(f"{m['name']}: keys {sorted(m)}")
            if m["moves"] not in e2e:
                raise ManifestError(f"{m['name']}: moves {m['moves']!r} is "
                                    "not an end-to-end metric")
            mover = e2e[m["moves"]]
            for w in m.get("workloads", ()):
                if "workloads" in mover and w not in mover["workloads"]:
                    raise ManifestError(
                        f"{m['name']}: cell {w} does not report "
                        f"{m['moves']}")
            desc = self.layer_metric(m["name"])
            for key in ("layer", "unit", "moves", "source"):
                if desc.get(key) != m[key]:
                    raise ManifestError(
                        f"{m['name']}: its file says {key}="
                        f"{desc.get(key)!r}, BENCHMARK.json {m[key]!r}")
        for w in wl_names:
            if len(self.metrics_for(w, "end_to_end")) < 2:
                raise ManifestError(f"{w}: fewer than two end-to-end metrics")
            if not self.metrics_for(w, "per_layer"):
                raise ManifestError(f"{w}: no per-layer metric")
