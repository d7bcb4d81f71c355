"""Finds everything by name: ``BENCHMARK.json`` names cells, configurations
and metrics; each has a file of its own under the benchmark's directory.

    configs/<config>.json    the configuration as it is run
    traffic/<traffic>.json   parameters of a traffic mix or a training job
    cells/<cell>.json        limits of the correctness check, sample sizes
    metrics/<metric>.py      one per-layer metric: ``read(run) -> float|None``
    families/<family>.py     what a configuration's ``family`` is: leaves,
                             builder, plain reference, counts (README.md)
    peaks.json               peaks by ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# what a family's module has to give: always, and for a cell of a traffic kind
FAMILY_NEEDS = ("REHEARSE", "leaf_specs", "build", "forward_logits")
KIND_NEEDS = {"train": ("TrainReference",),
              "open_loop": ("cache_bytes_per_context_token",)}


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # a dataclass defined there looks its module up
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, path=None):
        self.path = Path(path) if path else REPO / "BENCHMARK.json"
        self.repo = self.path.parent
        self.data = json.loads(self.path.read_text())
        self.root = self.repo / self.data["paths"][0]
        self._families = {}

    def _json(self, *parts):
        return json.loads(self.root.joinpath(*parts).read_text())

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}; it has "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.repo / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in {self.path}")

    def traffic(self, name: str) -> dict:
        return self._json("traffic", f"{name}.json")

    def cell(self, name: str) -> dict:
        return self._json("cells", f"{name}.json")

    def peaks(self, device_kind: str) -> dict:
        table = self._json("peaks.json")
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r}; "
                           f"peaks.json has {sorted(table)}")
        return table[device_kind]

    def metrics_of(self, cell: str, group: str) -> list:
        """Entries of ``end_to_end`` or ``per_layer`` that ``cell`` reports:
        those that list it under ``workloads``; a per-layer metric without
        the key belongs to every cell that reports what it ``moves``."""
        e2e = [m for m in self.data["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if group == "end_to_end":
            return e2e
        moved = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]

    def reader(self, metric: str):
        """The ``read`` function of ``metrics/<metric>.py``."""
        return _load(self.root / "metrics" / f"{metric}.py",
                     "benchmark_metric_" + re.sub(r"\W", "_", metric)).read

    def family(self, name: str):
        """The module ``families/<name>.py``, loaded once."""
        if name not in self._families:
            path = self.root / "families" / f"{name}.py"
            if not path.is_file():
                raise FileNotFoundError(
                    f"no module for model family {name!r}: looked for {path}")
            self._families[name] = _load(
                path, "benchmark_family_" + re.sub(r"\W", "_", name))
        return self._families[name]

    def _deployment_faults(self, w: dict, traffic: dict, seconds: float) -> list:
        """What ``serve_job.setup`` would refuse of a serving cell before it
        builds anything: a key of the traffic file's ``engine`` entry that is
        not the deployment's to state, a mix whose longest context, with the
        warm-up's tail, passes the engine's context."""
        from . import generator, serve_job

        try:
            serve_job.fit(traffic, self.config(w["config"]),
                          *generator.longest(traffic, seconds))
        except KeyError as e:
            return [f"cell {w['name']}: traffic/{w['traffic']}.json: lacks the key {e}"]
        except ValueError as e:
            return [f"cell {w['name']}: traffic/{w['traffic']}.json: {e}"]
        return []

    def validate(self) -> list:
        """Faults against the parts of the contract that can be checked
        without a chip; empty when sound."""
        d, bad = self.data, []
        keys = {"command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"}
        if set(d) != keys:
            bad.append(f"keys {sorted(set(d) ^ keys)} missing or unknown")
        names = lambda xs: [x["name"] for x in xs]
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            ns = names(d[group])
            bad += [f"{group}: bad name {n!r}" for n in ns if not NAME.match(n)]
            if len(set(ns)) != len(ns):
                bad.append(f"{group}: a name appears twice")
        if set(names(d["end_to_end"])) & set(names(d["per_layer"])):
            bad.append("a metric is both end-to-end and per-layer")
        if not 1 <= d["run_seconds"] <= 51:
            bad.append("run_seconds outside 1..51")
        cells, e2e = names(d["workloads"]), names(d["end_to_end"])
        if "setup_s" not in e2e:
            bad.append("no setup_s")
        four = sum(w["chips"] == 4 for w in d["workloads"])
        if four > max(1, len(cells) // 4):
            bad.append(f"{four} of {len(cells)} cells ask for four chips")
        pairs = [(w["config"], w["traffic"]) for w in d["workloads"]]
        if len(set(pairs)) != len(pairs):
            bad.append("a (config, traffic) pair appears twice")
        families = {}
        for c in d["configs"]:
            if c["name"] not in {w["config"] for w in d["workloads"]}:
                bad.append(f"config {c['name']} is used by no cell")
            if not (self.repo / c["file"]).is_file():
                bad.append(f"config file {c['file']} missing")
                continue
            cfg = self.config(c["name"])
            bad += [f"config {c['name']}: reduced key {k!r} is not a key of "
                    f"{c['file']}" for k in c["reduced"] if k not in cfg]
            try:
                fam = families[c["name"]] = self.family(cfg.get("family"))
            except FileNotFoundError as e:
                bad.append(f"config {c['name']}: {e}")
                continue
            bad += [f"config {c['name']}: families/{cfg['family']}.py has no {n}"
                    for n in FAMILY_NEEDS if not hasattr(fam, n)]
        for w in d["workloads"]:
            if w["config"] not in names(d["configs"]):
                bad.append(f"cell {w['name']}: unknown config")
            if w["chips"] not in (1, 4):
                bad.append(f"cell {w['name']}: chips must be 1 or 4")
            files = {kind: self.root / kind / f"{stem}.json"
                     for kind, stem in (("traffic", w["traffic"]), ("cells", w["name"]))}
            bad += [f"cell {w['name']}: no {kind}/{f.name}"
                    for kind, f in files.items() if not f.is_file()]
            if w["config"] in families and files["traffic"].is_file():
                traffic, fam = self.traffic(w["traffic"]), families[w["config"]]
                kind = traffic["kind"]
                bad += [f"cell {w['name']}: a {kind} cell of a family without {n} "
                        f"({Path(fam.__file__).name})"
                        for n in KIND_NEEDS.get(kind, ()) if not hasattr(fam, n)]
                if kind == "open_loop":
                    bad += self._deployment_faults(w, traffic, d["run_seconds"])
            mine = names(self.metrics_of(w["name"], "end_to_end"))
            if "setup_s" not in mine or len(mine) < 2:
                bad.append(f"cell {w['name']}: needs setup_s and one more metric")
            if not self.metrics_of(w["name"], "per_layer"):
                bad.append(f"cell {w['name']}: no per-layer metric")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT.match(m["unit"]):
                bad.append(f"metric {m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher") or m["source"] not in SOURCES:
                bad.append(f"metric {m['name']}: bad better/source")
            for c in m.get("workloads", []):
                if c not in cells:
                    bad.append(f"metric {m['name']}: unknown cell {c}")
        for m in d["end_to_end"]:
            if not 0 < m["bound"] <= 0.1:
                bad.append(f"metric {m['name']}: bound outside (0, 0.1]")
            if m["source"] not in ("host_clock", "device_trace"):
                bad.append(f"metric {m['name']}: an end-to-end metric is "
                           "taken by the benchmark itself")
        for m in d["per_layer"]:
            if m["moves"] not in e2e:
                bad.append(f"metric {m['name']}: moves unknown {m['moves']}")
            if not (self.root / "metrics" / f"{m['name']}.py").is_file():
                bad.append(f"metric {m['name']}: no metrics/{m['name']}.py")
            for c in m.get("workloads", []):
                if m["moves"] not in names(self.metrics_of(c, "end_to_end")):
                    bad.append(f"metric {m['name']}: cell {c} does not "
                               f"report {m['moves']}")
        return bad
