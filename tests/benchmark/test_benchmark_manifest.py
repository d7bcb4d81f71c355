"""BENCHMARK.json and the files it names, and that each kind of thing can be
added by new files plus one entry with no edit to a file that exists."""
import json
import shutil
from pathlib import Path

import pytest

from benchmark.manifest import NAME, UNIT, Manifest

REPO = Path(__file__).resolve().parents[2]


def test_manifest_is_sound():
    m = Manifest()
    assert m.validate() == []
    d = m.data
    assert d["command"] == ["python3", "-m", "benchmark.run"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in d[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for metric in d["end_to_end"] + d["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
    four = [w for w in d["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(d["workloads"]) // 4)
    for w in d["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]


def test_every_per_layer_metric_moves_what_its_cells_report():
    m = Manifest()
    for w in m.data["workloads"]:
        e2e = {x["name"] for x in m.metrics_of(w["name"], "end_to_end")}
        per_layer = m.metrics_of(w["name"], "per_layer")
        assert per_layer and {"setup_s"} < e2e
        for metric in per_layer:
            assert metric["moves"] in e2e, (w["name"], metric["name"])
            assert callable(m.reader(metric["name"]))


def test_configs_keep_published_widths():
    m = Manifest()
    xl, big = m.config("gpt3-xl-1p3b"), m.config("gpt3-6p7b-4chip")
    assert (xl["num_layers"], xl["hidden_size"], xl["num_heads"], xl["head_dim"]) == (24, 2048, 16, 128)
    assert (big["hidden_size"], big["num_heads"], big["head_dim"]) == (4096, 32, 128)
    assert big["published"]["num_layers"] == 32 and big["reduced"] == ["num_layers"]
    for c in m.data["configs"]:
        cfg = m.config(c["name"])
        assert c["reduced"] == cfg["reduced"] and c["source"] == cfg["source"]
        assert cfg["intermediate_size"] == 4 * cfg["hidden_size"]
        assert cfg["vocab_size"] == 50304 and cfg["max_position_embeddings"] == 2048


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        Manifest().peaks("cpu")
    assert Manifest().peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_validate_catches_faults(tmp_path):
    m = Manifest()
    bad = json.loads(json.dumps(m.data))
    bad["workloads"].append(dict(bad["workloads"][0], name="has space", chips=4))
    bad["per_layer"][0]["moves"] = "nothing"
    bad["end_to_end"][0]["bound"] = 0.5
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bad))
    shutil.copytree(m.root, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "recorded"))
    faults = "\n".join(Manifest(tmp_path / "BENCHMARK.json").validate())
    for want in ("bad name", "moves unknown", "bound outside", "pair appears twice"):
        assert want in faults, faults


def test_add_one_of_each_by_files_and_entries(tmp_path, capsys):
    """A later PR adds a configuration, a traffic mix, a cell and a per-layer
    metric: new files, one entry each, no existing file edited; the harness
    finds them by name and runs the cell (rehearsal sizes, CPU)."""
    m = Manifest()
    root = tmp_path / "benchmark"
    shutil.copytree(m.root, root, ignore=shutil.ignore_patterns("__pycache__", "recorded"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = dict(m.config("gpt3-xl-1p3b"), name="gpt3-large-760m", hidden_size=1536,
               num_heads=16, head_dim=96, intermediate_size=6144)
    (root / "configs" / "gpt3-large-760m.json").write_text(json.dumps(cfg))
    job = dict(m.traffic("pretrain-b2-s2048"), batch=4, seq=1024)
    (root / "traffic" / "pretrain-b4-s1024.json").write_text(json.dumps(job))
    (root / "cells" / "train-large-s1024.json").write_text(
        json.dumps(m.cell("train-xl-s2048")))
    (root / "metrics" / "steps_in_window.py").write_text(
        '"""Steps the window completed."""\n\ndef read(run):\n    return float(run["steps"])\n')
    data = json.loads(json.dumps(m.data))
    data["configs"].append({"name": "gpt3-large-760m", "source": cfg["source"],
                            "file": "benchmark/configs/gpt3-large-760m.json",
                            "reduced": [], "why": "a third width"})
    data["workloads"] += [
        {"name": "train-large-s1024", "config": "gpt3-large-760m",
         "traffic": "pretrain-b4-s1024", "chips": 1, "why": "shorter rows"}]
    for e in data["end_to_end"]:
        if e["name"] == "tokens_per_s_chip":
            e["workloads"].append("train-large-s1024")
    data["per_layer"].append({"name": "steps_in_window", "unit": "count", "better": "higher",
                              "source": "program_counter", "layer": "train entry",
                              "moves": "tokens_per_s_chip", "workloads": ["train-large-s1024"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    new = Manifest(tmp_path / "BENCHMARK.json")
    assert new.validate() == []
    names = [x["name"] for x in new.metrics_of("train-large-s1024", "per_layer")]
    assert "steps_in_window" in names and "mfu_pct" in names
    assert new.reader("steps_in_window")({"steps": 7}) == 7.0
    assert all(p.read_bytes() == b for p, b in before.items())

    from benchmark import run

    rc = run.main(["--workload", "train-large-s1024", "--seed", "2147483700",
                   "--seconds", "0.5", "--rehearse",
                   "--manifest", str(tmp_path / "BENCHMARK.json")])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] and line["correct"] and line["counts"]["steps"] > 0
