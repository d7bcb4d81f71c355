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
    for cfg in (xl, big):  # what Brown et al. leave to be assumed, of the GPT files alone
        assert cfg["intermediate_size"] == 4 * cfg["hidden_size"]
        assert cfg["vocab_size"] == 50304 and cfg["max_position_embeddings"] == 2048
    for c in m.data["configs"]:
        cfg = m.config(c["name"])
        assert c["reduced"] == cfg["reduced"] and c["source"] == cfg["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        Manifest().peaks("cpu")
    assert Manifest().peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _copy(tmp_path):
    """The benchmark's files copied under ``tmp_path``: (the repo's manifest,
    the copy's root, the manifest's data to edit)."""
    m = Manifest()
    shutil.copytree(m.root, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "recorded"))
    return m, tmp_path / "benchmark", json.loads(json.dumps(m.data))


def _manifest_of(tmp_path, data):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    return Manifest(tmp_path / "BENCHMARK.json")


def test_validate_catches_faults(tmp_path):
    _, _, bad = _copy(tmp_path)
    bad["workloads"].append(dict(bad["workloads"][0], name="has space", chips=4))
    bad["per_layer"][0]["moves"] = "nothing"
    bad["end_to_end"][0]["bound"] = 0.5
    faults = "\n".join(_manifest_of(tmp_path, bad).validate())
    for want in ("bad name", "moves unknown", "bound outside", "pair appears twice"):
        assert want in faults, faults


FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _entries(data, config, cell, traffic, moved, why):
    """One entry each in a copy of the manifest's data: the configuration,
    its cell, and the cell under the end-to-end metric it reports."""
    data["configs"].append({"name": config["name"], "source": config["source"],
                            "file": f"benchmark/configs/{config['name']}.json",
                            "reduced": [], "why": why})
    data["workloads"].append({"name": cell, "config": config["name"], "traffic": traffic,
                              "chips": 1, "why": why})
    for e in data["end_to_end"]:
        if e["name"] == moved:
            e["workloads"].append(cell)


def _a_third_width(m, root, data):
    """A configuration of a family the benchmark has, a traffic mix, a cell
    and a per-layer metric."""
    cfg = dict(m.config("gpt3-xl-1p3b"), name="gpt3-large-760m", hidden_size=1536,
               num_heads=16, head_dim=96, intermediate_size=6144)
    (root / "configs" / "gpt3-large-760m.json").write_text(json.dumps(cfg))
    job = dict(m.traffic("pretrain-b2-s2048"), batch=4, seq=1024)
    (root / "traffic" / "pretrain-b4-s1024.json").write_text(json.dumps(job))
    (root / "cells" / "train-large-s1024.json").write_text(
        json.dumps(m.cell("train-xl-s2048")))
    (root / "metrics" / "steps_in_window.py").write_text(
        '"""Steps the window completed."""\n\ndef read(run):\n    return float(run["steps"])\n')
    _entries(data, cfg, "train-large-s1024", "pretrain-b4-s1024", "tokens_per_s_chip",
             "a third width, shorter rows")
    data["per_layer"].append({"name": "steps_in_window", "unit": "count", "better": "higher",
                              "source": "program_counter", "layer": "train entry",
                              "moves": "tokens_per_s_chip", "workloads": ["train-large-s1024"]})
    return "train-large-s1024", "0.5"


def _a_second_family(m, root, data):
    """A FAMILY: its module (leaves, builder, plain reference, cache bytes),
    a configuration of it, an open-loop traffic mix and a serving cell, all
    files of ``fixtures/``; nothing of GPT's fits it."""
    added = [p.relative_to(FIXTURES) for p in FIXTURES.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts]
    assert added and not any((root / p).exists() for p in added)
    shutil.copytree(FIXTURES, root, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((root / "configs" / "llama-tiny.json").read_text())
    _entries(data, cfg, "serve-llama-tiny", "chat-tiny", "token_gap_p50_ms",
             "another family, served")
    return "serve-llama-tiny", "1.5"


@pytest.mark.parametrize("add", [_a_third_width, _a_second_family])
def test_add_one_of_each_by_files_and_entries(add, tmp_path, capsys):
    """A later PR adds a configuration, a traffic mix, a cell and a per-layer
    metric, or a whole model family: new files, one entry each, no existing
    file edited; the harness finds them by name and runs the cell (rehearsal
    sizes, CPU; the served family through ``serving.Engine``)."""
    m, root, data = _copy(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cell, seconds = add(m, root, data)
    new = _manifest_of(tmp_path, data)
    assert new.validate() == []
    assert all(p.read_bytes() == b for p, b in before.items())
    names = [x["name"] for x in new.metrics_of(cell, "per_layer")]
    if add is _a_third_width:
        assert "steps_in_window" in names and "mfu_pct" in names
        assert new.reader("steps_in_window")({"steps": 7}) == 7.0
    else:
        fam = new.family("llama")
        assert Path(fam.__file__) == root / "families" / "llama.py"
        # the dense model's share lists its cell, so a new family's cell is not asked for it
        assert "decode_hbm_roofline" not in names and not hasattr(fam, "weight_bytes")
        cfg = new.config("llama-tiny")
        assert fam.cache_bytes_per_context_token(cfg) == 2 * 2 * 2 * 32 * 2
        assert [s[0] for s in fam.leaf_specs(cfg)][:3] == ["wte", "h0.ln1.g", "h0.q.w"]

    from benchmark import run

    rc = run.main(["--workload", cell, "--seed", "2147483700", "--seconds", seconds,
                   "--trace", "1", "--rehearse",
                   "--manifest", str(tmp_path / "BENCHMARK.json")])
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] and line["correct"], out[-3000:]
    assert all(p.read_bytes() == b for p, b in before.items())
    # every reader read something or found nothing to read; none raised
    assert out.count("reader: ") == len(names)
    if add is _a_third_width:
        assert line["counts"]["steps"] > 0 and "reader: mfu_pct read something" in out
    else:
        assert line["counts"]["tokens"] > 0 and line["attempted"] > 0
        assert "check: served_logit_gap" in out and "reader: decode_step_ms read something" in out
        assert "reader: decode_hbm_roofline" not in out


def _copy_with(tmp_path, edit):
    """A copy of the benchmark with ``edit(root, data)`` applied."""
    _, root, data = _copy(tmp_path)
    edit(root, data)
    return _manifest_of(tmp_path, data)


def _unknown_family(root, data):
    path = root / "configs" / "gpt3-xl-1p3b.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), family="mamba")))


def _family_without_reference(root, data):
    path = root / "families" / "gpt.py"
    path.write_text(path.read_text() + "\ndel forward_logits\n")


def _train_cell_of_a_served_only_family(root, data):
    _a_second_family(Manifest(), root, data)
    data["workloads"].append({"name": "train-llama-tiny", "config": "llama-tiny",
                              "traffic": "pretrain-b2-s2048", "chips": 1, "why": "x"})
    (root / "cells" / "train-llama-tiny.json").write_text("{}")
    for e in data["end_to_end"]:
        if e["name"] == "tokens_per_s_chip":
            e["workloads"].append("train-llama-tiny")


def _reduced_key_not_in_file(root, data):
    data["configs"][1]["reduced"] = ["num_layers", "num_experts"]


@pytest.mark.parametrize("edit,wants", [
    (_unknown_family, ("config gpt3-xl-1p3b: no module for model family 'mamba': looked for",
                       "families/mamba.py")),
    (_family_without_reference, ("families/gpt.py has no forward_logits",)),
    (_train_cell_of_a_served_only_family,
     ("cell train-llama-tiny: a train cell of a family without TrainReference (llama.py)",)),
    (_reduced_key_not_in_file, ("config gpt3-6p7b-4chip: reduced key 'num_experts' is not a key",)),
])
def test_validate_names_a_fault_of_a_family(edit, wants, tmp_path):
    faults = _copy_with(tmp_path, edit).validate()
    for want in wants:
        assert any(want in f for f in faults), faults
    assert len(faults) <= 2, faults  # the fault and what follows from it, no more


def test_unknown_family_stops_a_run_and_names_the_file(tmp_path):
    from benchmark import run

    new = _copy_with(tmp_path, _unknown_family)
    with pytest.raises(FileNotFoundError, match=r"families/mamba\.py"):
        run.main(["--workload", "train-xl-s2048", "--seed", "1", "--seconds", "1",
                  "--rehearse", "--manifest", str(new.path)])


class _NoCompiles:
    between = staticmethod(lambda t0, t1: [])
    seconds_before = staticmethod(lambda t: 1.0)


@pytest.mark.parametrize("family,reported", [("gpt", True), (None, False)])
def test_a_family_without_a_count_leaves_the_metric_out_of_the_line(family, reported):
    """``mfu_pct`` through the family's ``train_flops_per_token``: GPT-3 XL at
    16,431 tokens/s/chip reads 70.6; a family that gives no count has no such
    metric in the result's line, and never GPT's formula."""
    from benchmark import run

    m = Manifest()
    facts = {"family": m.family(family) if family else object(),
             "config": m.config("gpt3-xl-1p3b"), "seq": 2048, "window": (0.0, 1.0),
             "end_to_end": {"tokens_per_s_chip": 16431.0}, "compiles": _NoCompiles,
             "trace": None, "peaks": m.peaks("TPU v5 lite")}
    line = run.per_layer(m, "train-xl-s2048", facts)
    assert ("mfu_pct" in line) is reported
    assert line["setup_compile_s"] == {"value": 1.0, "unit": "s"}
    assert "flash_roofline" not in line and "device_idle_pct.train" not in line  # no trace
    if reported:
        assert line["mfu_pct"]["value"] == pytest.approx(70.64, abs=0.01)


# sha256 (first 16 hex digits) of three leaves at rehearsal sizes, made by the
# parent commit of PR 26 (189c290, weights.leaf_specs in place)
PINNED = {
    ("gpt3-xl-1p3b", 2147483659): {"wte": "d6cb59a27f2e6f69", "h1.qkv.w": "7097dbb7680835ca",
                                   "lnf.g": "eb17729b5f474752"},
    ("gpt3-6p7b-4chip", 3000000019): {"wte": "9426221eb3c8ca43", "h1.qkv.w": "687ffc19ec4a2acb",
                                      "lnf.g": "c30cf46b8f46e193"},
}


@pytest.mark.parametrize("config,seed", list(PINNED))
def test_seeded_weights_are_the_bytes_they_were_before_the_family_module(config, seed):
    import hashlib

    import numpy as np

    from benchmark import weights as W

    m = Manifest()
    cfg = m.config(config)
    fam = m.family(cfg["family"])
    cfg = {**cfg, **fam.REHEARSE}
    specs = fam.leaf_specs(cfg)
    made = W.make_weights(cfg, seed, specs)
    names = [s[0] for s in specs]
    for leaf, want in PINNED[config, seed].items():
        whole = np.asarray(made[leaf]).tobytes()
        alone = np.asarray(W.make_leaf(cfg, seed, specs, names.index(leaf))).tobytes()
        assert hashlib.sha256(whole).hexdigest()[:16] == want, leaf
        assert alone == whole, leaf
