"""Every name in ``BENCHMARK.json`` resolves to its files, and a new
configuration, traffic mix and per-layer metric are added by new files and
entries alone, with no existing file edited."""

from __future__ import annotations

import hashlib
import json
import os
import shutil

from bench import harness, loadgen


def test_every_cell_resolves():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        cell = harness.resolve(spec, w["name"])
        assert cell.chips == w["chips"]
        harness.load_driver(cell.config["kind"])
        harness.load_reference(cell.config["reference"])
        loadgen.generate(cell.traffic, 1, cell.config["model"]["vocab_size"])
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]).read)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        moved = {m["moves"] for m in cell.per_layer}
        assert moved <= names


def test_spec_names_and_files():
    spec = harness.load_spec()
    for c in spec["configs"]:
        conf = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    assert spec["command"][1] == "bench/run.py"


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_by_new_files_alone(tmp_path):
    src = os.path.join(harness.ROOT, "bench")
    bench = tmp_path / "bench"
    shutil.copytree(src, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    before = _digests(bench)

    conf = harness.load_json(str(bench / "configs" / "qwen1.5-4b.json"))
    conf["name"] = "other-model"
    conf["model"]["num_hidden_layers"] = 4
    (bench / "configs" / "other-model.json").write_text(json.dumps(conf))
    mix = harness.load_json(str(bench / "traffic" / "sessions.json"))
    mix["arrival"]["rate_per_s"] = 0.04
    (bench / "traffic" / "slower.json").write_text(json.dumps(mix))
    (bench / "metrics" / "queue_depth.max.py").write_text(
        "def read(rec):\n    return 42.0\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "other-model", "source": "x",
                            "file": "bench/configs/other-model.json",
                            "reduced": ["num_hidden_layers"], "why": "x"})
    spec["workloads"].append({"name": "other-model.slower",
                              "config": "other-model", "traffic": "slower",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "queue_depth.max", "unit": "1",
                              "better": "lower", "source": "program_counter",
                              "layer": "engine", "moves": "ttft_p50_ms",
                              "workloads": ["other-model.slower"]})
    spec["end_to_end"][0]["workloads"].append("other-model.slower")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.resolve(harness.load_spec(str(tmp_path)),
                           "other-model.slower", str(bench))
    assert cell.config["model"]["num_hidden_layers"] == 4
    assert cell.traffic["arrival"]["rate_per_s"] == 0.04
    names = [m["name"] for m in cell.per_layer]
    assert "queue_depth.max" in names
    assert harness.load_reader("queue_depth.max", str(bench)).read(None) \
        == 42.0
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
