"""A configuration, a cell, a traffic mix and a metric added as new files
are found by name; no existing file is touched."""
import json
import os
import shutil
import sys
import types

import pytest

from bench import run, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIRS = ("configs", "cells", "traffic", "metrics")


def test_new_files_are_found(tmp_path):
    for d in DIRS:
        shutil.copytree(os.path.join(BENCH, d), tmp_path / d)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}

    cfg = spec.load("configs", "resnet18", str(tmp_path))
    cfg["name"] = "resnet18-wide-head"
    cfg["num_classes"] = 2000
    (tmp_path / "configs" / "resnet18-wide-head.json").write_text(
        json.dumps(cfg))
    (tmp_path / "traffic" / "b16.json").write_text(
        json.dumps({"batch": 16, "ring": 4}))
    (tmp_path / "cells" / "resnet18-wide-head.b16.json").write_text(
        json.dumps({"config": "resnet18-wide-head", "traffic": "b16",
                    "chips": 1, "dp": 1, "n_shards": 1, "why": "new",
                    "limits": {}}))
    (tmp_path / "metrics" / "ops_per_step.py").write_text(
        'UNIT = "ops"\n\n\ndef read(ctx):\n'
        '    return len(ctx["devices"][0]) / ctx["steps"]\n')

    cell, config, traffic = spec.resolve("resnet18-wide-head.b16",
                                         str(tmp_path))
    assert config["num_classes"] == 2000 and traffic["batch"] == 16
    ctx = {"devices": [[("a.1", "other", 0.0, 1.0)] * 6], "steps": 3,
           "window_s": 1.0, "busy_s": 0.5, "peaks": None,
           "oracle_calls": None, "config": config, "traffic": traffic,
           "chips": 1}
    got = run.read_metrics(ctx, str(tmp_path))
    assert got["ops_per_step"] == {"value": 2.0, "unit": "ops"}
    assert got["idle_share"]["value"] == 50.0
    # readers that find nothing to read return nothing
    assert "conv_ms_per_step" not in got and "oracle_calls" not in got
    for p, data in before.items():
        assert p.read_bytes() == data


def test_new_family_with_token_traffic_is_found(tmp_path, monkeypatch):
    """A family the benchmark has never run enters by files alone: its
    configuration, token traffic and cell resolve, and a reader whose count
    its work file lacks reads nothing."""
    for d in DIRS:
        shutil.copytree(os.path.join(BENCH, d), tmp_path / d)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}

    (tmp_path / "configs" / "decoder.json").write_text(json.dumps({
        "name": "decoder", "family": "lm", "arch": "granite-3-8b",
        "n_layers": 2, "d_model": 64, "vocab": 128}))
    (tmp_path / "traffic" / "t32.json").write_text(json.dumps(
        {"kind": "tokens", "batch": 2, "seq": 32, "ring": 2}))
    (tmp_path / "cells" / "decoder.t32.json").write_text(json.dumps(
        {"config": "decoder", "traffic": "t32", "chips": 1, "dp": 1,
         "n_shards": 1, "why": "new family", "limits": {}}))

    cell, config, traffic = spec.resolve("decoder.t32", str(tmp_path))
    assert config["family"] == "lm" and traffic["seq"] == 32
    work = types.ModuleType("bench.work.lm")
    work.model_flops_per_sample = lambda cfg, tr: 1e9 * tr["seq"]
    monkeypatch.setitem(sys.modules, "bench.work.lm", work)
    ops = [("convolution.1", "conv", 0.0, 2e8),
           ("quantize_fused.3", "kernel:quantize_fused", 2e8, 1e8)]
    ctx = {"devices": [ops], "steps": 2, "window_s": 1.0, "busy_s": 0.3,
           "peaks": {"int8_ops": 1e12, "hbm_bytes_per_s": 1e11},
           "oracle_calls": 0, "config": config, "traffic": traffic,
           "chips": 1}
    got = run.read_metrics(ctx, str(tmp_path))
    assert got["step_mfu"]["value"] == pytest.approx(100 * 32e9 * 2 * 2
                                                     / 1e12)
    assert got["conv_ms_per_step"]["value"] == pytest.approx(100.0)
    assert "conv_roofline" not in got and "quantize_roofline" not in got
    for p, data in before.items():
        assert p.read_bytes() == data


def test_benchmark_json_names_the_files():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        b = json.load(f)
    for c in b["configs"]:
        cfg = spec.load("configs", c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    for w in b["workloads"]:
        cell, config, traffic = spec.resolve(w["name"])
        assert spec.listed(w["name"]) == w
        assert set(cell["limits"]) == {"loss_gap", "grad_gap", "change_gap"}
    readers = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
               if f.endswith(".py")}
    assert {m["name"] for m in b["per_layer"]} <= readers


def test_cell_that_departs_from_benchmark_json_is_refused(tmp_path):
    shutil.copy(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"),
                tmp_path)
    root = tmp_path / "bench"
    for d in DIRS:
        shutil.copytree(os.path.join(BENCH, d), root / d)
    spec.resolve("resnet18.b8-online", str(root))
    path = root / "cells" / "resnet18.b8-online.json"
    cell = json.loads(path.read_text())
    path.write_text(json.dumps(dict(cell, traffic="b64")))
    with pytest.raises(SystemExit, match="differ on"):
        spec.resolve("resnet18.b8-online", str(root))
