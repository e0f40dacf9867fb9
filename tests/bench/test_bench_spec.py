"""The manifest, and everything it names, found by name."""
import json
import re
import shutil

import pytest

from bench.spec import BENCH, CHECKOUT, load_cell, load_json, load_peaks

MANIFEST = load_json(CHECKOUT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_manifest_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in MANIFEST["configs"]]
             + [w["name"] for w in MANIFEST["workloads"]]
             + [m["name"] for m in MANIFEST["end_to_end"]
                + MANIFEST["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    assert {m["name"] for m in MANIFEST["end_to_end"]} == {
        "setup_s", "train_tok_per_s"}
    assert all(0.01 <= m["bound"] <= 0.25 for m in MANIFEST["end_to_end"])


def test_every_named_piece_exists():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for w in MANIFEST["workloads"]:
        cell = load_cell(w["name"])
        assert cell.kind in ("serve", "train")
        assert (BENCH / "kinds" / f"{cell.kind}.py").is_file()
        assert (BENCH / "reference"
                / f"{cell.config['family']}.py").is_file()
        assert cell.config["name"] == w["config"]
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
    for m in MANIFEST["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        for c in m["workloads"]:
            assert c in cells
            assert c in e2e[m["moves"]].get("workloads", cells)
    for c in MANIFEST["configs"]:
        f = load_json(CHECKOUT / c["file"])
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert f["published"][k] != f[k]


def test_unknown_device_is_an_error():
    assert load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        load_peaks("TPU v99")


def test_a_new_cell_is_files_and_entries(tmp_path):
    """A configuration, a mix and a per-layer metric added as new files
    plus manifest entries, with no existing file edited."""
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    conf = load_json(BENCH / "configs" / "mistral-nemo-12b-8L.json")
    conf["name"] = "nemo-4L"
    conf["num_hidden_layers"] = 4
    (root / "configs" / "nemo-4L.json").write_text(json.dumps(conf))
    mix = load_json(BENCH / "traffic" / "chat-sysprompt.json")
    (root / "traffic" / "chat-burst.json").write_text(json.dumps(
        {**mix, "rate_per_s": 0.5, "strata": 4}))
    (root / "metrics" / "queue_depth.serve.py").write_text(
        "def read(data):\n    return data['counters'].get('queued')\n")
    manifest = {**MANIFEST,
                "workloads": MANIFEST["workloads"] + [
                    {"name": "nemo4-burst", "config": "nemo-4L",
                     "traffic": "chat-burst", "chips": 1, "why": "t"}],
                "per_layer": MANIFEST["per_layer"] + [
                    {"name": "queue_depth.serve", "unit": "requests",
                     "better": "lower", "source": "program_counter",
                     "layer": "scheduler", "moves": "itl_p95_ms",
                     "workloads": ["nemo4-burst"]}]}
    manifest["end_to_end"] = MANIFEST["end_to_end"] + [
        {"name": n, "unit": u, "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["nemo4-burst"]}
        for n, u in (("ttft_p90_ms", "ms"), ("itl_p95_ms", "ms"),
                     ("out_tok_per_s", "tokens/s"))]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell = load_cell("nemo4-burst", tmp_path / "BENCHMARK.json", root)
    assert cell.config["num_hidden_layers"] == 4
    assert cell.traffic["rate_per_s"] == 0.5
    assert cell.traffic["strata"] == 4
    assert cell.kind == "serve"
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "ttft_p90_ms", "itl_p95_ms", "out_tok_per_s"}
    assert [m["name"] for m in cell.per_layer] == ["queue_depth.serve"]
    assert cell.reader("queue_depth.serve").read(
        {"counters": {"queued": 3}}) == 3
    assert callable(cell.runner().run)
