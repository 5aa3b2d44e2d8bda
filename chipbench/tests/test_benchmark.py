"""BENCHMARK.json keeps to its contract, every cell resolves to its files,
and a new cell, traffic mix or per-layer metric is only new files and new
entries."""
import json
import re
import shutil

import pytest

from chipbench import bench, check

BENCH = bench.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_top_level_keys_and_limits():
    assert set(BENCH) == TOP
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert BENCH["paths"] == ["chipbench"]
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    assert (bench.ROOT / BENCH["command"][1]).is_file()


def _names(kind):
    return [e["name"] for e in BENCH[kind]]


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_unique_and_plain(kind):
    names = _names(kind)
    assert len(set(names)) == len(names)
    for n in names:
        assert NAME.match(n), n


def test_entries_have_only_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and (bench.ROOT / c["file"]).is_file()
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank", "_size"))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in _names("end_to_end")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_config_files_hold_what_is_run():
    for c in BENCH["configs"]:
        data = json.loads((bench.ROOT / c["file"]).read_text())
        assert data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])


@pytest.mark.parametrize("cell", _names("workloads"))
def test_every_cell_resolves(cell):
    c = bench.resolve(cell)
    assert {"batch", "seq_len", "chunk", "mode", "dtheta", "eta"} <= set(
        c.traffic)
    assert set(c.limits) >= {"loss_gap_nats", "grad_gap_nats", "change_gap"}
    assert bench.reference(c.config).Arch.from_config(c.config)
    pods = check.pods(c.traffic)
    assert pods in (1, c.chips) and c.traffic["batch"] % pods == 0
    assert "setup_s" in [m["name"] for m in c.end_to_end]
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(bench.metric_reader(m["name"]))


def test_per_layer_lists_name_cells_that_report_what_they_move():
    cells = set(_names("workloads"))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        for w in m.get("workloads", []):
            assert w in cells
            assert "workloads" not in e2e[m["moves"]] or \
                w in e2e[m["moves"]]["workloads"]


def test_a_new_cell_is_new_files_only(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix, a
    per-layer metric and a cell without any harness file changing."""
    root = tmp_path / "checkout"
    shutil.copytree(bench.ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    data = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    base = root / "chipbench"
    cfg = json.loads((base / "configs" / "qwen3-14b.json").read_text())
    cfg["num_hidden_layers"] = 2
    (base / "configs" / "qwen3-14b-2l.json").write_text(json.dumps(cfg))
    mix = json.loads((base / "traffic" / "train-4x512.json").read_text())
    mix.update(batch=1, seq_len=4096)
    (base / "traffic" / "train-1x4096.json").write_text(json.dumps(mix))
    (base / "limits" / "qwen3-14b-2l.train-1x4096.json").write_text(
        (base / "limits" / "qwen3-14b.train-4x512.json").read_text())
    (base / "metrics" / "attn.share.py").write_text(
        "def read(ctx):\n    return None\n")
    data["configs"].append(dict(data["configs"][0], name="qwen3-14b-2l",
                                file="chipbench/configs/qwen3-14b-2l.json"))
    data["workloads"].append({"name": "qwen3-14b-2l.train-1x4096",
                              "config": "qwen3-14b-2l",
                              "traffic": "train-1x4096", "chips": 1,
                              "why": "attention share at 4096 tokens"})
    data["per_layer"].append({"name": "attn.share", "unit": "%",
                              "better": "lower", "source": "device_trace",
                              "layer": "model (models/transformer.py)",
                              "moves": "train_tokens_per_s",
                              "workloads": ["qwen3-14b-2l.train-1x4096"]})
    for m in data["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("qwen3-14b-2l.train-1x4096")
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    cell = bench.resolve("qwen3-14b-2l.train-1x4096", root=root)
    assert cell.config["num_hidden_layers"] == 2
    assert cell.traffic["seq_len"] == 4096
    assert "attn.share" in [m["name"] for m in cell.per_layer]
    assert "train_tokens_per_s" in [m["name"] for m in cell.end_to_end]
    assert bench.metric_reader("attn.share", base)(None) is None
    old = bench.resolve("qwen3-14b.train-4x512", root=root)
    assert "attn.share" not in [m["name"] for m in old.per_layer]
