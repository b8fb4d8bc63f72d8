"""BENCHMARK.json against the benchmark contract, and the harness finding
cells, configurations and metrics by name."""

import json
import re
import shutil
import sys
import types

import pytest

import benchpath  # noqa: F401
from bench import compare, harness

ROOT = benchpath.ROOT
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTH = re.compile(r"(_dim|_rank)$|^(d_model|d_ff|hidden|intermediate|"
                   r"head_dim|moe_d_ff|ssm_state|ssm_expand|moe_top_k)")


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= BM["run_seconds"] <= 51 and isinstance(BM["run_seconds"], int)
    assert 1 <= len(BM["paths"]) <= 16
    for p in BM["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert len(BM["command"]) <= 32 and all(_line(w) for w in BM["command"])
    assert any(w.startswith("bench/") for w in BM["command"])


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries(section):
    names = [e["name"] for e in BM[section]]
    assert len(names) == len(set(names))
    for e in BM[section]:
        optional = {"workloads"} if section in ("end_to_end",
                                                "per_layer") else set()
        assert ENTRY_KEYS[section] <= set(e) <= ENTRY_KEYS[section] | optional
        assert NAME.match(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert _line(e[k])


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert {"round_s", "setup_s"} <= set(e2e)
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")


def test_every_cell_names_existing_files():
    configs = {c["name"]: c for c in BM["configs"]}
    per_layer = {m["name"]: m for m in BM["per_layer"]}
    used = set()
    for w in BM["workloads"]:
        assert w["chips"] in (1, 4)
        assert w["config"] in configs
        used.add(w["config"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        job = json.loads(
            (ROOT / "bench" / "workloads" / f"{w['name']}.json").read_text())
        assert set(job["limits"]) == set(compare.NUMBERS)
        reported = [m for m in per_layer.values()
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert reported, w["name"]
    assert used == set(configs)
    on_disk = {p.stem for p in (ROOT / "bench" / "workloads").glob("*.json")}
    assert on_disk == {w["name"] for w in BM["workloads"]}


@pytest.mark.parametrize("name", [m["name"] for m in BM["per_layer"]])
def test_metric_reader_matches_entry(name):
    m = {x["name"]: x for x in BM["per_layer"]}[name]
    assert callable(harness.metric_reader(name).read)
    assert m["moves"] in {e["name"] for e in BM["end_to_end"]}
    for w in m.get("workloads", []):
        assert w in {x["name"] for x in BM["workloads"]}


@pytest.mark.parametrize("name", [c["name"] for c in BM["configs"]])
def test_config_file(name):
    c = {x["name"]: x for x in BM["configs"]}[name]
    assert c["file"].startswith("bench/configs/")
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == name and cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    for k in c["reduced"]:
        assert NAME.match(k) and k in cfg and not WIDTH.search(k)


def test_harness_finds_a_cell_config_and_metric_added_as_files(tmp_path,
                                                               monkeypatch):
    """A later PR adds files; the harness finds them by name alone."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bm = json.loads(json.dumps(BM))
    old = bm["workloads"][0]
    cfg = json.loads((ROOT / {c["name"]: c for c in bm["configs"]}[
        old["config"]]["file"]).read_text())
    cfg.update(name="tiny", num_layers=2)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/few.json").write_text(json.dumps(
        {"clients": 2, "local_steps": 1, "seqs_per_step": 1, "seq_len": 8,
         "zipf_s": 1.1, "heterogeneity": 0.3}))
    job = json.loads((ROOT / f"bench/workloads/{old['name']}.json").read_text())
    (root / "bench/workloads/tiny.few.json").write_text(json.dumps(job))
    bm["configs"].append({"name": "tiny", "source": "a test",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test"})
    bm["workloads"].append({"name": "tiny.few", "config": "tiny",
                            "traffic": "few", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "zz_added", "unit": "%",
                            "better": "lower", "source": "device_trace",
                            "layer": "device", "moves": "round_s",
                            "workloads": ["tiny.few"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    (root / "bench/metrics/zz_added.py").write_text(
'def read(ctx):\n    return 42.0\n')
    spec = harness.load_spec("tiny.few", root=root)
    assert spec.config["num_layers"] == 2 and spec.traffic["seq_len"] == 8
    assert [m["name"] for m in spec.per_layer] == ["zz_added"]
    import bench.metrics
    monkeypatch.setattr(bench.metrics, "__path__",
                        [str(root / "bench/metrics")] + list(
                            bench.metrics.__path__))
    monkeypatch.delitem(sys.modules, "bench.metrics.zz_added", raising=False)
    assert harness.metric_reader("zz_added").read(
        types.SimpleNamespace()) == 42.0
