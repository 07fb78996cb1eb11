"""BENCHMARK.json against the files it names: every cell's configuration,
mix and per-layer metric file exists and loads, names and units use only
the permitted characters, and each per-layer metric moves an end-to-end
metric that every cell listing it reports."""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head", "expansion", "experts_per")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    for m in manifest["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}


def test_names_and_units(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
    assert len(names) == len(set(names))
    for w in manifest["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for c in manifest["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not k.endswith(("_dim", "_rank")), k
            assert not any(wd in k for wd in WIDTH_WORDS), k


def test_every_named_file_loads(manifest):
    import run as bench
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        spec = bench.load_cell(w["name"])
        cfg = spec["cfg"]
        for k in ("source", "assumed", "reduced", "batch_size",
                  "pass_capacity", "occupied_rows"):
            assert k in cfg, (w["config"], k)
        conf = [c for c in manifest["configs"] if c["name"] == w["config"]][0]
        assert cfg["source"] == conf["source"]
        assert set(conf["reduced"]) == set(cfg["reduced"])
        assert cfg["occupied_rows"] < cfg["pass_capacity"]
        for fn in ("build_model", "param_init", "forward",
                   "flops_per_example", "bytes_per_example"):
            assert callable(getattr(spec["cfg_mod"], fn))
        assert spec["cfg_mod"].flops_per_example(cfg) > 0
        assert spec["cfg_mod"].bytes_per_example(cfg, 10.0) > 0
        for k in ("files_per_pass", "pool_files", "stride", "trace_passes"):
            assert spec["mix"][k] >= 1
        assert spec["layer"], "a cell reports at least one per-layer metric"
        assert len(spec["end_to_end"]) >= 2


def test_layer_metrics_match_their_files(manifest):
    from harness import reducers
    end = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for m in manifest["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["kind"] in reducers.KINDS
        for k in ("layer", "unit", "moves", "better"):
            assert spec[k] == m[k], (m["name"], k)
        assert m["moves"] in end
        target = end[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in target.get("workloads", cells)
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            assert spec["kind"] == "work_over_peak"


def test_peaks_table():
    with open(os.path.join(BENCH, "harness", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
