"""Configurations, mixes, limits and metric readers are found by file name;
BENCHMARK.json names only things that exist."""
import json

import benchpath
import pytest

import harness
import model_cfg
import traffic_gen

FIX = benchpath.FIXTURES


def _fixture_manifest():
    spec = json.loads((FIX / "configs" / "tiny-dense.json").read_text())
    return {"workloads": [{"name": "tiny-chat", "config": "tiny-dense",
                           "traffic": "tiny-chat", "chips": 1, "why": "t"}],
            "configs": [{"name": "tiny-dense", "reduced": spec["reduced"]}],
            "end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "fixture_rows", "unit": "rows"}]}


def test_fixture_cell_found_by_name():
    cell = harness.load_cell(_fixture_manifest(), "tiny-chat", FIX)
    assert cell.spec["name"] == "tiny-dense" and cell.mix["prompt"]["max"] == 48
    assert cell.limits["sample_tokens"] == 200
    assert [m["name"] for m in cell.per_layer] == ["fixture_rows"]
    with pytest.raises(FileNotFoundError):
        traffic_gen.load_mix("no-such-mix", FIX / "traffic")
    with pytest.raises(FileNotFoundError):
        model_cfg.load("no-such-config", FIX / "configs")


def test_fixture_metric_reader_found_by_name():
    cell = harness.load_cell(_fixture_manifest(), "tiny-chat", FIX)

    class Run:
        spec = cell.spec

    got = harness.read_metrics(cell.per_layer, "layer_metrics", Run(), FIX)
    assert got == {"fixture_rows": {"value": 4.0, "unit": "rows"}}
    with pytest.raises(KeyError):
        harness.read_metrics([{"name": "absent", "unit": "s"}], "layer_metrics",
                             Run(), FIX)


def test_manifest_names_only_files_that_exist():
    man = harness.load_manifest()
    for w in man["workloads"]:
        cell = harness.load_cell(man, w["name"])
        assert cell.arch is model_cfg.load_arch(cell.spec["arch"])
        assert cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    e2e, layer = harness.readers("e2e_metrics"), harness.readers("layer_metrics")
    assert {m["name"] for m in man["end_to_end"]} <= set(e2e)
    assert {m["name"] for m in man["per_layer"]} <= set(layer)
    for c in man["configs"]:
        spec = model_cfg.load(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["reduced"] == spec["reduced"]
        # the architecture's own check that the file says what the program runs
        model_cfg.load_arch(spec["arch"]).model_config(spec)
