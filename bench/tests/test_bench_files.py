"""Every file BENCHMARK.json names loads by name, and the file keeps to the
benchmark's contract."""
import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["paths"] == ["bench"]


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_file_loads_and_lists_its_cuts(entry):
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert entry["file"].startswith("bench/")
    for key in entry["reduced"]:
        assert key in conf and key in conf["published"]
        assert conf[key] != conf["published"][key]
    assert conf["reference"]
    importlib.import_module(f"bench.reference.{conf['reference']}")
    dep = conf["deployment"]
    assert dep["clusters"] % dep["chips"] == 0


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda e: e["name"])
def test_cell_files_load_by_name(cell):
    configs = {c["name"] for c in SPEC["configs"]}
    assert cell["config"] in configs
    traffic = json.loads((ROOT / "bench/traffic" / f"{cell['traffic']}.json")
                         .read_text())
    # implementation selectors stay at HFLConfig's defaults
    assert not {"omega_impl", "sync_layout", "flat_shards"} & set(traffic)
    lim = json.loads((ROOT / "bench/limits" / f"{cell['name']}.json")
                     .read_text())["limits"]
    assert set(lim) == {"loss_gap", "grad_gap", "sync_gap", "change_gap"}
    assert cell["chips"] in (1, 4)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_exists(metric):
    mod = importlib.import_module(f"bench.metrics.{metric['name']}")
    assert callable(mod.read)
    for w in metric.get("workloads", []):
        assert w in CELLS


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_moves_a_reported_metric():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e


def test_peaks_table_refuses_an_unknown_device():
    from bench import run
    assert run.peak_table("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        run.peak_table("TPU v9 imaginary")
