"""Every file of the benchmark, loaded and held to the benchmark's rules."""

import json
import re
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _names(kind: str, suffix: str) -> list[str]:
    return sorted(p.name[: -len(suffix)] for p in (BENCH / kind).glob(f"*{suffix}")
                  if p.name != "__init__.py")


CONFIGS = _names("configs", ".json")
WORKLOADS = _names("workloads", ".json")
METRICS = _names("metrics", ".py")
MODES = _names("work", ".py")


def test_benchmark_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"][1] == "bench/run.py"
    names = [c["name"] for c in SPEC["configs"]] + \
        [w["name"] for w in SPEC["workloads"]] + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file(name):
    cfg = harness.load_json("configs", name)
    assert cfg["name"] == name and NAME.match(name)
    entry = {c["name"]: c for c in SPEC["configs"]}[name]
    assert entry["file"] == f"bench/configs/{name}.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    for key in cfg["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"
    for kind in cfg["blocks"].values():
        assert (BENCH / "reference" / f"{kind}.py").is_file(), kind
    # the program's architecture holds every width the file states
    harness.program_arch(cfg)


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_file(name):
    entry = {w["name"]: w for w in SPEC["workloads"]}[name]
    cell = harness.load_cell(name)
    assert entry["config"] == cell.wl["config"] in CONFIGS
    assert entry["chips"] == 1
    assert cell.wl["why"] == entry["why"] and len(entry["why"]) <= 200
    assert cell.wl["mode"] in MODES
    assert cell.wl["warm_steps"] > harness.CHECK_STEPS
    assert set(cell.wl["limits"]) == {"loss_gap", "grad_gap", "change_gap"}
    reported = {m["name"] for m in SPEC["end_to_end"]
                if name in m.get("workloads", [name])}
    assert {"setup_s", "tokens_per_s"} <= reported
    assert any(name in m.get("workloads", [name]) for m in SPEC["per_layer"])


def test_every_cell_has_a_file_and_every_file_a_cell():
    assert sorted(w["name"] for w in SPEC["workloads"]) == WORKLOADS
    assert {c["name"] for c in SPEC["configs"]} == set(CONFIGS)


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader(name):
    entry = {m["name"]: m for m in SPEC["per_layer"]}[name]
    assert callable(harness.load_module(BENCH / "metrics" / f"{name}.py").read)
    moves = {m["name"]: m for m in SPEC["end_to_end"]}[entry["moves"]]
    for cell in entry.get("workloads", WORKLOADS):
        assert cell in WORKLOADS
        assert cell in moves.get("workloads", [cell]), (name, cell)
    assert entry["layer"] and "\n" not in entry["layer"]


def test_every_per_layer_metric_has_a_reader():
    assert sorted(m["name"] for m in SPEC["per_layer"]) == METRICS


@pytest.mark.parametrize("mode", MODES)
def test_work_count(mode):
    work = harness.load_module(BENCH / "work" / f"{mode}.py")
    assert work.update_bytes([(10, 2)], 4) > 0


def test_unknown_device_is_an_error():
    assert harness.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak_of("cpu")


def test_a_missing_file_fails_loudly():
    with pytest.raises(FileNotFoundError):
        harness.load_module(BENCH / "work" / "no-such-mode.py")
    with pytest.raises(FileNotFoundError):
        harness.load_cell("no-such-cell")
