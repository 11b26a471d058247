"""Cells, configurations, traffic, limits and per-layer readers are found
by name, and BENCHMARK.json keeps to the shape the harness relies on."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness, runctx, spec as spec_mod, traffic  # noqa: E402
from chipbench.run import METRIC_DIR, reader  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
# the readings each kind of job can hold to a limit
READINGS = {"train": {"first_loss_gap", "loss_gap", "grad_gap", "change_gap",
                      "grad_angle"}}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names)), section
        assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(wl):
    found, _ = harness.cell(wl["name"])
    assert found is wl or found == wl
    spec, raw = spec_mod.load(wl["config"])
    entry = next(c for c in BENCH["configs"] if c["name"] == wl["config"])
    assert entry["file"] == f"chipbench/configs/{wl['config']}.json"
    assert entry["reduced"] == raw["reduced"]
    mix = traffic.load(wl["traffic"])
    limits = runctx.limits(wl["name"])
    assert limits and set(limits) <= READINGS[mix["kind"]]
    assert wl["chips"] in (1, 4) and len(wl["why"]) <= 200
    e2e = harness.metric_names(BENCH, wl["name"], "end_to_end")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert harness.metric_names(BENCH, wl["name"], "per_layer")


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(m):
    assert (METRIC_DIR / f"{m['name']}.py").exists()
    assert callable(reader(m["name"]))
    e2e = {e["name"]: e for e in BENCH["end_to_end"]}
    moved = e2e[m["moves"]]
    for cell in m["workloads"]:
        assert "workloads" not in moved or cell in moved["workloads"]


def test_every_config_is_used_and_bounds_are_in_range():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_unknown_cell_is_refused():
    with pytest.raises(SystemExit):
        harness.cell("no-such-cell")
