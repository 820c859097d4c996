"""BENCHMARK.json keeps to the benchmark's contract: names and units, every
per-layer metric's ``moves`` reported in the cells it lists, and the files
that each entry names."""

import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.benchmark()
CELLS = {w["name"] for w in BENCH["workloads"]}


def _reports(metric, cell):
    return cell in metric.get("workloads", CELLS)


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert all(not w.startswith("/") for w in BENCH["command"])


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_are_unique_and_well_formed(kind):
    names = [e["name"] for e in BENCH[kind]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_units_bounds_and_sources():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= CELLS
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert "bound" not in m
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_moves_is_reported_in_every_listed_cell():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert _reports(target, cell), (m["name"], cell)


def test_each_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in BENCH["end_to_end"] if _reports(m, cell)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(_reports(m, cell) for m in BENCH["per_layer"])


def test_layers_name_one_layer_each_on_one_line():
    for m in BENCH["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"] and len(m["layer"]) <= 200


def test_configs_and_cells_point_at_their_files():
    for c in BENCH["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith(tuple(BENCH["paths"]))
        conf = spec.config(c["name"])
        assert conf["source"] == c["source"] and conf["reduced"] == c["reduced"]
        assert all(not k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert spec.config(w["config"])["chips"] == w["chips"]


def test_limits_are_set():
    """Every limit is positive, but for an exact comparison, which the
    limits file names under ``exact`` and which has the limit 0."""
    for w in BENCH["workloads"]:
        lim = spec.limits(w["name"])
        for name, limit in lim["limits"].items():
            if name in lim.get("exact", []):
                assert limit == 0, (w["name"], name)
            else:
                assert 0 < limit < 1e6, (w["name"], name)
