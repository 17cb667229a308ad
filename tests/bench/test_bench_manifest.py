"""BENCHMARK.json: every piece is found by name and keeps to the rules."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
#: the device kinds this benchmark's cells have run on
RAN_ON = ("TPU v5 lite",)

CELLS = MANIFEST["workloads"]
PER_LAYER = MANIFEST["per_layer"]
END_TO_END = MANIFEST["end_to_end"]


def _reported(cell: str) -> set[str]:
    return {m["name"] for m in END_TO_END
            if "workloads" not in m or cell in m["workloads"]}


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0])


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_cell_pieces_found_by_name(cell):
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    cfg = configs[cell["config"]]
    cfg_file = ROOT / cfg["file"]
    assert cfg_file.is_file() and cfg_file.with_suffix(".py").is_file()
    traffic = BENCH / "traffic" / f"{cell['traffic']}.json"
    mix = json.loads(traffic.read_text())
    assert (BENCH / "drivers" / f"{mix['driver']}.py").is_file()
    assert cell["chips"] == 1
    assert "setup_s" in _reported(cell["name"])
    assert len(_reported(cell["name"])) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               for m in PER_LAYER)


@pytest.mark.parametrize("metric", PER_LAYER,
                         ids=[m["name"] for m in PER_LAYER])
def test_per_layer_reader_and_moves(metric):
    stem = metric["name"].split(".", 1)[0]
    assert (BENCH / "metrics" / f"{metric['name']}.py").is_file() \
        or (BENCH / "metrics" / f"{stem}.py").is_file()
    for cell in metric.get("workloads", [c["name"] for c in CELLS]):
        assert cell in {c["name"] for c in CELLS}
        assert metric["moves"] in _reported(cell)
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")


def test_names_units_and_bounds():
    entries = [*MANIFEST["configs"], *CELLS, *END_TO_END, *PER_LAYER]
    names = [e["name"] for e in entries]
    for group in (MANIFEST["configs"], CELLS, END_TO_END + PER_LAYER):
        assert len({e["name"] for e in group}) == len(group)
    for n in names + [c["config"] for c in CELLS] \
            + [c["traffic"] for c in CELLS]:
        assert NAME.match(n), n
    for c in MANIFEST["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key), key
    for m in END_TO_END + PER_LAYER:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in END_TO_END:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in PER_LAYER:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_peaks_cover_the_devices_run_on():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    for kind in RAN_ON:
        entry = peaks[kind]
        assert entry["hbm_bytes_per_s"] == 819e9
        assert entry["bf16_flop_per_s"] == 197e12
        assert entry["int8_op_per_s"] == 393e12
        assert entry["source"]
