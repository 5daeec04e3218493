"""BENCHMARK.json and the files its names resolve to."""
from __future__ import annotations

import hashlib
import json
import re
from pathlib import Path

import pytest

from bench_tiny import tiny  # noqa: F401  (fixture)
from bench import spec

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_resolves(workload):
    cell = spec.resolve(ROOT, workload)
    assert cell.chips == 1
    assert hasattr(cell.driver, "setup") and hasattr(cell.driver, "check")
    for m in cell.per_layer:
        assert callable(cell.metric_reader(m["name"]))
    limits = spec.load_json(ROOT / "bench" / "limits" / f"{workload}.json")
    assert limits["limits"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["workloads"]
        assert "\n" not in m["layer"]
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].split("/")[0] in BENCH["paths"]
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200


def test_peaks_known_and_unknown_kind():
    v5e = spec.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks("TPU v99 imaginary")


def test_unknown_workload_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.resolve(ROOT, "no.such.cell")


def _digests(root: Path) -> dict:
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_cell_and_metric_are_files_and_entries(tiny):
    """A throw-away configuration, traffic mix, cell and metric are added
    as new files and entries only, and resolve with no file edited."""
    before = _digests(tiny)
    b = tiny / "bench"
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["name"] = "tiny2"
    (b / "configs" / "tiny2.json").write_text(json.dumps(cfg))
    (b / "traffic" / "train.again.json").write_text(
        json.dumps({"driver": "train", "why": "throw-away"}))
    (b / "limits" / "tiny2.train.json").write_text(
        (b / "limits" / "tiny.train.json").read_text())
    (b / "metrics" / "throwaway.iterations.py").write_text(
        "def read(reading):\n    return reading.window['iterations']\n")
    spec_json = json.loads((tiny / "BENCHMARK.json").read_text())
    spec_json["configs"].append({"name": "tiny2", "source": "test",
                                 "file": "bench/configs/tiny2.json",
                                 "reduced": [], "why": "throw-away"})
    spec_json["workloads"].append({"name": "tiny2.train", "config": "tiny2",
                                   "traffic": "train.again", "chips": 1,
                                   "why": "throw-away"})
    spec_json["per_layer"].append({
        "name": "throwaway.iterations", "unit": "iterations",
        "better": "higher", "source": "host_clock", "layer": "test",
        "moves": "train_tokens_per_s", "workloads": ["tiny2.train"]})
    for m in spec_json["end_to_end"]:
        if "train_tokens_per_s" == m["name"]:
            m["workloads"].append("tiny2.train")
    (tiny / "BENCHMARK.json").write_text(json.dumps(spec_json))

    cell = spec.resolve(tiny, "tiny2.train")
    assert cell.config["name"] == "tiny2"
    assert [m["name"] for m in cell.per_layer][-1] == "throwaway.iterations"
    read = cell.metric_reader("throwaway.iterations")
    assert read(type("R", (), {"window": {"iterations": 3}})()) == 3
    after = _digests(tiny)
    assert all(after[p] == d for p, d in before.items())
