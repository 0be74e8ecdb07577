"""BENCHMARK.json and the files it names: every cell resolves to its
configuration, traffic, limits, loop, reference and counts, and every metric
to a reader; the configuration files state what they run."""
import json
from pathlib import Path

import pytest

import harness as H
import run

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    s = run.resolve(cell)
    fam = s["config"]["family"]
    for rel in (f"loops/{s['traffic']['kind']}.py", f"refs/{fam}.py", f"counts/{fam}.py",
                f"limits/{cell}.json"):
        assert (H.HERE / rel).exists(), rel
    assert s["limits"], "a cell's comparison has limits"
    names = {m["name"] for m in s["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert s["per_layer"]
    for m in s["per_layer"]:
        assert m["moves"] in names, (m["name"], "moves a metric the cell reports")


def test_every_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        assert callable(H.load_module(f"metrics/{m['name']}.py").read)


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_file(conf):
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert cfg["source"] == conf["source"] and cfg["reduced"] == conf["reduced"]
    assert cfg["model"]["tie_embeddings"] is True  # both published models tie them
    from repro.config import ModelConfig

    ModelConfig(**cfg["model"])  # every key is one the program takes
