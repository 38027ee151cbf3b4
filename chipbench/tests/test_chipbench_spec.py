"""The loader finds cells, configurations, traffic and metric readers by
name, and ``BENCHMARK.json`` keeps to the benchmark's contract."""

import json
import re
from pathlib import Path

import pytest

from chipbench import checks, spec

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


@pytest.fixture
def added(tmp_path):
    """A root holding one cell and one metric, added as files only."""
    bench = {
        "configs": [{"name": "tiny", "file": "chipbench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.b8", "config": "tiny",
                       "traffic": "t8", "chips": 1},
                      {"name": "tiny.b8.x4", "config": "tiny",
                       "traffic": "t8", "chips": 4}],
        "end_to_end": [{"name": "samples_per_s", "unit": "samples/s"}],
        "per_layer": [{"name": "answer", "unit": "%",
                       "workloads": ["tiny.b8"]},
                      {"name": "everywhere", "unit": "ms/step"}],
    }
    _write(tmp_path / "BENCHMARK.json", bench)
    pkg = tmp_path / "chipbench"
    _write(pkg / "configs" / "tiny.json", {"kind": "fcnn",
                                           "layer_sizes": [4, 8, 2],
                                           "reference": "fcnn"})
    _write(pkg / "traffic" / "t8.json", {"batch": 8, "samples": 64})
    for cell in ("tiny.b8", "tiny.b8.x4"):
        _write(pkg / "workloads" / f"{cell}.json", {"trace_steps": 3})
    _write(pkg / "metrics" / "answer.py",
           "def read(ctx):\n    return 2.0 * ctx\n")
    return tmp_path


def test_cell_added_as_files(added):
    cell = spec.load_cell("tiny.b8", root=added)
    assert (cell.chips, cell.config["layer_sizes"], cell.traffic["batch"]) \
        == (1, [4, 8, 2], 8)
    assert cell.params == {"trace_steps": 3}
    assert [m["name"] for m in cell.per_layer] == ["answer", "everywhere"]
    assert spec.load_reader(cell, "answer")(21.0) == 42.0


def test_metric_listed_for_some_cells_only(added):
    cell = spec.load_cell("tiny.b8.x4", root=added)
    assert cell.chips == 4
    assert [m["name"] for m in cell.per_layer] == ["everywhere"]


def test_unknown_cell(added):
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("tiny.b9", root=added)


def test_configuration_without_kind_names_its_file(added):
    path = added / "chipbench" / "configs" / "tiny.json"
    conf = json.loads(path.read_text())
    del conf["kind"]
    _write(path, conf)
    with pytest.raises(ValueError, match=r"configs/tiny\.json.*kind"):
        spec.load_cell("tiny.b8", root=added)


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_names_and_files():
    bench = _bench()
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in metrics])
    assert all(NAME.match(n) for n in names)
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    fours = sum(w["chips"] == 4 for w in bench["workloads"])
    assert fours <= max(1, len(bench["workloads"]) // 2)


KIND_INTERFACE = ("dataset", "build", "init_state", "feed", "first_steps",
                  "reference", "samples_per_step", "flops_per_sample",
                  "kernel_calls")


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_loads(cell):
    c = spec.load_cell(cell)
    assert c.chips in (1, 4)
    kind = spec.load_kind(c)
    assert all(callable(getattr(kind, f)) for f in KIND_INTERFACE)
    if c.config["kind"] == "fcnn":
        assert len(c.params["fp_degrees"]) == len(kind.sizes(c)) - 1
    assert c.params["limits"] and set(c.params["limits"]) <= set(
        checks.NAMES)
    assert (c.pkg / "references" / f"{c.config['reference']}.py").is_file()
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    for m in c.per_layer:
        assert callable(spec.load_reader(c, m["name"]))
