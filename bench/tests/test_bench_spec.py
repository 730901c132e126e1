"""Every cell of BENCHMARK.json resolves, by name, to its files, and its
configuration to an architecture module that exports the interface."""

import json
import re
import pathlib

import pytest

from bench import run, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in SPEC["configs"]}


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    cfg = run.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    assert CONFIGS[cell["config"]]["file"] == f"bench/configs/{cell['config']}.json"
    assert mix["rate_per_s"] > 0 and mix["drain_s"] > 0
    assert set(cfg["serving"]) >= {"hot_pool_bytes", "page_size", "max_batch"}
    assert cfg["check"]["max_logit_gap"] > 0 and cfg["check"]["max_logprob_gap"] > 0
    e2e = run.metrics_of(SPEC["end_to_end"], cell["name"])
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    layer = run.metrics_of(SPEC["per_layer"], cell["name"])
    assert layer
    for m in layer:
        assert m["moves"] in {e["name"] for e in e2e}
        assert callable(run.metric_reader(m["name"]))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_architecture_resolves(cell):
    cfg = run.load_config(cell["config"])
    assert (ROOT / cfg["reference"]).is_file()
    family = run.architecture(cfg)
    assert all(callable(getattr(family, n)) for n in run.ARCH_INTERFACE)


def test_architecture_without_the_interface_is_refused():
    with pytest.raises(AttributeError, match="program_params"):
        run.architecture({"reference": "bench/reference/ops.py"})


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_states_its_cuts(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_spec_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3" and SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
