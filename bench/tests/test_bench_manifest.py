"""``BENCHMARK.json`` against the benchmark's contract, and lookup of
every configuration, traffic mix and per-layer metric by name."""
import copy
import json

import pytest

import bench_tiny  # noqa: F401  (import paths)
from lib import manifest as mf


@pytest.fixture(scope="module")
def m():
    return mf.load()


def test_manifest_is_sound(m):
    assert mf.validate(m) == []


def test_every_piece_is_found_by_name(m):
    for c in m["configs"]:
        cfg = mf.config_file(m, c["name"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg)
    for w in m["workloads"]:
        assert mf.traffic_file(w["traffic"])["kind"] in ("serve", "ingest")
    for x in m["per_layer"]:
        assert callable(mf.metric_reader(x["name"]))


def test_each_layer_metric_moves_a_metric_its_cells_report(m):
    for x in m["per_layer"]:
        for wl in x["workloads"]:
            assert x["moves"] in [e["name"] for e in mf.e2e_of(m, wl)]


def _broken(m, path, value):
    out = copy.deepcopy(m)
    node = out
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value
    return out


@pytest.mark.parametrize("path,value,needle", [
    (("workloads", 0, "name"), "has space", "name rule"),
    (("workloads", 0, "name"), "a" * 65, "name rule"),
    (("end_to_end", 0, "unit"), "queries per s", "unit"),
    (("end_to_end", 0, "unit"), "µs", "unit"),
    (("end_to_end", 0, "bound"), 0.3, "bound"),
    (("end_to_end", 0, "bound"), 0.001, "bound"),
    (("end_to_end", 0, "source"), "program_counter", "source"),
    (("per_layer", 0, "workloads"), ["no.such.cell"], "no cell"),
    (("per_layer", 0, "moves"), "ingest_docs_per_s", "does not report"),
    (("per_layer", 0, "moves"), "nothing", "no end-to-end"),
    (("workloads", 1, "chips"), 2, "chips"),
    (("workloads", 1, "traffic"), "no.such.mix", "traffic"),
    (("run_seconds",), 52, "run_seconds"),
    (("command",), ["python3", "/abs/run.py"], "leaves the checkout"),
    (("configs", 0, "file"), "elsewhere/cw09b.json", "not under paths"),
])
def test_contract_violations_are_refused(m, path, value, needle):
    errors = mf.validate(_broken(m, path, value))
    assert any(needle in e for e in errors), errors


def test_four_chip_share_is_capped(m):
    out = copy.deepcopy(m)
    for w in out["workloads"]:
        w["chips"] = 4
    assert any("4 chips" in e for e in mf.validate(out))
    for w in out["workloads"][1:]:
        w["chips"] = 1
    assert not any("4 chips" in e for e in mf.validate(out))


def test_extra_key_is_refused(m):
    out = copy.deepcopy(m)
    out["per_layer"][0]["why"] = "not a key of a metric"
    assert any("keys" in e for e in mf.validate(out))
    assert len(json.dumps(m)) < 64 * 1024
