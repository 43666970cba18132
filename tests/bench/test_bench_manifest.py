"""BENCHMARK.json keeps to its contract, and every name in it resolves to
the file that holds it."""

import dataclasses
import json
import re

import pytest

from benchmarks.chip import manifest
from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MAN = manifest.load_manifest(ROOT)
CELLS = [w["name"] for w in MAN["workloads"]]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert (ROOT / p).is_dir()
    assert (ROOT / MAN["command"][1]).is_file()
    assert MAN["command"][1].startswith(MAN["paths"][0] + "/")


def _text(s):
    assert 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_entries():
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[group]:
            assert NAME.match(e["name"]), e["name"]
            names.add((group in ("end_to_end", "per_layer"), e["name"]))
            if "why" in e:
                _text(e["why"])
    assert len(names) == sum(len(MAN[g]) for g in MAN if g not in
                             ("command", "paths", "run_seconds"))
    e2e = {m["name"] for m in MAN["end_to_end"]}
    assert "setup_s" in e2e
    for m in MAN["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["moves"] in e2e
        _text(m["layer"])
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert sum(w["chips"] == 4 for w in MAN["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = manifest.load_cell(cell, ROOT)
    assert c.limits is not None and c.traffic["kind"] == "train"
    assert manifest.reference(c.config).train
    for m in c.per_layer:
        assert callable(manifest.metric_reader(m["name"]))


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    from repro.configs.base import ModelConfig
    from repro.models import registry
    with open(ROOT / conf["file"]) as f:
        c = json.load(f)
    assert conf["file"].startswith(MAN["paths"][0] + "/")
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    assert set(c["model"]) <= fields
    published = registry.get_arch(c["arch"]).cfg
    changed = {k for k, v in c["model"].items()
               if getattr(published, k) != v} | set(c["published"])
    # a key that differs from the program's published config is either a
    # cut (listed in ``reduced``) or set to the published model's value
    assert set(conf["reduced"]) == set(c["reduced"]) == set(c["published"])
    assert set(conf["reduced"]) <= changed
