"""BENCHMARK.json keeps to its contract, and every name in it resolves to
the file that holds it."""

import functools
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


# what a cut may never name: a width (hidden, intermediate, expert,
# state or projection size, a head size, an expansion factor) or the
# experts a token is routed to
WIDTHS = {"d_model", "d_ff", "head_dim", "d_expert", "shared_d_expert",
          "top_k", "d_state", "d_conv", "expand", "dt_rank"}


def _dotted(settings, prefix=""):
    for k, v in settings.items():
        if isinstance(v, dict):
            yield from _dotted(v, f"{prefix}{k}.")
        else:
            yield prefix + k, v


def _rule_breaches(reduced, c) -> list:
    """Where a configuration file ``c``, with its entry's ``reduced``
    list, breaks the rule: every setting that differs from the program's
    published config is a listed cut, with the published value beside it
    and its reason; the precision, which the file's deployment states, is
    the one other setting a file may change."""
    from repro.models import registry
    published = registry.get_arch(c["arch"]).cfg
    settings = dict(_dotted(c["model"]))
    out = []
    if not set(reduced) == set(c["reduced"]) == set(c["published"]):
        out.append("reduced and published name different keys")
    for key, value in settings.items():
        try:
            was = functools.reduce(getattr, key.split("."), published)
        except AttributeError:
            out.append(f"{key}: no such setting")
            continue
        if key in reduced:
            if value == was or c["published"].get(key) != was:
                out.append(f"{key}: a cut must differ from the published "
                           f"value {was!r}, which published states")
        elif value != was and key != "dtype":
            out.append(f"{key}: changed from {was!r} but not a listed cut")
    for key in reduced:
        if key.split(".")[-1] in WIDTHS or key.endswith(("_dim", "_rank")):
            out.append(f"{key}: a width is never cut")
        if key not in settings:
            out.append(f"{key}: listed as a cut but not set")
    return out


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    with open(ROOT / conf["file"]) as f:
        c = json.load(f)
    assert conf["file"].startswith(MAN["paths"][0] + "/")
    assert _rule_breaches(conf["reduced"], c) == []


SHARE = {"num_layers": 6, "vocab_size": 12800, "dtype": "float32",
         "moe": {"num_experts": 8}}
SHARE_CUTS = {"num_layers": 28, "vocab_size": 102400, "moe.num_experts": 64}


@pytest.mark.parametrize("model,cuts,breach", [
    (SHARE, SHARE_CUTS, None),
    # an unlisted change, at the top level and inside the group
    (dict(SHARE, d_ff=5472), SHARE_CUTS, "d_ff: changed"),
    (dict(SHARE, moe={"num_experts": 8, "top_k": 2}), SHARE_CUTS,
     "moe.top_k: changed"),
    # a listed cut without its published value, or of a width
    (SHARE, dict(SHARE_CUTS, **{"moe.num_experts": 8}),
     "moe.num_experts: a cut must differ"),
    (dict(SHARE, moe={"num_experts": 8, "d_expert": 704}),
     dict(SHARE_CUTS, **{"moe.d_expert": 1408}), "a width is never cut"),
])
def test_config_rule(model, cuts, breach):
    """A chip's share written with dotted cuts passes the rule that
    ``test_config_file`` holds every configuration to; an unlisted change
    does not."""
    c = {"arch": "deepseek-moe-16b", "model": model, "published": cuts,
         "reduced": {k: "why" for k in cuts}}
    found = _rule_breaches(list(cuts), c)
    if breach is None:
        assert found == []
    else:
        assert any(breach in f for f in found), found
