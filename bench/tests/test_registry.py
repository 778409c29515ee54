"""Every cell, configuration, model family, traffic mix, driver and metric
resolves by its name, and ``BENCHMARK.json`` keeps to the shape the
benchmark promises."""
import glob
import json
import os
import re

import pytest

from bench.harness import registry

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
REG = registry.Registry(REPO)
NAME = registry.NAME
UNIT = registry.UNIT
LINE = re.compile(r"^[^\t\n]{1,200}$")
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj\w*)_size$|"
                    r"_dim$|_rank$|head_size|expan|experts_per_tok")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["paths"] == ["bench"]
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_command_stays_in_paths():
    cmd = SPEC["command"]
    assert len(cmd) <= 32
    for w in cmd[1:]:
        assert not w.startswith("/") and ".." not in w
        if "/" in w:
            assert w.split("/")[0] in SPEC["paths"]
            assert os.path.exists(os.path.join(REPO, w))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(cell):
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and LINE.match(entry["why"])
    doc = REG.workload(cell)
    REG.config(doc["config"])
    REG.traffic(doc["traffic"])
    REG.driver(doc["driver"])
    e2e = {m["name"] for m in REG.end_to_end(cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = REG.per_layer(cell)
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        REG.reader(m["name"])


def test_cells_unique_and_few_on_four_chips():
    names = [w["name"] for w in SPEC["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(names) // 2)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("bench/configs/")
    doc = REG.config(entry["name"])
    assert doc["name"] == entry["name"]
    assert doc["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    for k in entry["reduced"]:
        assert NAME.match(k) and not WIDTHS.search(k), k
    assert {"source", "assumed", "deployment", "family"} <= set(doc)
    assert REG.family(doc["family"]).arch_config(doc).family == doc["family"]
    assert LINE.match(entry["source"]) and LINE.match(entry["why"])
    assert any(c["config"] == entry["name"] for c in SPEC["workloads"])


def test_config_files_differ():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(m.get("workloads", [])) <= cells
    if m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_file_is_named_from_name_characters():
    for dirpath, _, files in os.walk(os.path.join(REPO, "bench")):
        for f in files:
            if f.endswith(".pyc"):
                continue
            stem = f.rsplit(".", 1)[0]
            assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.-]*$", stem), f


def test_unknown_names_are_refused():
    with pytest.raises(ValueError):
        REG.config("../configs/qwen3-4b")
    with pytest.raises(KeyError):
        REG.workload("no-such-cell")


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(REPO, "bench", "families", "*.py"))), ids=os.path.basename)
def test_family_exposes_the_contract(path):
    mod = REG.family(os.path.basename(path)[:-3])
    for name in registry.FAMILY:
        if name == "SPANS":
            assert isinstance(mod.SPANS, tuple)
            assert all(isinstance(s, str) and s for s in mod.SPANS)
        else:
            assert callable(getattr(mod, name)), name


@pytest.mark.parametrize("name,error,says", [
    ("no-such-family", FileNotFoundError,
     os.path.join("families", "no-such-family.py")),
    ("../drivers/serve_open_loop", ValueError, "../drivers/serve_open_loop"),
    ("dense family", ValueError, "dense family"),
])
def test_unknown_family_is_refused(name, error, says):
    with pytest.raises(error, match=re.escape(says)):
        REG.family(name)


def test_family_without_the_contract_is_refused(tmp_path):
    """A family file that lacks a name of the contract is refused, and the
    error names the file and what it lacks."""
    from bench.tests import tiny
    root, bench = tiny.make_tree(tmp_path, families={
        "half": "SPANS = ()\n\ndef arch_config(cfg):\n    return None\n"})
    with pytest.raises(AttributeError) as e:
        registry.Registry(root, bench).family("half")
    assert "half.py" in str(e.value) and "Reference" in str(e.value)
