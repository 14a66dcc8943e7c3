"""BENCHMARK.json against its contract, and every file it names by name."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import json  # noqa: E402
import re  # noqa: E402

from benchmark.harness import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFESTS = {"benchmark": ROOT / "BENCHMARK.json",
             "rehearsal": ROOT / "benchmark" / "rehearsal" / "manifest.json"}
MAN = {k: json.loads(p.read_text()) for k, p in MANIFESTS.items()}
METRICS = [(k, g, m["name"]) for k, man in MAN.items() for g in ("end_to_end", "per_layer")
           for m in man[g]]
CELLS = [(k, w["name"]) for k, man in MAN.items() for w in man["workloads"]]


def _metric(which, group, name):
    return next(m for m in MAN[which][group] if m["name"] == name)


def _cells_of(man, m):
    return m.get("workloads", [w["name"] for w in man["workloads"]])


def test_exactly_the_contracts_keys():
    assert set(MAN["benchmark"]) == {"command", "paths", "run_seconds", "configs", "workloads",
                                     "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_command_and_paths_stay_inside_the_benchmarks_directories():
    man = MAN["benchmark"]
    assert 1 <= len(man["paths"]) <= 16 and len(man["command"]) <= 32
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in man["paths"])
    for c in man["configs"]:
        assert any(c["file"].startswith(p + "/") for p in man["paths"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = MAN["benchmark"]["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("which,group,name", METRICS)
def test_metric_entry_is_well_formed(which, group, name):
    man, m = MAN[which], _metric(which, group, name)
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if group == "end_to_end" else {"layer", "moves"}
    assert set(m) <= allowed and allowed - {"workloads"} <= set(m)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = {w["name"] for w in man["workloads"]}
    assert set(_cells_of(man, m)) <= cells and _cells_of(man, m)
    if group == "end_to_end":
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        moved = next(e for e in man["end_to_end"] if e["name"] == m["moves"])
        assert set(_cells_of(man, m)) <= set(_cells_of(man, moved))
        # its own reader, or the one a split quantity (<quantity>.<group>) shares
        stems = {name, name.rsplit(".", 1)[0]}
        assert any((ROOT / "benchmark" / "metrics" / f"{s}.py").is_file() for s in stems)
        assert callable(manifest.reader(name))


@pytest.mark.parametrize("which,cell", CELLS)
def test_cell_names_files_that_exist_and_reports_enough(which, cell):
    man = MAN[which]
    w = manifest.cell(man, cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    sizes = manifest.config_sizes(man, w["config"])
    assert sizes["dim"] == sizes["heads"] * sizes["dim_head"] or sizes["dim"] > 0
    traffic = manifest.traffic(w["traffic"])
    assert (ROOT / "benchmark" / "kinds" / f"{traffic['kind']}.py").is_file()
    e2e = [m["name"] for m in manifest.metrics_for(man, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_for(man, "per_layer", cell)


def test_configs_are_used_have_their_own_file_and_list_what_they_cut():
    man = MAN["benchmark"]
    names = [c["name"] for c in man["configs"]]
    files = [c["file"] for c in man["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    assert {w["config"] for w in man["workloads"]} == set(names)
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        sizes = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(sizes["reduced"]), "the file explains every cut key"
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank)$|hidden|dim_head|^dim$|heads", key), "a width"
        assert (ROOT / sizes["reference"]).is_file()


def test_no_two_names_collide_and_a_pair_of_config_and_traffic_appears_once():
    man = MAN["benchmark"]
    metrics = [m["name"] for g in ("end_to_end", "per_layer") for m in man[g]]
    assert len(set(metrics)) == len(metrics)
    cells = [w["name"] for w in man["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(1, len(cells) // 4)


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in MAN["benchmark"]["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert ok.match(str(f.relative_to(ROOT))), f
