"""BENCHMARK.json against its contract, and every file it names by name.  Each
check is a function of a manifest (`MANIFEST_RULES`, see bench_rules.py): it runs
on BENCHMARK.json as it is, on a copy extended the way a later PR would extend
it, and on doctored copies it is there to refuse."""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import json  # noqa: E402
import re  # noqa: E402

import bench_rules  # noqa: E402
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


def _cells_of(man, m):
    return m.get("workloads", [w["name"] for w in man["workloads"]])


# ---- the rules: functions of a manifest --------------------------------------
def exactly_the_contracts_keys(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert len(json.dumps(man, indent=1)) <= 64 * 1024


def command_and_paths_stay_inside_the_benchmarks_directories(man):
    assert 1 <= len(man["paths"]) <= 16 and len(man["command"]) <= 32
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in man["paths"])
    for c in man["configs"]:
        assert any(c["file"].startswith(p + "/") for p in man["paths"])


def run_seconds_fits_a_full_check_of_24_cells(man):
    rs = man["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def metric_entry_is_well_formed(man, group, m):
    name = m["name"]
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if group == "end_to_end" else {"layer", "moves"}
    assert set(m) <= allowed and allowed - {"workloads"} <= set(m)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = {w["name"] for w in man["workloads"]}
    assert set(_cells_of(man, m)) <= cells and _cells_of(man, m)
    assert len(set(_cells_of(man, m))) == len(_cells_of(man, m)), "a cell is listed once"
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


def every_metric_entry_is_well_formed(man):
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            metric_entry_is_well_formed(man, group, m)


def cell_names_files_that_exist_and_reports_enough(man, cell):
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


def every_cell_names_files_that_exist_and_reports_enough(man):
    for w in man["workloads"]:
        cell_names_files_that_exist_and_reports_enough(man, w["name"])


def configs_are_used_have_their_own_file_and_list_what_they_cut(man):
    names = [c["name"] for c in man["configs"]]
    files = [c["file"] for c in man["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    assert {w["config"] for w in man["workloads"]} == set(names), "a configuration without its cell"
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        sizes = json.loads((ROOT / c["file"]).read_text())
        # a file that cuts nothing need not say so
        assert set(c["reduced"]) == set(sizes.get("reduced", ())), "the file explains every cut key"
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank)$|hidden|dim_head|^dim$|heads", key), "a width"
        assert (ROOT / sizes["reference"]).is_file()


def no_two_names_collide_and_a_pair_of_config_and_traffic_appears_once(man):
    metrics = [m["name"] for g in ("end_to_end", "per_layer") for m in man[g]]
    assert len(set(metrics)) == len(metrics)
    cells = [w["name"] for w in man["workloads"]]
    assert len(set(cells)) == len(cells)
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(set(pairs)) == len(pairs)
    # the one place that holds the share of four-chip cells: a quarter, and one always may
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(1, len(cells) // 4)


MANIFEST_RULES = [exactly_the_contracts_keys, command_and_paths_stay_inside_the_benchmarks_directories,
                  run_seconds_fits_a_full_check_of_24_cells, every_metric_entry_is_well_formed,
                  every_cell_names_files_that_exist_and_reports_enough,
                  configs_are_used_have_their_own_file_and_list_what_they_cut,
                  no_two_names_collide_and_a_pair_of_config_and_traffic_appears_once]


# ---- on BENCHMARK.json (and the rehearsal manifest) as they are ---------------
def test_exactly_the_contracts_keys():
    exactly_the_contracts_keys(MAN["benchmark"])
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_command_and_paths_stay_inside_the_benchmarks_directories():
    command_and_paths_stay_inside_the_benchmarks_directories(MAN["benchmark"])


def test_run_seconds_fits_a_full_check_of_24_cells():
    run_seconds_fits_a_full_check_of_24_cells(MAN["benchmark"])


@pytest.mark.parametrize("which,group,name", METRICS)
def test_metric_entry_is_well_formed(which, group, name):
    metric_entry_is_well_formed(MAN[which], group, bench_rules.entry(MAN[which], group, name))


@pytest.mark.parametrize("which,cell", CELLS)
def test_cell_names_files_that_exist_and_reports_enough(which, cell):
    cell_names_files_that_exist_and_reports_enough(MAN[which], cell)


def test_configs_are_used_have_their_own_file_and_list_what_they_cut():
    configs_are_used_have_their_own_file_and_list_what_they_cut(MAN["benchmark"])


def test_no_two_names_collide_and_a_pair_of_config_and_traffic_appears_once():
    no_two_names_collide_and_a_pair_of_config_and_traffic_appears_once(MAN["benchmark"])


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in MAN["benchmark"]["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert ok.match(str(f.relative_to(ROOT))), f


# ---- on a copy a later PR has extended, and on copies the rules must refuse ----
def test_every_file_that_states_a_rule_of_the_manifest_is_found():
    files = {label.split("::")[0] for label, _ in bench_rules.manifest_rules()}
    assert {"test_bench_manifest.py", "test_bench_program_trace.py", "test_bench_rehearsal_glm.py",
            "test_bench_rehearsal_q3n.py"} <= files
    assert bench_rules.broken_by(MAN["benchmark"]) == []


def test_a_cell_a_configuration_and_a_reader_can_be_added_as_data():
    """ISSUE 32's experiment, kept: one configuration, a serving and a training
    cell and one per-layer reader appended to a copy of BENCHMARK.json in memory;
    every rule under tests/benchmark/ that reads the manifest holds on the copy."""
    before = MAN["benchmark"]
    man = bench_rules.extended(before)
    serve, train = bench_rules.names(man["workloads"])[len(before["workloads"]):]
    assert serve in bench_rules.entry(man, "per_layer", "decode_step_roofline")["workloads"]
    assert train in bench_rules.entry(man, "per_layer", "flash_device_ms")["workloads"]
    assert man["configs"][len(before["configs"]):] and man["per_layer"][len(before["per_layer"]):]
    assert bench_rules.broken_by(man) == []
    assert bench_rules.broken_by(bench_rules.extended(man)) == [], "and by the PR after that one"


def _swap_two_accepted_cells(man):
    w = man["workloads"]
    w[1], w[2] = w[2], w[1]


def _remove_a_cell(man):
    man["workloads"] = [w for w in man["workloads"] if w["name"] != "train_d8"]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [c for c in m["workloads"] if c != "train_d8"]


def _add_a_configuration_without_its_cell(man):
    man["configs"].append(dict(man["configs"][0], name="nobody_runs_it",
                               file="benchmark/rehearsal/tiny.json", reduced=[]))


def _put_the_dalle_blocks_share_of_peak_on_a_hybrid_cell(man):
    bench_rules.entry(man, "per_layer", "mfu_pct")["workloads"].append("train_q3n_ep16")


def _take_four_chips_for_one_cell_more_than_a_quarter(man):
    for w in man["workloads"][:max(1, len(man["workloads"]) // 4) + 1]:  # two of today's six
        w["chips"] = 4


def _take_a_reader_out_of_its_prs_run(man):
    names = bench_rules.names(man["per_layer"])
    moved = man["per_layer"].pop(names.index("train_mla_core_device_ms"))
    man["per_layer"].append(moved)


def _drop_a_cell_from_a_list_its_issue_named(man):
    bench_rules.entry(man, "per_layer", "train_moe_device_ms")["workloads"].remove("train_q3n_ep16")


REFUSED = [  # (what a PR might do to the manifest, the rule that is there to refuse it)
    (_swap_two_accepted_cells, "test_bench_rehearsal_glm.py::the_accepted_entries_keep_their_order"),
    (_remove_a_cell, "test_bench_rehearsal_glm.py::the_accepted_entries_keep_their_order"),
    (_add_a_configuration_without_its_cell,
     "test_bench_manifest.py::configs_are_used_have_their_own_file_and_list_what_they_cut"),
    (_put_the_dalle_blocks_share_of_peak_on_a_hybrid_cell,
     "test_bench_rehearsal_q3n.py::the_q3n_cell_and_its_metrics_are_as_the_issue_names_them"),
    (_take_four_chips_for_one_cell_more_than_a_quarter,
     "test_bench_manifest.py::no_two_names_collide_and_a_pair_of_config_and_traffic_appears_once"),
    (_take_a_reader_out_of_its_prs_run,
     "test_bench_rehearsal_glm.py::the_glm_cell_and_its_metrics_are_as_the_issue_names_them"),
    (_drop_a_cell_from_a_list_its_issue_named,
     "test_bench_rehearsal_q3n.py::the_q3n_cell_and_its_metrics_are_as_the_issue_names_them"),
]


@pytest.mark.parametrize("doctor,refused_by", REFUSED, ids=[d.__name__.strip("_") for d, _ in REFUSED])
@pytest.mark.parametrize("base", ["as_it_is", "extended"])
def test_the_rules_still_refuse_what_they_were_written_to_refuse(base, doctor, refused_by):
    man = copy.deepcopy(MAN["benchmark"])
    if base == "extended":  # the refusals do not lean on today's length either
        man = bench_rules.extended(man)
    doctor(man)
    assert refused_by in bench_rules.broken_by(man)
