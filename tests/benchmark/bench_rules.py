"""What the tests of this directory share when they state a RULE about
BENCHMARK.json, so that a later PR's cell, configuration or reader is added as
data and no test here has to be edited for it.

A test that speaks of the manifest is a function of a manifest (a dict), named
in its file's `MANIFEST_RULES`; the file's own test runs it on BENCHMARK.json.
`manifest_rules()` finds every such list in this directory, so
`test_bench_manifest.py` can run all of them on a manifest that was extended in
memory the way a later PR would extend it (`extended`), and on doctored copies
that the rules were written to refuse (`broken_by`).  A new test file joins by
defining `MANIFEST_RULES` itself.  (A test file finds this module because
pytest puts a test file's own directory first on `sys.path`.)

The rules a test of a cell states: what the cell must carry (`<=`) and what it
must not, by name; that a list CONTAINS the cells its issue named in their order
(`in_order`) or BEGINS with them; that a PR's readers are one run (`run_of`).
Never the length of a list, its tail, or equality with today's list.
"""
import copy
import functools
import importlib
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT, HERE):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmark.harness import manifest  # noqa: E402


def names(entries) -> list:
    return [e["name"] for e in entries]


def in_order(wanted, seq) -> bool:
    """Every one of `wanted` is in `seq`, in that relative order; `seq` may hold more."""
    rest = iter(seq)
    return all(w in rest for w in wanted)


def run_of(wanted, seq) -> bool:
    """`wanted` is one contiguous run of `seq`, wherever it lies."""
    wanted, seq = list(wanted), list(seq)
    return wanted[0] in seq and seq[seq.index(wanted[0]):][:len(wanted)] == wanted


def per_layer_of(man: dict, cell: str) -> set:
    return set(names(manifest.metrics_for(man, "per_layer", cell)))


def entry(man: dict, group: str, name: str) -> dict:
    return next(m for m in man[group] if m["name"] == name)


@functools.lru_cache(maxsize=None)
def manifest_rules() -> list:
    """(file::rule, function of a manifest) for every rule of this directory."""
    out = []
    for path in sorted(HERE.glob("test_bench_*.py")):
        if "MANIFEST_RULES" in path.read_text():
            mod = importlib.import_module(path.stem)
            out += [(f"{path.name}::{rule.__name__}", rule) for rule in mod.MANIFEST_RULES]
    return out


def broken_by(man: dict) -> list:
    """The rules that refuse `man`.  A rule that looks a cell or an entry up and
    does not find it has refused too."""
    out = []
    for label, rule in manifest_rules():
        try:
            rule(man)
        except (AssertionError, LookupError, StopIteration):
            out.append(label)
    return out


def extended(man: dict) -> dict:
    """`man` as a later PR would leave it: one configuration, a serving and a
    training cell of it, and one per-layer reader appended, each cell's name
    appended to the lists it joins (its end-to-end metrics' and a few accepted
    readers').  The files are tiny rehearsal ones; the reader shares
    metrics/host_dispatch_ms.py by the split-quantity rule."""
    man = copy.deepcopy(man)
    n = len(man["workloads"])  # in the names, so that an extended manifest can be extended again
    config, serve, train = f"added_config_{n}", f"added_serve_{n}", f"added_train_{n}"
    tiny = sorted(str(p.relative_to(ROOT)) for p in (ROOT / "benchmark" / "rehearsal").glob("tiny*.json"))
    file = next(f for f in tiny if f not in {c["file"] for c in man["configs"]})  # a file is one configuration's only
    man["configs"].append({"name": config, "source": "https://example.org/added/config.json",
                           "file": file, "reduced": [],
                           "why": "what a later model_config PR appends"})
    man["workloads"] += [
        {"name": serve, "config": config, "traffic": "tiny_closed_c4", "chips": 1,
         "why": "a served cell a later PR appends"},
        {"name": train, "config": config, "traffic": "tiny_steps", "chips": 1,
         "why": "a training cell a later PR appends"}]
    joins = {serve: {"gen_img_tok_per_s", "image_latency_p50_s", "window_compiles.serve",
                     "decode_step_device_ms", "decode_step_roofline", "admit_host_ms",
                     "decode_compute_device_ms", "idle_in_program_spans_pct"},
             train: {"train_img_tok_per_s", "window_compiles.train", "host_dispatch_ms",
                     "flash_device_ms", "train_attn_device_ms", "train_unscoped_pct",
                     "train_moe_device_ms", "train_mla_core_device_ms"}}
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:  # a metric without the key is every cell's already
            m["workloads"] += [c for c, lists in joins.items() if m["name"] in lists]
    man["per_layer"].append({"name": f"host_dispatch_ms.added_{n}", "unit": "ms", "better": "lower",
                             "source": "host_clock", "layer": "train loop",
                             "moves": "train_img_tok_per_s", "workloads": [train]})
    return man
