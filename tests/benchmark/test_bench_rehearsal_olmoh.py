"""The Olmo-Hybrid trunk's rehearsal: a tiny configuration of the same layer
cycle (gated_delta x3 + full, key heads 12 and value heads 24 wide, untied
head, norm on each branch's output) through `run.py --rehearse` with `--trace
1`, as the driver would run the cell `serve_olmoh_s32`; the new per-layer
readers on a trace that names their scopes and on one that does not; what the
two rooflines are measured against; the configuration's file against the
catalog's row."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import bench_rules  # noqa: E402
from benchmark.harness import manifest, work_olmoh  # noqa: E402

MANIFEST = ROOT / "benchmark" / "rehearsal" / "manifest_olmoh.json"
MAN = json.loads(MANIFEST.read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "serve_olmoh_s32"
NEW = ["decode_gdn_device_ms", "decode_gdn_step_roofline", "decode_olmoh_step_roofline"]
JOINED = ["window_compiles.serve", "lane_occupancy_pct", "gen_tok_per_s_median", "queue_wait_p50_ms",
          "ttft_p50_ms", "image_latency_done_p50_s", "decode_step_device_ms", "prefill_device_ms",
          "decode_kv_gather_device_ms", "decode_compute_device_ms", "decode_sample_device_ms",
          "decode_unscoped_pct", "vae_decode_device_ms", "admit_host_ms", "evict_host_ms",
          "idle_in_program_spans_pct"]


@pytest.fixture(scope="module")
def line():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--manifest", str(MANIFEST),
         "--rehearse", "--workload", "tiny_olmoh_serve", "--seed", str(2**31 + 13),
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_rehearsal_cell_is_correct_against_the_new_reference(line):
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    replay = line["detail"]["correct"]
    # float32 on the CPU: every delivered code lies inside the reference's top k
    assert replay["replayed"] == 2 and replay["codes"] == 2 * 16 and replay["top_k"] == 8
    assert replay["outside_top_k_share"] == 0 and replay["pixels_rms_err"] < 1e-4
    assert line["metrics"]["window_compiles.serve"]["value"] == 0
    assert line["metrics"]["lane_occupancy_pct"]["value"] == 100.0


def test_the_rehearsal_cells_correct_also_holds_the_state_the_window_left(line):
    """`closed_loop_state`: the engine that served the window hands out its
    in-flight lanes' states, and they are the reference's recurrence on each
    lane's own text and codes, kept in float32."""
    state = line["detail"]["correct"]
    assert state["state_layers"] == state["gdn_state_layers"] == 3
    assert len(state["state_lanes_at"]) == 2 and state["state_lanes_at"][0] > state["state_lanes_at"][1] > 9
    assert state["state_rms_err"] < 1e-4 and state["state_float32_share"] > 0.99
    assert (state["state_tolerance"], state["float32_share_limit"]) == (0.05, 0.5)
    assert state["gdn_step_kernel_layers"] == 0  # the kernel is the TPU's; the CPU runs the definition


@pytest.mark.parametrize("reading,refused_by", [
    ("system", None), ("state_bfloat16", "state_float32_share"), ("wrong_slot", "state_rms_err"),
    ("off_by_8_percent", "state_rms_err"), ("no_lane_in_flight", "state_rms_err")])
def test_each_limit_of_the_state_check_refuses_its_control(reading, refused_by):
    """`correct_state.state_agrees` on a snapshot made from the reference's own
    states (float32, tiny): as they are; rounded to bfloat16; another request's;
    8 % of their RMS off; none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import build, correct_state

    sizes = manifest.config_sizes(MAN, "tiny_olmoh")
    cfg = build.dalle_config(sizes)
    params = build.make_weights(cfg, 2**31 + 17, jnp.float32)
    ref = manifest.reference(sizes)
    rng = np.random.default_rng(5)

    class Req:
        def __init__(self):
            self.text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,)).astype(np.int32)

    def lane(positions):
        req, codes = Req(), rng.integers(0, cfg.num_image_tokens, (positions - cfg.text_seq_len,))
        states = [np.asarray(s) for s in ref.recurrent_states(
            params, sizes, req.text, codes.astype(np.int32), positions)]
        return {"request": req, "positions": positions, "codes": codes.astype(np.int32), "states": states}

    snapshot = [lane(12), lane(20), lane(16)]
    if reading == "state_bfloat16":
        for s in snapshot:
            s["states"] = [np.asarray(jax.lax.reduce_precision(a, 8, 7)) for a in s["states"]]
    elif reading == "wrong_slot":
        snapshot[1]["states"] = lane(20)["states"]  # another request's, at the same position
    elif reading == "off_by_8_percent":
        snapshot[2]["states"] = [a + 0.08 * np.sqrt((a ** 2).mean()) * rng.choice([-1.0, 1.0], a.shape)
                                 .astype(np.float32) for a in snapshot[2]["states"]]
    elif reading == "no_lane_in_flight":
        snapshot = []
    ok, detail = correct_state.state_agrees(params, sizes, snapshot)
    assert ok == (refused_by is None), detail
    within = {"state_rms_err": detail["state_rms_err"] <= detail["state_tolerance"],
              "state_float32_share": detail["state_float32_share"] >= detail["float32_share_limit"]}
    assert [name for name, fine in within.items() if not fine] == ([refused_by] if refused_by else [])
    if snapshot:
        assert detail["state_lanes_at"] == [20, 16]  # the furthest along and the middle one


def test_a_cpu_line_carries_counts_only_and_no_reader_raised(line):
    sources = {m["name"]: m["source"] for m in MAN["per_layer"]}
    assert set(line["metrics"]) <= set(sources)
    for name, m in line["metrics"].items():
        if sources[name] != "program_counter":
            assert m["value"] is None, name
    assert "breakdown" not in line


def _ctx(sizes, traffic, paths=None, peaks=None, records=None):
    from benchmark.harness import program_trace

    ctx = manifest.Context(sizes=sizes, traffic=traffic, records=records or {},
                           trace=None if paths is None else object(), peaks=peaks, end_to_end={})
    if paths is not None:
        ops = [["fusion", 10.0 * i, 5.0, p] for i, p in enumerate(paths)]
        ctx.program_trace = program_trace.ProgramTrace({"devices": {"/device:TPU:0": {
            "ops": ops, "modules": [["jit_serve_decode_step(1)", 0.0, 10.0 * len(paths)]]}},
            "host": []})
    return ctx


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_none_where_nothing_is_named(name):
    read = manifest.reader(name)
    sizes = manifest.config_sizes(MAN, "tiny_olmoh")
    assert read(_ctx(sizes, {"slots": 4})) is None  # no trace taken, no peaks: a rehearsal
    # a parent's program (the DALL-E block) names none of the new scopes
    dalle = manifest.config_sizes(BENCH, "dalle_2048_d8")
    old = _ctx(dalle, {"slots": 8}, peaks={"hbm_bytes_per_s": 819e9},
               paths=["jit(serve_decode_step)/attn/paged_decode_attn", "jit(serve_decode_step)/ff/dot_general"],
               records={"trace_positions": [[200] * 8], "weight_itemsize": 4, "kv_itemsize": 4})
    assert read(old) is None


def test_the_readers_find_the_new_scopes_in_a_trace():
    sizes = manifest.config_sizes(BENCH, "olmo_hybrid_7b_p1")
    paths = ["jit(serve_decode_step)/attn/gdn_proj/dot_general",
             "jit(serve_decode_step)/attn/gdn_conv_step/mul",
             "jit(serve_decode_step)/attn/gdn_step/reduce",
             "jit(serve_decode_step)/attn/gdn_step/add",
             "jit(serve_decode_step)/attn/gdn_gate_norm/mul",
             "jit(serve_decode_step)/attn/dot_general",
             "jit(serve_decode_step)/ff/dense_ff/dot_general",
             "jit(serve_decode_step)/sample/sort"]
    ctx = _ctx(sizes, {"slots": 32}, paths=paths, peaks={"hbm_bytes_per_s": 819e9},
               records={"trace_positions": [[129 + 128 * i for i in range(32)]],
                        "weight_itemsize": 2, "kv_itemsize": 2})
    assert manifest.reader("decode_gdn_device_ms")(ctx) == pytest.approx(25e-6)
    # 10 ns under gdn_step against 0.431 GB at 819 GB/s: the arithmetic, not a device's number
    want = 100.0 * work_olmoh.gdn_step_bytes(sizes, 32) / 819e9 / 10e-9
    assert manifest.reader("decode_gdn_step_roofline")(ctx) == pytest.approx(want)
    whole = manifest.reader("decode_olmoh_step_roofline")(ctx)
    byts = work_olmoh.decode_step_bytes(sizes, [129 + 128 * i for i in range(32)], 32, 2, 2)
    assert whole == pytest.approx(100.0 * byts / 819e9 / 80e-9)
    # the accepted readers by scope read this trunk's step too
    assert manifest.reader("decode_compute_device_ms")(ctx) == pytest.approx(35e-6)
    assert manifest.reader("decode_sample_device_ms")(ctx) == pytest.approx(5e-6)
    assert manifest.reader("decode_unscoped_pct")(ctx) == 0.0


def test_required_bytes_of_the_cell():
    """work_olmoh against the issue's own arithmetic: 832.5 M layer weights, 212
    MB of state, a step of about 3.25 GB = 3.97 ms at 819 GB/s."""
    sizes = manifest.config_sizes(BENCH, "olmo_hybrid_7b_p1")
    assert work_olmoh.layer_types(sizes) == ["gated_delta"] * 3 + ["full"]
    assert work_olmoh.layer_weights(sizes) == pytest.approx(832.5e6, rel=1e-3)
    assert work_olmoh.state_elements(sizes) * 4 * 32 == 32 * 3 * 30 * 96 * 192 * 4
    assert work_olmoh.taps_elements(sizes) == 3 * 3 * 11520
    assert work_olmoh.gdn_step_bytes(sizes, 32) == pytest.approx(0.431e9, rel=5e-3)
    spread = [129 + 128 * i for i in range(32)]  # one lane every 128 positions
    step = work_olmoh.decode_step_bytes(sizes, spread, 32, 2, 2)
    assert 3.1e9 < step < 3.4e9
    # an idle lane's state is still advanced; its keys are not read
    fewer = work_olmoh.decode_step_bytes(sizes, spread[:16], 32, 2, 2)
    assert step - fewer == pytest.approx(sum(p + 1 for p in spread[16:]) * 2 * 3840 * 2 + 16 * 3840 * 2)


def test_the_cells_configuration_keeps_every_published_width_and_the_whole_vocabulary():
    sizes = manifest.config_sizes(BENCH, "olmo_hybrid_7b_p1")
    catalog = {"model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
               "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
               "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
               "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
               "linear_num_key_heads": 30, "linear_num_value_heads": 30, "linear_key_head_dim": 96,
               "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
               "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
    for key, value in catalog.items():
        assert sizes[key] == value, key
    assert sizes["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8
    assert list(sizes["reduced"]) == ["depth"] and sizes["depth"] == 4
    # the program's keys say the same model
    assert (sizes["dim"], sizes["dense_ff_dim"], sizes["heads"], sizes["dim_head"]) == (3840, 11008, 30, 128)
    assert (sizes["gdn_key_heads"], sizes["gdn_value_heads"], sizes["gdn_key_dim"],
            sizes["gdn_value_dim"], sizes["gdn_conv_kernel"]) == (30, 30, 96, 192, 4)
    assert sizes["gdn_neg_eigval"] is True and sizes["attn_bias"] is False and sizes["norm_eps"] == 1e-06
    assert sizes["attn_types"] == ["gated_delta"] * 3 + ["full"] and sizes["dense_layers"] == sizes["depth"]
    assert sizes["num_text_tokens"] + sizes["text_seq_len"] + sizes["num_image_tokens"] == sizes["vocab_size"]
    assert sizes["share_input_output_emb"] is False and sizes["rotary_emb"] is False
    assert sizes["serve_recipe"]["param_dtype"] == "bfloat16" and "train_recipe" not in sizes
    assert len(sizes["assumed"]) >= 6 and "one TPU v5e chip" in sizes["deployment"]
    # and the program reads every one of its keys (build.dalle_config drops what DALLEConfig lacks)
    import dataclasses

    from benchmark.harness import build
    from dalle_pytorch_tpu.models.dalle import DALLEConfig

    cfg = build.dalle_config(sizes)
    known = {f.name for f in dataclasses.fields(DALLEConfig)}
    for key in ("gdn_neg_eigval", "pre_norm", "sandwich_norm", "qk_norm", "attn_bias", "axial_pos_emb",
                "dense_layers", "dense_ff_dim", "norm", "layer_scale"):
        assert key in known and getattr(cfg, key) == sizes[key], key
    assert cfg.total_tokens == 100352 and cfg.total_seq_len == 4224


def the_olmoh_cell_and_its_metrics_are_as_the_issue_names_them(bench):
    """A rule of the manifest (bench_rules.py): what this cell must carry and
    must not, that the lists it joined hold it after the cells that were there,
    and that its readers are one run.  How many cells follow is not this test's
    to say."""
    cell = manifest.cell(bench, CELL)
    assert cell["config"] == "olmo_hybrid_7b_p1" and cell["traffic"] == "closed_batch_s32_c32"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    config = bench_rules.entry(bench, "configs", "olmo_hybrid_7b_p1")
    assert config["reduced"] == ["depth"] and len(config["source"]) <= 200
    assert config["source"] == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    traffic = manifest.traffic(cell["traffic"])
    # closed_loop's generator, and a `correct` that also holds the state (kinds/closed_loop_state.py)
    assert traffic["kind"] == "closed_loop_state" and traffic["clients"] == traffic["slots"] == 32
    assert traffic["cond_scale"] == 1.0 and traffic["block_size"] == 64
    sizes = manifest.config_sizes(bench, cell["config"])
    vocabulary = sizes["num_text_tokens"] + sizes["text_seq_len"] + sizes["num_image_tokens"]
    # the sampler's k is taken of the WHOLE vocabulary: it has to filter the image columns
    assert int((1 - traffic["filter_thres"]) * vocabulary) < sizes["num_image_tokens"]
    per_layer = bench_rules.per_layer_of(bench, CELL)
    assert set(NEW) | set(JOINED) <= per_layer
    assert not per_layer & {"decode_step_roofline", "mfu_pct"}, "the DALL-E block's arithmetic"
    assert not {n for n in per_layer if n.startswith("train_")}, "a served cell"
    for name in ("gen_img_tok_per_s", "image_latency_p50_s"):
        assert bench_rules.in_order(["serve_batch", "serve_guided", CELL],
                                    bench_rules.entry(bench, "end_to_end", name)["workloads"])
    for name in JOINED:
        assert bench_rules.in_order(["serve_batch", "serve_guided", CELL],
                                    bench_rules.entry(bench, "per_layer", name)["workloads"]), name
    assert bench_rules.run_of(NEW, bench_rules.names(bench["per_layer"]))
    for name in NEW:
        assert bench_rules.entry(bench, "per_layer", name)["workloads"][0] == CELL
    assert bench_rules.in_order(["train_glm47_ep8", CELL], bench_rules.names(bench["workloads"]))


MANIFEST_RULES = [the_olmoh_cell_and_its_metrics_are_as_the_issue_names_them]


def test_the_new_cell_and_its_metrics_are_in_the_manifest_as_the_issue_names_them():
    the_olmoh_cell_and_its_metrics_are_as_the_issue_names_them(BENCH)


def test_the_rehearsal_manifest_carries_what_the_cell_carries():
    assert bench_rules.per_layer_of(MAN, "tiny_olmoh_serve") == bench_rules.per_layer_of(BENCH, CELL)
    assert bench_rules.broken_by(BENCH) == []
