#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, end to end, through the entry points a user calls,
at the full width of the 575 M DALL-E (`dim 2048, depth 8, heads 16 x 128`,
text 256 of a 10,000 vocabulary, image 32 x 32 codes of 8,192, sequence 1,280,
`full,axial_row,axial_col,conv_like`, shift tokens, rotary, shared embedding,
bf16 compute).  Weights are random, made from `--seed`; nothing of the model
is cut.  Phases, one child process after the other (a chip belongs to one
process at a time, and this parent never imports jax):

  native      make -B -C native (the C++ BPE; never a stray binary)
  data        a few dozen captioned 256 x 256 PNGs from the seed
  train_vae   train_vae.py      DiscreteVAE 256px / 3 layers / 8192 tokens
  train_dalle train_dalle.py    >= 4 optimizer steps, f32 Adam, checkpoint
  generate    generate.py       1 prompt, 2 images, dense cached sampler + VAE
  serve       python -m dalle_pytorch_tpu.cli.serve --loadgen 4 --slots 4
              --cond_scale 3    paged engine, guided lane pairs, 4 requests

Every child is `python chip_smoke.py --child ...`: it executes the CLI's own
script as `__main__` in-process (runpy), then reports the device JAX gave it,
its compiles, its compile cache and its peak device memory.  The parent
checks what came out (finite losses, a Pallas custom call in the train step, codes in range, PNG counts, 4/4 requests completed with none shed or
poisoned) and prints one JSON object per phase.  The LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

`ok` is true only for a run on a TPU in which every phase passed.  With no
accelerator the script prints no result and exits 3.  Any failed phase, or a
child past its time limit, ends the run with `"ok": false` and exit 1.

  --chips 4    on the four-chip host: ONLY the train_dalle phase on an
               `fsdp 2 x tp 2` mesh (ZeRO-3) and the same seed, data and
               global batch on one chip, compared (one train_vae.py step
               first writes the VAE checkpoint both need — set-up)
  --rehearse   the same phases at toy widths wherever JAX runs (the CPU
               here); reports "ok": false and "rehearsal": true — never
               mistaken for the smoke
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
WORK = HERE / ".chip_smoke"           # gitignored; wiped at every start
LOGS = HERE / "chiprun_out" / "chip_smoke"  # child logs, brought back by the chip tool
CHILD_TAG = "CHIP_SMOKE_CHILD "
RC_NO_TPU = 3
BUDGET_S = 1140                       # the contract allows 1200 s in all

SCRIPTS = {
    "train_vae": "train_vae.py",
    "train_dalle": "train_dalle.py",
    "generate": "generate.py",
    "serve": "dalle_pytorch_tpu.cli.serve",  # a module: python -m ...
}

COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(-start)?\(")

# the model, at full width (dim, heads and depth of benchmark/configs/dalle_2048_d8.json)
FULL = dict(
    image_size=256, vae_layers=3, num_tokens=8192, vae_emb=512, vae_hidden=256,
    n_images=40, batch=8,
    dim=2048, depth=8, heads=16, dim_head=128, text_seq_len=256,
    num_text_tokens=10000, gen_images=2, requests=4, block_size=64,
)
# toy widths for --rehearse: control flow only (seq 64 + 8x8 = 128)
TOY = dict(
    image_size=32, vae_layers=2, num_tokens=64, vae_emb=16, vae_hidden=16,
    n_images=40, batch=8,
    dim=64, depth=2, heads=2, dim_head=32, text_seq_len=64,
    num_text_tokens=128, gen_images=2, requests=4, block_size=16,
)
# per-child time limits (seconds); each is also capped by what is left of
# BUDGET_S.  A timeout is a failure, not a skipped phase.
LIMITS = {"train_vae": 300, "train_dalle": 480, "generate": 300, "serve": 360,
          "probe": 90}


# --------------------------------------------------------------------------
# child: runs in its own process, owns the chip, executes ONE CLI
# --------------------------------------------------------------------------

def child_main(kind: str, allow_cpu: bool, argv: list) -> int:
    import jax
    from jax import monitoring

    stats = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["compiles"] += 1
            stats["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats["cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu" and not allow_cpu:
        print(f"chip_smoke: JAX found no TPU (devices: {device}); this is the "
              "chip's smoke and it has no CPU mode (rehearse with --rehearse)",
              file=sys.stderr)
        return RC_NO_TPU
    report = {"device": device}
    if kind == "probe":
        print(CHILD_TAG + json.dumps(report), flush=True)
        return 0

    if kind == "generate":
        # the CLI writes PNGs only; the codes it decodes are checked where
        # they pass: the VAE-decode call of the repo's own sampler
        import numpy as np

        from dalle_pytorch_tpu.models import vae_registry

        decode, seen = vae_registry.decode_indices, []

        def spy(vae_params, vae_cfg, codes):
            c = np.asarray(codes)
            seen.append({"shape": list(c.shape), "min": int(c.min()),
                         "max": int(c.max())})
            return decode(vae_params, vae_cfg, codes)

        vae_registry.decode_indices = spy
        report["codes"] = seen

    if kind == "train_dalle":
        # the CLI's own memory cross-check compiles the train step ahead of
        # time (`.lower().compile()`): read that executable's text where it
        # is made — kernels and collectives that are really in the program
        compile_aot, aot = jax.stages.Lowered.compile, []

        def compile_spy(lowered, *a, **kw):
            compiled = compile_aot(lowered, *a, **kw)
            text = compiled.as_text() or ""
            colls = {}
            for m in COLLECTIVE_RE.finditer(text):
                colls[m.group(1)] = colls.get(m.group(1), 0) + 1
            ma = compiled.memory_analysis()
            aot.append({
                "tpu_custom_calls": text.count("tpu_custom_call"),
                "collectives": colls,
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None)})
            return compiled

        jax.stages.Lowered.compile = compile_spy
        report["compiled_train_step"] = aot

    import runpy

    target = SCRIPTS[kind]
    sys.argv = [target] + argv
    if target.endswith(".py"):
        runpy.run_path(str(HERE / target), run_name="__main__")
    else:
        runpy.run_module(target, run_name="__main__", alter_sys=True)

    report.update(stats, compile_s=round(stats["compile_s"], 2))
    report["cache_dir"] = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                           or jax.config.jax_compilation_cache_dir)
    mem = []
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        mem.append({"id": d.id, "peak_bytes_in_use": s.get("peak_bytes_in_use"),
                    "bytes_limit": s.get("bytes_limit")})
    report["memory"] = mem
    print(CHILD_TAG + json.dumps(report), flush=True)
    return 0


# --------------------------------------------------------------------------
# parent: no jax here
# --------------------------------------------------------------------------

class PhaseFailed(Exception):
    pass


class NoTPU(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


class Runner:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.t0 = time.monotonic()
        self.device = None

    def left(self) -> float:
        return BUDGET_S - (time.monotonic() - self.t0)

    def run_child(self, kind: str, argv: list, name: str = None,
                  env: dict = None) -> dict:
        """One child process, one time limit, its whole process group killed
        at the limit.  Returns the child's report + wall seconds."""
        name = name or kind
        limit = min(LIMITS[kind], self.left())
        if limit <= 5:
            raise PhaseFailed(f"{name}: no time left of the {BUDGET_S}s budget")
        cmd = [sys.executable, str(HERE / "chip_smoke.py"), "--child", kind]
        if self.rehearse:
            cmd.append("--allow_cpu")
        cmd += ["--"] + [str(a) for a in argv]
        full_env = dict(os.environ, PYTHONUNBUFFERED="1")
        full_env.update(env or {})
        LOGS.mkdir(parents=True, exist_ok=True)
        out_p, err_p = LOGS / f"{name}.out", LOGS / f"{name}.err"
        t0 = time.monotonic()
        with open(out_p, "w") as out_f, open(err_p, "w") as err_f:
            proc = subprocess.Popen(cmd, cwd=str(HERE), env=full_env,
                                    stdout=out_f, stderr=err_f,
                                    start_new_session=True)
            try:
                rc = proc.wait(timeout=limit)
            except subprocess.TimeoutExpired:
                rc = None
            finally:
                # stop everything the child started, finished or not
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        wall = round(time.monotonic() - t0, 1)
        out = out_p.read_text(errors="replace")
        if rc != 0:
            tail = err_p.read_text(errors="replace")[-3000:]
            print(f"---- {name} stderr tail ----\n{tail}", file=sys.stderr)
            if rc == RC_NO_TPU:
                raise NoTPU()
            why = (f"timed out after {limit:.0f}s" if rc is None
                   else f"exit code {rc}")
            raise PhaseFailed(f"{name}: child {why} (logs: {out_p}, {err_p})")
        lines = [ln for ln in out.splitlines() if ln.startswith(CHILD_TAG)]
        check(lines, f"{name}: child printed no report")
        rep = json.loads(lines[-1][len(CHILD_TAG):])
        rep["wall_s"] = wall
        dev = rep["device"]
        if not self.rehearse:
            check(dev["platform"] == "tpu", f"{name}: platform {dev['platform']!r}, not tpu")
        if self.device is None:
            self.device = dev  # the first child's: what the last line reports
        return rep


def phase_line(name: str, rep: dict, **extra) -> dict:
    """The per-phase JSON line: what is worth keeping of a child's report."""
    mem = [m["peak_bytes_in_use"] for m in rep.get("memory", [])]
    line = {
        "phase": name, "ok": True, "wall_s": rep["wall_s"],
        "platform": rep["device"]["platform"],
        "device_kind": rep["device"]["kind"], "devices": rep["device"]["count"],
        "compiles": rep.get("compiles"), "compile_s": rep.get("compile_s"),
        "cache_dir": rep.get("cache_dir"),
        "cache_hits": rep.get("cache_hits"), "cache_misses": rep.get("cache_misses"),
        "peak_bytes_in_use": mem if len(mem) != 1 else mem[0],
    }
    line.update(extra)
    return line


def keep_small_files(src: Path, dst: Path, cap: int = 2 << 20) -> None:
    """The runs' own records (metrics/telemetry JSONL, reports, hang dumps)
    go back with the logs; checkpoints, PNGs and IR dumps do not."""
    for p in src.rglob("*"):
        keep = p.suffix in (".jsonl", ".json") or p.name.startswith(("hang_", "oom_report"))
        if p.is_file() and keep and p.stat().st_size <= cap:
            out = dst / p.relative_to(src)
            out.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy2(p, out)


def cache_state() -> dict:
    """Where the children's compile cache lives (cli/common.py decides the
    same way) and whether this run starts with it warm."""
    d = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or HERE / ".jax_cache")
    n = sum(1 for p in d.glob("*") if p.is_file()) if d.is_dir() else 0
    return {"dir": str(d), "entries_at_start": n, "warm": n > 0,
            "placed_by": "JAX_COMPILATION_CACHE_DIR" if
            os.environ.get("JAX_COMPILATION_CACHE_DIR") else "fixed in-checkout path"}


def build_native() -> None:
    t0 = time.monotonic()
    r = subprocess.run(["make", "-B", "-C", str(HERE / "native")],
                       capture_output=True, text=True, timeout=120)
    check(r.returncode == 0, f"native: make failed: {r.stderr[-500:]}")
    emit({"phase": "native", "ok": True, "wall_s": round(time.monotonic() - t0, 1),
          "built": "native/_libbpe.so (make -B -C native): the tokenizer runs on "
                   "the C++ BPE built from the committed source"})


COLORS = {"red": (220, 40, 40), "green": (40, 200, 60), "blue": (50, 80, 220),
          "yellow": (230, 210, 50)}
SHAPES = ("circle", "square")


def make_dataset(folder: Path, n: int, size: int, seed: int) -> None:
    """Captioned coloured shapes on a flat ground
    (tests/test_cli.py::make_rainbow_dataset, scaled to `size`)."""
    import numpy as np
    from PIL import Image, ImageDraw

    t0 = time.monotonic()
    folder.mkdir(parents=True, exist_ok=True)
    rng = np.random.RandomState(seed)
    for i in range(n):
        color = list(COLORS)[i % len(COLORS)]
        shape = SHAPES[(i // len(COLORS)) % len(SHAPES)]
        img = Image.new("RGB", (size, size), (250, 250, 250))
        d = ImageDraw.Draw(img)
        x0, y0 = (int(v) for v in rng.randint(size // 16, size // 3, size=2))
        x1, y1 = (int(v) for v in (x0, y0) + rng.randint(size // 3, size // 2, size=2))
        if shape == "circle":
            d.ellipse([x0, y0, x1, y1], fill=COLORS[color])
        else:
            d.rectangle([x0, y0, x1, y1], fill=COLORS[color])
        img.save(folder / f"img{i:03d}.png")
        (folder / f"img{i:03d}.txt").write_text(f"a {color} {shape}")
    emit({"phase": "data", "ok": True, "wall_s": round(time.monotonic() - t0, 1),
          "images": n, "image_size": size, "seed": seed, "folder": str(folder)})


def read_losses(metrics_jsonl: Path) -> list:
    """[(step, loss)] the CLI logged: each is a float(loss) the CLI fetched
    from the device before printing it."""
    out = []
    for ln in metrics_jsonl.read_text().splitlines():
        rec = json.loads(ln)
        if "loss" in rec:
            out.append((rec.get("step"), rec["loss"]))
    return out


def check_losses(name: str, losses: list, at_least: int) -> None:
    check(len(losses) >= at_least,
          f"{name}: {len(losses)} loss line(s) logged, expected >= {at_least}")
    for step, loss in losses:
        check(isinstance(loss, (int, float)) and math.isfinite(loss),
              f"{name}: loss at step {step} is {loss!r}")


def vae_argv(z: dict, data: Path, out: Path, seed: int) -> list:
    return ["--image_folder", data, "--image_size", z["image_size"],
            "--num_layers", z["vae_layers"], "--num_tokens", z["num_tokens"],
            "--emb_dim", z["vae_emb"], "--hidden_dim", z["vae_hidden"],
            "--batch_size", z["batch"], "--epochs", 1, "--seed", seed,
            "--save_every_n_steps", 0, "--vae_output_file_name", out]


def dalle_argv(z: dict, data: Path, vae: Path, out: Path, seed: int,
               extra: list = ()) -> list:
    return ["--vae_path", vae, "--image_text_folder", data,
            "--dim", z["dim"], "--depth", z["depth"], "--heads", z["heads"],
            "--dim_head", z["dim_head"], "--text_seq_len", z["text_seq_len"],
            "--num_text_tokens", z["num_text_tokens"],
            "--attn_types", "full,axial_row,axial_col,conv_like",
            "--shift_tokens", "--rotary_emb", "--share_input_output_emb",
            "--bf16", "--truncate_captions", "--batch_size", z["batch"],
            "--epochs", 1, "--seed", seed, "--log_every_n_steps", 1,
            "--save_every_n_steps", 0, "--sample_every_n_steps", 0,
            "--dalle_output_file_name", out, *extra]


def width_of(z: dict) -> dict:
    fmap = z["image_size"] // 2 ** z["vae_layers"]
    return {"dim": z["dim"], "depth": z["depth"], "heads": z["heads"],
            "dim_head": z["dim_head"], "text_seq_len": z["text_seq_len"],
            "num_text_tokens": z["num_text_tokens"],
            "image_fmap": fmap, "num_image_tokens": z["num_tokens"],
            "seq_len": z["text_seq_len"] + fmap * fmap}


def train_dalle_phase(run: Runner, z: dict, name: str, argv: list, env: dict,
                      steps_expected: int) -> tuple:
    out = Path(argv[argv.index("--dalle_output_file_name") + 1])
    rep = run.run_child("train_dalle", argv, name=name, env=env)
    losses = read_losses(Path(f"{out}.metrics.jsonl"))
    check_losses(name, losses, steps_expected)
    ckpt = Path(f"{out}.pt")
    check(ckpt.exists() and ckpt.stat().st_size > 0, f"{name}: no checkpoint {ckpt}")
    compiled = rep["compiled_train_step"]
    check(compiled, f"{name}: the CLI compiled no train step ahead of time "
                    "(its memory cross-check): no program text to read")
    kernels = compiled[-1]["tpu_custom_calls"]
    if rep["device"]["platform"] == "tpu":
        # no silent dense attention: the Pallas kernels must be IN the program
        check(kernels >= 1, f"{name}: no tpu_custom_call in the train step — "
                            "the flash kernel is not in the program")
    line = phase_line(name, rep, width=width_of(z), batch=z["batch"],
                      steps=len(losses), losses=[l for _, l in losses],
                      tpu_custom_calls_in_train_step=kernels,
                      compiled_train_step=compiled[-1],
                      checkpoint_bytes=ckpt.stat().st_size)
    return rep, losses, line


def one_chip(run: Runner, z: dict, seed: int) -> None:
    data = WORK / "data"
    make_dataset(data, z["n_images"], z["image_size"], seed)
    steps = z["n_images"] // z["batch"]
    emit({"phase": "plan", "width": width_of(z), "train_steps": steps,
          "gen_images": z["gen_images"], "serve_requests": z["requests"],
          "compile_cache": cache_state(),
          "cuts": ("rehearsal: toy widths" if run.rehearse else
                   "none: full width; steps, images and requests are the "
                   "smoke's own small counts")})

    # ---- train_vae.py
    vae_out = WORK / "vae"
    rep = run.run_child("train_vae", vae_argv(z, data, vae_out, seed))
    losses = read_losses(Path(f"{vae_out}.metrics.jsonl"))
    check_losses("train_vae", losses, 1)
    check(Path(f"{vae_out}.pt").exists(), "train_vae: no checkpoint written")
    emit(phase_line("train_vae", rep, steps=steps, losses=[l for _, l in losses],
                    image_size=z["image_size"], num_tokens=z["num_tokens"]))

    # ---- train_dalle.py --vae_path
    dalle_out = WORK / "dalle"
    _, _, line = train_dalle_phase(
        run, z, "train_dalle",
        dalle_argv(z, data, f"{vae_out}.pt", dalle_out, seed), {}, steps)
    check(line["steps"] >= 4, f"train_dalle: {line['steps']} optimizer steps < 4")
    emit(line)

    # ---- generate.py --dalle_path
    gen_dir = WORK / "outputs"
    rep = run.run_child("generate", [
        "--dalle_path", f"{dalle_out}.pt", "--text", "a red circle",
        "--num_images", z["gen_images"], "--batch_size", z["gen_images"],
        "--seed", seed, "--outputs_dir", gen_dir])
    pngs = sorted(gen_dir.glob("*/*.png"))
    check(len(pngs) == z["gen_images"],
          f"generate: {len(pngs)} PNG(s) on disk, asked for {z['gen_images']}")
    from PIL import Image

    for p in pngs:
        with Image.open(p) as im:
            check(im.size == (z["image_size"], z["image_size"]),
                  f"generate: {p.name} is {im.size}")
    fmap2 = width_of(z)["image_fmap"] ** 2
    codes = rep["codes"]
    check(codes, "generate: the sampler decoded no codes")
    for c in codes:
        check(c["shape"][-1] == fmap2 and 0 <= c["min"] and c["max"] < z["num_tokens"],
              f"generate: codes {c} outside [0, {z['num_tokens']}) x {fmap2}")
    emit(phase_line("generate", rep, images=len(pngs), codes=codes))

    # ---- python -m dalle_pytorch_tpu.cli.serve --loadgen
    report_json = WORK / "serve_report.json"
    rep = run.run_child("serve", [
        "--dalle_path", f"{dalle_out}.pt", "--loadgen", z["requests"],
        "--slots", 4, "--cond_scale", 3, "--block_size", z["block_size"],
        "--seed", seed, "--telemetry", WORK / "serve_telemetry",
        "--report_json", report_json, "--outputs_dir", WORK / "serve_outputs"])
    r = json.loads(report_json.read_text())
    n = z["requests"]
    check(r["requests_completed"] == n and r["journeys_completed"] == n,
          f"serve: {r['requests_completed']} of {n} submitted requests completed")
    shed = r["requests_refused"] + r["refused_total"]
    poisoned = r["quarantined"] + r["poison_retries"]
    check(shed == 0, f"serve: {shed} request(s) shed")
    check(poisoned == 0, f"serve: {poisoned} poisoned decode(s) (nonfinite logits)")
    for k in ("ttft_p50_s", "ttft_p99_s", "latency_p99_s"):
        check(isinstance(r[k], float) and math.isfinite(r[k]), f"serve: {k} = {r[k]!r}")
    emit(phase_line(
        "serve", rep, submitted=n, completed=r["requests_completed"], shed=shed,
        poisoned=poisoned, guided_lane_pairs=True,
        codes_per_request=fmap2, pool_blocks=r["pool_blocks"],
        ttft_p50_s=r["ttft_p50_s"], ttft_p99_s=r["ttft_p99_s"],
        latency_p99_s=r["latency_p99_s"]))


# --------------------------------------------------------------------------
# --chips 4: the sharded train step against one chip of the same host
# --------------------------------------------------------------------------

# what the installed runtime offers to hand ONE chip of a host to a process
ONE_CHIP_ENVS = [
    {"TPU_VISIBLE_CHIPS": "0", "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
     "TPU_PROCESS_BOUNDS": "1,1,1"},
    {"TPU_VISIBLE_DEVICES": "0", "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
     "TPU_PROCESS_BOUNDS": "1,1,1"},
]
# How far the two programs' per-step losses may differ: bf16 rounding of the
# same computation, partitioned two ways (seen on the chip, PR 21: <= 0.51 %).
# The data has a FLAT ground for this: the frozen, barely trained VAE encodes
# in bf16 inside the step and its argmax runs over 8,192 near-tied logits, so
# on textured images it flipped with the partitioning, the two programs
# trained on different image codes, and their losses sat 2-9.5 % apart.
LOSS_RTOL = 3e-2


def gauge_bytes_per_device(spans_jsonl: Path) -> dict:
    """{device id: largest `device{id}/bytes_in_use` gauge the telemetry
    flushed while the train state was live} (README: per-device memory
    gauges)."""
    best = {}
    for ln in spans_jsonl.read_text().splitlines():
        rec = json.loads(ln)
        if rec.get("kind") != "metrics":
            continue
        for key, val in rec.get("metrics", {}).items():
            m = re.fullmatch(r"device(\d+)/bytes_in_use", key)
            if not m:
                continue
            if isinstance(val, dict):
                val = max(v for v in val.values() if isinstance(v, (int, float)))
            best[int(m.group(1))] = max(best.get(int(m.group(1)), 0), val)
    return best


def four_chips(run: Runner, z: dict, seed: int) -> None:
    data, vae_data = WORK / "data", WORK / "vae_data"
    steps = 4
    make_dataset(data, steps * z["batch"], z["image_size"], seed)
    make_dataset(vae_data, z["batch"], z["image_size"], seed)

    # set-up, not a phase: train_dalle.py needs a VAE checkpoint, and one
    # train_vae.py step writes it (on a chip: the host CPU takes 200 s for it)
    vae_out = WORK / "vae"
    rep = run.run_child("train_vae", vae_argv(z, vae_data, vae_out, seed))
    emit(phase_line("train_vae", rep, note="set-up for --chips 4: one step "
                    "writes the VAE checkpoint both train runs encode with"))

    # ---- fsdp 2 x tp 2, ZeRO-3: parameters and Adam moments on all four
    out4 = WORK / "dalle_fsdp2_tp2"
    mesh = ["--mesh_dp", 1, "--mesh_fsdp", 2, "--mesh_tp", 2, "--zero_stage", 3]
    rep4, loss4, line4 = train_dalle_phase(
        run, z, "train_dalle_fsdp2_tp2",
        dalle_argv(z, data, f"{vae_out}.pt", out4, seed, mesh), {}, steps)
    if not run.rehearse:
        check(rep4["device"]["count"] == 4,
              f"--chips 4 found {rep4['device']['count']} device(s)")
    collectives = rep4["compiled_train_step"][-1]["collectives"]
    check(collectives, "no collective in the compiled sharded train step")
    gauges4 = gauge_bytes_per_device(
        Path(f"{out4}.telemetry") / f"{out4.name}.spans.jsonl")
    peaks4 = [m["peak_bytes_in_use"] for m in rep4["memory"]]
    line4.update(mesh="fsdp 2 x tp 2", zero_stage=3, bytes_in_use_gauges=gauges4)
    emit(line4)

    # ---- the same seed, data and global batch on ONE chip of this host
    one_env, how = None, None
    if run.rehearse:
        one_env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
        how = "rehearsal: one virtual CPU device"
    else:
        for cand in ONE_CHIP_ENVS:
            try:
                probe = run.run_child("probe", [], name="probe_one_chip", env=cand)
            except PhaseFailed as e:
                emit({"phase": "probe_one_chip", "ok": False, "env": cand, "why": str(e)})
                continue
            emit({"phase": "probe_one_chip", "ok": True, "env": cand,
                  "devices": probe["device"]["count"]})
            if probe["device"]["count"] == 1:
                one_env, how = cand, f"one chip of this host via {cand}"
                break
    ref_mesh = []
    if one_env is None:
        one_env = {}
        ref_mesh = ["--mesh_dp", 4]
        how = ("FALLBACK: the runtime would not restrict a child to one chip; "
               "reference is dp 4 with replicated parameters and Adam moments")
    out1 = WORK / "dalle_reference"
    rep1, loss1, line1 = train_dalle_phase(
        run, z, "train_dalle_reference",
        dalle_argv(z, data, f"{vae_out}.pt", out1, seed, ref_mesh), one_env, steps)
    peaks1 = [m["peak_bytes_in_use"] for m in rep1["memory"]]
    gauges1 = gauge_bytes_per_device(
        Path(f"{out1}.telemetry") / f"{out1.name}.spans.jsonl")
    line1.update(reference=how, bytes_in_use_gauges=gauges1)
    emit(line1)

    # ---- the comparison
    check(len(loss4) >= 3 and len(loss1) >= 3, "fewer than 3 steps to compare")
    pairs = list(zip(loss4, loss1))
    rel = [abs(a - b) / abs(b) for (_, a), (_, b) in pairs]
    for ((s4, a), (s1, b)), r in zip(pairs, rel):
        check(s4 == s1 and r <= LOSS_RTOL,
              f"step {s4}: sharded loss {a} vs reference {b} (tolerance {LOSS_RTOL})")
    verdict = {"phase": "compare", "ok": True, "steps": len(pairs),
               "loss_sharded": [l for _, l in loss4],
               "loss_reference": [l for _, l in loss1],
               "rel_diff": rel, "tolerance": LOSS_RTOL,
               "collectives": collectives}
    if not run.rehearse:  # the CPU backend reports no allocator statistics
        # resident bytes while the train state is live (the telemetry's
        # gauges), not the allocator's peak: train_dalle.py initialises the
        # whole state on device 0 before it shards it, a start-up transient
        check(sorted(gauges4) == [0, 1, 2, 3] and min(gauges4.values()) > 0,
              f"bytes_in_use gauges do not cover four devices: {gauges4}")
        resident1 = max(gauges1.values())
        check(max(gauges4.values()) < 0.75 * resident1,
              f"per-device resident bytes {gauges4} are not well under the "
              f"reference's {resident1}")
        verdict.update(resident_bytes_sharded=gauges4,
                       resident_bytes_reference=gauges1,
                       peak_bytes_sharded=peaks4, peak_bytes_reference=peaks1)
    emit(verdict)


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths wherever JAX runs; never reports ok: true")
    ap.add_argument("--child", default=None, choices=[*SCRIPTS, "probe"],
                    help=argparse.SUPPRESS)
    ap.add_argument("--allow_cpu", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("rest", nargs=argparse.REMAINDER, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
        return child_main(args.child, args.allow_cpu, rest)

    missing = [s for s in SCRIPTS.values() if s.endswith(".py") and not (HERE / s).exists()]
    if missing or not (HERE / "dalle_pytorch_tpu").is_dir():
        print(f"chip_smoke: not inside the repository ({HERE} lacks "
              f"{missing or 'dalle_pytorch_tpu/'})", file=sys.stderr)
        return 2

    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    shutil.rmtree(LOGS, ignore_errors=True)
    run = Runner(args.rehearse)
    z = TOY if args.rehearse else FULL
    failed = None
    try:
        build_native()
        (four_chips if args.chips == 4 else one_chip)(run, z, args.seed)
    except NoTPU:
        return RC_NO_TPU  # no result: the message is on stderr
    except PhaseFailed as e:
        failed = str(e)
        emit({"phase": "failed", "ok": False, "why": failed})
    finally:
        keep_small_files(WORK, LOGS / "work")
        shutil.rmtree(WORK, ignore_errors=True)  # checkpoints are gigabytes
    dev = run.device or {"platform": None, "kind": None, "count": 0}
    ok = failed is None and not args.rehearse and dev["platform"] == "tpu"
    if ok and dev["count"] != args.chips:
        ok, failed = False, f"ran on {dev['count']} device(s), --chips {args.chips}"
    emit({"phase": "total", "wall_s": round(time.monotonic() - run.t0, 1),
          "budget_s": BUDGET_S})
    last = {"ok": ok, "device": dev}
    if args.rehearse:
        last["rehearsal"] = True
    if failed:
        last["failed"] = failed
    emit(last)
    return 0 if ok or (args.rehearse and failed is None) else 1


if __name__ == "__main__":
    sys.exit(main())
