"""An Olmo-Hybrid trunk served: `gated_delta` layers keep a recurrent state and
convolution taps per slot beside the one `full` layer's K/V blocks.

Everything here runs the program against `benchmark/reference/
olmo_hybrid_reference.py` (float32, token-by-token recurrence, full forward) at
a tiny size that keeps the cell's awkward shapes: key heads 12 wide and value
heads 24 wide (96 : 192, neither a multiple of a tile), three layers in four
recurrent, an untied head, no rotation, the norm on each branch's output.
"""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import build  # noqa: E402
from benchmark.reference import olmo_hybrid_reference as ref  # noqa: E402
from dalle_pytorch_tpu.models import dalle as dalle_mod  # noqa: E402
from dalle_pytorch_tpu.models import sampling  # noqa: E402
from dalle_pytorch_tpu.models import transformer as tr  # noqa: E402
from dalle_pytorch_tpu.ops.delta_rule import gated_delta_rule, gated_delta_step  # noqa: E402
from dalle_pytorch_tpu.ops.sampling import gumbel_sample, top_k_filter  # noqa: E402
from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine  # noqa: E402

RULE_ATOL = 3e-6          # float32 round-off of a state integrated over 150 positions
FORWARD_RMS = 1e-5        # full forward against the reference, share of the logits' RMS
SERVED_RMS = 1e-4         # prefill + paged decode against it (ISSUE 33's limit)
BLOCK = 4                 # 24 positions = 6 blocks: a request crosses five boundaries

SIZES = json.loads((ROOT / "benchmark" / "rehearsal" / "tiny_olmoh.json").read_text())


@pytest.fixture(scope="module")
def model():
    cfg = build.dalle_config(SIZES)
    return cfg, build.make_weights(cfg, 2**31 + 11, jnp.float32)


def _sequence(cfg, seed, pad_tail=2):
    rng = np.random.default_rng(seed)
    text = rng.integers(1, cfg.num_text_tokens, (cfg.text_seq_len,))
    text[cfg.text_seq_len - pad_tail:] = 0
    codes = rng.integers(0, cfg.num_image_tokens, (cfg.image_seq_len,))
    return text.astype(np.int32), codes.astype(np.int32)


def _rms_err(got, want):
    ok = np.isfinite(want)
    return float(np.sqrt(np.mean((got[ok] - want[ok]) ** 2) / np.mean(want[ok] ** 2)))


# ------------------------------------------------------------ the delta rule
def _rule_inputs(n, h=3, dk=12, dv=24, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, n, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, n, dk)))
    v = jax.random.normal(ks[2], (b, h, n, dv))
    rate = jax.random.uniform(ks[3], (h,), minval=0.01, maxval=16.0)  # slow and fast heads
    g = -rate[None, :, None] * jax.nn.softplus(jax.random.normal(ks[4], (b, h, n)) + 1.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (b, h, n)) + 1.0)  # most of it past 1
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule over a batch: (outputs, last state)."""
    seq = lambda a: jnp.moveaxis(a, 1, 2)  # (b, h, n, ...) -> (b, n, h, ...)
    out, state = jax.vmap(lambda *a: ref.delta_rule_recurrence(*a, with_state=True))(
        seq(q), seq(k), seq(v), seq(jnp.exp(g)), seq(beta))
    return jnp.moveaxis(out, 2, 1), state


@pytest.mark.parametrize("n,chunk", [(150, 64), (129, 64), (64, 64), (37, 16), (5, 64)])
def test_chunked_rule_and_its_last_state_equal_the_recurrence_for_beta_up_to_two(n, chunk):
    args = _rule_inputs(n)
    assert float(args[4].max()) > 1.5 and float(args[4].min()) > 0.0
    got, got_state = gated_delta_rule(*args, chunk=chunk)
    want, want_state = _recurrence(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=RULE_ATOL)
    # a padded tail neither writes nor decays: the state after the pad is position n - 1's
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state), atol=RULE_ATOL)


@pytest.mark.parametrize("start", [0, 37])
def test_one_token_rule_equals_the_recurrence_step_by_step(start):
    """From a zero state, and from the chunked rule's state after `start` positions."""
    n = start + 20
    q, k, v, g, beta = _rule_inputs(n, seed=3)
    want, want_state = _recurrence(q, k, v, g, beta)
    state = jnp.zeros((2, 3, 12, 24))
    if start:
        _, state = gated_delta_rule(*(a[:, :, :start] for a in (q, k, v, g, beta)), chunk=16)
    step = jax.jit(gated_delta_step)
    for t in range(start, n):
        o, state = step(q[:, :, t], k[:, :, t], v[:, :, t], g[:, :, t], beta[:, :, t], state)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want[:, :, t]), atol=RULE_ATOL)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state), atol=RULE_ATOL)


@pytest.mark.parametrize("slots,heads,dk,dv", [(3, 6, 16, 24), (2, 4, 8, 136)])
def test_the_kernel_of_the_one_token_rule_equals_its_definition(slots, heads, dk, dv):
    """kernels/delta_step.py in interpret mode: value heads wider and narrower
    than a 128-lane tile, several heads a grid step, the state aliased in to out."""
    from dalle_pytorch_tpu.kernels import delta_step

    q, k, v, g, beta = (a[:, :, 0] for a in _rule_inputs(1, h=heads, dk=dk, dv=dv, b=slots, seed=5))
    state = jax.random.normal(jax.random.PRNGKey(6), (slots, heads, dk, dv))
    assert delta_step.supports(heads, dk, dv) and not delta_step.supports(heads, dk + 4, dv)
    assert heads % delta_step.heads_per_step(heads, dk, dv) == 0
    want_o, want_state = gated_delta_step(q, k, v, g, beta, state)
    got_o, got_state = jax.jit(delta_step.gated_delta_step_kernel)(q, k, v, g, beta, state)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=RULE_ATOL)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state), atol=RULE_ATOL)


def test_the_decode_step_with_the_kernel_equals_the_decode_step_without(model, monkeypatch):
    """The layer picks the kernel on a TPU alone; steered onto it here, a paged
    decode step gives what the definition's step gives, state and taps too."""
    from dalle_pytorch_tpu.models import gated_layers

    cfg, params = model
    tcfg = cfg.transformer_config()
    assert not gated_layers._use_delta_kernel(tcfg)  # the CPU runs the definition
    per_seq = tr.paged_blocks_per_seq(tcfg, BLOCK)
    pool = tr.init_paged_pool(tcfg, 2 * per_seq + 1, BLOCK, jnp.float32, num_slots=2)
    layers, _ = _prefilled(params, cfg, _sequence(cfg, 40)[0])
    tables = jnp.asarray([1 + np.arange(per_seq), np.zeros(per_seq)], jnp.int32)
    pool = tr.write_prefill_to_pool(pool, tables[:1], layers, cfg.text_seq_len + 1, BLOCK,
                                    slots=jnp.asarray([0]))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 1, cfg.dim))
    offsets = jnp.asarray([cfg.text_seq_len + 1, 0], jnp.int32)
    step = lambda: tr.paged_decode_step(params["transformer"], tcfg, x, pool, tables, offsets, None, BLOCK)
    want_out, want_pool, _ = step()
    monkeypatch.setattr(gated_layers, "_use_delta_kernel", lambda cfg: True)
    got_out, got_pool, _ = step()
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out), atol=1e-5)
    for got, want in zip(got_pool["layers"][:3], want_pool["layers"][:3]):
        np.testing.assert_allclose(np.asarray(got["state"]), np.asarray(want["state"]), atol=RULE_ATOL)
        np.testing.assert_allclose(np.asarray(got["taps"]), np.asarray(want["taps"]), atol=1e-5)


# ------------------------------------------------------------- full forward
@pytest.mark.parametrize("n_codes", [16, 5, 0])
def test_full_forward_matches_the_reference(model, n_codes):
    cfg, params = model
    text, codes = _sequence(cfg, 1)
    got = dalle_mod.forward(params, cfg, text[None], codes[None, :n_codes] if n_codes else None)[0]
    want = np.asarray(ref.forward_logits(params, SIZES, text, codes[:n_codes]))
    got = np.asarray(got)
    assert ((got <= np.finfo(np.float32).min / 2) == np.isneginf(want)).all()  # the same mask
    assert _rms_err(got, want) < FORWARD_RMS


def test_the_block_is_the_olmo_placement(model):
    """No norm on a branch's input, one on its output; a whole-width QK-norm, no
    bias and no rotation on the full layer; no position table; an untied head."""
    cfg, params = model
    t = params["transformer"]
    assert all(set(layer) == {"attn_norm_out", "ff_norm_out"} for layer in t["layers"])
    full = t["shared_attn"]["3"]
    assert full["q_norm"]["w"].shape == full["k_norm"]["w"].shape == (cfg.heads * cfg.dim_head,)
    assert "b" not in full["out"] and tr.transformer_rotary(cfg.transformer_config()) is None
    assert not {"text_pos", "image_pos_h", "image_pos_w"} & set(params)
    assert {"text_emb", "image_emb"} <= set(params) and dalle_mod.image_pos_table(params, cfg) is None
    tcfg = cfg.transformer_config()
    assert tcfg.hybrid and tcfg.recurrent and not tcfg.unserved and tcfg.kv_layers == 1
    assert [tcfg.ff_type(i) for i in range(4)] == ["swiglu"] * 4


# ---------------------------------------- prefill, then the paged decode step
def _prefilled(params, cfg, text):
    cache, last = sampling._prefill_phase(params, cfg, jnp.asarray(text)[None], None, 0, 1.0)
    return cache["layers"], last[0]


def test_prefill_then_paged_decode_equals_the_references_forward_at_every_position(model):
    """Three requests in a pool of four slots: lanes 0, 2 and 3 hold requests
    admitted 0, 3 and 6 steps apart (so their offsets differ and they cross block
    boundaries in different steps), lane 1 is never admitted: it computes on
    whatever its slot holds and may write nowhere else."""
    cfg, params = model
    tcfg = cfg.transformer_config()
    S, n_pre, n_gen = 4, cfg.text_seq_len + 1, cfg.image_seq_len
    per_seq = tr.paged_blocks_per_seq(tcfg, BLOCK)
    pool = tr.init_paged_pool(tcfg, S * per_seq + 1, BLOCK, jnp.float32, num_slots=S)
    # the idle lane's slot starts from garbage, not zeros
    pool["layers"][0]["state"] = pool["layers"][0]["state"].at[1].set(1e3)
    tables = np.zeros((S, per_seq), np.int32)
    offsets = np.zeros((S,), np.int32)
    lanes = {0: 0, 2: 3, 3: 6}  # lane -> the step it is admitted in
    seqs = {lane: _sequence(cfg, 10 + lane) for lane in lanes}
    want = {lane: np.asarray(ref.forward_logits(params, SIZES, *seqs[lane])) for lane in lanes}
    fed = {lane: 0 for lane in lanes}  # codes fed so far

    write = jax.jit(lambda pool, bt, layers, slot: tr.write_prefill_to_pool(
        pool, bt, layers, n_pre, BLOCK, slots=slot))
    step = jax.jit(lambda pool, x, bt, off: tr.paged_decode_step(
        params["transformer"], tcfg, x, pool, bt, off, None, BLOCK)[:2])
    checked = 0
    for t in range(6 + n_gen):
        for lane, at in lanes.items():
            if at == t:
                layers, last = _prefilled(params, cfg, seqs[lane][0])
                assert _rms_err(np.asarray(last), want[lane][n_pre - 1]) < SERVED_RMS
                tables[lane] = 1 + lane * per_seq + np.arange(per_seq)
                pool = write(pool, jnp.asarray(tables[lane:lane + 1]), layers, jnp.asarray([lane]))
                offsets[lane] = n_pre
        x = np.zeros((S, 1, cfg.dim), np.float32)
        active = [lane for lane in lanes if lanes[lane] <= t and fed[lane] < n_gen - 1]
        for lane in active:
            x[lane] = np.asarray(dalle_mod.embed_image_codes(
                params, cfg, jnp.asarray(seqs[lane][1][None, fed[lane]:fed[lane] + 1]), start=fed[lane]))[0]
        out, pool = step(pool, jnp.asarray(x), jnp.asarray(tables), jnp.asarray(offsets))
        for lane in active:
            pos = int(offsets[lane])
            got = np.asarray(sampling._logits_at(params, cfg, out[lane:lane + 1], pos))[0]
            assert _rms_err(got, want[lane][pos]) < SERVED_RMS, (lane, pos)
            fed[lane] += 1
            offsets[lane] += 1
            checked += 1
    assert checked == 3 * (n_gen - 1) and all(int(o) == cfg.total_seq_len for o in offsets[[0, 2, 3]])
    # nothing of the idle lane's garbage reached a block a request owns: every logit above agreed


def test_dense_cache_decode_step_equals_the_references_forward(model):
    """The fused sampler's path: `prefill` then `decode_step` on the dense cache."""
    cfg, params = model
    tcfg = cfg.transformer_config()
    text, codes = _sequence(cfg, 4)
    want = np.asarray(ref.forward_logits(params, SIZES, text, codes))
    cache, _ = sampling._prefill_phase(params, cfg, jnp.asarray(text)[None], None, 0, 1.0)
    step = jax.jit(lambda x, cache: tr.decode_step(params["transformer"], tcfg, x, cache))
    for i in range(cfg.image_seq_len - 1):
        emb = dalle_mod.embed_image_codes(params, cfg, jnp.asarray(codes[None, i:i + 1]), start=i)
        out, cache = step(emb, cache)
        pos = cfg.text_seq_len + 1 + i
        assert _rms_err(np.asarray(sampling._logits_at(params, cfg, out, pos))[0], want[pos]) < SERVED_RMS


# ------------------------------------------------------------------ the engine
def _full_forward_sampler(params, cfg, text, key, filter_thres, cond_scale=1.0):
    """The sampler's definition with no cache at all: every code from a full
    forward over the prefix, the request's keys split as `sample_image_codes` does."""
    key, k0 = jax.random.split(jnp.asarray(key, jnp.uint32))
    step_keys = jax.random.split(key, cfg.image_seq_len - 1)
    text = jnp.asarray(text)[None]
    codes = jnp.zeros((1, 0), jnp.int32)
    fwd = jax.jit(lambda t, c: dalle_mod.forward(params, cfg, t, c)[:, -1])
    for i in range(cfg.image_seq_len):
        lg = fwd(text, codes)
        if cond_scale != 1.0:
            null = fwd(jnp.zeros_like(text), codes)
            lg = null + (lg - null) * cond_scale
        tok = gumbel_sample(k0 if i == 0 else step_keys[i - 1], top_k_filter(lg, thres=filter_thres))
        codes = jnp.concatenate(
            [codes, (tok - cfg.num_text_tokens_padded)[:, None].astype(jnp.int32)], axis=1)
    return np.asarray(codes[0])


@pytest.mark.parametrize("cond_scale", [1.0, 3.0])
def test_the_engine_delivers_the_codes_of_the_full_forward_sampler(model, cond_scale):
    """Three requests through two (unguided) or four (guided: a lane pair each)
    slots, so the third waits for an eviction and takes over a used slot."""
    cfg, params = model
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(
        num_slots=2 if cond_scale == 1.0 else 4, block_size=BLOCK, filter_thres=0.75))
    texts = [_sequence(cfg, 20 + i)[0] for i in range(3)]
    keys = [build.raw_key(7, i) for i in range(3)]
    reqs = [eng.submit(t, key=k, cond_scale=cond_scale) for t, k in zip(texts, keys)]
    eng.run_until_idle()
    for req, text, key in zip(reqs, texts, keys):
        assert req.outcome == "completed"
        np.testing.assert_array_equal(
            req.codes, _full_forward_sampler(params, cfg, text, key, 0.75, cond_scale))
    assert eng.paged_path_state() == {"paged_attn_kernel_layers": 0, "paged_attn_fallback_layers": 1}
    assert eng.recurrent_state_info() == {"gdn_state_layers": 3, "gdn_step_kernel_layers": 0,
                                          "state_bytes": eng.pool.state_bytes()}


def test_a_reused_slot_leaks_nothing_of_its_last_request(model):
    """One slot: request B after request A, against B alone in a fresh engine.
    The codes are equal, and so is every bit of the slot's state and taps."""
    cfg, params = model
    (text_a, _), (text_b, _) = _sequence(cfg, 30), _sequence(cfg, 31)

    def serve(texts):
        eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=1, block_size=BLOCK))
        reqs = [eng.submit(t, key=build.raw_key(9, i)) for i, t in texts]
        eng.run_until_idle()
        kept = [{k: np.asarray(v[0]) for k, v in layer.items() if k in ("state", "taps")}
                for layer in eng._state["pool"]["layers"]]
        return reqs[-1].codes, kept

    codes_reused, kept_reused = serve([(0, text_a), (1, text_b)])
    codes_fresh, kept_fresh = serve([(1, text_b)])
    np.testing.assert_array_equal(codes_reused, codes_fresh)
    assert sum(bool(k) for k in kept_fresh) == 3
    for a, b in zip(kept_reused, kept_fresh):
        for name in a:
            np.testing.assert_array_equal(a[name], b[name])


def test_the_engines_snapshot_is_the_references_state_of_each_lane_at_its_own_offset(model):
    """Three requests sent three polls apart through four slots: the snapshot
    gives every in-flight lane's position, codes so far and states, and each
    state is what the reference's recurrence reaches on that lane's own text
    and codes (`recurrent_states`, which stops at `positions` whatever follows)."""
    cfg, params = model
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=4, block_size=BLOCK))
    assert eng.recurrent_snapshot() == []
    reqs = []
    for i in range(3):
        reqs.append(eng.submit(_sequence(cfg, 50 + i)[0], key=build.raw_key(11, i)))
        for _ in range(3):
            eng.poll()
    snap = eng.recurrent_snapshot()
    assert [lane["request"] for lane in snap] == reqs
    assert [lane["positions"] for lane in snap] == [eng.n_pre + 9, eng.n_pre + 6, eng.n_pre + 3]  # the admitting poll decodes too
    want_states = jax.jit(lambda t, c, n: ref.recurrent_states(params, SIZES, t, c, n))
    for lane in snap:
        assert len(lane["codes"]) == lane["positions"] - eng.n_pre + 1 and len(lane["states"]) == 3
        padded = np.full((cfg.image_seq_len,), 7, np.int32)  # what follows the prefix is read by no state
        padded[:len(lane["codes"])] = lane["codes"]
        for got, want in zip(lane["states"], want_states(lane["request"].text, padded, lane["positions"])):
            assert got.dtype == np.float32 and _rms_err(got, np.asarray(want)) < SERVED_RMS
    whole, _ = _sequence(cfg, 50)
    np.testing.assert_allclose(
        ref.recurrent_states(params, SIZES, whole, padded, None)[0],
        ref.recurrent_states(params, SIZES, whole, padded, cfg.total_seq_len - 1)[0], atol=RULE_ATOL)
    eng.run_until_idle()
    assert eng.recurrent_snapshot() == []


@pytest.mark.parametrize("sampler", ["gumbel_sample", "lane_sample_pipeline"])
def test_bfloat16_logits_are_sampled_with_float32_noise(sampler):
    """A bfloat16 uniform has 128 values: an argmax over logits + that noise
    never reaches the tail of the kept logits.  With float32 noise a sampler
    over 512 equal bfloat16 logits draws every one of them sooner or later, and
    the draw is the float32 sampler's on the same key."""
    from dalle_pytorch_tpu.models import speculative
    from dalle_pytorch_tpu.ops.sampling import gumbel_noise

    flat = jnp.zeros((1, 512), jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(5), 4000)
    if sampler == "gumbel_sample":
        draw = jax.jit(jax.vmap(lambda k, lg: gumbel_sample(k, lg)[0], in_axes=(0, None)))
        got, want = draw(keys, flat), draw(keys, flat.astype(jnp.float32))
    else:
        cfg = build.dalle_config(dict(SIZES, num_image_tokens=512))
        split = cfg.num_text_tokens_padded
        state = {"head": {"table": jnp.zeros((512, cfg.dim), jnp.bfloat16)},
                 "poison_lane": jnp.asarray(-1), "partner": jnp.arange(1), "guided": jnp.zeros((1,), bool),
                 "cscale": jnp.ones((1,)), "active": jnp.ones((1,), bool), "cand_cap": jnp.zeros((1,), bool),
                 "temp": jnp.ones((1,)), "feed_src": jnp.arange(1)}
        norm = {"logits_norm": {"w": jnp.ones((cfg.dim,), jnp.bfloat16)}}

        def draw_one(k):
            return speculative.lane_sample_pipeline(
                norm, cfg, jnp.zeros((1, 1, cfg.dim), jnp.bfloat16), jnp.zeros((1,), jnp.int32),
                dict(state, keys=k[None, None]), 0.0, 0.0)[0][0]

        got = jax.jit(jax.vmap(draw_one))(keys)
        want = jax.vmap(lambda k: jnp.argmax(gumbel_noise(k, (1, cfg.total_tokens))[0, split:]))(keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(np.unique(np.asarray(got))) > 500


def test_the_pool_counts_blocks_of_the_layers_that_hold_them_and_prices_the_state(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=3, block_size=BLOCK))
    layers = eng._state["pool"]["layers"]
    assert [sorted(layer) for layer in layers] == [["state", "taps"]] * 3 + [["k", "v"]]
    assert layers[0]["state"].shape == (3, 3, 12, 24) and layers[0]["state"].dtype == jnp.float32
    assert layers[0]["taps"].shape == (3, 3, 2 * 36 + 72)
    held = sum(a.nbytes for layer in layers[:3] for a in layer.values())
    assert eng.pool.state_bytes() == held == eng.recurrent_state_info()["state_bytes"]
    assert eng.pool.bytes(4) == sum(a.nbytes for a in layers[3].values())


# -------------------------------------------------------------------- refusals
def _refused(fn, what):
    with pytest.raises(NotImplementedError, match="training path only") as e:
        fn()
    assert what in str(e.value) and "gated_delta" in str(e.value)


@pytest.mark.parametrize("what", ["spec_k", "quantize_kv", "PrefillWorker", "generate_texts",
                                  "init_paged_pool"])
def test_what_cannot_carry_a_recurrent_state_still_raises_the_one_error(model, what):
    from dalle_pytorch_tpu.serving.fleet import PrefillWorker

    cfg, params = model
    calls = {
        "spec_k": lambda: GenerationEngine(params, cfg, engine_cfg=EngineConfig(
            num_slots=2, block_size=BLOCK, spec_k=2)),
        "quantize_kv": lambda: GenerationEngine(params, cfg, engine_cfg=EngineConfig(
            num_slots=2, block_size=BLOCK, quantize_kv="int8")),
        "PrefillWorker": lambda: PrefillWorker(params, cfg),
        "generate_texts": lambda: sampling.generate_texts(
            params, cfg, jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)),
        "init_paged_pool": lambda: tr.init_paged_pool(
            cfg.transformer_config(), 8, BLOCK, quantize="int8", num_slots=2),
    }
    _refused(calls[what], what)


@pytest.mark.parametrize("change", [{"attn_types": ("gated_delta", "gated_full")},
                                    {"attn_types": ("mla",)},
                                    {"moe_experts": 4, "dense_layers": 0}])
def test_what_no_serving_entry_point_computes_is_refused_as_before(model, change):
    cfg, _ = model
    tcfg = dataclasses.replace(cfg.transformer_config(), **change)
    assert tcfg.unserved
    for what, call in {"init_cache": lambda: tr.init_cache(tcfg, 1),
                       "init_paged_pool": lambda: tr.init_paged_pool(tcfg, 4, BLOCK, num_slots=1)}.items():
        with pytest.raises(NotImplementedError, match="training path only") as e:
            call()
        assert what in str(e.value)


def test_a_state_needs_its_slots(model):
    cfg, _ = model
    with pytest.raises(ValueError, match="num_slots"):
        tr.init_paged_pool(cfg.transformer_config(), 8, BLOCK)
