"""The engine's lane writes run inside jitted programs: admission arms its
lanes in `serve_admit` / `serve_ingest` (the request's step keys split there
from its key), and eviction, drain and a poison retry free them through the
one donated `serve_lane_reset`.

Each program's result is held against the writes the engine made eagerly
before it had them, written here in numpy on a copy of the state: the same
fields, the same values, to the bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine
from dalle_pytorch_tpu.serving.fleet import PrefillWorker
from test_serving import tiny_cfg

# every per-lane field of the engine's state that admission or eviction writes
LANE_FIELDS = ("keys", "temp", "cscale", "active", "cand_cap", "guided", "partner",
               "feed_src", "block_tables", "offsets", "img_prev", "poisoned")


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    text = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (4, cfg.text_seq_len), 1, cfg.num_text_tokens))
    return cfg, params, text


def lane_state(eng):
    """A host copy of the engine's lane fields."""
    return {f: np.array(jax.device_get(eng._state[f])) for f in LANE_FIELDS}


def eager_admit(st, eng, req):
    """The lane writes admission made eagerly, in numpy."""
    st = {f: v.copy() for f, v in st.items()}
    key, _ = jax.random.split(jnp.asarray(req.key, jnp.uint32))
    step_keys = np.asarray(jax.random.split(key, max(eng.n_gen - 1, 1)))
    lanes = req.lanes
    cond = lanes[0]
    st["keys"][cond] = step_keys
    st["temp"][lanes] = np.float32(req.temperature)
    st["cscale"][lanes] = np.float32(req.cond_scale)
    st["active"][lanes] = True
    st["cand_cap"][lanes] = req.degrade_rung >= 2
    for i, lane in enumerate(lanes):
        st["block_tables"][lane] = eng.pool._owned[(req.id << 1) | i]
    st["offsets"][lanes] = eng.n_pre
    st["img_prev"][lanes] = 0
    if len(lanes) == 2:
        null = lanes[1]
        st["guided"][cond], st["guided"][null] = True, False
        st["partner"][cond] = st["partner"][null] = null
        st["feed_src"][cond] = st["feed_src"][null] = cond
    else:
        st["guided"][cond] = False
        st["partner"][cond] = cond
        st["feed_src"][cond] = cond
    return st


def eager_reset(st, lanes):
    """The lane writes eviction and drain made eagerly, in numpy."""
    st = {f: v.copy() for f, v in st.items()}
    for f in ("active", "block_tables", "offsets", "img_prev", "poisoned", "cand_cap"):
        st[f][lanes] = 0
    return st


def assert_lane_state(got, want):
    for f in LANE_FIELDS:
        assert got[f].dtype == want[f].dtype, f
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)


def record_resets(eng):
    """Wrap the engine's reset: [(lanes, state before, state after)]."""
    calls = []
    inner = eng._reset_lanes

    def spy(lanes):
        before = lane_state(eng)
        inner(lanes)
        calls.append((list(lanes), before, lane_state(eng)))

    eng._reset_lanes = spy
    return calls


@pytest.mark.parametrize("handoff", [False, True], ids=["fused", "handoff"])
@pytest.mark.parametrize("cond_scale,temperature,rung", [
    (1.0, 1.0, 0), (1.0, 0.7, 2), (3.0, 1.3, 0), (2.0, 1.0, 2)],
    ids=["one_lane", "one_lane_capped", "guided_pair", "guided_pair_capped"])
def test_admission_arms_the_lanes_as_the_eager_writes_did(model, handoff, cond_scale,
                                                          temperature, rung):
    cfg, params, text = model
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=4, block_size=4))
    if handoff:
        eng.prefill_backend = PrefillWorker(params, cfg)
    # a first request on lane 0 (and 1), so the one under test lands beside it
    eng.submit(text[0], key=jax.random.PRNGKey(5))
    eng.poll()
    req = eng.submit(text[1], key=jax.random.PRNGKey(6), temperature=temperature,
                     cond_scale=cond_scale)
    req.degrade_rung = rung
    before = lane_state(eng)
    assert eng._admit_ready()[1] == 1
    assert req.lanes == ([1, 2] if cond_scale != 1.0 else [1])
    assert_lane_state(lane_state(eng), eager_admit(before, eng, req))


def test_eviction_resets_the_lanes_as_the_eager_writes_did(model):
    """Staggered one-lane and guided requests: every reset the evictions make
    frees exactly the finished requests' lanes, and nothing else moves."""
    cfg, params, text = model
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=4, block_size=4))
    calls = record_resets(eng)
    reqs = [eng.submit(text[0], key=jax.random.PRNGKey(30))]
    for _ in range(3):
        eng.poll()
    reqs.append(eng.submit(text[1], key=jax.random.PRNGKey(31), cond_scale=2.0))
    eng.poll()
    reqs.append(eng.submit(text[2], key=jax.random.PRNGKey(32)))
    eng.run_until_idle()
    assert [c[0] for c in calls] == [r.lanes for r in reqs]
    for lanes, before, after in calls:
        assert before["active"][lanes].all()
        assert_lane_state(after, eager_reset(before, lanes))


def test_drain_resets_every_lane_as_the_eager_writes_did(model):
    cfg, params, text = model
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=4, block_size=4))
    calls = record_resets(eng)
    eng.submit(text[0], key=jax.random.PRNGKey(40), cond_scale=2.0)
    eng.poll()
    eng.submit(text[1], key=jax.random.PRNGKey(41))
    for _ in range(3):
        eng.poll()
    exports = eng.drain()
    assert len(exports) == 2 and len(calls) == 1
    lanes, before, after = calls[0]
    assert sorted(lanes) == [0, 1, 2]
    assert_lane_state(after, eager_reset(before, lanes))
    assert not after["active"].any()


def test_poison_retry_resets_the_lanes_as_the_eager_writes_did(model):
    """A lane whose nonfinite flag is up at eviction is freed, flag and all,
    and its request re-admitted: the reset clears `poisoned`."""
    cfg, params, text = model
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=2, block_size=4))
    calls = record_resets(eng)
    req = eng.submit(text[0], key=jax.random.PRNGKey(50))
    eng.poll()
    lane = req.lanes[0]
    eng._state = dict(eng._state, poisoned=eng._state["poisoned"].at[lane].set(True))
    while req.poison_retries == 0:
        eng.poll()
    lanes, before, after = calls[0]
    assert lanes == [lane] and before["poisoned"][lane]
    assert_lane_state(after, eager_reset(before, lanes))
    eng.run_until_idle()
    assert req.outcome == "completed" and len(calls) == 2


def test_one_reset_program_serves_every_eviction_and_the_counters_count_it(model):
    """One lane, a guided pair, two requests in one poll and a drain: one
    compile of `serve_lane_reset`, and its counters equal the evictions and
    lanes the engine's own rows record."""
    cfg, params, text = model
    eng = GenerationEngine(params, cfg, engine_cfg=EngineConfig(num_slots=6, block_size=4))
    calls0 = obs_metrics.counter("serving/lane_reset_calls").value
    lanes0 = obs_metrics.counter("serving/lane_reset_lanes").value
    reqs = [eng.submit(text[0], key=jax.random.PRNGKey(60))]
    eng.poll()
    eng.poll()
    reqs.append(eng.submit(text[1], key=jax.random.PRNGKey(61), cond_scale=2.0))
    eng.poll()
    reqs += [eng.submit(text[i], key=jax.random.PRNGKey(62 + i)) for i in (2, 3)]
    eng.run_until_idle()
    rows = eng.polls.rows()
    evicting = int(np.count_nonzero(rows["evicted"]))
    assert rows["evicted"].sum() == len(reqs) and evicting < len(reqs)  # two shared a poll
    assert obs_metrics.counter("serving/lane_reset_calls").value - calls0 == evicting
    assert (obs_metrics.counter("serving/lane_reset_lanes").value - lanes0
            == sum(len(r.lanes) for r in reqs) == 5)
    eng.submit(text[0], key=jax.random.PRNGKey(70), cond_scale=2.0)
    eng.poll()
    eng.drain()
    assert obs_metrics.counter("serving/lane_reset_calls").value - calls0 == evicting + 1
    assert eng._lane_reset_fn._cache_size() == 1
