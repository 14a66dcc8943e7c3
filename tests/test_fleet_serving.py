"""Serving fleet (serving/fleet.py + router.py): disaggregation + preemption.

Three load-bearing properties, all BIT-level:

* **Disaggregated parity** — a request whose prefill ran on the separate
  worker pool (KV prefix handed to the decode replica through
  `write_prefill_to_pool`) produces exactly the codes the fused
  single-engine path (and so `sample_image_codes`) produces — greedy,
  stochastic, and CFG-guided.
* **Drain exactness** — draining an engine mid-decode exports each slot's
  accepted codes + RNG position, and resubmitting (same text, same key) to
  a fresh engine reproduces the identical sequence: the exported prefix
  must match the resubmission's first `codes_done` codes.
* **Serve-through-preemption** — killing a replica mid-load requeues every
  in-flight request onto survivors, which complete them bit-identically,
  with exactly one `replica_lost` alarm and zero silent drops.

The handoff is priced: the comms-ledger row's analytic byte count must
match the actual KV-prefix + ring bytes the worker hands over.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.models import dalle as dalle_mod
from dalle_pytorch_tpu.models.dalle import DALLEConfig
from dalle_pytorch_tpu.models.sampling import sample_image_codes
from dalle_pytorch_tpu.observability import metrics as obs_metrics
from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine
from dalle_pytorch_tpu.serving.fleet import FleetConfig, PrefillWorker, ServingFleet
from dalle_pytorch_tpu.serving.router import Router
from dalle_pytorch_tpu.training import resilience

# effective argmax: gumbel_sample scales the noise by temperature, so a tiny
# temperature is greedy without the division-by-zero of exactly 0.0
GREEDY = 1e-4


def tiny_cfg(**kw):
    base = dict(
        dim=32, depth=2, num_text_tokens=64, text_seq_len=8, heads=2,
        dim_head=8, num_image_tokens=32, image_fmap_size=4, shift_tokens=True,
    )
    base.update(kw)
    return DALLEConfig(**base)


def fused_ref(params, cfg, text_row, key, temperature=1.0, cond_scale=1.0):
    return np.asarray(sample_image_codes(
        params, cfg, jnp.asarray(text_row)[None], key,
        filter_thres=0.9, temperature=temperature, cond_scale=cond_scale,
    ))


@pytest.fixture(scope="module")
def base():
    cfg = tiny_cfg()
    params = dalle_mod.init_dalle(jax.random.PRNGKey(0), cfg)
    text = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (4, cfg.text_seq_len), 1, cfg.num_text_tokens))
    return cfg, params, text


def _ecfg(**kw):
    base = dict(num_slots=2, block_size=4)
    base.update(kw)
    return EngineConfig(**base)


# --------------------------------------------------------------- router


def test_router_spreads_load_and_parity(base):
    """2 replicas behind the router: placement spreads requests (both
    replicas serve some), every result is bit-identical to its fused
    reference, and records are replica-tagged."""
    cfg, params, text = base
    fleet = ServingFleet(params, cfg,
                         fleet_cfg=FleetConfig(replicas=2, engine=_ecfg()))
    keys = [jax.random.PRNGKey(10 + i) for i in range(4)]
    reqs = fleet.generate(text, keys=keys)
    for i, req in enumerate(reqs):
        want = fused_ref(params, cfg, text[i], keys[i])
        np.testing.assert_array_equal(req.codes[None], want)
    # the router placed onto live load — with 4 sequential blocking submits
    # both replicas must have been used (the busy one scores worse)
    assert all(e.replica_id is not None for e in fleet.engines)
    admitted = [obs_metrics.counter(f"router/submitted_r{i}").value
                for i in range(2)]
    assert min(admitted) > 0, f"router starved a replica: {admitted}"


def test_router_sheds_when_all_refuse(base):
    """Every replica refusing = ONE router-level shed, counted."""
    from dalle_pytorch_tpu.serving.scheduler import AdmissionRefused

    cfg, params, text = base
    fleet = ServingFleet(
        params, cfg,
        fleet_cfg=FleetConfig(replicas=2, engine=_ecfg(max_queue=1)))
    before = obs_metrics.counter("router/shed").value
    # fill both replicas' queues without polling, then overflow
    for i in range(2):
        fleet.submit(text[0], key=jax.random.PRNGKey(i))
    with pytest.raises(AdmissionRefused):
        fleet.submit(text[1], key=jax.random.PRNGKey(99))
    assert obs_metrics.counter("router/shed").value == before + 1
    fleet.run_until_idle()


# --------------------------------------------------------- disaggregation


@pytest.mark.parametrize("temperature,cond_scale", [
    (GREEDY, 1.0),   # greedy
    (1.0, 1.0),      # stochastic
    (1.0, 2.0),      # CFG-guided (2 lanes, null prompt partner)
], ids=["greedy", "stochastic", "guided"])
def test_disaggregated_parity(base, temperature, cond_scale):
    """Prefill on the worker pool + KV handoff into the decode replica's
    paged pool is bit-identical to the fused single-engine admit."""
    cfg, params, text = base
    fleet = ServingFleet(
        params, cfg,
        fleet_cfg=FleetConfig(replicas=2, disaggregate=True, engine=_ecfg()))
    assert all(e.prefill_backend is fleet.prefill_worker
               for e in fleet.engines)
    keys = [jax.random.PRNGKey(40 + i) for i in range(2)]
    reqs = fleet.generate(text[:2], keys=keys, temperature=temperature,
                          cond_scale=cond_scale)
    for i, req in enumerate(reqs):
        want = fused_ref(params, cfg, text[i], keys[i],
                         temperature=temperature, cond_scale=cond_scale)
        np.testing.assert_array_equal(req.codes[None], want)


def test_handoff_priced_as_comms_row(base):
    """The comms-ledger row's analytic bytes match the ACTUAL handoff: the
    n_pre-prefix of the worker's KV cache layers plus the token-shift ring
    tails — cross-checked against the arrays `prefill` returns."""
    from dalle_pytorch_tpu.serving.scheduler import Request

    cfg, params, text = base
    worker = PrefillWorker(params, cfg)
    req = Request(id=0, text=text[0], key=np.asarray(jax.random.PRNGKey(7)),
                  temperature=1.0, cond_scale=1.0)
    handoff = worker.prefill(req)
    row = handoff["comms_row"]
    n_pre = cfg.text_seq_len + 1

    # actual KV payload: every layer's k/v sliced to the n_pre prefix
    # (cache buffers are allocated full-length; only the prefix is live)
    layers = handoff["layers"]
    payload = 0
    rings = 0

    def _leaf_bytes(a, live_len):
        a = np.asarray(a)
        return a.itemsize * a.size // a.shape[-2] * live_len

    for layer in layers:
        for name in ("k", "v"):
            a = np.asarray(layer[name])
            payload += a.itemsize * (a.size // a.shape[-2]) * n_pre
        for name in ("shift_attn", "shift_ff"):
            if name in layer:
                a = np.asarray(layer[name])
                rings += a.nbytes
    assert row["payload_bytes"] == payload
    assert row["ring_bytes"] == rings
    assert row["bytes_per_step"] == payload + rings
    assert row["axis"] == "handoff" and row["op"] == "prefill_to_decode"


def test_handoff_counters(base):
    """Every disaggregated admission counts one handoff + its bytes."""
    cfg, params, text = base
    before_n = obs_metrics.counter("serving/handoff_requests").value
    before_b = obs_metrics.counter("serving/handoff_bytes").value
    fleet = ServingFleet(
        params, cfg,
        fleet_cfg=FleetConfig(replicas=1, disaggregate=True, engine=_ecfg()))
    fleet.generate(text[:2], keys=[jax.random.PRNGKey(i) for i in range(2)])
    assert obs_metrics.counter("serving/handoff_requests").value == before_n + 2
    per_req = fleet.prefill_worker.handoff_row(1)["bytes_per_step"]
    assert (obs_metrics.counter("serving/handoff_bytes").value
            == before_b + 2 * per_req)
    ledger = fleet.handoff_ledger()
    assert ledger["per_axis"][0]["bytes_per_step"] == per_req


# ------------------------------------------------------- drain / requeue


# tier-1 budget: the stochastic leg is slow-marked — drain/resubmit
# exactness stays fast via the greedy leg (the RNG-stream replay math is
# identical; only the sampler differs)
@pytest.mark.parametrize(
    "temperature",
    [GREEDY, pytest.param(1.0, marks=pytest.mark.slow)],
    ids=["greedy", "stochastic"])
def test_drain_mid_decode_resubmit_exact(base, temperature):
    """Satellite: drain an engine mid-decode, resubmit to a FRESH engine —
    the re-decode is bit-identical, and the drained export's accepted-codes
    prefix matches the final sequence's first `codes_done` codes."""
    cfg, params, text = base
    eng = GenerationEngine(params, cfg, engine_cfg=_ecfg())
    key = jax.random.PRNGKey(77)
    req = eng.submit(text[0], key=key, temperature=temperature)
    for _ in range(6):  # admit + a few decode steps, NOT the full sequence
        eng.poll()
    exports = eng.drain()
    assert len(exports) == 1 and not eng.busy
    exp = exports[0]
    assert 0 < exp["codes_done"] < cfg.image_seq_len, (
        "drain must catch the request MID-decode for this test to bite")
    assert req.outcome == "deferred"

    fresh = GenerationEngine(params, cfg, engine_cfg=_ecfg())
    redone = fresh.generate(exp["text"][None],
                            keys=[exp["key"]],
                            temperature=exp["temperature"],
                            cond_scale=exp["cond_scale"])[0]
    want = fused_ref(params, cfg, text[0], key, temperature=temperature)
    np.testing.assert_array_equal(redone.codes[None], want)
    # the accepted prefix survived the preemption exactly
    np.testing.assert_array_equal(exp["codes"],
                                  redone.codes[:exp["codes_done"]])


def test_kill_replica_requeues_and_completes(base):
    """Kill a replica mid-load: ONE replica_lost alarm, every in-flight
    request requeued onto the survivor, every request completes
    bit-identically — zero drops."""
    cfg, params, text = base
    alarms = []
    fleet = ServingFleet(
        params, cfg,
        fleet_cfg=FleetConfig(replicas=2, engine=_ecfg()),
        on_alarm=alarms.append)
    keys = [jax.random.PRNGKey(60 + i) for i in range(4)]
    reqs = [fleet.submit(text[i], key=keys[i]) for i in range(4)]
    for _ in range(3):
        fleet.poll()
    requeued = fleet.kill_replica(0)
    done = fleet.run_until_idle()

    assert [a["type"] for a in alarms] == ["replica_lost"]
    assert alarms[0]["replica"] == 0
    assert alarms[0]["requeued"] == len(requeued) > 0
    assert len(fleet.router.alive()) == 1

    # zero drops: every submission completed — either the original request
    # object (survivor replica) or its requeued reincarnation (same key)
    final = {}
    for r in reqs + requeued:
        if r.codes is not None:
            final[int(np.asarray(r.key)[-1])] = r
    for i, key in enumerate(keys):
        got = final[int(np.asarray(key)[-1])]
        want = fused_ref(params, cfg, text[i], key)
        np.testing.assert_array_equal(got.codes[None], want)
    # the dead replica refuses new work; the survivor absorbs it
    assert fleet.engines[0].replica_id == 0
    r5 = fleet.submit_when_able(text[0], key=jax.random.PRNGKey(99))
    fleet.run_until_idle()
    assert r5.codes is not None


def test_kill_replica_with_reshard(base):
    """reshard_on_kill re-places survivor weights through the partitioning
    registry; serving continues bit-identically afterwards."""
    cfg, params, text = base
    fleet = ServingFleet(
        params, cfg,
        fleet_cfg=FleetConfig(replicas=2, engine=_ecfg(),
                              reshard_on_kill=True))
    fleet.kill_replica(1)
    assert obs_metrics.gauge("fleet_serving/reshard_s").value is not None
    key = jax.random.PRNGKey(31)
    req = fleet.submit_when_able(text[0], key=key)
    fleet.run_until_idle()
    np.testing.assert_array_equal(req.codes[None],
                                  fused_ref(params, cfg, text[0], key))


def test_kill_last_replica_refused(base):
    """The fleet never kills its last replica (that would drop work with
    no survivor to requeue onto)."""
    cfg, params, text = base
    fleet = ServingFleet(params, cfg,
                         fleet_cfg=FleetConfig(replicas=1, engine=_ecfg()))
    assert fleet.kill_replica(0) == []
    assert len(fleet.router.alive()) == 1


def test_kill_replica_fault_parse_and_fire():
    """kill-replica@ITER:IDX parses into the fault seam and fires ONCE."""
    f = resilience.parse_fault("kill-replica@3:1")
    assert f.kind == "kill-replica" and f.step == 3 and f.stall_s == 1
    inj = resilience.FaultInjector(f).install()
    try:
        assert resilience.take_kill_replica_fault(2) is None
        assert resilience.take_kill_replica_fault(3) == 1
        assert resilience.take_kill_replica_fault(4) is None  # fired once
    finally:
        inj.uninstall()
    # default victim is replica 0
    assert resilience.parse_fault("kill-replica@5").stall_s == 0.0


# ------------------------------------------------- satellite: scheduler


def test_queue_overflow_counted_refusal(base):
    """A full queue is a COUNTED refusal reason, distinct from never-fits."""
    from dalle_pytorch_tpu.serving.scheduler import AdmissionRefused

    cfg, params, text = base
    eng = GenerationEngine(params, cfg, engine_cfg=_ecfg(max_queue=2))
    before = obs_metrics.counter("serving/refused_queue_overflow").value
    eng.submit(text[0], key=jax.random.PRNGKey(0))
    eng.submit(text[1], key=jax.random.PRNGKey(1))
    with pytest.raises(AdmissionRefused) as ei:
        eng.submit(text[2], key=jax.random.PRNGKey(2))
    assert ei.value.kind == "queue_overflow"
    assert (obs_metrics.counter("serving/refused_queue_overflow").value
            == before + 1)
    eng.run_until_idle()


# -------------------------------------------------- satellite: kv_pool


def test_pool_high_water_and_fragmentation(base):
    """The pool tracks peak occupancy and free-list fragmentation, and
    publishes both as gauges."""
    cfg, params, _ = base
    eng = GenerationEngine(params, cfg, engine_cfg=_ecfg())
    pool = eng.pool
    assert pool.high_water == 0 and pool.fragmentation_frac == 0.0
    t1 = pool.alloc_table(owner=1)
    t2 = pool.alloc_table(owner=2)
    hw = pool.used_blocks
    assert pool.high_water == hw
    pool.free_table(1)  # free the FIRST allocation: free list now has the
    # recycled low blocks appended after the high tail — fragmented
    assert pool.high_water == hw  # high water survives frees
    assert 0.0 <= pool.fragmentation_frac <= 1.0
    g = obs_metrics.gauge("serving/pool_high_water").value
    assert g == hw
    assert (obs_metrics.gauge("serving/pool_fragmentation_frac").value
            == pool.fragmentation_frac)
    assert obs_metrics.gauge("serving/pool_blocks_free").value == pool.free_blocks
    pool.free_table(2)
    assert pool.high_water == hw


# ------------------------------------------------------------ slow tier


@pytest.mark.slow
def test_chaos_kill_replica_drill(tmp_path):
    """The full chaos drill: serve CLI subprocess, 2 replicas, Poisson load,
    kill-replica fault mid-run — zero drops, one replica_lost alarm."""
    import sys
    sys.path.insert(0, str(__import__("pathlib").Path(
        __file__).resolve().parent.parent / "tools"))
    from chaos import kill_replica_drill

    assert kill_replica_drill(workdir=str(tmp_path), disaggregate=True) == 0
