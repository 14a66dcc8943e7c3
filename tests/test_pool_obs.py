"""KV-pool flight recorder + capacity simulator (ISSUE 17).

Invariants under test:

  1. every recorded alloc has a matching free and lifetimes are
     non-negative (alloc/free pairing, direct pool + real engine trace);
  2. reserved-unused waste matches hand-computed numbers (direct pool with
     partial writes; engine run where every lane completes -> zero waste);
  3. the simulator's self-validation reproduces a recorded run at the
     actual config EXACTLY — including a 2-replica Poisson fleet trace;
  4. a prefix-sharing forecast never needs more blocks than no-sharing
     (strictly fewer on an overlapping shared-prefix trace);
  5. the recorder ring stays bounded under flood, drops are counted, and
     the drops marker reaches the flushed stream;
  6. with no recorder attached the pool hooks record nothing at all;
  7. the guided-zipf trace forecast shows >= 1.5x admissible slots for
     expected-blocks + sharing over worst-case at the same pool bytes.
"""
import sys
import time
from pathlib import Path

import pytest

import jax

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))

import pool_report
from loadgen import PoissonLoadGen, synthetic_request_maker

from dalle_pytorch_tpu.models.transformer import TransformerConfig
from dalle_pytorch_tpu.observability import telemetry
from dalle_pytorch_tpu.observability.pool import (
    PoolGauges,
    aggregate_events,
    overcommit_safe_slots,
)
from dalle_pytorch_tpu.serving.engine import EngineConfig, GenerationEngine
from dalle_pytorch_tpu.serving.fleet import FleetConfig, ServingFleet
from dalle_pytorch_tpu.serving.kv_pool import BlockPool, PoolFlightRecorder

from test_serving import base, fused_ref, tiny_cfg  # noqa: F401


class _FakeSpans:
    """Collects write_event calls as the JSONL records they would become."""

    def __init__(self):
        self.records = []

    def write_event(self, kind, **fields):
        self.records.append({"kind": kind, **fields})


def _tiny_pool(num_blocks=24, block_size=4, seq_len=24):
    tcfg = TransformerConfig(dim=16, depth=1, seq_len=seq_len, heads=2,
                             dim_head=8)
    return BlockPool(tcfg, num_blocks=num_blocks, block_size=block_size)


def _attach_recorder(pool, num_slots=8, n_pre=9, n_gen=16, capacity=4096):
    rec = PoolFlightRecorder(capacity=capacity)
    rec.config = {
        "num_blocks": pool.num_blocks, "block_size": pool.block_size,
        "blocks_per_seq": pool.blocks_per_seq, "num_slots": num_slots,
        "n_pre": n_pre, "n_gen": n_gen, "kv_quant": None,
        "bytes_per_block": int(pool.bytes() / (pool.num_blocks + 1)),
    }
    pool.recorder = rec
    return rec


# ---------------------------------------------------------------------------
# recorder mechanics (no jax compiles)
# ---------------------------------------------------------------------------


def test_recorder_ring_bounded_and_drops_flushed():
    """Invariant 5: flood past capacity keeps the ring bounded, counts the
    evictions, and the flush stream carries config + drops markers."""
    rec = PoolFlightRecorder(capacity=8)
    rec.config = {"num_blocks": 4, "block_size": 4, "blocks_per_seq": 1,
                  "num_slots": 1, "n_pre": 1, "n_gen": 4}
    for i in range(20):
        rec.record("alloc", owner=i, reserved=1, occupancy=1,
                   high_water=1, free=3)
    assert len(rec) == 8
    assert rec.dropped == 12

    spans = _FakeSpans()
    n = rec.flush(spans, replica=0)
    assert n == 8 and len(rec) == 0
    ops = [r["op"] for r in spans.records]
    assert ops[0] == "config" and ops[1] == "drops"
    assert spans.records[1]["dropped"] == 12
    # oldest-out: the survivors are the NEWEST 8 events
    assert [r["owner"] for r in spans.records[2:]] == list(range(12, 20))

    # a second flush repeats neither config nor drops, only new events
    rec.record("free", owner=19, released=1, occupancy=0, high_water=1,
               free=4)
    spans2 = _FakeSpans()
    assert rec.flush(spans2, replica=0) == 1
    assert [r["op"] for r in spans2.records] == ["free"]


def test_recorder_off_pool_records_nothing(monkeypatch):
    """Invariant 6: recorder=None makes the hooks a bare `is None` test —
    record() is never entered on any pool operation."""
    monkeypatch.setattr(
        PoolFlightRecorder, "record",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("recorded")))
    pool = _tiny_pool()
    assert pool.recorder is None
    t = pool.alloc_table(0)
    assert len(t) == pool.blocks_per_seq
    assert pool.truncate_slot(0, 10) == 3
    pool.free_table(0, written_tokens=10)
    assert pool.free_blocks == pool.num_blocks


def test_direct_pool_pairing_and_hand_computed_waste():
    """Invariants 1 + 2 on a hand-driven pool: alloc/free pairing closes
    every lifecycle and reserved-unused matches arithmetic done by hand.

    Geometry: bps=6 (seq 24, block 4).  Owner 0 writes the full 24 tokens
    (6 blocks, 0 wasted); owner 2 is evicted after 13 tokens (ceil(13/4)=4
    blocks ever written, 2 wasted).  Total waste = 2 of 12 freed."""
    pool = _tiny_pool(num_blocks=24)
    rec = _attach_recorder(pool)
    gauges = PoolGauges(pool.num_blocks, pool.block_size,
                        pool.blocks_per_seq)
    rec.on_event = gauges.observe

    rec.ctx = {"req": 0, "lanes": 1, "guided": False, "prefix_hash": "p0"}
    pool.alloc_table(0)
    rec.ctx = {"req": 1, "lanes": 1, "guided": False, "prefix_hash": "p1"}
    pool.alloc_table(2)
    rec.ctx = None
    time.sleep(0.002)
    pool.free_table(0, written_tokens=24)
    pool.free_table(2, written_tokens=13)

    s = gauges.summary()
    assert s["allocs"] == 2 and s["frees"] == 2 and s["open_lanes"] == 0
    assert s["reserved_unused_blocks"] == 2
    assert s["reserved_unused_frac"] == round(2 / 12, 4)
    assert s["block_lifetime_p50_s"] > 0.0
    # footprints: ever-written blocks per request -> [6, 4]
    assert s["footprint_blocks_p50"] == 5.0

    # the flushed trace pairs up the same way the gauges saw live
    spans = _FakeSpans()
    rec.flush(spans, replica=None)
    pools = pool_report.build_pools(spans.records)
    (p,) = pools.values()
    reqs = p["requests"]
    assert len(reqs) == 2
    assert all(r["t_free"] >= r["t_admit"] for r in reqs)
    assert sorted(r["written"][0] for r in reqs) == [13, 24]
    # offline twin agrees with the live gauges
    off = aggregate_events(p["events"], pool.num_blocks, pool.block_size,
                           pool.blocks_per_seq)
    assert off["reserved_unused_blocks"] == s["reserved_unused_blocks"]
    assert off["footprint_blocks_p50"] == s["footprint_blocks_p50"]


def test_overcommit_safe_slots_arithmetic():
    """Normal-fit overcommit: sigma=0 footprints make the scan exact."""
    # 4 requests, 4 blocks each, pool of 24, worst demand 6/request:
    # worst-case admits 4; expected fits floor(24/4)=6 -> 2 extra slots.
    assert overcommit_safe_slots([4.0, 4.0, 4.0, 4.0], 24, 6.0) == 2
    assert overcommit_safe_slots([4.0], 24, 6.0) is None  # no distribution
    assert overcommit_safe_slots([], 24, 6.0) is None


# ---------------------------------------------------------------------------
# simulator on a hand-driven overlapping guided trace (no jax compiles)
# ---------------------------------------------------------------------------


def _overlapping_guided_trace():
    """Two guided requests (2 lanes each) with the SAME prompt prefix,
    alive at the same time: the sharing forecast must strictly beat
    no-sharing on peak occupancy."""
    pool = _tiny_pool(num_blocks=24)
    rec = _attach_recorder(pool, num_slots=8)
    for req, owners in ((0, (0, 1)), (1, (2, 3))):
        for lane, owner in enumerate(owners):
            rec.ctx = {"req": req, "journey": f"j{req}", "lanes": 2,
                       "guided": True, "prefix_hash": "shared"}
            pool.alloc_table(owner)
    rec.ctx = None
    time.sleep(0.002)
    for owner in (0, 1, 2, 3):
        pool.free_table(owner, written_tokens=24)
    spans = _FakeSpans()
    rec.flush(spans, replica=None)
    return pool_report.build_pools(spans.records)


def test_simulator_sharing_never_needs_more_blocks():
    """Invariant 4: at the recorded config, sharing's peak occupancy is
    strictly below no-sharing (both guided requests overlap and share both
    the prompt prefix and the null-lane prefix), and its admissible-slot
    forecast is at least as large."""
    pools = _overlapping_guided_trace()
    for policy in ("worst", "expected"):
        off = pool_report.simulate(pools, policy=policy, sharing=False)
        on = pool_report.simulate(pools, policy=policy, sharing=True)
        assert on["peak_occupancy_blocks"] < off["peak_occupancy_blocks"]
        assert on["admissible_slots"] >= off["admissible_slots"]
        assert on["admitted"] == off["admitted"] == 2
        assert on["shed"] == off["shed"] == 0
    # no-sharing worst-case peak is the full whole-sequence reservation
    off = pool_report.simulate(pools, policy="worst", sharing=False)
    assert off["peak_occupancy_blocks"] == 24  # 2 req * 2 lanes * 6 blocks


def test_validate_passes_then_catches_corruption():
    """Invariant 3 (mechanism): a faithful trace validates exactly; the
    same trace with one doctored occupancy fails loudly."""
    pools = _overlapping_guided_trace()
    val = pool_report.validate(pools)
    assert val["ok"], val
    row = val["pools"]["None"]
    assert row["admitted"] == 2
    assert row["high_water"] == row["recorded_high_water"] == 24

    # corrupt one alloc's recorded occupancy -> replay must disagree
    ev = next(e for e in pools[None]["events"] if e["op"] == "alloc")
    ev["occupancy"] += 1
    bad = pool_report.validate(pools)
    assert not bad["ok"]
    assert bad["pools"]["None"]["mismatches"]

    # a torn trace (recorder drops) refuses to validate as well
    pools2 = _overlapping_guided_trace()
    pools2[None]["dropped"] = 3
    assert not pool_report.validate(pools2)["ok"]


# ---------------------------------------------------------------------------
# real engine traces (jax compiles: kept to one tiny engine + one 2-replica
# fleet for the whole module)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def guided_trace(base, tmp_path_factory):
    """One recorded guided-zipf serving run: 6 requests, 4-prompt zipf(1.5)
    mix, all guided (2 lanes each), pool sized at 3x worst-case demand."""
    cfg, params, _ = base
    tmp = tmp_path_factory.mktemp("pool_trace")
    tele = telemetry.configure(str(tmp), run_name="serve",
                               heartbeat_s=None, watch_compiles=False)
    try:
        eng = GenerationEngine(
            params, cfg,
            engine_cfg=EngineConfig(num_slots=2, block_size=4, num_blocks=36,
                                    telemetry_every=4))
        make = synthetic_request_maker(cfg, seed=5, cond_scale=2.0,
                                       zipf_s=1.5, prompt_pool=4)
        for i in range(6):
            eng.submit_when_able(**make(i))
        done = eng.run_until_idle()
        eng.pool.recorder.flush(tele.spans, replica=None)
        obs = eng.pool_observability()
        eng.close()
    finally:
        tele.flush(fleet=False)
        tele.close()
    records = pool_report.load_records([tmp])
    return {"records": records, "obs": obs, "completed": len(done)}


def test_engine_trace_selfcheck_exact(guided_trace):
    """Invariant 3: replaying the recorded trace at the actual config
    reproduces every occupancy/high-water number and every recorded
    deferral decision exactly."""
    pools = pool_report.build_pools(guided_trace["records"])
    assert len(pools) == 1
    val = pool_report.validate(pools)
    assert val["ok"], val
    (row,) = val["pools"].values()
    assert row["admitted"] == 6
    assert row["mismatches"] == []
    assert row["high_water"] == row["recorded_high_water"]
    assert row["high_water"] == guided_trace["obs"]["high_water"]
    # 6 guided requests x 2 lanes against 2 slots: deferrals were recorded,
    # and the replayed admission decision agreed with every one of them
    assert row["deferral_events"] > 0
    assert row["deferrals_replayed"] == row["deferrals_agreed"] > 0


def test_engine_trace_pairing_and_zero_waste(guided_trace):
    """Invariants 1 + 2 on the real trace: every admission's lanes free,
    and a run where every lane wrote its full sequence wastes nothing
    (reserved == ceil(24/4) == written blocks, hand-computed)."""
    pools = pool_report.build_pools(guided_trace["records"])
    (p,) = pools.values()
    allocs = [e for e in p["events"] if e["op"] == "alloc"]
    frees = [e for e in p["events"] if e["op"] == "free"]
    assert len(allocs) == len(frees) == 12  # 6 requests x 2 lanes
    assert {e["owner"] for e in allocs} == {e["owner"] for e in frees}
    assert len(p["requests"]) == 6
    for r in p["requests"]:
        assert r["lanes"] == 2 and r["t_free"] >= r["t_admit"]
        # full sequence = n_pre + n_gen - 1 = 24 tokens = 6 blocks/lane
        assert r["written"] == [24, 24]
    obs = guided_trace["obs"]
    assert obs["reserved_unused_blocks"] == 0
    assert obs["reserved_unused_frac"] == 0.0
    assert obs["recorder_dropped"] == 0
    assert obs["footprint_blocks_p50"] == 12.0  # 2 lanes x 6 blocks


def test_engine_trace_overcommit_forecast(guided_trace):
    """Invariant 7 (the acceptance number): expected-blocks + prefix
    sharing forecasts >= 1.5x the admissible slots of worst-case admission
    at the same pool bytes, and the payload carries the ratio."""
    pools = pool_report.build_pools(guided_trace["records"])
    worst = pool_report.simulate(pools, policy="worst", sharing=False)
    best = pool_report.simulate(pools, policy="expected", sharing=True)
    assert worst["admissible_slots"] == 3  # 36 blocks / (2 lanes * 6 bps)
    assert best["admissible_slots"] / worst["admissible_slots"] >= 1.5
    payload = pool_report.build_payload(pools)
    assert payload["validation"]["ok"]
    assert payload["overcommit_slots_ratio"] >= 1.5
    # the serving-report section carries the same verdict
    section = pool_report.pool_section(guided_trace["records"])
    assert section is not None and section["validation_ok"]
    assert section["overcommit_slots_ratio"] >= 1.5


def test_engine_trace_serving_report_renders(guided_trace):
    """serving_report grows a pool section fed by the same records."""
    import serving_report

    text = serving_report.build_report(guided_trace["records"])
    assert "kv pool (flight recorder):" in text
    assert "simulator self-validation: PASS" in text
    summary = serving_report.build_summary(guided_trace["records"])
    assert summary["pool"]["validation_ok"]


def test_fleet_poisson_trace_validates(base, tmp_path):
    """Invariant 3 at fleet scale (the acceptance trace): a recorded
    2-replica Poisson run self-validates exactly, per replica."""
    cfg, params, _ = base
    tele = telemetry.configure(str(tmp_path), run_name="serve",
                               heartbeat_s=None, watch_compiles=False)
    try:
        fleet = ServingFleet(
            params, cfg,
            fleet_cfg=FleetConfig(replicas=2, engine=EngineConfig(
                num_slots=2, block_size=4, telemetry_every=4)))
        gen = PoissonLoadGen(6, rate=20.0, streams=2, seed=0)
        rep = gen.run(fleet, synthetic_request_maker(cfg, seed=0))
        hw = {e.replica_id: e.pool.high_water for e in fleet.engines}
        for e in fleet.engines:
            e.pool.recorder.flush(tele.spans, replica=e.replica_id)
        fleet.close()
    finally:
        tele.flush(fleet=False)
        tele.close()
    assert rep["requests_completed"] == 6
    pools = pool_report.build_pools(pool_report.load_records([tmp_path]))
    assert set(pools) == {0, 1}
    val = pool_report.validate(pools)
    assert val["ok"], val
    assert sum(r["admitted"] for r in val["pools"].values()) == 6
    for rid, row in val["pools"].items():
        assert row["high_water"] == row["recorded_high_water"] == hw[int(rid)]
