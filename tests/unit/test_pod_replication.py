"""Checkpoint-free pod recovery (ISSUE 20): buddy-replicated host state,
live-step adoption, and zero-rollback round resume (docs/POD.md
"Live-state recovery").

Unit layers: the buddy ring under shrink, seal/verify integrity, the
size-capped CAS slab documents, the HostReplicator step path (including
the ``replica_every_k=0`` zero-regression contract and the SIGTERM
``seal_now`` path), the consistent-cut planner with its generation fence
and double-kill refusal, the at-most-one-adopter claim, the engine
snapshot/ingest roundtrip with loss continuity, and the
``tools/store_check.py`` replica-protocol rules on synthetic histories.
Acceptance: the seeded buddy-kill soak (``tools/chaos_soak.py --mode
pod --scenario buddy_kill``) resumes at the last sealed cut with
rollback <= k, strictly fewer rollback steps than the checkpoint-restart
baseline on the same kill schedule."""
import os
import sys
import time

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.elasticity import (
    FileCoordinationStore,
    HostReplicator,
    POD_ADOPT_PREFIX,
    REPLICA_KEEP,
    ReplicaAdoptionError,
    ReplicaIntegrityError,
    adopt_replicas,
    announce_replica_round,
    buddy_ring,
    claim_adoption,
    pending_replica_round,
    plan_adoption,
    publish_replica,
    read_replica,
    record_dead,
    replica_adoptions_total,
    seal_entry,
    verify_entry,
)
from deepspeed_tpu.monitor import InMemoryMonitor
from deepspeed_tpu.parallel import mesh as mesh_mod

from .simple_model import SimpleModel, make_config, random_batch

HID = 16
TOOLS = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                     "tools")


def _store(tmp_path, clock=None):
    return FileCoordinationStore(str(tmp_path / "coord"), clock=clock)


def _engine():
    mesh_mod.reset_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(HID), config=make_config(batch_size=16))
    return engine


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, "timed out waiting"
        time.sleep(0.005)


# ------------------------------------------------------------- buddy ring

def test_buddy_ring_wraps_and_survives_shrink():
    ring = buddy_ring(["h0", "h1", "h2", "h3"])
    assert ring == {"h0": "h1", "h1": "h2", "h2": "h3", "h3": "h0"}
    # membership shrink re-rings over the survivors (order-independent)
    assert buddy_ring(["h3", "h0", "h2"]) == \
        {"h0": "h2", "h2": "h3", "h3": "h0"}
    assert buddy_ring(["h2", "h0"]) == {"h0": "h2", "h2": "h0"}
    # a single host has nobody to replicate to; so does an empty pod
    assert buddy_ring(["h0"]) == {}
    assert buddy_ring([]) == {}


# ---------------------------------------------------------- seal / verify

def test_seal_verify_roundtrip_and_integrity():
    payload = b"shard bytes " * 64
    entry = seal_entry(payload, step=6, generation=2)
    assert entry["step"] == 6 and entry["generation"] == 2
    assert entry["bytes"] == len(payload)
    assert verify_entry(entry) == payload
    # torn payload: the checksum catches it
    torn = dict(entry)
    torn["payload"] = seal_entry(b"other", 6, 2)["payload"]
    with pytest.raises(ReplicaIntegrityError, match="checksum|truncated"):
        verify_entry(torn)
    # a lying digest
    lied = dict(entry, sha256="0" * 64)
    with pytest.raises(ReplicaIntegrityError, match="checksum"):
        verify_entry(lied)
    # truncation claim mismatch
    short = dict(entry, bytes=entry["bytes"] - 1)
    with pytest.raises(ReplicaIntegrityError, match="truncated"):
        verify_entry(short)
    # undecodable payload
    junk = dict(entry, payload="!!not base64!!")
    with pytest.raises(ReplicaIntegrityError):
        verify_entry(junk)


# -------------------------------------------------------- publish / read

def test_publish_keeps_newest_entries_deduped(tmp_path):
    s = _store(tmp_path)
    for step in (2, 4, 4, 6, 8, 10, 12):    # step 4 re-sealed (coalesced)
        publish_replica(s, "h1", seal_entry(f"s{step}".encode(), step, 1),
                        buddy="h2")
    doc = read_replica(s, "h1")
    assert doc["host"] == "h1" and doc["buddy"] == "h2"
    assert doc["seq"] == 7                  # every publish CAS-advanced
    steps = [e["step"] for e in doc["entries"]]
    assert steps == [12, 10, 8, 6, 4][:REPLICA_KEEP]   # newest first
    assert len(steps) == REPLICA_KEEP
    for e in doc["entries"]:
        assert verify_entry(e) == f"s{e['step']}".encode()


def test_publish_rejects_oversize_slab(tmp_path):
    s = _store(tmp_path)
    entry = seal_entry(b"x", 2, 1)
    entry["bytes"] = (64 << 20) + 1
    with pytest.raises(ValueError, match="over the"):
        publish_replica(s, "h1", entry)


def test_replica_round_announcement_roundtrip(tmp_path):
    s = _store(tmp_path)
    assert pending_replica_round(s, 3) is None
    announce_replica_round(s, 3, step=6)
    assert pending_replica_round(s, 3) == 6
    announce_replica_round(s, 3, step=8)    # newest boundary wins
    assert pending_replica_round(s, 3) == 8
    assert pending_replica_round(s, 4) is None   # generation-scoped


# ------------------------------------------------------- host replicator

def test_replicator_disabled_is_inert(tmp_path):
    """replica_every_k=0: no snapshots, no store traffic, no worker —
    the zero-step-time-regression contract."""
    s = _store(tmp_path)
    calls = []
    rep = HostReplicator(s, "h0", 1, ["h0", "h1"],
                         snapshot_fn=lambda: calls.append(1) or b"x",
                         replica_every_k=0)
    for step in range(1, 8):
        assert rep.maybe_replicate(step) is False
    assert rep.seal_now(7) is False
    rep.stop()
    assert calls == [] and rep.seals_total == 0
    assert read_replica(s, "h0") is None


def test_replicator_seals_on_boundaries(tmp_path):
    s = _store(tmp_path)
    mon = InMemoryMonitor()
    sealed = []
    rep = HostReplicator(s, "h0", 1, ["h0", "h1"],
                         snapshot_fn=lambda: b"state " * 8,
                         replica_every_k=2, monitor=mon,
                         on_sealed=sealed.append)
    for step in range(1, 7):
        fired = rep.maybe_replicate(step)
        assert fired == (step % 2 == 0)
        if fired:   # drain so the coalescing worker can't skip a boundary
            _wait(lambda: rep.last_step == step)
    rep.stop()
    assert sealed == [2, 4, 6] and rep.seals_total == 3
    doc = read_replica(s, "h0")
    assert [e["step"] for e in doc["entries"]] == [6, 4, 2]
    assert doc["buddy"] == "h1"
    names = {e[0] for e in mon.events_snapshot()}
    assert {"pod/replica_seals_total", "pod/replica_bytes_total",
            "pod/replica_last_step"} <= names


def test_replicator_seal_now_is_best_effort(tmp_path):
    """The SIGTERM path: a failing seal logs and returns False — the
    durable preemption checkpoint must still run, so it never raises."""
    s = _store(tmp_path)

    def boom():
        raise RuntimeError("device gone")

    rep = HostReplicator(s, "h0", 1, ["h0", "h1"], snapshot_fn=boom,
                         replica_every_k=2)
    assert rep.seal_now(5) is False
    assert rep.publish_failures == 1
    rep.stop()
    # and a healthy seal_now publishes OFF-boundary (step 5, k=2): the
    # preemption seal takes whatever step is in flight
    ok = HostReplicator(s, "h1", 1, ["h0", "h1"],
                        snapshot_fn=lambda: b"bye", replica_every_k=2)
    assert ok.seal_now(5) is True
    ok.stop()
    assert read_replica(s, "h1")["entries"][0]["step"] == 5


# ------------------------------------------------------------- adoption

HOSTS = ["h0", "h1", "h2"]


def _seed_slabs(s, steps_by_host, generation=1):
    ring = buddy_ring(sorted(steps_by_host))
    for h, steps in steps_by_host.items():
        for step in steps:
            publish_replica(
                s, h, seal_entry(f"{h}@{step}".encode(), step, generation),
                buddy=ring.get(h))


def test_plan_adoption_newest_common_cut(tmp_path):
    s = _store(tmp_path)
    _seed_slabs(s, {h: [2, 4] for h in HOSTS})
    record_dead(s, "h1", generation=1, reported_by="h0")
    plan = plan_adoption(s, HOSTS, ["h1"])
    assert plan["step"] == 4 and plan["generation"] == 1
    assert plan["victims"] == {"h1": "h2"}
    assert sorted(plan["entries"]) == HOSTS
    assert verify_entry(plan["entries"]["h0"]) == b"h0@4"


def test_plan_adoption_mid_seal_previous_replica_wins(tmp_path):
    """The victim died mid-seal: survivors hold the newer boundary, the
    victim only the previous one — the shared older cut is adopted."""
    s = _store(tmp_path)
    _seed_slabs(s, {"h0": [2, 4], "h1": [2], "h2": [2, 4]})
    record_dead(s, "h1", generation=1, reported_by="h0")
    assert plan_adoption(s, HOSTS, ["h1"])["step"] == 2


def test_plan_adoption_skips_corrupt_newest(tmp_path):
    s = _store(tmp_path)
    _seed_slabs(s, {"h0": [2, 4], "h2": [2, 4]})
    good = seal_entry(b"h1@2", 2, 1)
    bad = seal_entry(b"h1@4", 4, 1)
    bad["sha256"] = "0" * 64                 # torn publish
    publish_replica(s, "h1", good, buddy="h2")
    publish_replica(s, "h1", bad, buddy="h2")
    record_dead(s, "h1", generation=1, reported_by="h0")
    assert plan_adoption(s, HOSTS, ["h1"])["step"] == 2


def test_plan_adoption_requires_every_member_slab(tmp_path):
    s = _store(tmp_path)
    _seed_slabs(s, {"h0": [2], "h1": [2]})   # h2 never sealed
    record_dead(s, "h1", generation=1, reported_by="h0")
    with pytest.raises(ReplicaAdoptionError, match="no published replica"):
        plan_adoption(s, HOSTS, ["h1"])


def test_plan_adoption_refuses_dead_buddy_double_kill(tmp_path):
    s = _store(tmp_path)
    _seed_slabs(s, {h: [2] for h in HOSTS})
    with pytest.raises(ReplicaAdoptionError, match="double-kill"):
        plan_adoption(s, HOSTS, ["h1", "h2"])   # h1's buddy IS h2


def test_plan_adoption_generation_fence(tmp_path):
    """Slabs sealed by a pre-death incarnation (generation below the
    victim's dead marker) must never be adopted."""
    s = _store(tmp_path)
    _seed_slabs(s, {h: [2, 4] for h in HOSTS}, generation=1)
    record_dead(s, "h1", generation=2, reported_by="h0")
    with pytest.raises(ReplicaAdoptionError, match="no consistent cut"):
        plan_adoption(s, HOSTS, ["h1"])


def test_plan_adoption_needs_a_victim(tmp_path):
    s = _store(tmp_path)
    with pytest.raises(ReplicaAdoptionError, match="no victim"):
        plan_adoption(s, HOSTS, ["elsewhere"])


def test_claim_adoption_at_most_one_adopter(tmp_path):
    s = _store(tmp_path)
    record_dead(s, "h1", generation=2, reported_by="h0")
    assert claim_adoption(s, 3, "h1", adopter="h2", step=4,
                          slab_generation=2)
    # a second adopter loses; the winner's re-claim is idempotent
    assert not claim_adoption(s, 3, "h1", adopter="h0", step=4,
                              slab_generation=2)
    assert claim_adoption(s, 3, "h1", adopter="h2", step=4,
                          slab_generation=2)
    doc = s.get(f"{POD_ADOPT_PREFIX}/gen3/h1")
    assert doc["adopter"] == "h2" and doc["dead_generation"] == 2
    # a different round is a fresh claim space
    assert claim_adoption(s, 4, "h1", adopter="h0", step=6,
                          slab_generation=2)


# ------------------------------------- engine snapshot/ingest + adoption

def test_engine_replica_roundtrip_with_loss_continuity(tmp_path):
    """The acceptance kernel: a live slab re-ingested into a FRESH engine
    replays the next step's loss exactly — adoption resumes at the cut
    with zero divergence from the uninterrupted run."""
    eng = _engine()
    for i in range(2):
        eng.train_batch(batch=random_batch(16, 16, seed=i))
    slab = eng.replica_snapshot()
    loss_ref = float(eng.train_batch(batch=random_batch(16, 16, seed=2)))

    s = _store(tmp_path)
    hosts = ["host0", "host1", "host2"]
    ring = buddy_ring(hosts)
    for h in hosts:
        payload = slab if h == "host0" else f"{h} shard".encode()
        publish_replica(s, h, seal_entry(payload, 2, 1), buddy=ring[h])
    record_dead(s, "host1", generation=1, reported_by="host0")

    eng2 = _engine()
    before = replica_adoptions_total()
    resumed = adopt_replicas(s, eng2, hosts, ["host1"], generation=2,
                             host_id="host0")
    assert resumed == 2 and int(eng2.global_steps) == 2
    assert replica_adoptions_total() == before + 1
    # the buddy claimed its victim, generation-fenced
    claim = s.get(f"{POD_ADOPT_PREFIX}/gen2/host1")
    assert claim["adopter"] == "host2" and claim["slab_generation"] == 1
    loss_adopted = float(eng2.train_batch(batch=random_batch(16, 16,
                                                             seed=2)))
    assert abs(loss_adopted - loss_ref) < 1e-6


def test_engine_replica_ingest_rejects_garbage():
    eng = _engine()
    with pytest.raises(Exception):
        eng.replica_ingest(b"definitely not a slab")


def test_adopt_replicas_step_mismatch_is_loud(tmp_path):
    """A slab whose sealed step lies about its contents must abort
    adoption (the caller then falls back to the checkpoint walk)."""
    eng = _engine()
    eng.train_batch(batch=random_batch(16, 16, seed=0))   # global_steps=1
    slab = eng.replica_snapshot()
    s = _store(tmp_path)
    hosts = ["host0", "host1"]
    for h in hosts:
        payload = slab if h == "host0" else b"peer shard"
        publish_replica(s, h, seal_entry(payload, 3, 1),  # lies: step 3
                        buddy=buddy_ring(hosts)[h])
    record_dead(s, "host1", generation=1, reported_by="host0")
    eng2 = _engine()
    with pytest.raises(ReplicaAdoptionError, match="ingested state"):
        adopt_replicas(s, eng2, hosts, ["host1"], generation=2,
                       host_id="host0")


# ------------------------------------------- store_check replica rules

def _adopt_ev(key, adopter, slab_gen, expected=None, t=2.0):
    return {"client": adopter, "op": "cas", "key": key,
            "expected": expected,
            "new": {"victim": key.rsplit("/", 1)[-1], "adopter": adopter,
                    "step": 4, "slab_generation": slab_gen,
                    "dead_generation": 2}, "ok": True, "t": t}


def test_store_check_replica_rules_on_synthetic_histories():
    sys.path.insert(0, TOOLS)
    from store_check import check_history

    dead = {"client": "h0", "op": "put", "key": "dead/h1",
            "value": {"host_id": "h1", "generation": 2}, "t": 1.0}
    # clean: slab generation meets the fence, one adopter
    v = check_history([dead, _adopt_ev("pod/adopt/gen3/h1", "h2", 2)])
    assert v.ok and v.counts["adopt"] == 1
    # fence violation: the adopted slab predates the dead marker
    v = check_history([dead, _adopt_ev("pod/adopt/gen3/h1", "h2", 1)])
    assert not v.ok and "generation fence" in v.violations[0]
    # two adopters admitted for one victim in one round
    first = _adopt_ev("pod/adopt/gen3/h1", "h2", 2)
    second = _adopt_ev("pod/adopt/gen3/h1", "h0", 2,
                       expected=first["new"], t=3.0)
    v = check_history([dead, first, second])
    assert not v.ok and "two adopters" in v.violations[0]
    # distinct rounds are distinct claim spaces
    v = check_history([dead, _adopt_ev("pod/adopt/gen3/h1", "h2", 2),
                       _adopt_ev("pod/adopt/gen4/h1", "h0", 2, t=4.0)])
    assert v.ok


# ------------------------------------------- acceptance: seeded scenarios

@pytest.mark.chaos
def test_pod_buddy_kill_adopts_last_sealed_cut(tmp_path, monkeypatch):
    """ISSUE 20 acceptance (pinned seed): a buddy-kill resumes from the
    last sealed replica cut — rollback <= replica_every_k — with loss
    continuity and a clean store_check verdict over the recorded
    protocol history.  The schedule is the seed's however the machine
    schedules the peers' threads: every peer's read of the lease table
    takes 50 ms here (a starved thread; under six loaded workers the
    victim once read its lease four steps late and the round adopted step
    8, ROADMAP D11), and the cut, the rollback and the adoption are the
    same."""
    import threading

    import deepspeed_tpu.elasticity as elasticity

    sys.path.insert(0, TOOLS)
    from chaos_soak import run_pod_soak

    lease_table = elasticity.lease_table

    def starved(store, *args, **kw):
        if threading.current_thread().name.startswith("pod-sim-"):
            time.sleep(0.05)
        return lease_table(store, *args, **kw)

    monkeypatch.setattr(elasticity, "lease_table", starved)
    stats = run_pod_soak(seed=3, total_steps=12, ckpt_every=5,
                         ckpt_dir=str(tmp_path / "ckpt"),
                         coord_dir=str(tmp_path / "coord"), verbose=False,
                         replica_every_k=2, scenario="buddy_kill")
    assert stats["replica_adoptions"] == 1
    assert stats["replica_fallbacks"] == 0
    assert stats["adopted_step"] == stats["kill_step"] - 1
    assert 0 < stats["rollback_steps"] <= 2
    assert stats["store_check_ok"] is True
    assert stats["recovery_wall_s"] is not None
    assert stats["final_step"] == 12
    assert stats["continuity_checked"] >= 1


@pytest.mark.chaos
def test_pod_recover_compare_beats_checkpoint_restart(tmp_path):
    """Replica adoption vs checkpoint restart on the SAME seeded kill
    schedule: adoption must roll back strictly fewer steps."""
    sys.path.insert(0, TOOLS)
    from chaos_soak import run_pod_recover_compare

    out = run_pod_recover_compare(seed=7, root=str(tmp_path),
                                  total_steps=12, ckpt_every=5,
                                  replica_every_k=2, verbose=False)
    assert out["replica_adoption"]["rollback_steps"] \
        < out["checkpoint_restart"]["rollback_steps"]
    assert out["rollback_saved_steps"] >= 1
    assert out["replica_adoption"]["store_check_ok"]
    assert out["checkpoint_restart"]["store_check_ok"]


@pytest.mark.slow
@pytest.mark.chaos
def test_pod_replica_scenarios_multiseed(tmp_path):
    """Long-form: every replica scenario across seeds (double-kill and
    corrupt-slab fall back loudly; mid-seal adopts the previous cut)."""
    sys.path.insert(0, TOOLS)
    from chaos_soak import run_pod_soak

    for seed in (3, 11):
        for sc in ("buddy_kill", "double_kill", "mid_seal",
                   "corrupt_slab"):
            root = tmp_path / f"s{seed}_{sc}"
            stats = run_pod_soak(seed=seed, total_steps=12, ckpt_every=5,
                                 ckpt_dir=str(root / "ckpt"),
                                 coord_dir=str(root / "coord"),
                                 verbose=False, replica_every_k=2,
                                 scenario=sc)
            assert stats["store_check_ok"], (seed, sc)
            assert stats["final_step"] == 12, (seed, sc)
