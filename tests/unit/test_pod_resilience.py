"""Pod-level fault tolerance — coordination store, heartbeat leases,
rendezvous, all-hosts checkpoint commit, shrink-to-healthy supervision
(docs/POD.md).

Deterministic throughout: lease expiry runs on injected store clocks, fault
sites fire from seeded injectors at exact call counts, and the acceptance
scenario drives the same simulated-pod harness as
``tools/chaos_soak.py --mode pod`` at a pinned seed."""
import json
import os
import sys
import threading
import time

import pytest

import deepspeed_tpu
from deepspeed_tpu.elasticity import (
    ElasticityIncompatibleWorldSize,
    FileCoordinationStore,
    HeartbeatWatchdog,
    PodContext,
    PodElasticAgent,
    PodRendezvousTimeout,
    PodSupervisor,
    RC_POD_UNRECOVERABLE,
    SupervisorStandDown,
    advertise_host,
    beat,
    bump_generation,
    clear_dead,
    compute_elastic_config,
    dead_hosts,
    dead_set,
    host_advertisements,
    lease_table,
    pending_commit,
    read_coordinator,
    read_generation,
    record_dead,
    rendezvous,
    rollup_host_gauges,
    save_pod_checkpoint,
    shrink_to_healthy,
)
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.resilience import (
    CheckpointIntegrityError,
    FaultInjector,
    InjectedFault,
    PodCommitTimeout,
    SITE_POD_HEARTBEAT,
    SITE_POD_RENDEZVOUS,
    SITE_SHARD_COMMIT,
    candidate_tags,
    clear_injector,
    commit_pod_manifest,
    install_injector,
    pod_checkpoint_progress_fn,
    pod_committed,
    verify_pod_checkpoint_dir,
    write_host_manifest,
)
from deepspeed_tpu.resilience.fault_injection import corrupt_file
from deepspeed_tpu.runtime.config import ElasticityConfig

from .simple_model import SimpleModel, make_config, random_batch

HID = 16


@pytest.fixture(autouse=True)
def _clean_injector():
    clear_injector()
    yield
    clear_injector()


def _store(tmp_path, clock=None):
    return FileCoordinationStore(str(tmp_path / "coord"), clock=clock)


def _ec(n_hosts=4):
    return ElasticityConfig(enabled=True, max_train_batch_size=16,
                            micro_batch_sizes=[2, 4], min_gpus=1,
                            max_gpus=n_hosts)


def _engine(**extra):
    mesh_mod.reset_mesh()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(HID), config=make_config(batch_size=16, **extra))
    return engine


# --------------------------------------------------------------- the store
def test_store_put_get_list_delete(tmp_path):
    s = _store(tmp_path)
    assert s.get("heartbeat/h0") is None
    s.put("heartbeat/h0", {"a": 1})
    s.put("heartbeat/h1", {"a": 2})
    assert s.get("heartbeat/h0") == {"a": 1}
    assert s.list("heartbeat") == ["h0", "h1"]
    assert s.list("nope") == []
    s.delete("heartbeat/h0")
    assert s.get("heartbeat/h0") is None
    s.delete("heartbeat/h0")              # idempotent


def test_store_rejects_traversal_keys(tmp_path):
    s = _store(tmp_path)
    with pytest.raises(ValueError):
        s.put("../escape", {})
    with pytest.raises(ValueError):
        s.get("")


# ----------------------------------------------------------- leases + clock
def test_lease_expiry_on_injected_clock(tmp_path):
    clock = [100.0]
    s = _store(tmp_path, clock=lambda: clock[0])
    beat(s, "h0", generation=1, lease_s=1.0, step=7)
    beat(s, "h1", generation=1, lease_s=1.0)
    table = lease_table(s)
    assert table["h0"].attrs["step"] == 7
    assert dead_hosts(s, 1, miss_limit=2) == []
    clock[0] = 101.5                      # 1.5 leases: not dead at limit 2
    assert dead_hosts(s, 1, miss_limit=2) == []
    clock[0] = 102.0                      # exactly 2 missed leases
    beat(s, "h1", generation=1, lease_s=1.0)   # h1 renews, h0 does not
    assert dead_hosts(s, 1, miss_limit=2) == ["h0"]
    # generation-scoped: the stale lease is invisible to generation 2
    assert dead_hosts(s, 2, miss_limit=2) == []


def test_dead_hosts_counts_never_beaten_expected(tmp_path):
    clock = [0.0]
    s = _store(tmp_path, clock=lambda: clock[0])
    beat(s, "h0", generation=3, lease_s=1.0)
    assert dead_hosts(s, 3, 2, expected=["h0", "h9"]) == ["h9"]
    # a lease stuck at an OLDER generation = never reached this one = dead;
    # a NEWER one is proof of life (a stale watchdog scanning for its old
    # generation must not dead-mark the hosts that re-formed without it)
    beat(s, "h1", generation=2, lease_s=1.0)
    beat(s, "h2", generation=4, lease_s=1.0)
    assert dead_hosts(s, 3, 2, expected=["h0", "h1", "h2"]) == ["h1"]


def test_dead_markers_roundtrip(tmp_path):
    s = _store(tmp_path)
    assert dead_set(s) == []
    record_dead(s, "h2", generation=4, reported_by="h0")
    assert dead_set(s) == ["h2"]
    clear_dead(s, "h2")
    assert dead_set(s) == []


def test_generation_monotonic(tmp_path):
    s = _store(tmp_path)
    assert read_generation(s) == 0
    assert bump_generation(s) == 1
    assert bump_generation(s) == 2
    assert read_generation(s) == 2


# --------------------------------------------------------------- rendezvous
def test_rendezvous_completes_and_is_generation_scoped(tmp_path):
    s = _store(tmp_path)
    got = {}
    t = threading.Thread(target=lambda: got.setdefault(
        "h1", rendezvous(s, "h1", 1, ["h0", "h1"], timeout_s=5.0,
                         poll_s=0.005)), daemon=True)
    t.start()
    members = rendezvous(s, "h0", 1, ["h0", "h1"], timeout_s=5.0,
                         poll_s=0.005)
    t.join(timeout=5.0)
    assert members == ["h0", "h1"] and got["h1"] == ["h0", "h1"]
    # gen-1 registrations are invisible to generation 2
    with pytest.raises(PodRendezvousTimeout, match=r"missing \['h1'\]"):
        rendezvous(s, "h0", 2, ["h0", "h1"], timeout_s=0.1, poll_s=0.005)


# ------------------------------------------------------ heartbeat watchdog
@pytest.mark.chaos
def test_watchdog_declares_silent_peer_dead_and_records_marker(tmp_path):
    s = _store(tmp_path)
    dead = []
    wd = HeartbeatWatchdog(s, "h0", generation=1, peers=["h0", "h1"],
                           lease_s=0.05, miss_limit=2, renew_s=0.01,
                           on_peer_dead=dead.append, grace_beats=10 ** 6)
    beat(s, "h1", generation=1, lease_s=0.05)   # h1 beats once, then dies
    wd.start()
    try:
        deadline = time.monotonic() + 5.0
        while not wd.dead and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        wd.stop()
    assert dead == ["h1"]
    assert dead_set(s) == ["h1"]                # durable marker for re-plan


def test_beat_once_concurrent_callers_lose_no_beats(tmp_path):
    """graft-lint thread-guard regression (ISSUE 14): ``beat_once()``
    runs on BOTH the renew daemon and the training step loop, and
    ``beats += 1`` plus the advert rate-limit check-then-set were
    unlocked read-modify-writes — concurrent renewals could lose beats,
    and ``beats`` gates the dead-host grace window in ``_scan``.  Now
    both run under ``_beat_lock``: N concurrent callers == exactly N
    beats."""
    s = _store(tmp_path)
    wd = HeartbeatWatchdog(s, "h0", generation=1, peers=["h1"],
                           lease_s=10.0, renew_s=10.0,
                           on_peer_dead=lambda h: None)
    n_threads, n_calls = 8, 200
    start_gate = threading.Event()

    def hammer():
        start_gate.wait()
        for _ in range(n_calls):
            wd.beat_once()

    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)   # force preemption inside the hot +=
    try:
        threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
        for t in threads:
            t.start()
        start_gate.set()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(old_interval)
    assert wd.beats == n_threads * n_calls


def test_watchdog_quiet_while_peers_renew(tmp_path):
    # six leases long.  A lease is 0.2 s: beside five other workers that
    # compile, the renewing thread was starved for two leases of 0.05 s and
    # the watchdog, rightly, declared it dead (the driver's run of PR 32)
    s = _store(tmp_path)
    stop = threading.Event()

    def renew():
        while not stop.is_set():
            beat(s, "h1", generation=1, lease_s=0.2)
            time.sleep(0.04)

    t = threading.Thread(target=renew, daemon=True)
    t.start()
    wd = HeartbeatWatchdog(s, "h0", generation=1, peers=["h1"],
                           lease_s=0.2, miss_limit=2, renew_s=0.04,
                           on_peer_dead=lambda h: None)
    wd.start()
    try:
        time.sleep(1.2)
        assert wd.dead == []
    finally:
        wd.stop()
        stop.set()
        t.join()


# -------------------------------------------------------------- fault sites
@pytest.mark.chaos
def test_pod_fault_sites_fire(tmp_path):
    inj = install_injector(FaultInjector())
    inj.add(site=SITE_POD_HEARTBEAT, kind="raise", at_call=1)
    inj.add(site=SITE_POD_RENDEZVOUS, kind="raise", at_call=1)
    inj.add(site=SITE_SHARD_COMMIT, kind="raise", at_call=1)
    s = _store(tmp_path)
    with pytest.raises(InjectedFault):
        beat(s, "h0", 1, 1.0)
    with pytest.raises(InjectedFault):
        rendezvous(s, "h0", 1, ["h0"], timeout_s=1.0)
    with pytest.raises(InjectedFault):
        write_host_manifest(str(tmp_path), "h0", 1, 0, files=[])
    assert [e["site"] for e in inj.log] == [
        SITE_POD_HEARTBEAT, SITE_POD_RENDEZVOUS, SITE_SHARD_COMMIT]


# ------------------------------------------------------ pod commit protocol
def _write_shard(tag_dir, host):
    rel = os.path.join("shards", f"{host}.bin")
    path = os.path.join(tag_dir, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(f"shard of {host}".encode() * 4)
    return [rel]


def test_pod_commit_waits_for_all_hosts_then_publishes(tmp_path):
    tag_dir = str(tmp_path / "global_step3")
    os.makedirs(tag_dir)
    for h in ("h0", "h1"):
        write_host_manifest(tag_dir, h, generation=2, global_steps=3,
                            files=_write_shard(tag_dir, h))
    assert not pod_committed(tag_dir)
    commit_pod_manifest(tag_dir, 2, expected_hosts=["h0", "h1"],
                        timeout_s=1.0)
    assert pod_committed(tag_dir)
    pod = verify_pod_checkpoint_dir(tag_dir)
    assert pod["hosts"] == ["h0", "h1"]
    assert pod["global_steps"] == 3


def test_pod_commit_times_out_on_missing_host(tmp_path):
    tag_dir = str(tmp_path / "global_step3")
    os.makedirs(tag_dir)
    write_host_manifest(tag_dir, "h0", generation=1, global_steps=3,
                        files=_write_shard(tag_dir, "h0"))
    with pytest.raises(PodCommitTimeout) as ei:
        commit_pod_manifest(tag_dir, 1, expected_hosts=["h0", "h1"],
                            timeout_s=0.1, poll_s=0.01)
    assert ei.value.missing == ["h1"]
    assert not pod_committed(tag_dir)     # the tag stays torn
    with pytest.raises(CheckpointIntegrityError, match="torn"):
        verify_pod_checkpoint_dir(tag_dir)


def test_pod_commit_ignores_stale_generation_manifests(tmp_path):
    """A manifest left by a previous generation's torn commit must not
    satisfy the new generation's commit."""
    tag_dir = str(tmp_path / "global_step3")
    os.makedirs(tag_dir)
    write_host_manifest(tag_dir, "h1", generation=1, global_steps=3,
                        files=_write_shard(tag_dir, "h1"))
    write_host_manifest(tag_dir, "h0", generation=2, global_steps=3,
                        files=_write_shard(tag_dir, "h0"))
    with pytest.raises(PodCommitTimeout) as ei:
        commit_pod_manifest(tag_dir, 2, expected_hosts=["h0", "h1"],
                            timeout_s=0.1, poll_s=0.01)
    assert ei.value.missing == ["h1"]


@pytest.mark.chaos
def test_pod_verify_catches_missing_and_corrupt_shards(tmp_path):
    tag_dir = str(tmp_path / "global_step5")
    os.makedirs(tag_dir)
    for h in ("h0", "h1"):
        write_host_manifest(tag_dir, h, generation=1, global_steps=5,
                            files=_write_shard(tag_dir, h))
    commit_pod_manifest(tag_dir, 1, expected_hosts=["h0", "h1"],
                        timeout_s=1.0)
    # bit-rot one host's shard: size unchanged, checksum drifts
    corrupt_file(os.path.join(tag_dir, "shards", "h1.bin"))
    with pytest.raises(CheckpointIntegrityError, match="checksum"):
        verify_pod_checkpoint_dir(tag_dir)
    # a host manifest vanishing entirely is just as fatal
    os.remove(os.path.join(tag_dir, "host_manifests", "hosth1.json"))
    with pytest.raises(CheckpointIntegrityError, match="manifest missing"):
        verify_pod_checkpoint_dir(tag_dir)


def test_host_payload_files_partition_covers_every_file(tmp_path):
    """Per-process payload attribution (ISSUE 8 satellite): files under a
    process-named component go to that process, everything unclaimed to
    process 0 — the union covers the whole payload listing, so every
    shard file is attested by exactly one host."""
    from deepspeed_tpu.resilience import host_payload_files

    tag = tmp_path / "global_step3"
    layout = [
        "state/ocdbt.process_0/d/data0",         # orbax OCDBT shard, p0
        "state/ocdbt.process_1/d/data1",         # p1
        "state/params.leaf/process_1/shard.bin",  # bare process dir, p1
        "state/_METADATA",                        # shared metadata -> p0
        "state/zarray.json",                     # unclaimed -> p0
        "offload_optimizer/step.bin",            # unclaimed -> p0
    ]
    for rel in layout:
        p = tag / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(rel.encode())
    p0 = host_payload_files(str(tag), process_index=0)
    p1 = host_payload_files(str(tag), process_index=1)
    assert sorted(p0 + p1) == sorted(layout)          # full cover
    assert not set(p0) & set(p1)                      # no double-claim
    assert "state/ocdbt.process_1/d/data1" in p1
    assert "state/params.leaf/process_1/shard.bin" in p1
    assert "state/_METADATA" in p0
    # a legit name containing "process" but no index stays unclaimed -> p0
    extra = tag / "state" / "processing_notes.txt"
    extra.write_bytes(b"x")
    assert "state/processing_notes.txt" in host_payload_files(str(tag), 0)
    assert "state/processing_notes.txt" not in host_payload_files(str(tag), 1)


@pytest.mark.chaos
def test_pod_save_attests_payload_files_and_detects_missing_shard(tmp_path):
    """The ISSUE 8 satellite closing PR 5's gap: host manifests list the
    REAL orbax payload files (not just the simulated shard_writer files),
    so verify_pod_checkpoint_dir detects a missing shard FILE — not just a
    missing manifest."""
    engine = _engine()
    engine.train_batch(batch=random_batch(16, HID, seed=0))
    store = _store(tmp_path)
    ckpt = str(tmp_path / "ckpt")
    ctx = PodContext(store, "host0", ["host0"], generation=1,
                     commit_timeout_s=5.0)
    tag_dir = save_pod_checkpoint(engine, ckpt, ctx)
    from deepspeed_tpu.resilience import read_host_manifests

    listed = read_host_manifests(tag_dir)["host0"]["files"]
    payload = [rel for rel in listed if rel.startswith("state")]
    assert payload, listed        # the orbax payload really is attested
    verify_pod_checkpoint_dir(tag_dir)
    # lose one attested payload file: the pod verify must catch it
    victim = os.path.join(tag_dir, payload[0])
    os.remove(victim)
    with pytest.raises(CheckpointIntegrityError, match="missing"):
        verify_pod_checkpoint_dir(tag_dir)


def test_pod_progress_fn_counts_only_pod_committed(tmp_path):
    fn = pod_checkpoint_progress_fn(str(tmp_path))
    assert fn() == -1
    # host-committed but not pod-committed: invisible to pod progress
    tag_dir = str(tmp_path / "global_step4")
    os.makedirs(tag_dir)
    (tmp_path / "global_step4" / "client_state.json").write_text(
        json.dumps({"global_steps": 4}))
    assert fn() == -1
    write_host_manifest(tag_dir, "h0", generation=1, global_steps=4)
    commit_pod_manifest(tag_dir, 1, expected_hosts=["h0"], timeout_s=1.0)
    assert fn() == 4


# --------------------------------------------------------- shrink planning
def test_shrink_to_healthy_picks_largest_admitted_slice():
    ec = _ec(4)
    hosts4 = [f"host{i}" for i in range(4)]
    members, plan = shrink_to_healthy(ec, hosts4)
    assert len(members) == 4 and plan.as_triad() == (16, 4, 1)
    # one host lost: 3 healthy, largest valid count is 2
    members, plan = shrink_to_healthy(ec, hosts4[:3])
    assert members == ["host0", "host1"]
    assert plan.as_triad() == (16, 4, 2)
    assert plan.as_triad() == compute_elastic_config(ec, 2).as_triad()
    with pytest.raises(ElasticityIncompatibleWorldSize):
        shrink_to_healthy(ec, [])


# ---------------------------------------------------------- pod supervisor
def test_pod_supervisor_reforms_after_recorded_death(tmp_path):
    s = _store(tmp_path)
    hosts = [f"host{i}" for i in range(4)]
    seen = []

    def attempt(rnd):
        seen.append(rnd)
        if len(seen) == 1:
            # a peer's watchdog records host3 dead mid-round; round fails
            record_dead(s, "host3", rnd.generation, "host0")
            return 87
        return 0

    sup = PodSupervisor(s, _ec(4), attempt, hosts, backoff_s=0,
                        max_restarts=4)
    assert sup.run() == 0
    assert [r.n_hosts for r in seen] == [4, 2]
    assert seen[0].generation == 1 and seen[1].generation == 2
    assert "host3" not in seen[1].hosts
    assert seen[1].plan.as_triad() == (16, 4, 2)


def test_pod_supervisor_unrecoverable_is_terminal(tmp_path):
    s = _store(tmp_path)
    for h in ("host0", "host1"):
        record_dead(s, h, 1, "op")
    calls = []
    sup = PodSupervisor(s, _ec(2), lambda rnd: calls.append(rnd) or 0,
                        ["host0", "host1"], backoff_s=0, max_restarts=5)
    assert sup.run() == RC_POD_UNRECOVERABLE
    assert calls == []                      # never launched an impossible round
    assert "unrecoverable" in sup.diagnosis
    # clearing the markers re-admits the hosts
    clear_dead(s, "host0")
    clear_dead(s, "host1")
    sup2 = PodSupervisor(s, _ec(2), lambda rnd: 0, ["host0", "host1"],
                         backoff_s=0, max_restarts=5)
    assert sup2.run() == 0


# --------------------------- elected pod supervisor (ISSUE 8 tentpole)

def test_pod_supervisor_election_standby_takeover(tmp_path):
    """The PodSupervisor round loop runs under ``elect_coordinator``: a
    standby takes over a LAPSED term, adopts the current pod generation
    and dead-host set from the store, and continues rounds — the same
    protocol (and exactly-one-driver CAS proof) the FleetRouter uses."""
    clock = [0.0]
    s = _store(tmp_path, clock=lambda: clock[0])
    hosts = [f"host{i}" for i in range(4)]
    drivers = []

    def mk(name, rcs):
        it = iter(rcs)

        def attempt(rnd):
            drivers.append((name, rnd.generation))
            return next(it)

        return PodSupervisor(s, _ec(4), attempt, hosts, backoff_s=0,
                             max_restarts=4, supervisor_id=name,
                             coordinator_lease_s=5.0, standby_poll_s=0.001)

    sup_a = mk("supA", [87, 0])
    assert sup_a.run() == 0
    assert sup_a.is_coordinator and sup_a.term == 1
    gen_a = read_generation(s)
    assert gen_a == 2                       # one bump per driven round
    # supA's process is gone: a peer recorded a death, the lease lapses,
    # and the standby must adopt BOTH facts on takeover
    record_dead(s, "host3", generation=gen_a, reported_by="host0")
    clock[0] += 60.0
    sup_b = mk("supB", [0])
    assert sup_b.run() == 0
    assert sup_b.term == 2 and sup_b.elections_total == 1
    assert read_generation(s) == gen_a + 1  # monotonic across takeover
    assert "host3" not in sup_b.rounds[-1].hosts
    assert [d[0] for d in drivers] == ["supA", "supA", "supB"]
    gens = [d[1] for d in drivers]
    assert gens == sorted(gens) and len(set(gens)) == len(gens)


def test_pod_supervisor_standby_stands_down_under_live_leader(tmp_path):
    """A standby whose leader stays healthy past ``standby_max_wait_s``
    stands down CLEANLY (SupervisorStandDown: no budget burned, no backoff
    loop) without ever driving a round."""
    clock = [0.0]
    s = _store(tmp_path, clock=lambda: clock[0])
    hosts = ["host0", "host1"]
    driven = []
    leader = PodSupervisor(s, _ec(2), lambda rnd: driven.append(rnd) or 0,
                           hosts, backoff_s=0, supervisor_id="leader",
                           coordinator_lease_s=100.0)
    assert leader.run() == 0 and len(driven) == 1
    standby = PodSupervisor(s, _ec(2),
                            lambda rnd: driven.append(rnd) or 0, hosts,
                            backoff_s=0, supervisor_id="standby",
                            coordinator_lease_s=100.0,
                            standby_poll_s=0.001, standby_max_wait_s=0.1)
    assert standby.run() == 0
    assert standby.elections_total == 0 and len(driven) == 1
    assert "stand-down" in standby.diagnosis
    assert read_coordinator(s, key=standby.election_key).leader_id == "leader"


def test_pod_supervisor_racing_standbys_exactly_one_drives(tmp_path):
    """Two standbys racing the same lapsed lease: the CAS admits exactly
    one — the loser stands down having driven nothing."""
    clock = [0.0]
    s = _store(tmp_path, clock=lambda: clock[0])
    hosts = ["host0", "host1"]
    dead = PodSupervisor(s, _ec(2), lambda rnd: 0, hosts, backoff_s=0,
                         supervisor_id="dead", coordinator_lease_s=5.0)
    assert dead.run() == 0
    clock[0] += 60.0                        # the dead leader's lease lapses
    drivers = []
    outcomes = {}
    barrier = threading.Barrier(2)

    def racer(name):
        sup = PodSupervisor(
            s, _ec(2), lambda rnd: drivers.append((name, rnd)) or 0, hosts,
            backoff_s=0, supervisor_id=name, coordinator_lease_s=100.0,
            standby_poll_s=0.001, standby_max_wait_s=1.0)
        barrier.wait()
        outcomes[name] = (sup.run(), sup.elections_total, sup.term)

    ts = [threading.Thread(target=racer, args=(n,)) for n in ("rA", "rB")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    winners = [n for n, (rc, won, _) in outcomes.items() if won]
    assert len(winners) == 1, outcomes
    assert len(drivers) == 1 and drivers[0][0] == winners[0]
    assert outcomes[winners[0]][2] == 2     # took the next term
    assert all(rc == 0 for rc, _, _ in outcomes.values())


def test_pod_renew_coordinator_reports_deposition(tmp_path):
    """Long rounds renew mid-round: a renewal returning False means a
    standby deposed us and the round must stop driving."""
    clock = [0.0]
    s = _store(tmp_path, clock=lambda: clock[0])
    sup = PodSupervisor(s, _ec(2), lambda rnd: 0, ["host0", "host1"],
                        backoff_s=0, supervisor_id="supA",
                        coordinator_lease_s=5.0)
    assert sup.run() == 0
    assert sup.renew_coordinator()          # healthy leader renews freely
    clock[0] += 60.0                        # ...then wedges past its lease
    usurper = PodSupervisor(s, _ec(2), lambda rnd: 0, ["host0", "host1"],
                            backoff_s=0, supervisor_id="supB",
                            coordinator_lease_s=5.0)
    assert usurper.run() == 0               # takes term 2
    assert not sup.renew_coordinator()      # the old leader must stand down
    assert not sup.is_coordinator


@pytest.mark.chaos
def test_pod_supervisor_standby_takeover_training_continuity(tmp_path):
    """ISSUE 8 acceptance (pod half): supervisor A drives real training
    rounds and dies mid-job; standby B takes the next term, restores the
    last pod-committed checkpoint, and re-executed steps reproduce their
    original losses — generation monotonic, exactly one driver per round."""
    clock = [0.0]
    s = _store(tmp_path, clock=lambda: clock[0])
    ckpt = str(tmp_path / "ckpt")
    loss_log = {}
    continuity = {"checked": 0}
    drivers = []
    TOTAL = 8

    class _SupervisorDied(RuntimeError):
        pass

    def make_attempt(name, die_at=None):
        def attempt(rnd):
            drivers.append((name, rnd.generation))
            engine = _engine()
            ctx = PodContext(s, "host0", list(rnd.hosts), rnd.generation,
                             commit_timeout_s=5.0)
            agent = PodElasticAgent(engine, ckpt, ctx, ckpt_every=2)

            def step_fn(eng, i):
                if die_at is not None and i >= die_at:
                    raise _SupervisorDied(f"{name} killed at step {i}")
                loss = float(eng.train_batch(
                    batch=random_batch(16, HID, seed=i)))
                if i in loss_log:
                    assert abs(loss - loss_log[i]) < 1e-4, \
                        f"loss continuity broken at step {i}"
                    continuity["checked"] += 1
                loss_log[i] = loss
                clock[0] += 1.0

            try:
                last = agent.run(step_fn, TOTAL)
            finally:
                agent.guard.uninstall()
            return 0 if last >= TOTAL else 75

        return attempt

    sup_a = PodSupervisor(s, _ec(1), make_attempt("supA", die_at=5),
                          ["host0"], backoff_s=0, max_restarts=0,
                          supervisor_id="supA", coordinator_lease_s=5.0,
                          standby_poll_s=0.001)
    with pytest.raises(_SupervisorDied):
        sup_a._pod_round(0)                 # the whole PROCESS dies mid-round
    assert sup_a.term == 1
    clock[0] += 60.0                        # its lease lapses
    sup_b = PodSupervisor(s, _ec(1), make_attempt("supB"), ["host0"],
                          backoff_s=0, max_restarts=4,
                          supervisor_id="supB", coordinator_lease_s=5.0,
                          standby_poll_s=0.001)
    assert sup_b.run() == 0
    assert sup_b.term == 2
    assert pod_checkpoint_progress_fn(ckpt)() == TOTAL
    assert continuity["checked"] >= 1       # re-executed steps reproduced
    assert [d[0] for d in drivers] == ["supA", "supB"]
    gens = [d[1] for d in drivers]
    assert gens == sorted(gens) and len(set(gens)) == len(gens)


# ---------------------- pod/hosts advertisements (ISSUE 8 satellite)

def test_host_advertisements_roundtrip_and_rollup(tmp_path):
    from deepspeed_tpu.monitor import InMemoryMonitor

    s = _store(tmp_path)
    mon = InMemoryMonitor()
    advertise_host(s, "host0", 3, monitor=mon, step=7)
    advertise_host(s, "host1", 3, step=7)
    ads = host_advertisements(s)
    assert set(ads) == {"host0", "host1"}
    assert ads["host0"]["attrs"]["step"] == 7
    for key in ("flight_dropped", "flight_src", "monitor_dropped",
                "monitor_src", "generation"):
        assert key in ads["host0"], key
    g = rollup_host_gauges(s, mon, tick=1)
    assert g["pod/hosts_advertised"] == 2.0
    names = {e[0] for e in mon.events_snapshot()}
    assert {"pod/flight_dropped_total", "pod/monitor_dropped_total",
            "pod/hosts_advertised"} <= names
    # dedup keys carry a machine identity, not a bare pid: containerized
    # pods commonly run every host as pid 1, which would silently merge
    # distinct hosts' counters
    from deepspeed_tpu.elasticity.coordination import process_src

    assert ads["host0"]["flight_src"] == process_src()
    assert "." in ads["host0"]["flight_src"]


def test_rollup_ages_out_dead_hosts_advertisements(tmp_path):
    """Advertisements are never deleted, so the rollup must age them out:
    a host lost generations ago may not inflate the pod gauges forever."""
    clock = [0.0]
    s = _store(tmp_path, clock=lambda: clock[0])
    advertise_host(s, "dead_host", 1, step=1)
    clock[0] = 100.0
    advertise_host(s, "live_host", 2, step=9)
    g = rollup_host_gauges(s, None, max_age_s=15.0)
    assert g["pod/hosts_advertised"] == 1.0
    # without the bound, both still show (full history on demand)
    assert rollup_host_gauges(s, None)["pod/hosts_advertised"] == 2.0


def test_watchdog_advertises_and_rolls_up_cross_host_view(tmp_path):
    """Each host's HeartbeatWatchdog publishes its pod/hosts advertisement
    with every renewal and (with a monitor) folds the fleet of
    advertisements into pod-scope gauges — one cross-host /metrics view,
    mirroring the serving fleet's fleet/engines rollup."""
    from deepspeed_tpu.monitor import InMemoryMonitor
    from deepspeed_tpu.observability import prometheus_text

    s = _store(tmp_path)
    mon = InMemoryMonitor()
    wd0 = HeartbeatWatchdog(s, "host0", 1, ["host0", "host1"], lease_s=5.0,
                            monitor=mon, renew_s=0.01,
                            on_peer_dead=lambda h: None)
    wd1 = HeartbeatWatchdog(s, "host1", 1, ["host0", "host1"], lease_s=5.0,
                            renew_s=0.01, on_peer_dead=lambda h: None)
    wd0.set_attrs(step=3)
    try:
        wd0.start()
        wd1.start()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            ads = host_advertisements(s)
            names = {e[0] for e in mon.events_snapshot()}
            if (set(ads) >= {"host0", "host1"}
                    and "pod/hosts_advertised" in names):
                break
            time.sleep(0.01)
    finally:
        wd0.stop()
        wd1.stop()
    ads = host_advertisements(s)
    assert set(ads) >= {"host0", "host1"}
    assert ads["host0"]["attrs"].get("step") == 3
    names = {e[0] for e in mon.events_snapshot()}
    assert {"pod/hosts_advertised", "pod/flight_dropped_total",
            "pod/monitor_dropped_total"} <= names
    # the rollup reaches the Prometheus exposition like every other gauge
    text = prometheus_text(monitor=mon)
    assert "dstpu_pod_hosts_advertised" in text
    # a disabled watchdog stays store-silent
    s2 = _store(tmp_path / "quiet")
    wd2 = HeartbeatWatchdog(s2, "host0", 1, ["host0"], advertise=False,
                            on_peer_dead=lambda h: None)
    wd2.beat_once()
    assert host_advertisements(s2) == {}


# ----------------------------------- pod checkpoints with a real engine
def _peer_commit_thread(store, ckpt_dir, host, generation, stop_evt):
    """Minimal simulated peer: write shard + manifest for every announced
    commit of this generation."""
    handled = set()

    def loop():
        while not stop_evt.is_set():
            tag = pending_commit(store, generation)
            if tag is not None and tag not in handled:
                handled.add(tag)
                tag_dir = os.path.join(ckpt_dir, tag)
                write_host_manifest(tag_dir, host, generation,
                                    int(tag.replace("global_step", "")),
                                    files=_write_shard(tag_dir, host))
            time.sleep(0.005)

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    return t


@pytest.mark.chaos
def test_pod_save_commits_only_after_all_hosts(tmp_path):
    engine = _engine()
    for _ in range(2):
        engine.train_batch(batch=random_batch(16, HID, seed=0))
    store = _store(tmp_path)
    ckpt = str(tmp_path / "ckpt")
    ctx = PodContext(store, "host0", ["host0", "host1"], generation=1,
                     commit_timeout_s=5.0, shard_writer=_write_shard)
    stop = threading.Event()
    t = _peer_commit_thread(store, ckpt, "host1", 1, stop)
    try:
        tag_dir = save_pod_checkpoint(engine, ckpt, ctx)
    finally:
        stop.set()
        t.join(timeout=5.0)
    pod = verify_pod_checkpoint_dir(tag_dir)
    assert pod["hosts"] == ["host0", "host1"]
    assert (tmp_path / "ckpt" / "latest").read_text() == "global_step2"
    # and with the peer gone, the same save TEARS instead of committing
    engine.train_batch(batch=random_batch(16, HID, seed=1))
    ctx2 = PodContext(store, "host0", ["host0", "host1"], generation=2,
                      commit_timeout_s=0.3, shard_writer=_write_shard)
    with pytest.raises(PodCommitTimeout):
        save_pod_checkpoint(engine, ckpt, ctx2)
    assert (tmp_path / "ckpt" / "latest").read_text() == "global_step2"
    assert not pod_committed(str(tmp_path / "ckpt" / "global_step3"))


@pytest.mark.chaos
def test_torn_pod_tag_quarantined_and_fallback_crosses_pod_sizes(tmp_path):
    """The satellite contract: a torn pod checkpoint (one host's manifest
    missing) is never selected for restore, lands in ``<tag>.corrupt``, and
    the walk falls back to a generation written by a DIFFERENT pod size."""
    engine = _engine()
    store = _store(tmp_path)
    ckpt = str(tmp_path / "ckpt")
    # generation 1, 2-host pod: fully committed at step 1
    engine.train_batch(batch=random_batch(16, HID, seed=0))
    ctx1 = PodContext(store, "host0", ["host0", "host1"], generation=1,
                      commit_timeout_s=5.0, shard_writer=_write_shard)
    stop = threading.Event()
    t = _peer_commit_thread(store, ckpt, "host1", 1, stop)
    try:
        save_pod_checkpoint(engine, ckpt, ctx1)
    finally:
        stop.set()
        t.join(timeout=5.0)
    # generation 2: host1 died mid-commit -> torn tag at step 2
    engine.train_batch(batch=random_batch(16, HID, seed=1))
    ctx2 = PodContext(store, "host0", ["host0", "host1"], generation=2,
                      commit_timeout_s=0.2, shard_writer=_write_shard)
    with pytest.raises(PodCommitTimeout):
        save_pod_checkpoint(engine, ckpt, ctx2)
    # generation 3 re-forms at ONE host and restores
    ctx3 = PodContext(store, "host0", ["host0"], generation=3,
                      commit_timeout_s=5.0, shard_writer=_write_shard)
    agent = PodElasticAgent(engine, ckpt, ctx3)
    try:
        resumed = agent.restore_if_present()
    finally:
        agent.guard.uninstall()
    assert resumed == 1                      # the 2-host committed generation
    assert engine.global_steps == 1
    assert (tmp_path / "ckpt" / "global_step2.corrupt").is_dir()
    assert not (tmp_path / "ckpt" / "global_step2").exists()
    assert candidate_tags(ckpt) == ["global_step1"]
    # and the 1-host pod can carry the lineage forward
    engine.train_batch(batch=random_batch(16, HID, seed=1))
    tag_dir = save_pod_checkpoint(engine, ckpt, ctx3)
    assert verify_pod_checkpoint_dir(tag_dir)["hosts"] == ["host0"]
    assert pod_checkpoint_progress_fn(ckpt)() == 2


@pytest.mark.chaos
def test_pod_prune_skips_torn_tags_and_keeps_pod_committed(tmp_path):
    """Prune candidacy is pod-scope for the pod agent: a torn pod tag
    (host-committed, no pod manifest) neither counts toward the keep
    window nor gets deleted — it is left for the quarantine sweep, and the
    keep-newest window holds only generations the restore path accepts."""
    engine = _engine()
    store = _store(tmp_path)
    ckpt = tmp_path / "ckpt"
    for step, torn in ((2, False), (4, True), (6, False), (8, False)):
        d = ckpt / f"global_step{step}"
        d.mkdir(parents=True)
        (d / "manifest.json").write_text(json.dumps({"global_steps": step}))
        (d / "client_state.json").write_text(
            json.dumps({"global_steps": step}))
        if not torn:
            write_host_manifest(str(d), "host0", 1, step)
            commit_pod_manifest(str(d), 1, ["host0"], timeout_s=1.0)
    ctx = PodContext(store, "host0", ["host0"], 1)
    agent = PodElasticAgent(engine, str(ckpt), ctx, keep=2)
    try:
        agent._prune_generations()
    finally:
        agent.guard.uninstall()
    assert not (ckpt / "global_step2").exists()       # 3rd-newest committed
    assert (ckpt / "global_step4").is_dir()           # torn: never rmtree'd
    assert (ckpt / "global_step6").is_dir()
    assert (ckpt / "global_step8").is_dir()


# ------------------------------------------------- launcher + comm wiring
def test_launcher_pod_attempt_bumps_generation_and_env(tmp_path, monkeypatch):
    from deepspeed_tpu.launcher import runner as runner_mod

    coord = str(tmp_path / "coord")
    args = runner_mod.parse_args(["--pod_coord_dir", coord,
                                  "--pod_lease", "2.5",
                                  "--elastic_restarts", "3", "train.py"])
    assert args.pod_coord_dir == coord and args.pod_lease == 2.5
    dispatched = []
    monkeypatch.setattr(runner_mod, "_dispatch",
                        lambda a: dispatched.append(
                            os.environ["DS_TPU_POD_GENERATION"]) or 0)
    attempt = runner_mod._pod_attempt(args)
    assert attempt(0) == 0
    assert attempt(1) == 0
    assert dispatched == ["1", "2"]
    assert os.environ["DS_TPU_POD_COORD_DIR"] == coord
    assert os.environ["DS_TPU_POD_LEASE"] == "2.5"
    assert read_generation(FileCoordinationStore(coord)) == 2
    # _pod_attempt writes os.environ directly (monkeypatch would restore
    # the leaked values at teardown instead of clearing them)
    for key in ("DS_TPU_POD_GENERATION", "DS_TPU_POD_COORD_DIR",
                "DS_TPU_POD_LEASE", "DS_TPU_POD_MISS_LIMIT"):
        os.environ.pop(key, None)


def test_comm_pod_generation_env(monkeypatch):
    from deepspeed_tpu.comm.comm import get_pod_generation

    assert get_pod_generation() == 0
    monkeypatch.setenv("DS_TPU_POD_GENERATION", "7")
    assert get_pod_generation() == 7
    monkeypatch.setenv("DS_TPU_POD_GENERATION", "junk")
    assert get_pod_generation() == 0


# ----------------------------------------- acceptance: simulated pod chaos
@pytest.mark.chaos
@pytest.mark.slow
def test_pod_chaos_kill_reforms_and_restores(tmp_path):
    """ISSUE 5 acceptance: a simulated 4-host run killed at a seeded point
    (this seed: a mid-commit host death) auto-detects the loss, re-forms at
    2 hosts with the ``compute_elastic_config`` triad, quarantines the torn
    pod tag, restores the committed generation and converges with loss
    continuity.  Same harness as ``tools/chaos_soak.py --mode pod``."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, os.pardir, "tools"))
    from chaos_soak import run_pod_soak

    stats = run_pod_soak(seed=5, total_steps=12, ckpt_every=2,
                         ckpt_dir=str(tmp_path / "ckpt"),
                         coord_dir=str(tmp_path / "coord"), verbose=False)
    assert stats["kill_mode"] == "mid_commit"
    assert stats["final_hosts"] == 2
    assert stats["final_triad"] == (16, 4, 2)
    assert stats["final_step"] == 12
    assert stats["quarantined"]              # the torn tag ended .corrupt
    assert stats["continuity_checked"] >= 1


@pytest.mark.chaos
@pytest.mark.slow
def test_pod_chaos_step_kill_detected_by_leases(tmp_path):
    """Second deterministic seed: a silent mid-step death (the lease just
    stops renewing) detected by the heartbeat watchdog."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, os.pardir, "tools"))
    from chaos_soak import run_pod_soak

    stats = run_pod_soak(seed=6, total_steps=12, ckpt_every=2,
                         ckpt_dir=str(tmp_path / "ckpt"),
                         coord_dir=str(tmp_path / "coord"), verbose=False)
    assert stats["kill_mode"] == "step"
    assert stats["final_hosts"] == 2
    assert stats["final_triad"] == (16, 4, 2)
    assert stats["final_step"] == 12


@pytest.mark.slow
@pytest.mark.chaos
def test_pod_chaos_soak_multiseed(tmp_path):
    """Long-form randomized variant (tools/chaos_soak.py --mode pod)."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, os.pardir, "tools"))
    from chaos_soak import run_pod_soak

    for seed in (0, 1, 2, 3):
        root = tmp_path / f"s{seed}"
        run_pod_soak(seed=seed, total_steps=12, ckpt_every=2,
                     ckpt_dir=str(root / "ckpt"),
                     coord_dir=str(root / "coord"), verbose=False)
