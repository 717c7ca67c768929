#!/usr/bin/env python
"""Chaos soak: N supervised sessions under seeded random fault injection.

Three modes (``--mode train`` is the default):

- **train**: supervised elastic training rounds — preemption SIGTERMs,
  checkpoint-write failures, corruption of the newest generation — must
  still converge to ``--total-steps`` (invariants below);
- **serve**: a ``ServingSupervisor`` request stream hammered with
  randomized ``serve.decode`` / ``serve.prefill`` / ``serve.replay``
  kills plus bounded-queue shedding and a dead-on-arrival deadline — every
  request must reach a terminal result, completed outputs must be
  token-identical to a fault-free reference run, and page accounting must
  balance after drain (pool pages = free + quarantined);
- **pod**: a simulated multi-host run (peer hosts as threads over a
  file-backed coordination store, the coordinator owning a real engine on
  the virtual CPU mesh) with a seeded host kill — mid-step or mid-commit —
  that must be detected by missed leases, re-form at the largest healthy
  slice ``compute_elastic_config`` admits, restore the last *committed*
  pod checkpoint (torn pod tags quarantined), and converge with loss
  continuity (docs/POD.md);
- **fleet**: a 3-engine serving fleet on a file-backed coordination store
  (injected store clock, one router round per clock tick) under a seeded
  random ENGINE kill — silent lease lapse or fault-injected restart-budget
  exhaustion — plus, half the time, a coordinator kill with a standby
  router taking the next election term.  Token journaling runs hot
  (``journal_every_k=2``), so kills land MID-STREAM with journaled
  batches outstanding: failover must RESUME after the last journaled
  token.  Every request must reach a terminal result, completed outputs
  must be token-identical to a fault-free single-engine reference (no
  token duplicated, none lost — resumed streams included), each SURVIVING
  engine's page accounting must balance, the dead engine must carry a
  lapsed lease or a durable ``fleet/dead`` marker, every journal entry
  must be GC'd by the collecting router (original or standby), and the
  fleet generation must bump monotonically across coordinator terms
  (docs/FLEET.md);
- **store_partition**: the STORE is the fault axis (ISSUE 18) — a router
  plus daemonized members run over per-client ``FaultyStore`` views of
  one recorded file store: transient-error brownouts the retry policy
  must absorb (zero failovers), a sub-grace member blackout that must
  NOT fail over (the member decodes dark and republishes its outbox on
  heal), an over-grace asymmetric partition that MUST (token-exact
  resume; the healed victim stale-drops its buffered copies — zero
  duplicate serves), and the live-but-partitioned LEADER, which must
  self-fence within ``lease_s`` (zero dispatches, zero journal deletes)
  while a successor takes the next term.  The complete linearized op
  history must pass every ``tools/store_check.py`` invariant
  (docs/FLEET.md "Store brownouts and partitions").

Each soak round draws a fault mix from a seeded PRNG — preemption SIGTERMs
at random steps, checkpoint-write failures, corruption of the newest
committed generation, publish-point crashes — and runs a supervised
training session (Supervisor + ElasticAgent + a real engine on the virtual
CPU mesh) to ``--total-steps``.  The invariants checked after every soak:

- the supervisor exits 0 (work completed despite the faults);
- the final committed checkpoint verifies and carries ``total_steps``;
- every corrupted generation ended in a ``*.corrupt`` quarantine, never in
  the resume path.

Deterministic per ``--seed``: the same seed replays the same fault
schedule.  Usage::

    JAX_PLATFORMS=cpu python tools/chaos_soak.py --soaks 3 --seed 7
    JAX_PLATFORMS=cpu python tools/chaos_soak.py --mode serve --soaks 3

The tier-1 suite runs the equivalent single deterministic scenarios
(tests/unit/test_resilience.py for train,
tests/unit/test_serving_resilience.py for serve); this driver is the
long-form randomized variant (its pytest hooks are marked ``slow``).
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import threading
import time
from random import Random

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "tests"))


def run_soak(seed: int, total_steps: int, ckpt_every: int, ckpt_dir: str,
             verbose: bool = True) -> dict:
    """One supervised session under a random fault schedule; returns stats.
    Raises AssertionError when an invariant breaks."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import deepspeed_tpu
    from deepspeed_tpu.elasticity import ElasticAgent, Supervisor
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.resilience import (FaultInjector, candidate_tags,
                                          checkpoint_progress_fn,
                                          clear_injector, install_injector,
                                          verify_checkpoint_dir)
    from deepspeed_tpu.resilience.fault_injection import (
        SITE_CKPT_SAVE, SITE_LATEST_PUBLISH, SITE_TRAIN_STEP, corrupt_file)
    from unit.simple_model import SimpleModel, make_config, random_batch

    rng = Random(seed)
    inj = FaultInjector()
    # a couple of preemptions at random steps across the session
    for _ in range(rng.randint(1, 2)):
        inj.add(site=SITE_TRAIN_STEP, kind="sigterm",
                at_call=rng.randint(2, max(3, total_steps - 1)))
    # one failed save and/or one publish-point crash
    if rng.random() < 0.8:
        inj.add(site=SITE_CKPT_SAVE, kind="raise",
                at_call=rng.randint(1, 3))
    if rng.random() < 0.5:
        inj.add(site=SITE_LATEST_PUBLISH, kind="raise",
                at_call=rng.randint(1, 2))
    corrupt_in_round = rng.randint(1, 3) if rng.random() < 0.8 else -1
    install_injector(inj)

    corrupted = []

    def attempt(round_idx):
        if round_idx == corrupt_in_round and not corrupted:
            tags = candidate_tags(ckpt_dir)
            if tags:
                victim = os.path.join(
                    ckpt_dir, tags[0],
                    rng.choice(["client_state.json", "manifest.json"]))
                if os.path.exists(victim):
                    corrupt_file(victim, seed=seed)
                    corrupted.append(victim)
        mesh_mod.reset_mesh()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(16), config=make_config(batch_size=16))
        agent = ElasticAgent(engine, ckpt_dir, ckpt_every=ckpt_every)
        try:
            last = agent.run(
                lambda eng, i: eng.train_batch(
                    batch=random_batch(16, 16, seed=i)), total_steps)
        finally:
            agent.guard.uninstall()
        return 0 if last >= total_steps else 75

    progress = checkpoint_progress_fn(ckpt_dir)
    sup = Supervisor(attempt, max_restarts=12, backoff_s=0,
                     progress_fn=progress, zero_progress_limit=4, seed=seed)
    rc = sup.run()
    clear_injector()

    assert rc == 0, f"soak seed={seed}: supervisor exited rc={rc} " \
                    f"(diagnosis: {sup.diagnosis})"
    final = progress()
    assert final == total_steps, \
        f"soak seed={seed}: converged to step {final}, wanted {total_steps}"
    newest = candidate_tags(ckpt_dir)[0]
    verify_checkpoint_dir(os.path.join(ckpt_dir, newest))
    stats = {
        "seed": seed,
        "faults_fired": len(inj.log),
        "fault_log": inj.log,
        "corrupted": [os.path.relpath(c, ckpt_dir) for c in corrupted],
        "quarantined": sorted(d for d in os.listdir(ckpt_dir)
                              if ".corrupt" in d),
        "final_step": final,
    }
    if corrupted:
        assert stats["quarantined"], \
            f"soak seed={seed}: corruption injected but nothing quarantined"
    if verbose:
        print(f"  seed={seed}: OK — {stats['faults_fired']} fault(s) fired, "
              f"{len(stats['quarantined'])} quarantined, "
              f"final step {final}")
    return stats


def run_serve_soak(seed: int, n_requests: int = 8, b_slots: int = 3,
                   verbose: bool = True, tp: int = 1,
                   host_tier_pages: int = None, num_pages: int = None,
                   require_tier_cycles: bool = False,
                   kv_dtype: str = None) -> dict:
    """One supervised serving session under a seeded random kill schedule.

    ``tp > 1`` runs the WHOLE session on a ``tp``-device mesh (model axis =
    tp over the first tp virtual host devices): the paged pool shards its
    KV-head dim, every kill/replay lands on sharded programs, and the same
    page-accounting + refcount invariants must hold — plus the sharded
    extras (mesh facts in health(), per-device pool bytes = total/tp).

    ``host_tier_pages`` (with a deliberately small ``num_pages``) runs the
    session under KV-page tiering POOL PRESSURE (ISSUE 11): the shared
    system prompt's pages demote to the host tier and promote back across
    the kill schedule, and the extra invariants are asserted after every
    audit — the extended page accounting (``balanced`` now includes the
    demoted ledger: demoted index entries == host-tier buffers), token
    exactness of promoted-prefix streams (the parity check), and that
    quarantine / warm restarts never strand a demoted page (the ledger
    re-balances on the replacement engine, which CARRIES the host tier).
    ``require_tier_cycles`` additionally asserts the schedule really
    demoted AND promoted (the tier-1 pinned seed uses it).

    ``kv_dtype="int8"`` (ISSUE 17) runs BOTH the fault-free reference and
    the supervised session on the QUANTIZED paged pool, so the parity
    loop asserts that promoted int8 streams (half-byte host-tier slabs +
    scale rows) replay token-exactly against an unkilled int8 engine —
    quantization error never compounds across demote/promote/kill/replay
    because pages move as raw int8 bytes, never round-tripping through
    float (docs/SERVING.md "Quantized KV pages").

    The soak draws decode/prefill/replay kill points (and, half the time, a
    bounded queue + one dead-on-arrival deadline) from ``seed``, replays a
    mixed-length stream through :class:`ServingSupervisor`, and asserts the
    ISSUE 3 acceptance invariants:

    - every submitted request reaches a terminal ``RequestResult``
      (completed / ``"deadline"`` / ``"shed"`` — none lost);
    - completed outputs are token-identical to a fault-free reference run
      of the same stream (greedy decode makes supervisor replay exact —
      including requests admitted through shared prefix pages: half the
      stream shares a seeded system prompt, so kills land mid-prefill and
      mid-decode on REFCOUNTED shared pages);
    - the refcount pool invariant holds after every kill and after
      ``drain()``: pool pages = free + quarantined + referenced, with no
      page leaked or double-freed (a double-free raises inside the engine).
    """
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.resilience import (FaultInjector, clear_injector,
                                          install_injector)
    from deepspeed_tpu.resilience.fault_injection import (
        SITE_SERVE_DECODE, SITE_SERVE_PREFILL, SITE_SERVE_REPLAY)

    rng = Random(seed)
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(0))
    mesh_kw = {}
    if tp > 1:
        from deepspeed_tpu.parallel.mesh import initialize_serving_mesh

        mesh_kw["mesh"] = initialize_serving_mesh(tp=tp, n_devices=tp)
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params, **mesh_kw)

    nprng = np.random.default_rng(seed)
    # half the stream shares a seeded system prompt (long enough for one
    # full 8-token page + a COW boundary — TWO full pages under tiering
    # pressure, so a whole immutable chunk demotes/promotes), so the kill
    # schedule hits refcounted shared pages mid-prefill/mid-decode; the
    # rest stay unique
    tiered = host_tier_pages is not None
    system = nprng.integers(1, model.config.vocab_size,
                            19 if tiered else 11).astype(np.int32)

    def prompt(i):
        if i % 2 == 0:
            uniq = nprng.integers(1, model.config.vocab_size,
                                  int(nprng.integers(2, 6))).astype(np.int32)
            return np.concatenate([system, uniq])
        return nprng.integers(1, model.config.vocab_size,
                              int(nprng.integers(3, 14))).astype(np.int32)

    base = [Request(rid=i, input_ids=prompt(i),
                    max_new_tokens=int(nprng.choice((4, 6, 8))))
            for i in range(n_requests)]

    def copies(deadline_rid=None):
        return [Request(rid=r.rid, input_ids=r.input_ids,
                        max_new_tokens=r.max_new_tokens,
                        deadline_s=(1e-4 if r.rid == deadline_rid else None))
                for r in base]

    tier_kw = dict(host_tier_pages=host_tier_pages, num_pages=num_pages) \
        if tiered else {}

    # fault-free reference (no injector installed yet; NO tiering — the
    # parity of the tiered run against an untiered reference is exactly
    # the promoted-prefix token-exactness invariant)
    ref_serve = engine.serving(b_slots=b_slots, page_size=8, max_model_len=64,
                               kv_dtype=kv_dtype)
    ref = {r.rid: r.output_ids for r in ref_serve.run(copies())}

    # seeded random kill schedule.  The first decode kill lands early so a
    # short (possibly shed-thinned) stream still exercises a restart;
    # later kills may or may not fire before the stream drains.
    inj = FaultInjector()
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=rng.randint(2, 5))
    for _ in range(rng.randint(0, 2)):
        inj.add(site=SITE_SERVE_DECODE, kind="raise",
                at_call=rng.randint(2, 2 * n_requests))
    if rng.random() < 0.7:
        inj.add(site=SITE_SERVE_PREFILL, kind="raise",
                at_call=rng.randint(1, n_requests))
    if rng.random() < 0.3:
        inj.add(site=SITE_SERVE_REPLAY, kind="raise", at_call=1)
    max_queue = rng.randint(3, n_requests) if rng.random() < 0.5 else None
    deadline_rid = rng.randrange(n_requests) if rng.random() < 0.5 else None
    install_injector(inj)
    try:
        sup = engine.supervised_serving(
            b_slots=b_slots, page_size=8, max_model_len=64,
            max_queue=max_queue, max_restarts=12, kv_dtype=kv_dtype,
            **tier_kw)
        results = sup.run(copies(deadline_rid), max_ticks=5000)
    finally:
        clear_injector()

    # invariant: none lost — a terminal result per submitted rid
    by_rid = {r.rid: r for r in results}
    assert sorted(by_rid) == sorted(r.rid for r in base), \
        f"serve soak seed={seed}: lost requests " \
        f"{sorted(set(r.rid for r in base) - set(by_rid))}"
    # invariant: completed outputs token-identical to the fault-free run
    parity_checked = 0
    for rid, res in by_rid.items():
        if res.finish_reason in ("eos", "length"):
            assert np.array_equal(res.output_ids, ref[rid]), \
                f"serve soak seed={seed}: rid {rid} diverged after replay"
            parity_checked += 1
        else:
            assert res.finish_reason in ("deadline", "shed"), res.finish_reason
    # invariant: the refcount pool accounting balances after drain — every
    # page is exactly one of free / quarantined / referenced (referenced =
    # prefix-index cache + any surviving slot refs; no leak, no double-free)
    unserved = sup.drain(max_ticks=500)
    assert not unserved, f"serve soak seed={seed}: {len(unserved)} unserved"
    h = sup.health()
    acct = sup.engine.page_accounting()
    assert acct["balanced"], \
        f"serve soak seed={seed}: page accounting broken: {acct} / {h}"
    assert h["free_pages"] + h["quarantined_pages"] + h["referenced_pages"] \
        == sup.engine.num_pages - 1, \
        f"serve soak seed={seed}: page accounting broken: {h}"
    # after drain no slot is active: every referenced page is index-cached
    assert acct["referenced"] == acct["cached"], \
        f"serve soak seed={seed}: leaked slot reference: {acct}"
    if tiered:
        # extended invariants (ISSUE 11): the demoted ledger balances —
        # every demoted index entry has exactly one host buffer (already
        # folded into `balanced`, re-checked explicitly here), the byte
        # gauge agrees with the buffers, and neither quarantine nor the
        # warm restarts stranded a demoted page on either side of the
        # ledger.  Promoted-prefix token exactness is the parity loop
        # above (the reference ran untiered).
        eng = sup.engine
        assert acct["demoted"] == len(eng._tier), \
            f"serve soak seed={seed}: demoted ledger torn: {acct} vs " \
            f"{len(eng._tier)} host buffer(s)"
        assert h["demoted_pages"] == acct["demoted"]
        assert h["host_tier_bytes"] == eng._tier.bytes()
        assert eng._prefix.demoted <= eng._tier.max_pages
        if require_tier_cycles:
            assert h["demotions_total"] > 0 and h["promotions_total"] > 0, \
                f"serve soak seed={seed}: tier never cycled " \
                f"(demotions={h['demotions_total']}, " \
                f"promotions={h['promotions_total']})"
    if tp > 1:
        # sharded extras (ISSUE 10): the mesh the session ran on is
        # visible in health() and the pool's per-device footprint is
        # total/tp — the page-accounting + refcount invariants above
        # already held on the SHARDED pool across every kill/replay
        assert h["mesh_devices"] == tp, \
            f"serve soak seed={seed}: mesh facts wrong: {h['mesh_devices']}"
        assert h["mesh_axes"].get("model") == tp, h["mesh_axes"]
        if kv_dtype is None:
            # replicated scale planes break the exact 1/tp split on a
            # quantized meshed pool (execution.pool_bytes docstring), so
            # the equality is an fp-only invariant
            assert h["kv_pool_bytes_per_device"] * tp \
                == h["kv_pool_bytes_total"], \
                f"serve soak seed={seed}: per-device pool bytes not 1/tp"
    stats = {
        "seed": seed,
        "tp": tp,
        "kv_dtype": kv_dtype or "fp",
        "submitted": len(base),
        "terminal": len(by_rid),
        "parity_checked": parity_checked,
        "faults_fired": len(inj.log),
        "fault_log": inj.log,
        "restarts": sup.restarts,
        "shed": h["shed_total"],
        "deadline_expired": h["deadline_expired_total"],
        "quarantined_slots": h["quarantined_slots"],
        "prefix_hits": h["prefix_hits_total"],
        "cow_copies": h["cow_copies_total"],
        "demotions": h["demotions_total"],
        "promotions": h["promotions_total"],
        "demoted_pages": h["demoted_pages"],
    }
    if verbose:
        print(f"  seed={seed}: OK — {stats['faults_fired']} fault(s) fired, "
              f"{stats['restarts']} restart(s), {stats['shed']} shed, "
              f"{stats['deadline_expired']} expired, "
              f"{parity_checked} parity-checked")
    return stats


def run_fleet_soak(seed: int, coord_dir: str, n_requests: int = 10,
                   n_engines: int = 3, verbose: bool = True,
                   collect_traces: str = None) -> dict:
    """One serving-fleet session under a seeded random kill (docs/FLEET.md).

    The seed draws the victim engine, the router round it dies at, and the
    kill mode — ``lease`` (silent process kill: the lease just stops
    renewing, detection is ``miss_limit`` missed periods on the injected
    store clock) or ``budget`` (injected ``serve.decode`` faults exhaust
    the member's restart budget: it writes a durable ``fleet/dead`` marker
    as a dying breath and failover is immediate).  Half the time a standby
    router is registered and the COORDINATOR is killed a few rounds later:
    the standby must win the next election term, bump the fleet generation
    through the CAS store, adopt the request journal, and finish the
    stream.

    Token journaling runs at ``journal_every_k=2`` so the seeded kill lands
    mid-stream with journaled batches outstanding and failover exercises
    the resume path (ISSUE 8): the replacement re-prefills
    ``prompt + journaled`` and continues AFTER the last journaled token.

    A third of the stream is SAMPLED (ISSUE 9: per-request temperature/
    top-k/top-p lanes with per-request seeds) so kills land on stochastic
    streams too: the journal carries the RNG lane (sampling params +
    counter) and the counter-based key schedule
    (``fold_in(PRNGKey(seed), position)``) must make the resumed sampled
    stream token-identical to the fault-free reference — not merely
    distribution-equal.

    Another third is ADAPTER-TAGGED (ISSUE 19: rotating tenant ids over a
    two-tenant LoRA registry shared by every member) so kills land on
    multi-tenant streams: the journal carries ``adapter_id``, failover
    re-prefills under the SAME adapter on the survivor, and parity
    against the fault-free reference proves the resumed delta-path
    stream is token-identical — a resume under the wrong (or no) adapter
    would diverge at the first continued token.

    ``collect_traces=<dir>`` (ISSUE 15) runs the soak with the tracer ON,
    members publishing span segments every beat, assembles the fleet
    trace at the end (``<dir>/fleet_trace.json``) and asserts the
    distributed-tracing contract: every failed-over COMPLETED stream
    carries one ``trace_id`` end to end, its assembled spans appear on
    BOTH the dead engine's and the survivor's tracks in causal
    (skew-corrected) order, and the victim's pre-kill spans — including
    the decode ticks whose ``slot_rids`` tag names the rid — never
    overlap the survivor's post-failover prefill.

    Invariants asserted: every submitted request reaches a terminal result
    (none lost); completed outputs are token-identical to a fault-free
    single-engine reference run — for resumed streams this proves zero
    duplicated emissions and zero lost tokens, and for sampled resumed
    streams that the journaled lane re-derived the identical key at every
    continuation position; every surviving engine's
    refcount page accounting balances; the dead engine is visibly dead
    through the store (lapsed lease or dead marker); every journal entry
    is GC'd once its result is collected (even by a freshly elected
    standby); the fleet generation is strictly monotonic across
    coordinator terms.
    """
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.elasticity import (FileCoordinationStore, dead_set,
                                          lease_table, read_generation)
    from deepspeed_tpu.inference.fleet import FleetMember, FleetRouter
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.resilience import (FaultInjector, clear_injector,
                                          install_injector)
    from deepspeed_tpu.resilience.fault_injection import SITE_SERVE_DECODE

    rng = Random(seed)
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)

    nprng = np.random.default_rng(seed)
    # half the stream shares a seeded system prompt so kills land on
    # refcounted shared pages (the per-engine prefix index path)
    system = nprng.integers(1, model.config.vocab_size, 11).astype(np.int32)

    def prompt(i):
        if i % 2 == 0:
            uniq = nprng.integers(1, model.config.vocab_size,
                                  int(nprng.integers(2, 6))).astype(np.int32)
            return np.concatenate([system, uniq])
        return nprng.integers(1, model.config.vocab_size,
                              int(nprng.integers(3, 14))).astype(np.int32)

    from deepspeed_tpu.inference.sampling import SamplingParams

    def lane(i):
        # every third request is sampled: per-request seed, rotating
        # temperature/top-k/top-p mix — kills must land on stochastic
        # streams with journaled RNG-lane state outstanding
        if i % 3 != 1:
            return None
        return SamplingParams(temperature=0.8 if i % 2 else 1.2,
                              top_k=0 if i % 6 == 1 else 12,
                              top_p=0.9, seed=500 + i)

    # two-tenant LoRA registry shared by every member AND the reference:
    # rotating adapter ids tag roughly a third of the stream, so seeded
    # kills land on multi-tenant slots with journaled deltas outstanding
    from deepspeed_tpu.inference.adapters import AdapterRegistry
    from deepspeed_tpu.runtime.lora import LoRAConfig

    reg = AdapterRegistry(params["layers"])
    for t_i, aid in enumerate(("acme", "globex")):
        cfg = LoRAConfig(rank=4, alpha=8.0)
        trng = np.random.default_rng(seed * 100 + t_i)
        lora = {}
        for t in cfg.targets:
            L, d_in, d_out = (int(s) for s in np.shape(params["layers"][t]))
            lora[t] = {"A": trng.standard_normal(
                           (L, d_in, 4)).astype(np.float32) * 0.5,
                       "B": trng.standard_normal(
                           (L, 4, d_out)).astype(np.float32) * 0.05}
        reg.register(aid, lora, cfg)

    def adapter(i):
        if i % 3 != 2:
            return None
        return ("acme", "globex")[(i // 3) % 2]

    base = [Request(rid=i, input_ids=prompt(i),
                    max_new_tokens=int(nprng.choice((4, 6, 8))),
                    sampling=lane(i), adapter_id=adapter(i))
            for i in range(n_requests)]

    def copies():
        return [Request(rid=r.rid, input_ids=r.input_ids,
                        max_new_tokens=r.max_new_tokens,
                        sampling=r.sampling, adapter_id=r.adapter_id)
                for r in base]

    # fault-free single-engine reference (greedy AND sampled outputs are
    # engine-independent: counter-based lane keys are pure functions of
    # (seed, position), so one reference serves every failover schedule;
    # the same registry makes adapter-tagged outputs engine-independent
    # too — the batched delta is a pure function of the tenant's factors)
    ref_serve = engine.serving(b_slots=3, page_size=8, max_model_len=64,
                               adapters=reg)
    ref = {r.rid: r.output_ids for r in ref_serve.run(copies())}
    del ref_serve

    if collect_traces:
        # tracing goes on AFTER the reference run (its spans are nobody's)
        from deepspeed_tpu.observability import configure_tracer, get_tracer

        configure_tracer(enabled=True, capacity=1 << 16)
        get_tracer().reset()

    try:
        victim = f"engine{rng.randrange(n_engines)}"
        kill_mode = rng.choice(("lease", "budget"))
        kill_round = rng.randint(2, 6)
        kill_coordinator = rng.random() < 0.5
        coord_kill_round = kill_round + rng.randint(1, 3)

        LEASE_S, MISS = 1.0, 3
        clock_box = [0.0]
        store = FileCoordinationStore(coord_dir, clock=lambda: clock_box[0])

        serve_kw = dict(b_slots=2, page_size=8, max_model_len=64,
                        adapters=reg)
        members = [FleetMember(f"engine{i}",
                               engine.supervised_serving(
                                   max_restarts=0 if kill_mode == "budget"
                                   else 5, **serve_kw),
                               store, lease_s=LEASE_S)
                   for i in range(n_engines)]
        # the router election lease rides the same injected clock: long enough
        # that +1/round clock ticks never depose a LIVE router (it renews every
        # round), short enough that a killed one is succeeded within the soak
        ROUTER_LEASE = 30.0
        # journal every 2 rounds: the kill (rounds 2-6) lands with journaled
        # batches outstanding, so failover must RESUME, not re-decode
        router = FleetRouter(store, members, router_id="router0",
                             lease_s=ROUTER_LEASE, miss_limit=MISS,
                             journal_every_k=2)
        standby = (FleetRouter(store, members, router_id="router1",
                               lease_s=ROUTER_LEASE, miss_limit=MISS,
                               journal_every_k=2)
                   if kill_coordinator else None)
        if collect_traces:
            # every beat publishes (no real-clock rate limit): the kill must
            # land with the victim's spans already durable on the store
            for m in members:
                m.trace_publish_interval_s = 0.0
            router.trace_publish_interval_s = 0.0
            if standby is not None:
                standby.trace_publish_interval_s = 0.0

        inj = FaultInjector()
        if kill_mode == "budget":
            # with max_restarts=0, the first decode fault on the victim's turn
            # exhausts its budget — the seed picks WHEN, scheduling picks whom
            # (attributed post-hoc below)
            inj.add(site=SITE_SERVE_DECODE, kind="raise",
                    at_call=rng.randint(3, 3 * n_engines))
        install_injector(inj)

        gens = []
        state = {"victim_killed": False}

        def on_tick(r, rounds):
            clock_box[0] += 1.0
            gens.append(read_generation(store, key=r.generation_key))
            if kill_mode == "lease" and rounds == kill_round \
                    and not state["victim_killed"]:
                r.members[victim].kill()
                state["victim_killed"] = True
            if kill_coordinator and rounds == coord_kill_round and r.alive \
                    and r is router:
                r.kill()

        try:
            try:
                results = router.run(copies(), max_ticks=4000, on_tick=on_tick)
            except RuntimeError:
                # the coordinator was killed mid-run (its own step() raising is
                # the in-process stand-in for the process dying): the standby
                # must win the next term and converge the stream
                if not (kill_coordinator and not router.alive):
                    raise
                results = list(router.take_results())
                results += standby.run([], max_ticks=4000, on_tick=on_tick)
        finally:
            clear_injector()

        live_router = standby if (standby is not None
                                  and standby.is_coordinator) else router
        # invariant: none lost — a terminal result per submitted rid
        by_rid = {r.rid: r for r in results}
        assert sorted(by_rid) == sorted(r.rid for r in base), \
            f"fleet soak seed={seed}: lost requests " \
            f"{sorted(set(r.rid for r in base) - set(by_rid))}"
        # invariant: completed outputs token-identical to the reference — for
        # resumed streams (journaled prefix + decoded continuation) equality
        # proves no token was duplicated at the stitch and none was lost
        parity_checked = resumed_results = resumed_tokens = 0
        sampled_parity_checked = sampled_resumed_results = 0
        adapter_parity_checked = adapter_resumed_results = 0
        sampled_rids = {r.rid for r in base if r.sampling is not None}
        adapter_rids = {r.rid: r.adapter_id for r in base
                        if r.adapter_id is not None}
        for rid, res in by_rid.items():
            if res.finish_reason in ("eos", "length"):
                assert np.array_equal(res.output_ids, ref[rid]), \
                    f"fleet soak seed={seed}: rid {rid} diverged after failover"
                parity_checked += 1
                if rid in sampled_rids:
                    sampled_parity_checked += 1
                if rid in adapter_rids:
                    adapter_parity_checked += 1
                    # the tenant identity survives the journal round-trip
                    assert res.adapter_id == adapter_rids[rid], \
                        f"fleet soak seed={seed}: rid {rid} finished under " \
                        f"{res.adapter_id!r}, submitted {adapter_rids[rid]!r}"
                if res.resumed_tokens:
                    resumed_results += 1
                    resumed_tokens += res.resumed_tokens
                    if rid in sampled_rids:
                        sampled_resumed_results += 1
                    if rid in adapter_rids:
                        adapter_resumed_results += 1
                    assert res.resumed_tokens <= len(res.output_ids), res
            else:
                assert res.finish_reason in ("deadline", "shed"), \
                    res.finish_reason
        # invariant: surviving engines' page accounting balances
        for eid, m in live_router.members.items():
            if m.alive:
                acct = m.sup.engine.page_accounting()
                assert acct["balanced"], \
                    f"fleet soak seed={seed}: {eid} accounting broken: {acct}"
        # invariant: the dead engine is visibly dead through the store
        dead_ids = live_router._failed_engines
        if kill_mode == "budget":
            assert dead_ids, f"fleet soak seed={seed}: budget kill never landed"
        for eid in dead_ids:
            marked = eid in dead_set(store, prefix="fleet/dead")
            lease = lease_table(store, prefix="fleet/heartbeat").get(eid)
            lapsed = lease is None or lease.missed(clock_box[0]) >= MISS
            assert marked or lapsed, \
                f"fleet soak seed={seed}: {eid} failed over while visibly alive"
        if kill_mode == "lease":
            assert victim in dead_ids, \
                f"fleet soak seed={seed}: killed {victim} never declared dead"
        if not kill_coordinator:
            # one router saw every failover, so its counter must equal the sum
            # of the per-result stamps (across a takeover the stamps survive
            # via the journal but the counter is per-router, so the equality
            # only holds when the coordinator survived)
            assert router.failovers_total == \
                sum(r.failovers for r in by_rid.values()), \
                f"fleet soak seed={seed}: failover accounting mismatch"
        # invariant: fleet generation monotonic across coordinator terms
        assert all(b >= a for a, b in zip(gens, gens[1:])), \
            f"fleet soak seed={seed}: generation not monotonic: {gens}"
        if kill_coordinator:
            assert standby.is_coordinator and standby.term == 2, \
                f"fleet soak seed={seed}: election never converged " \
                f"(term {standby.term})"
        # invariant: every journal entry was GC'd once its result was
        # collected — including by a freshly elected standby (the stream is
        # done, so a surviving entry would be a leak the next takeover adopts)
        leftover = store.list("fleet/requests")
        assert not leftover, \
            f"fleet soak seed={seed}: journal entries leaked: {leftover}"
        trace_stats = {}
        if collect_traces:
            trace_stats = _fleet_trace_checks(
                seed, collect_traces, store, live_router,
                [r for r in (router, standby) if r is not None],
                list(by_rid.values()), set(dead_ids), kill_mode)
        stats = {
            "seed": seed,
            "submitted": len(base),
            "terminal": len(by_rid),
            "parity_checked": parity_checked,
            "kill_mode": kill_mode,
            "victim": victim,
            "killed_coordinator": kill_coordinator,
            "dead_engines": sorted(dead_ids),
            "failovers": live_router.failovers_total,
            "resumed_results": resumed_results,
            "resumed_tokens": resumed_tokens,
            "sampled_parity_checked": sampled_parity_checked,
            "sampled_resumed_results": sampled_resumed_results,
            "adapter_tagged": len(adapter_rids),
            "adapter_parity_checked": adapter_parity_checked,
            "adapter_resumed_results": adapter_resumed_results,
            "faults_fired": len(inj.log),
            "final_term": live_router.term,
            "final_generation": live_router.generation,
            **trace_stats,
        }
        if verbose:
            print(f"  seed={seed}: OK — kill={kill_mode}({victim}"
                  f"{'+coordinator' if kill_coordinator else ''}), "
                  f"{stats['failovers']} failover(s), "
                  f"{resumed_tokens} resumed token(s), "
                  f"term {stats['final_term']}, {parity_checked} parity-checked")
        return stats
    finally:
        if collect_traces:
            # a failing invariant must never leak an enabled global
            # tracer into the caller (the checks helper also disables
            # on its own path; double-disable is harmless)
            from deepspeed_tpu.observability import (configure_tracer,
                                                     get_tracer)

            configure_tracer(enabled=False)
            get_tracer().reset()


def _fleet_trace_checks(seed: int, out_dir: str, store, live_router,
                        routers, results, dead_ids, kill_mode) -> dict:
    """Assemble the soaked fleet's published trace and assert the
    distributed-tracing contract (ISSUE 15 acceptance): a killed engine's
    failed-over stream is ONE trace_id whose assembled spans cover BOTH
    the dead engine's and a survivor's tracks, causally ordered after
    skew correction, with the victim's pre-kill spans (admissions plus
    the decode ticks naming the rid through ``slot_rids``) strictly
    before the survivor's post-failover prefill.  The tracer is disabled
    before the assertions run, so a failing check never leaks an enabled
    global tracer into the caller."""
    import os

    from deepspeed_tpu.observability import configure_tracer, get_tracer
    from deepspeed_tpu.observability.trace_assembly import (
        assemble_fleet_trace, events_for_trace, load_segments)

    os.makedirs(out_dir, exist_ok=True)
    try:
        for m in live_router.members.values():
            if m.alive:
                m.publish_trace_segments(force=True)
        for r in routers:
            r.publish_trace_segments(force=True)
        path = os.path.join(out_dir, "fleet_trace.json")
        doc = assemble_fleet_trace(load_segments(store), out_path=path)
    finally:
        configure_tracer(enabled=False)
        get_tracer().reset()
    owners = doc["otherData"]["owners"]
    pid_of = {o: i for i, o in enumerate(owners, start=1)}
    dead_pids = {pid_of[e] for e in dead_ids if e in pid_of}
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    checked = two_track = 0
    for res in results:
        if not res.failovers or res.finish_reason not in ("eos", "length"):
            continue
        tid = res.trace_id
        assert tid, (f"fleet soak seed={seed}: failed-over rid {res.rid} "
                     "carries no trace_id")
        if any(e[0] == "finish" and e[2] == "journal" for e in res.lifecycle):
            # finished straight from the journal (_finish_from_journal):
            # the stream completed on the victim before the kill and was
            # never re-served — there is no survivor span to order against
            continue
        evs = events_for_trace(doc, tid)
        rid_s = str(res.rid)
        victim_evs = [e for e in evs if e["pid"] in dead_pids]
        victim_decodes = [
            e for e in spans
            if e["pid"] in dead_pids
            and e["name"] in ("serve.decode", "serve.tick")
            and rid_s in (((e.get("args") or {}).get("slot_rids") or {})
                          .values())]
        if not victim_evs and not victim_decodes:
            # the kill landed before the victim's first segment publish
            # (possible in budget mode when the injected fault fires in
            # the very first pumped round) — nothing durable to order
            continue
        survivor_evs = [e for e in evs if e["pid"] not in dead_pids]
        assert survivor_evs, \
            f"fleet soak seed={seed}: trace {tid} has no survivor spans"
        checked += 1
        if victim_evs:
            two_track += 1
        pre_end = max(e["ts"] + e["dur"]
                      for e in victim_evs + victim_decodes)
        post_prefills = [e for e in survivor_evs
                         if e["name"] == "serve.prefill"]
        assert post_prefills, (f"fleet soak seed={seed}: trace {tid} has "
                               "no post-failover prefill on a survivor")
        post_start = min(e["ts"] for e in post_prefills)
        assert pre_end <= post_start, \
            (f"fleet soak seed={seed}: trace {tid} pre-kill spans overlap "
             f"the post-failover prefill ({pre_end:.1f}us > "
             f"{post_start:.1f}us after skew correction)")
    if kill_mode == "lease":
        # a lease kill always lands past round 2, i.e. past a publishing
        # beat: the strong two-track assertion must have had material
        assert checked > 0, \
            (f"fleet soak seed={seed}: no failed-over completed stream "
             "had durable victim spans to order")
    return {
        "trace_path": path,
        "trace_owners": owners,
        "trace_rids_checked": checked,
        "trace_two_track_rids": two_track,
        "trace_spans_assembled": len(spans),
    }


def run_pod_soak(seed: int, total_steps: int = 12, ckpt_every: int = 2,
                 ckpt_dir: str = "", coord_dir: str = "", n_hosts: int = 4,
                 verbose: bool = True, replica_every_k: int = 0,
                 scenario: str = None) -> dict:
    """One simulated pod session under a seeded host kill (docs/POD.md).

    The coordinator ("host0") runs in the calling thread with a REAL engine
    on the virtual CPU mesh under a :class:`PodElasticAgent`; peer hosts
    are threads that rendezvous, heartbeat, and take part in the all-hosts
    checkpoint commit (shard file + per-host manifest).  Lease expiry runs
    on an injected store clock advanced one tick per training step, so
    detection latency is measured in *steps*, deterministic across
    machines.  The seed draws the victim host, the kill step, and the kill
    mode:

    - ``step``: the victim silently stops heartbeating at a step — peers
      detect ``miss_limit`` missed leases and exit for re-formation;
    - ``mid_commit``: the victim dies during a pod checkpoint after its
      shard but before its manifest — the pod commit times out, the tag
      stays TORN, and the next round must quarantine it and fall back.

    Invariants asserted: the supervisor converges (rc 0) at a SHRUNKEN
    slice whose batch triad matches ``compute_elastic_config`` for the
    healthy host count; the final checkpoint is pod-committed and
    verifies; every surviving tag is pod-committed (torn ones quarantined,
    when the kill produced one); re-executed steps reproduce their
    original losses (continuity).

    **Replica scenarios** (ISSUE 20, docs/POD.md "Live-state recovery").
    ``replica_every_k > 0`` turns on the in-RAM replica layer: the
    coordinator seals real ``engine.replica_snapshot()`` slabs through a
    :class:`HostReplicator` and announces each sealed boundary
    (``announce_replica_round``); peers poll the announcement and publish
    their own (simulated) shard slabs — a consistent cut every k steps.
    ``scenario`` picks the seeded kill shape (all silent lease-stops,
    recorded through a :class:`RecordingStore` whose history is replayed
    by ``store_check.check_history`` — verdict must be clean):

    - ``buddy_kill``: one victim dies off-boundary — the next round
      ADOPTS the last sealed cut (rollback <= k, strictly better than
      the checkpoint-restart baseline on the same schedule);
    - ``double_kill``: the victim AND its ring buddy die — the buddy's
      replica RAM died with it, so adoption refuses and the round falls
      back to checkpoint restart;
    - ``mid_seal``: the victim dies mid-seal (snapshot taken, publish
      never lands) — the PREVIOUS replica wins the cut;
    - ``corrupt_slab``: every slab the victim publishes fails its
      checksum — no verifiable cut, checkpoint fallback.

    Scenario runs add ``rollback_steps`` / ``recovery_wall_s`` /
    ``replica_adoptions`` / ``replica_fallbacks`` / ``store_check_ok``
    to the stats dict.  ``scenario=None, replica_every_k=0`` is exactly
    the legacy soak (pinned seeds stay byte-identical).
    """
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import deepspeed_tpu
    from deepspeed_tpu.elasticity import (FileCoordinationStore,
                                          HeartbeatWatchdog, HostReplicator,
                                          POD_ADOPT_PREFIX, PodContext,
                                          PodElasticAgent, PodPeerLost,
                                          PodSupervisor,
                                          announce_replica_round, buddy_ring,
                                          compute_elastic_config, lease_table,
                                          pending_commit,
                                          pending_replica_round, publish_replica,
                                          record_dead, rendezvous,
                                          replica_adoptions_total,
                                          replica_fallbacks_total, seal_entry)
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.resilience import (PodCommitTimeout,
                                          pod_checkpoint_progress_fn,
                                          pod_committed, candidate_tags,
                                          verify_pod_checkpoint_dir,
                                          write_host_manifest)
    from deepspeed_tpu.runtime.config import ElasticityConfig
    from unit.simple_model import SimpleModel, make_config, random_batch

    rng = Random(seed)
    hosts = [f"host{i}" for i in range(n_hosts)]
    victim = hosts[rng.randrange(1, n_hosts)]   # host0 owns the engine
    kill_mode = rng.choice(("step", "mid_commit"))
    kill_step = rng.randint(ckpt_every, max(ckpt_every, total_steps - 6))
    kill_commit = rng.randint(1, 2)
    kill_set: set = set()
    ring = buddy_ring(hosts)
    if scenario is not None:
        assert scenario in ("buddy_kill", "double_kill", "mid_seal",
                            "corrupt_slab"), f"unknown scenario {scenario!r}"
        assert replica_every_k > 0 or scenario == "buddy_kill", \
            f"scenario {scenario!r} needs replica_every_k > 0 (only " \
            "buddy_kill has a replica_every_k=0 checkpoint-baseline leg)"
        kill_mode = scenario
        if scenario == "double_kill" and ring[victim] == "host0":
            # the buddy must be killable (host0 owns the engine and the
            # calling thread): remap the drawn victim deterministically
            victim = hosts[1]
        # schedule normalization, deliberately INDEPENDENT of
        # replica_every_k so the adoption run and its k=0 checkpoint
        # baseline see the IDENTICAL kill schedule: the kill lands off
        # the (cadence-2) replica boundary AND off the checkpoint
        # boundary, so both rollbacks are nonzero and comparable
        kill_step = max(kill_step, 5)
        while kill_step % 2 == 0 or kill_step % max(ckpt_every, 1) == 0:
            kill_step += 1
        kill_set = ({victim, ring[victim]} if scenario == "double_kill"
                    else {victim})
    # the last replica boundary at/under the kill; mid_seal's victim dies
    # sealing exactly this one, so the previous boundary wins the cut
    skip_from = ((kill_step // replica_every_k) * replica_every_k
                 if replica_every_k > 0 else 0)
    # commit timeout 5s: peers respond in ~10ms on an idle machine, and
    # the torn-commit rounds (which always burn the full timeout) stay
    # cheap enough for the tier-1 seeds that import this harness.  It is a
    # WALL-clock wait (the store clock stands still inside it), so it is the
    # one margin the lockstep below cannot replace: at 2s a tier-1 run under
    # six loaded workers named a LIVE peer missing beside the victim (PR 55's
    # check: "dead: ['host1', 'host3']"), which turns a buddy's adoption
    # into a fallback whenever that peer is the victim's buddy
    LEASE_S, MISS, COMMIT_TIMEOUT = 1.0, 2, 5.0
    if scenario is not None:
        # scenario kills must be detected at the next pod-commit barrier:
        # its timeout names EVERY missing host at once.  Lease expiry
        # rides the per-step store clock, so a double-kill's two expiries
        # can straddle one tick and flag a single victim — the round
        # would then re-form around a dead-but-unmarked buddy and adopt
        # from its (durably published) slab instead of falling back.  A
        # tolerance past the final tick keeps the watchdog quiet.
        MISS = 10

    clock_box = [0.0]   # fake store clock: +1 per coordinator train step
    store = FileCoordinationStore(coord_dir, clock=lambda: clock_box[0])
    rec = None
    if scenario is not None:
        # record every client's store ops so the replica protocol history
        # (seals, dead markers, adoption claims) can be replayed against
        # store_check's invariants — including the adoption fence rules
        from store_check import RecordingStore, check_history

        rec = RecordingStore(store, client="host0")
        store = rec

    def store_for(host):
        return rec.handle(host) if rec is not None else store
    ec = ElasticityConfig(enabled=True, max_train_batch_size=16,
                          micro_batch_sizes=[2, 4], min_gpus=1,
                          max_gpus=n_hosts)

    def shard_writer(tag_dir, host_id):
        rel = os.path.join("shards", f"{host_id}.bin")
        path = os.path.join(tag_dir, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(f"{host_id} shard of {os.path.basename(tag_dir)}\n"
                    .encode() * 8)
        return [rel]

    loss_log: dict = {}
    continuity = {"checked": 0}
    killed = {"done": False, "at_step": None}
    killed_hosts: set = set()
    torn_tags: list = []
    resumes: list = []           # per-round (adopted_step, resumed_step)
    recovery = {"fail_t": None, "wall_s": None}
    # lockstep in the wall clock's place (``settle``): the peers whose
    # threads run, the whole scans each has made, the newest replica
    # boundary the coordinator has announced
    alive: set = set()
    scans: dict = {}
    announced = [0]
    adoptions0 = replica_adoptions_total()
    fallbacks0 = replica_fallbacks_total()

    def peer_main(host, members, gen, stop_evt):
        """One simulated peer host: rendezvous, heartbeat, commit shards
        for every tag the coordinator announces for this generation, and
        (replica scenarios) publish this host's shard slab at every
        boundary the coordinator announces sealed."""
        pstore = store_for(host)
        alive.add(host)
        dead_flag: list = []
        # grace disabled: detection in the sim is lease EXPIRY on the fake
        # clock, never "host absent" races during real-time round setup
        wd = HeartbeatWatchdog(pstore, host, gen, list(members),
                               lease_s=LEASE_S, miss_limit=MISS,
                               on_peer_dead=dead_flag.append, renew_s=0.01,
                               grace_beats=10 ** 6)
        rendezvous(pstore, host, gen, list(members), timeout_s=10.0)
        wd.start()
        handled: set = set()
        sealed: set = set()
        try:
            # scenario runs: survivors do NOT bail the instant their
            # watchdog flags the victim — a live host keeps serving the
            # round's commits and replica seals until the coordinator
            # tears the round down (stop_evt), exactly so the post-kill
            # checkpoint boundary can't misread every peer as dead
            while not stop_evt.is_set() and (scenario is not None
                                             or not dead_flag):
                if (host in kill_set and host not in killed_hosts
                        and scenario != "mid_seal"):
                    lease = lease_table(pstore).get("host0")
                    if lease and lease.attrs.get("step", 0) >= kill_step:
                        killed_hosts.add(host)
                        if killed.get("at_step") is None:
                            killed["at_step"] = int(
                                lease.attrs.get("step", 0))
                        return   # silent death: the lease just stops
                if (kill_mode == "step" and host == victim
                        and not killed["done"]):
                    lease = lease_table(pstore).get("host0")
                    if lease and lease.attrs.get("step", 0) >= kill_step:
                        killed["done"] = True
                        return   # silent death: the lease just stops
                if replica_every_k > 0:
                    rstep = pending_replica_round(pstore, gen)
                    if rstep is not None and rstep not in sealed:
                        sealed.add(rstep)
                        if (scenario == "mid_seal" and host == victim
                                and rstep >= skip_from):
                            # mid-seal death: the snapshot was taken but
                            # the publish never lands — the previous
                            # replica must win the next round's cut
                            killed_hosts.add(host)
                            if killed.get("at_step") is None:
                                killed["at_step"] = int(rstep)
                            return
                        payload = (f"{host} shard-state step {rstep} "
                                   f"gen {gen}\n").encode() * 8
                        entry = seal_entry(payload, rstep, gen)
                        if scenario == "corrupt_slab" and host == victim:
                            # sealed checksum lies about the payload: no
                            # entry of this host's slab ever verifies
                            entry["sha256"] = "0" * 64
                        publish_replica(pstore, host, entry,
                                        buddy=buddy_ring(members).get(host))
                tag = pending_commit(pstore, gen)
                if tag is not None and tag not in handled:
                    handled.add(tag)
                    tag_dir = os.path.join(ckpt_dir, tag)
                    files = shard_writer(tag_dir, host)
                    if (kill_mode == "mid_commit" and host == victim
                            and len(handled) >= kill_commit
                            and not killed["done"]):
                        # die after the shard, before the manifest: the
                        # pod commit of this tag can never complete
                        killed["done"] = True
                        torn_tags.append(tag)
                        return
                    step = int(tag.replace("global_step", "") or -1) \
                        if tag.startswith("global_step") else -1
                    write_host_manifest(tag_dir, host, gen, step,
                                        files=files)
                scans[host] = scans.get(host, 0) + 1
                time.sleep(0.005)
        finally:
            alive.discard(host)
            wd.stop()

    def attempt(rnd):
        members = list(rnd.hosts)
        stop_evt = threading.Event()
        peers = [threading.Thread(target=peer_main, name=f"pod-sim-{h}",
                                  args=(h, members, rnd.generation, stop_evt),
                                  daemon=True)
                 for h in members if h != "host0"]
        for t in peers:
            t.start()
        mesh_mod.reset_mesh()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(16), config=make_config(batch_size=16))
        dead_seen: list = []
        wd0 = HeartbeatWatchdog(store, "host0", rnd.generation, members,
                                lease_s=LEASE_S, miss_limit=MISS,
                                on_peer_dead=dead_seen.append, renew_s=0.01,
                                grace_beats=10 ** 6)
        ctx = PodContext(store, "host0", members, rnd.generation,
                         lease_s=LEASE_S, miss_limit=MISS,
                         commit_timeout_s=COMMIT_TIMEOUT,
                         shard_writer=shard_writer,
                         replica_every_k=replica_every_k)
        replicator = None
        adopt_kw = {}
        if replica_every_k > 0:
            # the coordinator seals REAL engine slabs; each publish
            # announces the boundary so the (simulated) peers seal the
            # same consistent cut.  Adoption args only flow with the
            # layer on — the k=0 run is the pure checkpoint baseline.
            replicator = HostReplicator(
                store, "host0", rnd.generation, members,
                snapshot_fn=engine.replica_snapshot,
                replica_every_k=replica_every_k,
                on_sealed=lambda s, g=rnd.generation: (
                    announce_replica_round(store, g, s),
                    announced.__setitem__(0, s)))
            adopt_kw = dict(adopt_prev_hosts=rnd.prev_hosts,
                            adopt_dead=rnd.dead)
        agent = PodElasticAgent(engine, ckpt_dir, ctx, watchdog=wd0,
                                replicator=replicator,
                                ckpt_every=ckpt_every, **adopt_kw)

        first_step: list = []

        def settle(i, timeout_s=60.0):
            """Before step ``i`` runs, every peer has seen what the steps
            before it published, however the machine schedules the
            threads: the replica boundary due by now is announced (the
            replicator publishes on a thread of its own), the
            coordinator's lease carries its step (beaten here, not left to
            the heartbeat thread's cadence), and every peer whose thread
            runs has made two whole scans since (the first may have read
            the lease before the beat).  A victim due at step ``i`` has
            then died before step ``i`` runs and a live peer has sealed
            the boundary and served the commit it was shown: the kill
            schedule is the seed's, in steps.  A fixed sleep a step stood
            here; under six loaded tier-1 workers the coordinator ran four
            steps before the victim's thread read its lease once, the
            victim sealed the boundary past its kill step and the round
            adopted step 8 where the schedule said 6 (PR 55's check)."""
            def wait(ready, what):
                deadline = time.monotonic() + timeout_s
                while not ready():
                    assert time.monotonic() < deadline, (
                        f"pod soak seed={seed}: step {i} waited "
                        f"{timeout_s:.0f}s for {what}")
                    time.sleep(0.002)

            if not first_step:
                first_step.append(i)
            k = replica_every_k
            due = i // k * k if k > 0 else 0
            if due > first_step[0]:     # sealed by this round, not the last
                wait(lambda: announced[0] >= due,
                     f"the replica boundary {due} to be announced")
            wd0.beat_once()
            base = dict(scans)
            wait(lambda: all(scans.get(h, 0) >= base.get(h, 0) + 2
                             for h in tuple(alive)),
                 "two scans of every peer (peers: "
                 f"{sorted(alive)}, scans: {scans})")

        def step_fn(eng, i):
            settle(i)
            if recovery["fail_t"] is not None and recovery["wall_s"] is None:
                recovery["wall_s"] = time.monotonic() - recovery["fail_t"]
            loss = float(eng.train_batch(batch=random_batch(16, 16, seed=i)))
            if i in loss_log:
                assert abs(loss - loss_log[i]) < 1e-4, \
                    f"pod soak seed={seed}: loss continuity broken at " \
                    f"step {i}: {loss} != {loss_log[i]}"
                continuity["checked"] += 1
            loss_log[i] = loss
            clock_box[0] += 1.0   # one store-clock tick per step

        try:
            rendezvous(store, "host0", rnd.generation, members,
                       timeout_s=10.0)
            wd0.start()
            last = agent.run(step_fn, total_steps)
            return 0 if last >= total_steps else 75
        except PodPeerLost:
            if recovery["fail_t"] is None:
                recovery["fail_t"] = time.monotonic()
            return 87
        except PodCommitTimeout as e:
            if recovery["fail_t"] is None:
                recovery["fail_t"] = time.monotonic()
            # the store clock is frozen while we block in the commit wait
            # (it only advances on train steps), so lease expiry cannot
            # flag the dead writer here — but the commit protocol itself
            # just did: the host that never reported its shard within the
            # (generous) timeout is the casualty.  Record it for the next
            # round's re-plan.
            for h in e.missing:
                if h != "host0":
                    record_dead(store, h, rnd.generation, "host0")
            return 87
        finally:
            wd0.stop()
            agent.guard.uninstall()
            resumes.append({"adopted": agent.adopted_step,
                            "resumed": agent.resumed_step})
            stop_evt.set()
            for t in peers:
                t.join(timeout=10.0)

    sup = PodSupervisor(store, ec, attempt, hosts, max_restarts=8,
                        backoff_s=0,
                        progress_fn=pod_checkpoint_progress_fn(ckpt_dir),
                        zero_progress_limit=4, seed=seed)
    rc = sup.run()

    assert rc == 0, f"pod soak seed={seed}: supervisor exited rc={rc} " \
                    f"(diagnosis: {sup.diagnosis})"
    progress = pod_checkpoint_progress_fn(ckpt_dir)()
    assert progress == total_steps, \
        f"pod soak seed={seed}: pod-committed step {progress}, " \
        f"wanted {total_steps}"
    # the job shrank to the largest healthy slice and its planned triad
    assert len(sup.rounds) >= 2, "the kill never forced a re-formation"
    final = sup.rounds[-1]
    assert victim not in final.hosts
    expect_hosts, expect_plan = len(final.hosts), final.plan
    ref_plan = compute_elastic_config(ec, expect_hosts)
    assert expect_plan.as_triad() == ref_plan.as_triad()
    # every surviving tag is pod-committed; torn tags ended quarantined
    newest = candidate_tags(ckpt_dir)[0]
    verify_pod_checkpoint_dir(os.path.join(ckpt_dir, newest))
    for tag in candidate_tags(ckpt_dir):
        assert pod_committed(os.path.join(ckpt_dir, tag)), \
            f"pod soak seed={seed}: uncommitted tag {tag} survived"
    quarantined = sorted(d for d in os.listdir(ckpt_dir) if ".corrupt" in d)
    for torn in torn_tags:
        # the torn incarnation was quarantined by the next round's sweep;
        # the tag NAME may exist again only as a fresh pod-committed
        # re-save of the same step
        p = os.path.join(ckpt_dir, torn)
        assert not os.path.isdir(p) or pod_committed(p), \
            f"pod soak seed={seed}: torn tag {torn} survived uncommitted"
    if torn_tags:
        assert quarantined, \
            f"pod soak seed={seed}: torn tag(s) {torn_tags} never quarantined"
    stats = {
        "seed": seed, "victim": victim, "kill_mode": kill_mode,
        "kill_step": kill_step, "kill_commit": kill_commit,
        "rounds": len(sup.rounds), "final_hosts": expect_hosts,
        "final_triad": expect_plan.as_triad(),
        "continuity_checked": continuity["checked"],
        "quarantined": quarantined, "final_step": progress,
    }
    if scenario is not None:
        adoptions = replica_adoptions_total() - adoptions0
        fallbacks = replica_fallbacks_total() - fallbacks0
        assert killed["at_step"] is not None, \
            f"pod soak seed={seed}: the {scenario} kill never triggered"
        r2 = resumes[1] if len(resumes) > 1 else {"adopted": None,
                                                 "resumed": 0}
        landing = (r2["adopted"] if r2["adopted"] is not None
                   else r2["resumed"])
        # rollback measured against the kill schedule (the victim's last
        # participating step), not against the sim-artifact solo steps
        # the coordinator runs while detection latency elapses
        rollback = kill_step - int(landing)
        if replica_every_k == 0:
            # checkpoint-baseline leg of the recovery compare: the layer
            # is off, so the round restarts from the newest pod-committed
            # tag — same kill schedule, checkpoint-grained rollback
            assert adoptions == 0 and fallbacks == 0
            assert r2["adopted"] is None
            assert int(r2["resumed"]) % max(ckpt_every, 1) == 0, \
                f"pod soak seed={seed}: baseline leg resumed at " \
                f"{r2['resumed']}, not a checkpoint boundary"
        elif scenario in ("buddy_kill", "mid_seal"):
            expect_cut = ((kill_step // replica_every_k) * replica_every_k
                          if scenario == "buddy_kill"
                          else skip_from - replica_every_k)
            assert adoptions == 1 and fallbacks == 0, \
                f"pod soak seed={seed}: {scenario} expected exactly one " \
                f"adoption (got {adoptions} adoptions, {fallbacks} " \
                "fallbacks)"
            assert r2["adopted"] == expect_cut, \
                f"pod soak seed={seed}: {scenario} adopted step " \
                f"{r2['adopted']}, wanted the sealed cut {expect_cut}"
            bound = (replica_every_k if scenario == "buddy_kill"
                     else 2 * replica_every_k)
            assert 0 < rollback <= bound, \
                f"pod soak seed={seed}: {scenario} rolled back " \
                f"{rollback} step(s), bound {bound}"
            assert continuity["checked"] > 0, \
                f"pod soak seed={seed}: adoption resumed without a " \
                "single loss-continuity recheck"
        else:   # double_kill / corrupt_slab: loud checkpoint fallback
            assert r2["adopted"] is None and fallbacks >= 1, \
                f"pod soak seed={seed}: {scenario} must fall back to " \
                f"checkpoint restart (adopted={r2['adopted']}, " \
                f"fallbacks={fallbacks})"
            assert adoptions == 0
            assert int(r2["resumed"]) % max(ckpt_every, 1) == 0, \
                f"pod soak seed={seed}: checkpoint fallback resumed at " \
                f"{r2['resumed']}, not a checkpoint boundary"
        if scenario == "double_kill":
            assert ring[victim] not in final.hosts, \
                f"pod soak seed={seed}: the killed buddy " \
                f"{ring[victim]} re-formed into the final round"
        verdict = check_history(rec.events)
        assert verdict.ok, \
            f"pod soak seed={seed}: store_check verdict dirty: " \
            f"{verdict.violations}"
        stats.update({
            "scenario": scenario, "replica_every_k": replica_every_k,
            "killed_at_step": killed["at_step"],
            "adopted_step": r2["adopted"], "resumed_step": r2["resumed"],
            "rollback_steps": rollback,
            "recovery_wall_s": recovery["wall_s"],
            "replica_adoptions": adoptions,
            "replica_fallbacks": fallbacks,
            "adoption_claims": len(store.list(POD_ADOPT_PREFIX)),
            "store_check_ok": verdict.ok,
            "store_events": len(rec.events),
        })
    if verbose:
        print(f"  seed={seed}: OK — killed {victim} ({kill_mode}), "
              f"{stats['rounds']} round(s), re-formed at "
              f"{expect_hosts} host(s) triad={stats['final_triad']}, "
              f"{len(quarantined)} quarantined, "
              f"{continuity['checked']} continuity check(s)"
              + (f", rollback={stats['rollback_steps']} "
                 f"adoptions={stats['replica_adoptions']}"
                 if scenario is not None else ""))
    return stats


def run_pod_recover_compare(seed: int, root: str, total_steps: int = 12,
                            ckpt_every: int = 5, replica_every_k: int = 2,
                            n_hosts: int = 4, verbose: bool = True) -> dict:
    """Replica adoption vs checkpoint restart on the SAME seeded kill
    schedule (ISSUE 20 acceptance; docs/POD.md "Live-state recovery").

    Runs the ``buddy_kill`` scenario twice from one seed — once with the
    replica layer on (``replica_every_k``) and once with it off (the pure
    checkpoint baseline).  ``run_pod_soak``'s schedule normalization is
    deliberately independent of ``replica_every_k``, so both legs kill
    the same victim at the same step; the adoption leg must roll back
    STRICTLY fewer steps.  Returns the comparison dict (``--json``)."""
    adopt = run_pod_soak(seed, total_steps=total_steps,
                         ckpt_every=ckpt_every,
                         ckpt_dir=os.path.join(root, "adopt", "ckpt"),
                         coord_dir=os.path.join(root, "adopt", "coord"),
                         n_hosts=n_hosts, verbose=verbose,
                         replica_every_k=replica_every_k,
                         scenario="buddy_kill")
    ckpt = run_pod_soak(seed, total_steps=total_steps,
                        ckpt_every=ckpt_every,
                        ckpt_dir=os.path.join(root, "base", "ckpt"),
                        coord_dir=os.path.join(root, "base", "coord"),
                        n_hosts=n_hosts, verbose=verbose,
                        replica_every_k=0, scenario="buddy_kill")
    assert (adopt["victim"], adopt["kill_step"]) == \
           (ckpt["victim"], ckpt["kill_step"]), \
        f"compare seed={seed}: the two legs diverged on the kill schedule " \
        f"({adopt['victim']}@{adopt['kill_step']} vs " \
        f"{ckpt['victim']}@{ckpt['kill_step']}) — not comparable"
    assert adopt["rollback_steps"] < ckpt["rollback_steps"], \
        f"compare seed={seed}: adoption rolled back " \
        f"{adopt['rollback_steps']} step(s), not strictly fewer than the " \
        f"checkpoint baseline's {ckpt['rollback_steps']}"
    out = {
        "seed": seed, "total_steps": total_steps,
        "ckpt_every": ckpt_every, "replica_every_k": replica_every_k,
        "n_hosts": n_hosts,
        "victim": adopt["victim"], "kill_step": adopt["kill_step"],
        "replica_adoption": {k: adopt[k] for k in (
            "adopted_step", "resumed_step", "rollback_steps",
            "recovery_wall_s", "replica_adoptions", "replica_fallbacks",
            "store_check_ok", "continuity_checked")},
        "checkpoint_restart": {k: ckpt[k] for k in (
            "resumed_step", "rollback_steps", "recovery_wall_s",
            "store_check_ok")},
        "rollback_saved_steps":
            ckpt["rollback_steps"] - adopt["rollback_steps"],
    }
    if verbose:
        print(f"  compare seed={seed}: adoption rollback "
              f"{adopt['rollback_steps']} vs checkpoint rollback "
              f"{ckpt['rollback_steps']} "
              f"(saved {out['rollback_saved_steps']} step(s))")
    return out


def run_fleet_procs_soak(seed: int, root: str, n_requests: int = 6,
                         n_members: int = 2, verbose: bool = True) -> dict:
    """Host-scale fleet soak: REAL member-daemon subprocesses, a real
    SIGKILL, and the stalled-leader/compare-delete race (ISSUE 16;
    docs/FLEET.md "Member daemons").

    Phase 1 — subprocess kill.  ``n_members`` ``tools/fleet_member.py``
    daemons are spawned as real OS processes against a shared real-clock
    file store; the router drives them through
    :class:`~deepspeed_tpu.inference.fleet_daemon.StoreMemberProxy`
    handles (assignments/results/control ride store channels — no shared
    memory, no pipes).  One daemon is SIGKILLed the moment the journal
    shows it mid-stream (journaled tokens outstanding, stream unfinished):
    its lease lapses, the router fails the in-flight work over, and the
    survivor daemon resumes AFTER the last journaled token.  Invariants:
    every rid reaches exactly ONE terminal result (results published to
    the durable channel before the kill are claimed, never re-served);
    completed outputs are token-identical to a fault-free in-process
    reference (the daemons build the same seeded tiny model, and sampled
    lanes use counter-based keys, so parity is exact across process
    boundaries); resumed streams keep their submission ``trace_id``
    end-to-end; the victim is visibly dead through the store; the journal
    is empty after collection.

    Phase 2 — stalled leader vs compare-delete.  A separate injected-clock
    store: router A leads and dispatches until a stream has journaled
    tokens, then stalls (stops stepping — the in-process stand-in for a
    GC'd/hung leader process).  B wins the next election term and
    RE-STAMPS every adopted journal entry with its own owner/term.  The
    stalled A then wakes and runs its GC path: ``_journal_delete`` is a
    ``compare_and_delete`` against A's stale mirror, so it MUST lose —
    the entry B adopted survives, owner intact.  A's stale token-append
    loses its CAS and stands down.  After B collects and GC's the stream,
    the delete's tombstone must also block A's resurrection write
    (``CAS(key, None, stale_doc)`` -> False).  Zero duplicate serves,
    zero resurrected journal entries.
    """
    import signal
    import subprocess

    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.elasticity import (FileCoordinationStore, dead_set,
                                          lease_table)
    from deepspeed_tpu.inference.fleet import FleetRouter
    from deepspeed_tpu.inference.fleet_daemon import StoreMemberProxy
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.models import CausalLM

    rng = Random(seed)
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)

    nprng = np.random.default_rng(seed)

    def lane(i):
        if i % 3 != 1:
            return None
        return SamplingParams(temperature=0.8 if i % 2 else 1.2,
                              top_k=0 if i % 6 == 1 else 12,
                              top_p=0.9, seed=900 + i)

    # long streams (16 new tokens) so the SIGKILL window — journaled
    # tokens outstanding, stream unfinished — stays open across many
    # real-clock router rounds
    base = [Request(rid=i,
                    input_ids=nprng.integers(
                        1, model.config.vocab_size,
                        int(nprng.integers(3, 12))).astype(np.int32),
                    max_new_tokens=16, sampling=lane(i),
                    trace_id=f"procs-{seed}-{i}")
            for i in range(n_requests)]

    def copies():
        return [Request(rid=r.rid, input_ids=r.input_ids,
                        max_new_tokens=r.max_new_tokens,
                        sampling=r.sampling, trace_id=r.trace_id)
                for r in base]

    # fault-free in-process reference: the daemons build the identical
    # seeded model, and greedy/sampled outputs are engine-independent
    ref_serve = engine.serving(b_slots=3, page_size=8, max_model_len=64)
    ref = {r.rid: r.output_ids for r in ref_serve.run(copies())}
    del ref_serve

    # ---- phase 1: real daemon subprocesses, real SIGKILL -----------------
    coord_dir = os.path.join(root, "coord")
    store = FileCoordinationStore(coord_dir)   # REAL clock: leases are wall
    # 1s lease x3 missed: detection ~3s of wall clock after the SIGKILL,
    # with enough slack that a straggler compile or scheduler stall on a
    # LIVE daemon never reads as a death
    LEASE_S, MISS = 1.0, 3
    member_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "fleet_member.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs, logs = {}, {}
    stats = {}
    try:
        for i in range(n_members):
            eid = f"engine{i}"
            ready = os.path.join(root, f"ready_{eid}")
            logs[eid] = open(os.path.join(root, f"{eid}.log"), "w")
            procs[eid] = subprocess.Popen(
                [sys.executable, member_py, "--engine_id", eid,
                 "--coord_dir", coord_dir, "--lease_s", str(LEASE_S),
                 "--idle_sleep_s", "0.002", "--max_restarts", "5",
                 "--ready_file", ready],
                env=env, stdout=logs[eid], stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 180.0
        for i in range(n_members):
            ready = os.path.join(root, f"ready_engine{i}")
            while not os.path.exists(ready):
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"fleet_procs seed={seed}: engine{i} daemon never "
                        f"came ready (see {root}/engine{i}.log)")
                if procs[f"engine{i}"].poll() is not None:
                    raise RuntimeError(
                        f"fleet_procs seed={seed}: engine{i} daemon died "
                        f"at startup (see {root}/engine{i}.log)")
                time.sleep(0.05)

        proxies = [StoreMemberProxy(f"engine{i}", store,
                                    router_id="router0", lease_s=LEASE_S)
                   for i in range(n_members)]
        for p in proxies:
            p.beat()
        router = FleetRouter(store, proxies, router_id="router0",
                             lease_s=30.0, miss_limit=MISS,
                             journal_every_k=1)
        victim = f"engine{rng.randrange(n_members)}"
        state = {"killed": False, "kill_round": None}

        def on_tick(r, rounds):
            time.sleep(0.005)   # real clock: let the daemons decode
            if state["killed"]:
                return
            mid_stream = any(
                doc.get("engine") == victim and doc.get("tokens")
                and len(doc["tokens"]) < r._requests[rid].max_new_tokens
                for rid, doc in r._journal_docs.items()
                if rid in r._requests)
            # fallback: if scheduling starves the victim of a journaled
            # mid-stream window, kill anyway — failover is still exercised
            if mid_stream or rounds >= 600:
                os.kill(procs[victim].pid, signal.SIGKILL)
                state["killed"] = True
                state["kill_round"] = rounds

        results = router.run(copies(), max_ticks=60000, on_tick=on_tick)
        assert state["killed"], \
            f"fleet_procs seed={seed}: stream finished before any kill"

        by_rid = {}
        for res in results:
            assert res.rid not in by_rid, \
                f"fleet_procs seed={seed}: rid {res.rid} served TWICE"
            by_rid[res.rid] = res
        assert sorted(by_rid) == sorted(r.rid for r in base), \
            f"fleet_procs seed={seed}: lost requests " \
            f"{sorted(set(r.rid for r in base) - set(by_rid))}"
        parity_checked = resumed_results = resumed_tokens = 0
        for rid, res in by_rid.items():
            assert res.finish_reason in ("eos", "length"), res.finish_reason
            assert np.array_equal(res.output_ids, ref[rid]), \
                f"fleet_procs seed={seed}: rid {rid} diverged across the " \
                f"process boundary after failover"
            assert res.trace_id == f"procs-{seed}-{rid}", \
                f"fleet_procs seed={seed}: rid {rid} lost its trace_id " \
                f"({res.trace_id})"
            parity_checked += 1
            if res.resumed_tokens:
                resumed_results += 1
                resumed_tokens += res.resumed_tokens
        assert router.failovers_total >= 1, \
            f"fleet_procs seed={seed}: SIGKILL never became a failover"
        # the victim must be visibly dead THROUGH THE STORE (lapsed lease
        # or dead marker) — the router may not invent deaths
        assert victim in router._failed_engines, \
            f"fleet_procs seed={seed}: {victim} never declared dead"
        lease = lease_table(store, prefix="fleet/heartbeat").get(victim)
        lapsed = lease is None or lease.missed(store.now()) >= MISS
        marked = victim in dead_set(store, prefix="fleet/dead")
        assert lapsed or marked, \
            f"fleet_procs seed={seed}: {victim} failed over while its " \
            f"lease was live"
        leftover = store.list("fleet/requests")
        assert not leftover, \
            f"fleet_procs seed={seed}: journal entries leaked: {leftover}"
        stats = {
            "seed": seed,
            "submitted": len(base),
            "terminal": len(by_rid),
            "parity_checked": parity_checked,
            "victim": victim,
            "kill_round": state["kill_round"],
            "failovers": router.failovers_total,
            "resumed_results": resumed_results,
            "resumed_tokens": resumed_tokens,
            "channel_dropped": sum(p.channel_dropped_total for p in proxies),
            "cas_contended": getattr(store, "cas_contended_total", 0),
        }
    finally:
        for eid, proc in procs.items():
            if proc.poll() is None:
                proc.terminate()
        for eid, proc in procs.items():
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
                proc.wait(timeout=10)
        for f in logs.values():
            f.close()

    # ---- phase 2: stalled leader vs compare-delete fencing ---------------
    stats.update(_stalled_leader_scenario(
        seed, os.path.join(root, "stalled"), engine, model, ref, base))
    if verbose:
        print(f"  seed={seed}: OK — SIGKILLed {victim} at round "
              f"{stats['kill_round']}, {stats['failovers']} failover(s), "
              f"{stats['resumed_tokens']} resumed token(s), "
              f"{stats['parity_checked']} parity-checked; stalled-leader "
              f"fencing held (delete fenced, append stood down, "
              f"resurrection tombstoned)")
    return stats


def _stalled_leader_scenario(seed: int, coord_dir: str, engine, model,
                             ref: dict, base: list) -> dict:
    """Phase 2 of :func:`run_fleet_procs_soak` — see its docstring.  Uses
    an injected store clock (election timing must be exact) and in-process
    members shared by two routers, which is the real topology: the members
    outlive the stalled leader, and the successor resyncs the live streams
    it adopts from the journal."""
    import numpy as np

    from deepspeed_tpu.elasticity import FileCoordinationStore
    from deepspeed_tpu.inference.fleet import (FLEET_REQUESTS_PREFIX,
                                               FleetMember, FleetRouter,
                                               _rid_key)
    from deepspeed_tpu.inference.serving import Request

    clock = [0.0]
    store = FileCoordinationStore(coord_dir, clock=lambda: clock[0])
    serve_kw = dict(b_slots=2, page_size=8, max_model_len=64)
    members = [FleetMember(f"engine{i}",
                           engine.supervised_serving(max_restarts=5,
                                                     **serve_kw),
                           store, lease_s=1.0)
               for i in range(2)]
    ROUTER_LEASE, MISS = 5.0, 3
    A = FleetRouter(store, members, router_id="routerA",
                    lease_s=ROUTER_LEASE, miss_limit=MISS, journal_every_k=1)
    B = FleetRouter(store, members, router_id="routerB",
                    lease_s=ROUTER_LEASE, miss_limit=MISS, journal_every_k=1)

    def copies():
        return [Request(rid=r.rid, input_ids=r.input_ids,
                        max_new_tokens=r.max_new_tokens,
                        sampling=r.sampling, trace_id=r.trace_id)
                for r in base]

    for r in copies():
        A.submit(r)
    # step A until a stream is journaled MID-FLIGHT, then stall it there
    target = None
    for _ in range(200):
        A.step()
        clock[0] += 0.2
        for rid, doc in A._journal_docs.items():
            if doc.get("engine") and doc.get("tokens") \
                    and rid in A._requests:
                target = rid
                break
        if target is not None:
            break
    assert target is not None, \
        f"stalled-leader seed={seed}: no mid-stream journal entry appeared"
    key = f"{FLEET_REQUESTS_PREFIX}/{_rid_key(target)}"
    stale_doc = dict(A._journal_docs[target])   # A's last-written view
    assert stale_doc.get("owner") == "routerA"

    # A stalls: no more steps.  Advance the clock past its election lease
    # so B wins term 2 and adopts (+ re-stamps) the journal.
    clock[0] += ROUTER_LEASE * MISS + 1.0
    for _ in range(50):
        B.step()
        clock[0] += 0.2
        if B.is_coordinator:
            break
    assert B.is_coordinator and B.term == 2, \
        f"stalled-leader seed={seed}: election never converged ({B.term})"
    adopted = store.get(key)
    assert adopted is not None and adopted.get("owner") == "routerB", \
        f"stalled-leader seed={seed}: takeover did not re-stamp {key}: " \
        f"{adopted}"

    # the stalled ex-leader wakes mid-GC: its compare-delete carries the
    # STALE expected doc and must lose — zero resurrected entries
    A._journal_delete(target)
    after = store.get(key)
    assert after is not None and after.get("owner") == "routerB", \
        f"stalled-leader seed={seed}: deposed leader deleted the " \
        f"successor's journal entry ({after})"
    # ... and its stale token-append must lose its CAS and stand down
    A._flush_token_journal()
    assert target not in A._journal_docs, \
        f"stalled-leader seed={seed}: deposed leader kept fighting for " \
        f"{target} after losing the append CAS"
    assert store.get(key).get("owner") == "routerB"

    # B converges the stream; every rid terminal EXACTLY once across both
    # routers' claims (A may hold results it collected before stalling)
    results = list(A.take_results())
    results += B.run([], max_ticks=4000,
                     on_tick=lambda r, n: clock.__setitem__(0, clock[0] + 1.0))
    by_rid = {}
    for res in results:
        assert res.rid not in by_rid, \
            f"stalled-leader seed={seed}: rid {res.rid} served TWICE"
        by_rid[res.rid] = res
    assert sorted(by_rid) == sorted(r.rid for r in base), \
        f"stalled-leader seed={seed}: lost " \
        f"{sorted(set(r.rid for r in base) - set(by_rid))}"
    for rid, res in by_rid.items():
        assert res.finish_reason in ("eos", "length"), res.finish_reason
        assert np.array_equal(res.output_ids, ref[rid]), \
            f"stalled-leader seed={seed}: rid {rid} diverged"
    leftover = store.list(FLEET_REQUESTS_PREFIX)
    assert not leftover, \
        f"stalled-leader seed={seed}: journal leaked: {leftover}"
    # B's GC left a tombstone on the key: the deposed leader's stale
    # append-as-create must NOT resurrect the finished request
    assert not store.compare_and_swap(key, None, stale_doc), \
        f"stalled-leader seed={seed}: tombstone failed to block the " \
        f"deposed leader's resurrection write"
    assert store.get(key) is None
    return {
        "stalled_target": target,
        "stalled_final_term": B.term,
        "stalled_parity_checked": len(by_rid),
    }


def run_store_partition_soak(seed: int, root: str, n_requests: int = 8,
                             verbose: bool = True) -> dict:
    """Store-partition soak (ISSUE 18; docs/FLEET.md "Store brownouts
    and partitions"): live traffic through daemonized members while the
    coordination store itself browns out and partitions — the fault
    axis process-kill chaos leaves untouched.

    Topology: one router driving two cooperative in-process
    :class:`~deepspeed_tpu.inference.fleet_daemon.FleetMemberDaemon`
    loops over a shared injected-clock file store.  Every client
    (router, each daemon) sits behind its OWN
    :class:`~deepspeed_tpu.elasticity.FaultyStore` proxy over a shared
    ``tools/store_check.RecordingStore`` handle, so faults are
    per-client (asymmetric by construction) and the complete linearized
    op history is protocol-checked after the fact.  The fault proxy
    wraps the recording handle, not the other way round: an op a
    blackout rejected never reached the store, so it must not enter the
    history either.

    Schedule (store clock; one router round + both daemon rounds per
    0.05s tick):

    1. **warmup** until both engines hold a mid-stream journal entry;
    2. **brownout** — seeded transient-error rules on the ROUTER's ops
       for a 0.6s window: the retry policy must absorb every one
       (``store_retries_total`` grows; zero failovers; nobody dead);
    3. **sub-grace blackout** — engine1 fully partitioned for 1.5s
       (< lease_s*miss = 3s): it keeps DECODING dark, buffers results
       in its outbox, republishes on heal; still zero failovers;
    4. **over-grace partition** — engine0 partitioned for 4.5s: the
       router declares it dead through the (healthy) store and fails
       its streams over with a token-exact resume; the victim finishes
       its copies dark and must STALE-DROP every one on heal (journal
       re-stamped to the survivor) — zero duplicate serves;
    5. **heal + drain** — every rid terminal exactly once,
       token-identical to a fault-free reference, journal GC'd, and
       the recorded history passes every checker invariant.

    Phase 2 (:func:`_partitioned_leader_scenario`) puts the PARTITION
    ON THE LEADER itself and proves it self-fences.
    """
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.elasticity import (FaultyStore, FileCoordinationStore,
                                          StoreFaultRule,
                                          store_retries_total)
    from deepspeed_tpu.inference.fleet import (FLEET_REQUESTS_PREFIX,
                                               FleetMember, FleetRouter)
    from deepspeed_tpu.inference.fleet_daemon import (FleetMemberDaemon,
                                                      StoreMemberProxy)
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.models import CausalLM
    from tools.store_check import RecordingStore, check_history

    MAX_NEW = 24
    model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla")
    params = model.init_fn(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)
    nprng = np.random.default_rng(seed)

    def lane(i):
        if i % 3 != 1:
            return None
        return SamplingParams(temperature=0.8 if i % 2 else 1.2,
                              top_k=0 if i % 6 == 1 else 12,
                              top_p=0.9, seed=900 + i)

    # long streams: the brownout/blackout/partition windows all need
    # mid-stream journal entries to land on
    base = [Request(rid=i,
                    input_ids=nprng.integers(
                        1, model.config.vocab_size,
                        int(nprng.integers(3, 12))).astype(np.int32),
                    max_new_tokens=MAX_NEW, sampling=lane(i),
                    trace_id=f"storepart-{seed}-{i}")
            for i in range(n_requests)]

    def copies(reqs=None):
        return [Request(rid=r.rid, input_ids=r.input_ids,
                        max_new_tokens=r.max_new_tokens,
                        sampling=r.sampling, trace_id=r.trace_id)
                for r in (base if reqs is None else reqs)]

    # the last few requests are held back and submitted mid-run when the
    # blackout heals: the over-grace partition needs engine0 to hold a
    # stream with REAL work left, and by that point in the schedule its
    # upfront share has usually finished
    n_late = max(1, min(3, n_requests - 2))
    upfront, late = base[:-n_late], base[-n_late:]

    ref_serve = engine.serving(b_slots=3, page_size=8, max_model_len=64)
    ref = {r.rid: r.output_ids for r in ref_serve.run(copies())}
    del ref_serve

    clock = [0.0]
    DT = 0.05
    backend = FileCoordinationStore(os.path.join(root, "coord"),
                                    clock=lambda: clock[0])
    recorded = RecordingStore(backend, client="base")
    views = {c: FaultyStore(recorded.handle(c), client=c)
             for c in ("router0", "engine0", "engine1")}
    LEASE_S, MISS = 1.0, 3   # member death grace = 3.0 store-sec
    serve_kw = dict(b_slots=2, page_size=8, max_model_len=64)
    daemons = []
    for i in range(2):
        eid = f"engine{i}"
        m = FleetMember(eid, engine.supervised_serving(max_restarts=5,
                                                       **serve_kw),
                        views[eid], lease_s=LEASE_S)
        m.beat(force=True)
        daemons.append(FleetMemberDaemon(m, views[eid]))
    proxies = [StoreMemberProxy(f"engine{i}", views["router0"],
                                router_id="router0", lease_s=LEASE_S)
               for i in range(2)]
    for p in proxies:
        p.beat()
    router = FleetRouter(views["router0"], proxies, router_id="router0",
                         lease_s=5.0, miss_limit=MISS, journal_every_k=1)

    def midstream(eid, min_remaining=1):
        return any(doc.get("engine") == eid and doc.get("tokens")
                   and len(doc["tokens"]) <= MAX_NEW - min_remaining
                   for rid, doc in router._journal_docs.items()
                   if rid in router._requests)

    st = {"phase": "warmup", "until": None, "rule": None,
          "retries0": None, "retries_brownout": None,
          "brownout_faults": 0, "failovers_at_blackout": None,
          "blackout_dark_seen": False, "failovers_pre_partition": None,
          "victim_declared_round": None}

    def on_tick(r, rounds):
        for d in daemons:
            d.poll_once()
        clock[0] += DT
        ph = st["phase"]
        if ph == "warmup":
            if midstream("engine0") and midstream("engine1"):
                st["retries0"] = store_retries_total()
                st["until"] = clock[0] + 0.6
                st["rule"] = StoreFaultRule(
                    ops=("get", "put", "cas", "list"), kind="error",
                    probability=0.3, until_t=st["until"], seed=seed)
                views["router0"].rules.append(st["rule"])
                st["phase"] = "brownout"
            elif rounds > 2000:
                raise RuntimeError(
                    f"store_partition seed={seed}: warmup never saw both "
                    f"engines mid-stream")
        elif ph == "brownout":
            if clock[0] >= st["until"]:
                views["router0"].rules.remove(st["rule"])
                st["brownout_faults"] = st["rule"].fires
                st["retries_brownout"] = \
                    store_retries_total() - st["retries0"]
                st["failovers_at_blackout"] = r.failovers_total
                st["phase"] = "pre_blackout"
        elif ph == "pre_blackout":
            if midstream("engine1"):
                views["engine1"].partitioned = True
                st["until"] = clock[0] + 1.5   # < the 3.0s death grace
                st["phase"] = "blackout"
            elif rounds > 4000:
                raise RuntimeError(
                    f"store_partition seed={seed}: engine1 never "
                    f"mid-stream for the sub-grace blackout")
        elif ph == "blackout":
            if daemons[1]._store_dark:
                st["blackout_dark_seen"] = True
            if clock[0] >= st["until"]:
                views["engine1"].partitioned = False
                # submit the held-back requests NOW: engine1's buffered
                # terminals keep the run loop pending through this round,
                # and the fresh streams give engine0 real work to be
                # mid-stream on when the partition lands
                for req in copies(late):
                    r.submit(req)
                st["phase"] = "pre_partition"
        elif ph == "pre_partition":
            if midstream("engine0", min_remaining=MAX_NEW // 2):
                st["failovers_pre_partition"] = r.failovers_total
                views["engine0"].partitioned = True
                st["until"] = clock[0] + 4.5   # > the 3.0s death grace
                st["phase"] = "partition"
            elif rounds > 6000:
                raise RuntimeError(
                    f"store_partition seed={seed}: engine0 never "
                    f"mid-stream for the over-grace partition")
        elif ph == "partition":
            if st["victim_declared_round"] is None \
                    and "engine0" in r._failed_engines:
                st["victim_declared_round"] = rounds
            if clock[0] >= st["until"]:
                views["engine0"].partitioned = False
                st["phase"] = "drain"

    results = router.run(copies(upfront), max_ticks=60000, on_tick=on_tick)
    assert st["phase"] in ("partition", "drain"), \
        f"store_partition seed={seed}: schedule stuck in {st['phase']!r}"
    # the survivor usually finishes the failed-over work BEFORE the
    # partition window closes, so the run returns with the victim still
    # dark: heal it now and give both daemons a few more polls so the
    # republish-after-heal staleness check actually runs (drops are
    # asserted below; a wrongly REPUBLISHED copy would also fail the
    # history checker's duplicate-serve invariant)
    views["engine0"].partitioned = False
    for _ in range(5):
        for d in daemons:
            d.poll_once()
        clock[0] += DT
    by_rid = {}
    for res in results:
        assert res.rid not in by_rid, \
            f"store_partition seed={seed}: rid {res.rid} served TWICE"
        by_rid[res.rid] = res
    assert sorted(by_rid) == sorted(r.rid for r in base), \
        f"store_partition seed={seed}: lost requests " \
        f"{sorted(set(r.rid for r in base) - set(by_rid))}"
    resumed_results = 0
    for rid, res in by_rid.items():
        assert res.finish_reason in ("eos", "length"), res.finish_reason
        assert np.array_equal(res.output_ids, ref[rid]), \
            f"store_partition seed={seed}: rid {rid} diverged under " \
            f"store faults"
        assert res.trace_id == f"storepart-{seed}-{rid}", \
            f"store_partition seed={seed}: rid {rid} lost its trace_id"
        if res.resumed_tokens:
            resumed_results += 1
    # brownout: absorbed by the retry policy, never escalated
    assert st["brownout_faults"] > 0, \
        f"store_partition seed={seed}: the brownout injected nothing"
    assert st["retries_brownout"] > 0, \
        f"store_partition seed={seed}: brownout faults never hit the " \
        f"retry policy"
    assert st["failovers_at_blackout"] == 0, \
        f"store_partition seed={seed}: a brownout became a failover"
    # sub-grace blackout: dark, decoding, never declared dead
    assert st["blackout_dark_seen"], \
        f"store_partition seed={seed}: engine1 never went dark"
    assert st["failovers_pre_partition"] == 0, \
        f"store_partition seed={seed}: a sub-grace blackout became a " \
        f"failover"
    assert daemons[1].outbox_republished_total >= 1, \
        f"store_partition seed={seed}: engine1 republished nothing " \
        f"after its blackout healed"
    # over-grace partition: a real failover, through the healthy store
    assert router.failovers_total >= 1, \
        f"store_partition seed={seed}: the partition never failed over"
    assert "engine0" in router._failed_engines, \
        f"store_partition seed={seed}: engine0 never declared dead"
    assert "engine1" not in router._failed_engines, \
        f"store_partition seed={seed}: engine1 wrongly declared dead"
    assert resumed_results >= 1, \
        f"store_partition seed={seed}: failover never resumed a stream"
    assert daemons[0].outbox_stale_dropped_total >= 1, \
        f"store_partition seed={seed}: the healed victim dropped no " \
        f"stale buffered result — its copies went somewhere"
    assert daemons[0].outbox_dropped_total == 0 \
        and daemons[1].outbox_dropped_total == 0, \
        f"store_partition seed={seed}: outbox cap overflowed"
    assert router.fences_total == 0 and not router.self_fenced, \
        f"store_partition seed={seed}: the sole router self-fenced"
    leftover = backend.list(FLEET_REQUESTS_PREFIX)
    assert not leftover, \
        f"store_partition seed={seed}: journal entries leaked: {leftover}"
    # the recorded linearized history passes every protocol invariant
    recorded.save(os.path.join(root, "history.jsonl"))
    verdict = check_history(recorded.events)
    assert verdict.ok, \
        f"store_partition seed={seed}: history checker FAILED: " \
        f"{verdict.violations}"
    stats = {
        "seed": seed,
        "submitted": len(base),
        "terminal": len(by_rid),
        "resumed_results": resumed_results,
        "failovers": router.failovers_total,
        "victim_declared_round": st["victim_declared_round"],
        "brownout_faults": st["brownout_faults"],
        "brownout_retries": st["retries_brownout"],
        "router_store_unavailable": router.store_unavailable_total,
        "daemon_store_unavailable": [d.store_unavailable_total
                                     for d in daemons],
        "outbox_republished": daemons[1].outbox_republished_total,
        "outbox_stale_dropped": daemons[0].outbox_stale_dropped_total,
        "history_events": verdict.checked_events,
        "history_checks": verdict.counts,
    }
    stats.update(_partitioned_leader_scenario(
        seed, os.path.join(root, "fenced"), engine, ref, base))
    if verbose:
        print(f"  seed={seed}: OK — brownout absorbed "
              f"({stats['brownout_faults']} fault(s), "
              f"{stats['brownout_retries']} retrie(s), 0 failovers); "
              f"sub-grace blackout decoded dark "
              f"({stats['outbox_republished']} republished on heal, 0 "
              f"failovers); over-grace partition failed over "
              f"({stats['failovers']}) with {stats['resumed_results']} "
              f"resumed stream(s) and "
              f"{stats['outbox_stale_dropped']} stale-dropped victim "
              f"result(s); history clean over "
              f"{stats['history_events']} op(s); partitioned leader "
              f"self-fenced in {stats['fence_rounds']} round(s) with 0 "
              f"dispatches/deletes, successor term "
              f"{stats['partition_final_term']}")
    return stats


def _partitioned_leader_scenario(seed: int, coord_dir: str, engine,
                                 ref: dict, base: list) -> dict:
    """Phase 2 of :func:`run_store_partition_soak` — the LIVE but
    partitioned leader (contrast :func:`_stalled_leader_scenario`'s
    GC'd/hung one): router A keeps STEPPING while its own store view is
    blacked out.  Within ``lease_s`` of its last successful renewal it
    must self-fence — zero dispatches, zero journal deletes, not one
    store op from the GC/flush paths while fenced — B must win the next
    term through the healthy store and adopt, and on heal A's first
    successful election poll re-reads leadership and stands down,
    leaving B's re-stamped entries untouched."""
    import numpy as np

    from deepspeed_tpu.elasticity import FaultyStore, FileCoordinationStore
    from deepspeed_tpu.inference.fleet import (FLEET_REQUESTS_PREFIX,
                                               FleetMember, FleetRouter,
                                               _rid_key)
    from deepspeed_tpu.inference.serving import Request

    clock = [0.0]
    store = FileCoordinationStore(coord_dir, clock=lambda: clock[0])
    a_store = FaultyStore(store, client="routerA")
    serve_kw = dict(b_slots=2, page_size=8, max_model_len=64)
    members = [FleetMember(f"engine{i}",
                           engine.supervised_serving(max_restarts=5,
                                                     **serve_kw),
                           store, lease_s=1.0)
               for i in range(2)]
    ROUTER_LEASE, MISS = 5.0, 3
    A = FleetRouter(a_store, members, router_id="routerA",
                    lease_s=ROUTER_LEASE, miss_limit=MISS,
                    journal_every_k=1)
    B = FleetRouter(store, members, router_id="routerB",
                    lease_s=ROUTER_LEASE, miss_limit=MISS,
                    journal_every_k=1)

    def copies():
        return [Request(rid=r.rid, input_ids=r.input_ids,
                        max_new_tokens=r.max_new_tokens,
                        sampling=r.sampling, trace_id=r.trace_id)
                for r in base]

    # one extra LONG greedy stream is the fence target: the base copies
    # are short enough to finish while A steps fenced (degraded rounds
    # still pump the data plane), and the fence assertions need a
    # journal entry that is still LIVE when B adopts.  Submitted first
    # so it takes a decode slot immediately.
    def probe_copy():
        return Request(rid="fence_probe",
                       input_ids=np.arange(1, 7, dtype=np.int32),
                       max_new_tokens=56,
                       trace_id=f"storepart-{seed}-probe")

    ref = dict(ref)
    ref["fence_probe"] = {
        r.rid: r.output_ids
        for r in engine.serving(**serve_kw).run([probe_copy()])
    }["fence_probe"]
    all_rids = set(r.rid for r in base) | {"fence_probe"}

    A.submit(probe_copy())
    for r in copies():
        A.submit(r)
    target = "fence_probe"
    key = f"{FLEET_REQUESTS_PREFIX}/{_rid_key(target)}"
    for _ in range(200):
        A.step()
        clock[0] += 0.2
        doc = A._journal_docs.get(target)
        if doc and doc.get("engine") and doc.get("tokens") \
                and target in A._requests:
            break
    else:
        raise AssertionError(
            f"partitioned-leader seed={seed}: probe never mid-stream")

    # the partition: A is alive and stepping, but every store op it
    # issues fails.  Its data plane must keep ticking; its control
    # plane must freeze itself within lease_s.
    a_store.partitioned = True
    fence_rounds = 0
    for _ in range(int(ROUTER_LEASE / 0.2) + 10):
        A.step()
        clock[0] += 0.2
        fence_rounds += 1
        if A.self_fenced:
            break
    assert A.self_fenced and A.is_coordinator, \
        f"partitioned-leader seed={seed}: no self-fence after " \
        f"{fence_rounds} dark round(s)"
    disp0 = A.dispatches_total
    flushes0 = A.journal_flushes_total
    for _ in range(20):
        A.step()
        clock[0] += 0.2
    assert A.dispatches_total == disp0, \
        f"partitioned-leader seed={seed}: fenced router dispatched"
    assert A.journal_flushes_total == flushes0, \
        f"partitioned-leader seed={seed}: fenced router flushed the " \
        f"journal"

    # B wins the next term through the healthy store and re-stamps
    for _ in range(50):
        B.step()
        clock[0] += 0.2
        if B.is_coordinator:
            break
    assert B.is_coordinator and B.term == 2, \
        f"partitioned-leader seed={seed}: election never converged " \
        f"({B.term})"
    adopted = store.get(key)
    assert adopted is not None and adopted.get("owner") == "routerB", \
        f"partitioned-leader seed={seed}: takeover did not re-stamp " \
        f"{key}: {adopted}"

    # the fenced ex-leader's GC and flush paths must not attempt ONE
    # store op — deferral, not a lost compare-delete race
    ops0 = a_store.ops_total
    A._journal_delete(target)
    A._flush_token_journal()
    assert a_store.ops_total == ops0, \
        f"partitioned-leader seed={seed}: a fenced router reached for " \
        f"the store"
    assert target in A._pending_gc, \
        f"partitioned-leader seed={seed}: fenced GC not deferred"
    assert store.get(key).get("owner") == "routerB"

    # heal: the first successful poll IS the leadership re-read
    a_store.partitioned = False
    A.step()
    clock[0] += 0.2
    assert not A.self_fenced and not A.is_coordinator, \
        f"partitioned-leader seed={seed}: healed ex-leader kept leading"
    assert store.get(key).get("owner") == "routerB", \
        f"partitioned-leader seed={seed}: heal disturbed the " \
        f"successor's adopted entry"

    # B converges every stream; each rid terminal EXACTLY once across
    # both routers' claims (A holds only what it collected-and-GC'd
    # while healthy — degraded rounds never collect)
    results = list(A.take_results())
    results += B.run([], max_ticks=4000,
                     on_tick=lambda r, n: clock.__setitem__(0, clock[0] + 1.0))
    by_rid = {}
    for res in results:
        assert res.rid not in by_rid, \
            f"partitioned-leader seed={seed}: rid {res.rid} served TWICE"
        by_rid[res.rid] = res
    assert set(by_rid) == all_rids, \
        f"partitioned-leader seed={seed}: lost " \
        f"{sorted(map(repr, all_rids - set(by_rid)))}"
    for rid, res in by_rid.items():
        assert res.finish_reason in ("eos", "length"), res.finish_reason
        assert np.array_equal(res.output_ids, ref[rid]), \
            f"partitioned-leader seed={seed}: rid {rid} diverged"
    leftover = store.list(FLEET_REQUESTS_PREFIX)
    assert not leftover, \
        f"partitioned-leader seed={seed}: journal leaked: {leftover}"
    return {
        "fenced_target": target,
        "fence_rounds": fence_rounds,
        "fences_total": A.fences_total,
        "fenced_dispatch_delta": A.dispatches_total - disp0,
        "partition_final_term": B.term,
        "partition_parity_checked": len(by_rid),
    }


def run_hybrid_soak(seed: int, rounds: int = 3, steps_per_round: int = 2,
                    n_prompts: int = 5, max_new: int = 6,
                    verbose: bool = True) -> dict:
    """One hybrid train+rollout session under a seeded kill schedule
    (ISSUE 13; docs/HYBRID.md).

    The actor loop (train K steps → publish the weight epoch → rollout a
    mixed greedy/sampled prompt batch) runs under BOTH supervision tiers:
    mid-rollout kills (``serve.decode`` / ``serve.prefill`` /
    ``serve.replay``) are absorbed by the :class:`ServingSupervisor`
    inside :class:`RolloutEngine` (warm restart, adopted program
    inventory, token-exact replay under the same lane + epoch), while
    mid-train-step kills (``train.step`` — fired BEFORE the optimizer
    mutates state) escape the round and are retried by an
    ``elasticity.Supervisor`` driving a RESUMABLE round loop (completed
    substeps are skipped, so a retry re-executes exactly the killed
    step — the same shape a ``PodSupervisor`` round gives the loop on a
    real pod).

    Invariants asserted against a fault-free reference run of the same
    seeded schedule:

    - **loss continuity**: every executed train step's loss equals the
      reference's for that (round, step) — no step lost, re-run on
      mutated state, or double-applied;
    - **rollout replay parity**: every rollout of every round is
      token-identical to the reference (greedy and sampled lanes — the
      counter-based keys make replays and restarts exact);
    - **the pool invariant**: page accounting balances after the session
      (and update_params re-checks it at every epoch flip);
    - **the epoch ladder**: one weight epoch per round, on the ladder the
      reference climbed.
    """
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.elasticity import Supervisor
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.resilience import (FaultInjector, clear_injector,
                                          install_injector)
    from deepspeed_tpu.resilience.fault_injection import (
        SITE_SERVE_DECODE, SITE_SERVE_PREFILL, SITE_SERVE_REPLAY,
        SITE_TRAIN_STEP)
    from deepspeed_tpu.rollout import RolloutEngine

    rng = Random(seed)
    nprng = np.random.default_rng(seed)
    cfg = {
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2},
    }

    def build():
        mesh_mod.reset_mesh()
        model = CausalLM("tiny", dtype=jnp.float32, attn_impl="xla",
                         max_seq_len=64)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
        return engine, RolloutEngine(engine, b_slots=3, page_size=8,
                                     max_model_len=64, max_restarts=12)

    # one deterministic schedule both runs replay: per-round train batches,
    # prompt batches, and a mixed greedy/sampled lane assignment
    prompts = [[nprng.integers(1, 256, int(nprng.integers(4, 12)))
                .astype(np.int32) for _ in range(n_prompts)]
               for _ in range(rounds)]
    lanes = [[(SamplingParams(temperature=0.9, top_k=25,
                              seed=100 * r + i) if i % 3 == 1 else
               SamplingParams(temperature=1.1, top_p=0.9,
                              seed=200 * r + i) if i % 3 == 2 else None)
              for i in range(n_prompts)]
             for r in range(rounds)]

    def drive(ro, on_loss, on_rollout, progress):
        """The resumable round loop (completed substeps are skipped)."""
        while progress["round"] < rounds:
            r = progress["round"]
            while progress["step"] < steps_per_round:
                k = progress["step"]
                loss = float(ro.hybrid.train_batch(batch=batches[r][k]))
                on_loss(r, k, loss)
                progress["step"] += 1
            if not progress["published"]:
                ro.publish_weights()
                progress["published"] = True
            results = ro.rollout(prompts[r], max_new_tokens=max_new,
                                 sampling=lanes[r], max_ticks=8000)
            on_rollout(r, results)
            progress["round"] += 1
            progress["step"] = 0
            progress["published"] = False

    # ---- fault-free reference (no injector installed yet)
    _, ref_ro = build()
    bs = ref_ro.engine.train_batch_size
    batches = [[{"input_ids": nprng.integers(
        0, 256, (bs, 16)).astype(np.int32)} for _ in range(steps_per_round)]
        for _ in range(rounds)]
    ref_losses: dict = {}
    ref_rollouts: dict = {}
    drive(ref_ro,
          lambda r, k, loss: ref_losses.__setitem__((r, k), loss),
          lambda r, res: ref_rollouts.__setitem__(
              r, {x.rid[1]: x.output_ids for x in res}),
          {"round": 0, "step": 0, "published": False})
    assert ref_ro.weight_epoch == rounds

    # ---- chaos run
    _, ro = build()
    total_steps = rounds * steps_per_round
    inj = FaultInjector()
    # at least one decode kill early in a rollout, maybe more later
    inj.add(site=SITE_SERVE_DECODE, kind="raise", at_call=rng.randint(2, 6))
    for _ in range(rng.randint(0, 2)):
        inj.add(site=SITE_SERVE_DECODE, kind="raise",
                at_call=rng.randint(6, rounds * n_prompts * max_new))
    # at least one mid-train-step kill (train.step fires before the
    # optimizer mutates state, so the retry is loss-continuous)
    inj.add(site=SITE_TRAIN_STEP, kind="raise",
            at_call=rng.randint(2, total_steps))
    if rng.random() < 0.5:
        inj.add(site=SITE_SERVE_PREFILL, kind="raise",
                at_call=rng.randint(1, rounds * n_prompts))
    if rng.random() < 0.3:
        inj.add(site=SITE_SERVE_REPLAY, kind="raise", at_call=1)
    install_injector(inj)

    losses: dict = {}
    rollouts: dict = {}
    progress = {"round": 0, "step": 0, "published": False}

    def record_loss(r, k, loss):
        assert (r, k) not in losses, \
            f"hybrid soak seed={seed}: step ({r},{k}) applied twice"
        losses[(r, k)] = loss

    def attempt(_):
        drive(ro, record_loss,
              lambda r, res: rollouts.__setitem__(
                  r, {x.rid[1]: x.output_ids for x in res}),
              progress)
        return 0

    sup = Supervisor(
        attempt, max_restarts=12, backoff_s=0,
        progress_fn=lambda: (progress["round"] * (steps_per_round + 1)
                             + progress["step"]),
        zero_progress_limit=6, seed=seed)
    rc = sup.run()
    clear_injector()
    assert rc == 0, f"hybrid soak seed={seed}: supervisor exited rc={rc} " \
                    f"(diagnosis: {sup.diagnosis})"

    # invariant: loss continuity — every executed step matches the
    # reference exactly (same program, same state, same batch)
    assert sorted(losses) == sorted(ref_losses), \
        f"hybrid soak seed={seed}: steps lost/extra: " \
        f"{sorted(set(ref_losses) ^ set(losses))}"
    for key, loss in losses.items():
        assert abs(loss - ref_losses[key]) < 1e-5, \
            f"hybrid soak seed={seed}: loss continuity broken at {key}: " \
            f"{loss} != {ref_losses[key]}"
    # invariant: rollout replay parity, every round, token-exact
    parity_checked = 0
    for r in range(rounds):
        assert sorted(rollouts[r]) == sorted(ref_rollouts[r]), \
            f"hybrid soak seed={seed}: round {r} lost rollouts"
        for i, out in rollouts[r].items():
            assert np.array_equal(out, ref_rollouts[r][i]), \
                f"hybrid soak seed={seed}: rollout ({r},{i}) diverged " \
                "after replay"
            parity_checked += 1
    # invariant: the pool + demoted ledgers balance, the epoch ladder
    # matches the reference's (one epoch per round — train-step retries
    # must not double-publish)
    acct = ro.serving.page_accounting()
    assert acct["balanced"], \
        f"hybrid soak seed={seed}: page accounting broken: {acct}"
    assert ro.weight_epoch == rounds, \
        f"hybrid soak seed={seed}: weight epoch {ro.weight_epoch} != " \
        f"{rounds} (double publish?)"
    train_kills = sum(1 for e in inj.log if e["site"] == "train.step")
    stats = {
        "seed": seed,
        "rounds": rounds,
        "faults_fired": len(inj.log),
        "fault_log": inj.log,
        "train_kills": train_kills,
        "outer_restart_rounds": train_kills,   # each escaped to Supervisor
        "serve_restarts": ro.supervisor.restarts,
        "weight_epoch": ro.weight_epoch,
        "train_steps_total": total_steps,
        "losses_checked": len(losses),
        "rollouts_total": rounds * n_prompts,
        "parity_checked": parity_checked,
        "balanced": acct["balanced"],
    }
    if verbose:
        print(f"  seed={seed}: OK — {stats['faults_fired']} fault(s) fired "
              f"({train_kills} mid-train), {stats['serve_restarts']} serving "
              f"restart(s), {parity_checked} rollout(s) parity-checked, "
              f"epoch {ro.weight_epoch}")
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="randomized fault-injection soak for the resilience "
                    "subsystem")
    ap.add_argument("--mode",
                    choices=("train", "serve", "pod", "fleet",
                             "fleet_procs", "store_partition", "hybrid"),
                    default="train",
                    help="train: supervised elastic rounds; serve: "
                         "ServingSupervisor kill/replay soak; pod: "
                         "simulated multi-host kill + shrink-to-healthy "
                         "re-formation; fleet: serving-fleet engine + "
                         "coordinator kills with store-lease failover; "
                         "fleet_procs: REAL member-daemon subprocesses "
                         "with a mid-stream SIGKILL plus the stalled-"
                         "leader/compare-delete race (ISSUE 16, "
                         "docs/FLEET.md); store_partition: brownouts, "
                         "asymmetric member partitions and a partitioned "
                         "LEADER over per-client FaultyStore views, with "
                         "the recorded op history protocol-checked "
                         "(ISSUE 18, docs/FLEET.md \"Store brownouts and "
                         "partitions\"); hybrid: train+rollout rounds "
                         "with mid-train-step AND mid-rollout kills (loss "
                         "continuity + rollout replay parity + pool "
                         "invariant, docs/HYBRID.md)")
    ap.add_argument("--soaks", type=int, default=3,
                    help="number of supervised sessions to soak")
    ap.add_argument("--total-steps", type=int, default=8)
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8,
                    help="serve mode: requests per soak stream")
    ap.add_argument("--tp", type=int, default=1,
                    help="serve mode: run each soak on a tp-device mesh "
                         "(model axis = tp over the first tp virtual host "
                         "devices; ISSUE 10 sharded serving)")
    ap.add_argument("--tier_pages", type=int, default=0,
                    help="serve mode: enable KV-page tiering with a host "
                         "tier of N pages AND shrink the device pool "
                         "(--pool_pages) so the kill schedule lands on "
                         "demote/promote cycles (ISSUE 11; 0 = off)")
    ap.add_argument("--pool_pages", type=int, default=14,
                    help="serve mode with --tier_pages: device pool size "
                         "(small = pool pressure)")
    ap.add_argument("--kv_dtype", choices=("int8",), default=None,
                    help="serve mode (ISSUE 17): run reference AND "
                         "supervised session on the quantized paged pool "
                         "— promoted int8 streams must replay token-"
                         "exactly across the kill schedule")
    ap.add_argument("--hosts", type=int, default=4,
                    help="pod mode: simulated hosts per soak")
    ap.add_argument("--replica_every_k", type=int, default=0,
                    help="pod mode (ISSUE 20): seal an in-RAM replica cut "
                         "every k steps so a killed host's state is "
                         "ADOPTED from its ring buddy instead of rolled "
                         "back to the last checkpoint (0 = layer off, "
                         "legacy soak)")
    ap.add_argument("--scenario", default=None,
                    choices=("buddy_kill", "double_kill", "mid_seal",
                             "corrupt_slab"),
                    help="pod mode: pin the replica kill shape instead of "
                         "the seeded legacy draw (see run_pod_soak; "
                         "requires --replica_every_k > 0 except "
                         "buddy_kill's k=0 baseline leg)")
    ap.add_argument("--compare_recovery", action="store_true",
                    help="pod mode: run the buddy_kill scenario twice on "
                         "the SAME seeded kill schedule — replica "
                         "adoption vs checkpoint restart — and assert "
                         "adoption rolls back strictly fewer steps "
                         "(stats dict via --json)")
    ap.add_argument("--members", type=int, default=2,
                    help="fleet_procs mode: member daemon subprocesses "
                         "per soak")
    ap.add_argument("--json", default=None, metavar="OUT.json",
                    help="write the per-seed stats dicts (plus a pass/"
                         "fail tally) as a JSON artifact")
    ap.add_argument("--rounds", type=int, default=3,
                    help="hybrid mode: train+rollout rounds per soak")
    ap.add_argument("--steps-per-round", type=int, default=2,
                    help="hybrid mode: train steps per round")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed; soak i uses seed+i")
    ap.add_argument("--keep-dirs", action="store_true",
                    help="keep the per-soak checkpoint dirs for inspection")
    ap.add_argument("--collect_traces", default=None, metavar="DIR",
                    help="fleet mode: soak with the tracer ON, members "
                         "publishing span segments to the store, and "
                         "assemble+assert the fleet trace into "
                         "DIR/fleet_trace.json — a killed engine's "
                         "failed-over stream must read as ONE trace_id "
                         "across both engine tracks, causally ordered "
                         "(docs/OBSERVABILITY.md \"Distributed tracing\")")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="trace the whole soak and write a Chrome/Perfetto "
                         "artifact (spans from every round, incl. failed "
                         "attempts + warm restarts)")
    args = ap.parse_args(argv)
    if args.collect_traces and args.mode != "fleet":
        ap.error("--collect_traces assembles the FLEET trace — use "
                 "--mode fleet (whole-soak tracing wants --trace)")
    if args.collect_traces and args.trace:
        ap.error("--collect_traces manages the tracer itself; it does not "
                 "compose with --trace")

    if args.trace:
        from deepspeed_tpu.observability import configure_tracer

        configure_tracer(enabled=True, capacity=1 << 17)

    failures = 0
    all_stats = []
    for i in range(args.soaks):
        seed = args.seed + i
        if args.mode == "fleet_procs":
            root = tempfile.mkdtemp(prefix=f"chaos_fleetprocs_{seed}_")
            print(f"fleet_procs soak {i + 1}/{args.soaks} (seed={seed}, "
                  f"members={args.members}) -> {root}")
            try:
                all_stats.append(run_fleet_procs_soak(
                    seed, root, n_requests=args.requests
                    if args.requests != 8 else 6,
                    n_members=args.members))
            except Exception as e:
                failures += 1
                print(f"  FAILED ({type(e).__name__}): {e}", file=sys.stderr)
            finally:
                if not args.keep_dirs:
                    shutil.rmtree(root, ignore_errors=True)
            continue
        if args.mode == "store_partition":
            root = tempfile.mkdtemp(prefix=f"chaos_storepart_{seed}_")
            print(f"store_partition soak {i + 1}/{args.soaks} "
                  f"(seed={seed}) -> {root}")
            try:
                all_stats.append(run_store_partition_soak(
                    seed, root, n_requests=args.requests))
            except Exception as e:
                failures += 1
                print(f"  FAILED ({type(e).__name__}): {e}", file=sys.stderr)
            finally:
                if not args.keep_dirs:
                    shutil.rmtree(root, ignore_errors=True)
            continue
        if args.mode == "serve":
            print(f"serve soak {i + 1}/{args.soaks} (seed={seed}"
                  + (f", tp={args.tp}" if args.tp > 1 else "")
                  + (f", tier={args.tier_pages}" if args.tier_pages else "")
                  + (f", kv={args.kv_dtype}" if args.kv_dtype else "")
                  + ")")
            try:
                run_serve_soak(
                    seed, n_requests=args.requests, tp=args.tp,
                    host_tier_pages=args.tier_pages or None,
                    num_pages=args.pool_pages if args.tier_pages else None,
                    kv_dtype=args.kv_dtype)
            # broad catch by design: RestartBudgetExhausted / ServeTimeout /
            # an escaped InjectedFault ARE the per-seed failure signal this
            # driver exists to tally — one bad seed must not kill the rest
            except Exception as e:
                failures += 1
                print(f"  FAILED ({type(e).__name__}): {e}", file=sys.stderr)
            continue
        if args.mode == "hybrid":
            print(f"hybrid soak {i + 1}/{args.soaks} (seed={seed}, "
                  f"rounds={args.rounds}x{args.steps_per_round})")
            try:
                run_hybrid_soak(seed, rounds=args.rounds,
                                steps_per_round=args.steps_per_round,
                                n_prompts=args.requests
                                if args.requests != 8 else 5)
            except Exception as e:
                failures += 1
                print(f"  FAILED ({type(e).__name__}): {e}", file=sys.stderr)
            continue
        if args.mode == "fleet":
            root = tempfile.mkdtemp(prefix=f"chaos_fleet_{seed}_")
            print(f"fleet soak {i + 1}/{args.soaks} (seed={seed}) -> {root}")
            try:
                all_stats.append(run_fleet_soak(
                    seed, coord_dir=os.path.join(root, "coord"),
                    n_requests=args.requests,
                    collect_traces=args.collect_traces))
            except Exception as e:
                failures += 1
                print(f"  FAILED ({type(e).__name__}): {e}", file=sys.stderr)
            finally:
                if not args.keep_dirs:
                    shutil.rmtree(root, ignore_errors=True)
            continue
        if args.mode == "pod":
            root = tempfile.mkdtemp(prefix=f"chaos_pod_{seed}_")
            print(f"pod soak {i + 1}/{args.soaks} (seed={seed}"
                  + (f", k={args.replica_every_k}"
                     if args.replica_every_k else "")
                  + (f", scenario={args.scenario}" if args.scenario else "")
                  + (", compare_recovery" if args.compare_recovery else "")
                  + f") -> {root}")
            try:
                if args.compare_recovery:
                    all_stats.append(run_pod_recover_compare(
                        seed, root, total_steps=args.total_steps,
                        ckpt_every=args.ckpt_every,
                        replica_every_k=args.replica_every_k or 2,
                        n_hosts=args.hosts))
                else:
                    all_stats.append(run_pod_soak(
                        seed, total_steps=args.total_steps,
                        ckpt_every=args.ckpt_every,
                        ckpt_dir=os.path.join(root, "ckpt"),
                        coord_dir=os.path.join(root, "coord"),
                        n_hosts=args.hosts,
                        replica_every_k=args.replica_every_k,
                        scenario=args.scenario))
            except Exception as e:
                failures += 1
                print(f"  FAILED ({type(e).__name__}): {e}", file=sys.stderr)
            finally:
                if not args.keep_dirs:
                    shutil.rmtree(root, ignore_errors=True)
            continue
        ckpt_dir = tempfile.mkdtemp(prefix=f"chaos_soak_{seed}_")
        print(f"soak {i + 1}/{args.soaks} (seed={seed}) -> {ckpt_dir}")
        try:
            run_soak(seed, args.total_steps, args.ckpt_every, ckpt_dir)
        except Exception as e:
            failures += 1
            print(f"  FAILED ({type(e).__name__}): {e}", file=sys.stderr)
        finally:
            if not args.keep_dirs:
                shutil.rmtree(ckpt_dir, ignore_errors=True)
    if args.trace:
        from deepspeed_tpu.observability import (configure_tracer,
                                                 write_chrome_trace)

        configure_tracer(enabled=False)
        write_chrome_trace(args.trace, metadata={
            "tool": "chaos_soak", "mode": args.mode, "seed": args.seed,
            "soaks": args.soaks})
        print(f"trace artifact -> {args.trace}")
    if args.json:
        import json

        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            json.dump({"mode": args.mode, "soaks": args.soaks,
                       "failures": failures, "base_seed": args.seed,
                       "stats": all_stats}, f, indent=2, default=str)
        print(f"stats artifact -> {args.json}")
    print(f"chaos soak ({args.mode}): "
          f"{args.soaks - failures}/{args.soaks} converged")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
