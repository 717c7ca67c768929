"""`deepspeed_tpu` launcher CLI (reference ``launcher/runner.py:382``).

TPU-first redesign: the unit of launch is a **host process**, not a GPU rank.
Each host runs ONE controller process that drives all of its local TPU chips
(JAX single-controller-per-host model); the launcher's job is host discovery,
filtering, and fan-out — it does not manage per-chip ranks the way the
reference manages ``LOCAL_RANK`` per GPU (``launcher/launch.py:132``).

Resource discovery order:
  1. ``--hostfile`` (lines of ``hostname slots=N``; N = TPU chips, informational)
  2. single localhost fallback

Fan-out:
  - 1 host, rank 0 == us  -> exec locally (no ssh)
  - multiple hosts        -> ssh per host (pdsh-style thread fan-out), each
                             remote command exports the coordinator env
                             (COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID)
                             consumed by ``deepspeed_tpu.comm.init_distributed``
  - ``--simulate N``      -> N local processes on a virtual CPU platform
                             (debug SPMD code without a pod)

``--include`` / ``--exclude`` use the reference's filter syntax
(``runner.py:249``): ``host1@host2`` selects hosts, ``host1:0,2@host2:0-3``
selects chip slots (slot selection narrows the advertised chip count; chip
*visibility* is delegated to the TPU runtime via TPU_VISIBLE_CHIPS).
"""
from __future__ import annotations

import argparse
import base64
import json
import os
import shlex
import subprocess
import sys
from collections import OrderedDict
from typing import Dict, List, Optional

from ..utils.logging import logger

DEFAULT_COORD_PORT = 8476


def parse_args(args=None):
    p = argparse.ArgumentParser(
        prog="deepspeed_tpu",
        description="deepspeed_tpu multi-host launcher",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("-H", "--hostfile", default="/job/hostfile",
                   help="hostfile: lines of 'hostname slots=N'")
    p.add_argument("-i", "--include", default="",
                   help="hosts/slots to include, e.g. 'h1@h2' or 'h1:0,1@h2:0-3'")
    p.add_argument("-e", "--exclude", default="",
                   help="hosts/slots to exclude (mutually exclusive with -i per host)")
    p.add_argument("--num_nodes", type=int, default=-1,
                   help="cap the number of hosts used (first N of the pool)")
    p.add_argument("--num_chips", "--num_gpus", dest="num_chips", type=int,
                   default=-1, help="cap advertised chips per host")
    p.add_argument("--master_addr", default="",
                   help="coordinator address; default = first host in the pool")
    p.add_argument("--master_port", type=int, default=DEFAULT_COORD_PORT,
                   help="coordinator port")
    p.add_argument("--launcher", default="ssh",
                   choices=["ssh", "local", "pod", "slurm", "openmpi", "impi",
                            "mpich"],
                   help="multinode backend: ssh fan-out, local subprocesses, "
                        "'pod' = TPU-VM/GKE metadata discovery + ssh, "
                        "'slurm' = srun, 'openmpi'/'impi'/'mpich' = mpirun")
    p.add_argument("--launcher_args", default="",
                   help="extra args spliced into the selected backend's "
                        "command: ssh flags for ssh/pod (e.g. '-p 2222'), "
                        "srun flags for slurm (e.g. '--partition=tpu'), "
                        "mpirun flags for openmpi/mpich/impi")
    p.add_argument("--ssh_port", type=int, default=None)
    p.add_argument("--module", action="store_true",
                   help="run user_script as 'python -m <module>'")
    p.add_argument("--no_python", action="store_true",
                   help="exec user_script directly (no python interpreter)")
    p.add_argument("--simulate", type=int, default=0, metavar="N",
                   help="run N local processes on a virtual CPU platform "
                        "(SPMD debugging without a pod)")
    p.add_argument("--save_pid", action="store_true",
                   help="write launcher pid to /tmp/ds_tpu_launcher.pid")
    p.add_argument("--elastic_restarts", type=int, default=0, metavar="N",
                   help="elastic supervisor: relaunch the job up to N times "
                        "on failure/preemption, re-discovering resources "
                        "each round (0 = off); training scripts should use "
                        "elasticity.ElasticAgent so restarts resume from "
                        "the last committed checkpoint")
    p.add_argument("--elastic_backoff", type=float, default=3.0,
                   help="base seconds between elastic relaunches (grows "
                        "exponentially with consecutive failures, jittered)")
    p.add_argument("--elastic_backoff_max", type=float, default=60.0,
                   help="cap on the exponential relaunch backoff")
    p.add_argument("--elastic_zero_progress", type=int, default=0,
                   metavar="K",
                   help="circuit breaker: stop relaunching after K "
                        "consecutive failed rounds with no checkpoint "
                        "progress (0 = off; needs --elastic_ckpt_dir)")
    p.add_argument("--elastic_ckpt_dir", default="",
                   help="checkpoint dir the training script writes; lets "
                        "the supervisor track committed-step progress so "
                        "productive restarts refresh the restart budget")
    p.add_argument("--pod_coord_dir", default="",
                   help="pod coordination store root (storage every host "
                        "mounts, e.g. next to the checkpoint dir): enables "
                        "pod-level fault tolerance — heartbeat leases, "
                        "dead-host exclusion, and a monotonically bumped "
                        "pod generation exported to every round as "
                        "DS_TPU_POD_GENERATION (docs/POD.md)")
    p.add_argument("--pod_lease", type=float, default=5.0,
                   help="heartbeat lease period in seconds (hosts renew at "
                        "lease/3; a host is dead after pod_miss_limit "
                        "missed leases)")
    p.add_argument("--pod_miss_limit", type=int, default=3,
                   help="missed leases before a host is declared dead and "
                        "peers exit 87 for pod re-formation")
    p.add_argument("--fleet", type=int, default=0, metavar="N",
                   help="serving fleet tier: export DS_TPU_FLEET_SIZE=N "
                        "plus the fleet lease contract so the serving "
                        "script builds N leased engines and a FleetRouter "
                        "on the coordination store — one binary, train or "
                        "serve, elastic either way (docs/FLEET.md)")
    p.add_argument("--fleet_coord_dir", default="",
                   help="fleet coordination store root (defaults to "
                        "--pod_coord_dir): engines lease under fleet/*, "
                        "the router is elected by CAS on fleet/coordinator")
    p.add_argument("--fleet_lease", type=float, default=5.0,
                   help="fleet engine lease period in seconds; the router "
                        "fails an engine's requests over to survivors "
                        "after fleet_miss_limit missed leases")
    p.add_argument("--fleet_miss_limit", type=int, default=3,
                   help="missed leases before the router declares an "
                        "engine dead and fails its requests over")
    p.add_argument("--fleet_daemon", action="store_true",
                   help="host-scale fleet (docs/FLEET.md): spawn the N "
                        "--fleet members as PER-PROCESS member daemons "
                        "(tools/fleet_member.py children, store-only "
                        "coupling) instead of in-process engines; the "
                        "serving script drives StoreMemberProxy handles")
    p.add_argument("--fleet_routers", type=int, default=0, metavar="N",
                   help="sharded admission: export DS_TPU_FLEET_ROUTERS=N "
                        "so the serving script runs N routers under one "
                        "coordinator election, each CAS-claiming admission "
                        "partitions (rid-hash sharded; docs/FLEET.md)")
    p.add_argument("--force_multi", action="store_true",
                   help="use the multinode path even for a single local host")
    p.add_argument("user_script", help="training script (or module with --module)")
    p.add_argument("user_args", nargs=argparse.REMAINDER)
    parsed = p.parse_args(args)
    if parsed.elastic_zero_progress > 0 and not parsed.elastic_ckpt_dir:
        # without a progress source the breaker silently never arms — the
        # job would crash-loop through the whole restart budget undiagnosed
        p.error("--elastic_zero_progress needs --elastic_ckpt_dir (the "
                "breaker tracks committed checkpoint steps)")
    if parsed.fleet:
        if parsed.fleet < 1:
            p.error(f"--fleet {parsed.fleet}: need at least one engine")
        if not (parsed.fleet_coord_dir or parsed.pod_coord_dir):
            p.error("--fleet needs a coordination store: pass "
                    "--fleet_coord_dir (or --pod_coord_dir, which it "
                    "defaults to) — engine leases and the coordinator "
                    "election live there")
    if parsed.fleet_daemon and not parsed.fleet:
        p.error("--fleet_daemon needs --fleet N: the daemons ARE the "
                "fleet members")
    if parsed.fleet_routers:
        if parsed.fleet_routers < 1:
            p.error(f"--fleet_routers {parsed.fleet_routers}: need at "
                    "least one router")
        if not parsed.fleet:
            p.error("--fleet_routers needs --fleet N: routers shard "
                    "admission over the fleet's store")
    return parsed


def fleet_env(args) -> dict:
    """The fleet contract exported to every child process: size, store
    root, and lease cadence — ``InferenceEngine.serving_fleet`` consumers
    read these to build their members (docs/FLEET.md)."""
    if not args.fleet:
        return {}
    env = {
        "DS_TPU_FLEET_SIZE": str(args.fleet),
        "DS_TPU_FLEET_COORD_DIR": args.fleet_coord_dir or args.pod_coord_dir,
        "DS_TPU_FLEET_LEASE": str(args.fleet_lease),
        "DS_TPU_FLEET_MISS_LIMIT": str(args.fleet_miss_limit),
    }
    if args.fleet_daemon:
        # the members run as child daemon processes: the serving script
        # builds StoreMemberProxy handles instead of in-process engines
        env["DS_TPU_FLEET_DAEMON"] = "1"
    if args.fleet_routers:
        env["DS_TPU_FLEET_ROUTERS"] = str(args.fleet_routers)
    return env


def spawn_fleet_daemons(args, env) -> list:
    """Start the ``--fleet N`` member daemons as children of the launcher
    (one ``tools/fleet_member.py`` process per engine, store coupling
    only).  Returns the ``subprocess.Popen`` handles; the caller reaps
    them after the serving script exits (the script itself shuts members
    down through the control channel)."""
    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "..", "tools", "fleet_member.py")
    script = os.path.normpath(script)
    if not os.path.isfile(script):
        raise FileNotFoundError(
            f"--fleet_daemon: member entry point not found at {script}")
    procs = []
    for i in range(args.fleet):
        child_env = dict(env)
        child_env["DS_TPU_FLEET_ENGINE_ID"] = f"engine{i}"
        procs.append(subprocess.Popen(
            [sys.executable, script], env=child_env))
        logger.info("launcher: fleet member daemon engine%d -> pid %d",
                    i, procs[-1].pid)
    return procs


def fetch_hostfile(path: str) -> "OrderedDict[str, int]":
    """Parse ``hostname slots=N`` lines; missing file -> empty pool."""
    if not os.path.isfile(path):
        return OrderedDict()
    pool: "OrderedDict[str, int]" = OrderedDict()
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.split("#")[0].strip()
            if not line:
                continue
            parts = line.split()
            host = parts[0]
            slots = 1
            for tok in parts[1:]:
                if tok.startswith("slots="):
                    try:
                        slots = int(tok.split("=", 1)[1])
                    except ValueError:
                        raise ValueError(f"{path}:{ln}: bad slots in {line!r}")
                else:
                    raise ValueError(
                        f"{path}:{ln}: unrecognized token {tok!r} "
                        f"(expected 'slots=N')")
            if host in pool:
                raise ValueError(f"{path}:{ln}: duplicate host {host!r}")
            pool[host] = slots
    return pool


def _expand_slots(spec: str, nslots: int) -> List[int]:
    """'0,2' | '0-3' | '1,3-5' -> sorted slot indices, validated."""
    out = set()
    for piece in spec.split(","):
        piece = piece.strip()
        if not piece:
            continue
        if "-" in piece:
            lo, hi = piece.split("-", 1)
            out.update(range(int(lo), int(hi) + 1))
        else:
            out.add(int(piece))
    bad = [s for s in out if s < 0 or s >= nslots]
    if bad:
        raise ValueError(f"slot(s) {sorted(bad)} out of range [0,{nslots})")
    return sorted(out)


def parse_resource_filter(pool: "OrderedDict[str, int]", include: str = "",
                          exclude: str = "") -> "OrderedDict[str, List[int]]":
    """Apply the '@'-separated host[:slots] filter grammar to the pool.

    Returns host -> selected slot indices.  A host may appear in include or
    exclude, not both; slot-less exclude drops the whole host.
    """
    full: "OrderedDict[str, List[int]]" = OrderedDict(
        (h, list(range(n))) for h, n in pool.items())
    if include and exclude:
        inc_hosts = {t.split(":")[0] for t in include.split("@") if t}
        exc_hosts = {t.split(":")[0] for t in exclude.split("@") if t}
        both = inc_hosts & exc_hosts
        if both:
            raise ValueError(f"host(s) {sorted(both)} in both -i and -e")

    def _parse(filter_str):
        sel: "OrderedDict[str, Optional[List[int]]]" = OrderedDict()
        for term in filter_str.split("@"):
            term = term.strip()
            if not term:
                continue
            if ":" in term:
                host, slots = term.split(":", 1)
                host = host.strip()
                if host not in full:
                    raise ValueError(f"filter host {host!r} not in resource pool")
                sel[host] = _expand_slots(slots, pool[host])
            else:
                if term not in full:
                    raise ValueError(f"filter host {term!r} not in resource pool")
                sel[term] = None  # whole host
        return sel

    if include:
        inc = _parse(include)
        out: "OrderedDict[str, List[int]]" = OrderedDict()
        for h, slots in inc.items():
            out[h] = slots if slots is not None else full[h]
        return out
    if exclude:
        exc = _parse(exclude)
        out = OrderedDict()
        for h, slots in full.items():
            if h in exc:
                dropped = exc[h]
                if dropped is None:
                    continue  # whole host excluded
                keep = [s for s in slots if s not in dropped]
                if keep:
                    out[h] = keep
            else:
                out[h] = slots
        return out
    return full


def encode_world_info(active: "OrderedDict[str, List[int]]") -> str:
    return base64.urlsafe_b64encode(
        json.dumps(active).encode()).decode()


def decode_world_info(blob: str) -> "OrderedDict[str, List[int]]":
    return OrderedDict(json.loads(base64.urlsafe_b64decode(blob.encode())))


def _build_user_cmd(args) -> List[str]:
    if args.no_python:
        cmd = [args.user_script]
    elif args.module:
        cmd = [sys.executable, "-u", "-m", args.user_script]
    else:
        cmd = [sys.executable, "-u", args.user_script]
    return cmd + list(args.user_args)


def _run_local_single(args, active) -> int:
    env = dict(os.environ)
    env.pop("COORDINATOR_ADDRESS", None)  # single-process mode
    env.update(fleet_env(args))
    daemons = spawn_fleet_daemons(args, env) if args.fleet_daemon else []
    cmd = _build_user_cmd(args)
    logger.info("launcher: single-host local exec: %s", shlex.join(cmd))
    try:
        return subprocess.call(cmd, env=env)
    finally:
        for p in daemons:
            if p.poll() is None:
                p.terminate()
        for p in daemons:
            try:
                p.wait(timeout=10.0)
            except subprocess.TimeoutExpired:   # pragma: no cover
                p.kill()
                p.wait()


def wait_all_or_fail(procs, poll_s: float = 0.2, on_fail=None,
                     kill_grace_s: float = 15.0) -> int:
    """Wait on a set of processes; on the FIRST nonzero exit, terminate the
    survivors and return that exit code (a sequential ``wait`` loop would hang
    on an earlier-indexed process blocked in rendezvous while a later one has
    already died).  KeyboardInterrupt terminates everything and returns 130.
    ``on_fail(idx, rc)`` is called for the root-cause process only — never for
    the SIGTERM-ed survivors.  Reaping escalates SIGTERM -> SIGKILL after
    ``kill_grace_s``: a survivor blocked inside a native collective (its
    peer just died) never runs the python signal handler, so a plain
    ``wait()`` would hang the launcher forever."""
    import time

    def _reap_all():
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + kill_grace_s
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    try:
        while True:
            rcs = [p.poll() for p in procs]
            failed = [(i, rc) for i, rc in enumerate(rcs) if rc not in (None, 0)]
            if failed:
                _reap_all()
                idx, rc = failed[0]
                if on_fail is not None:
                    on_fail(idx, rc)
                return rc
            if all(rc is not None for rc in rcs):
                return 0
            time.sleep(poll_s)
    except KeyboardInterrupt:
        _reap_all()
        return 130


def _run_simulate(args, n: int) -> int:
    """N local processes, virtual CPU devices, loopback coordinator."""
    procs = []
    for pid in range(n):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "COORDINATOR_ADDRESS": f"127.0.0.1:{args.master_port}",
            "NUM_PROCESSES": str(n),
            "PROCESS_ID": str(pid),
            "TPU_VISIBLE_CHIPS": "",
        })
        procs.append(subprocess.Popen(_build_user_cmd(args), env=env))
    return wait_all_or_fail(procs)


def main(args=None) -> int:
    args = parse_args(args)
    if args.save_pid:
        with open("/tmp/ds_tpu_launcher.pid", "w") as f:
            f.write(str(os.getpid()))

    if args.elastic_restarts > 0:
        from ..elasticity.supervisor import Supervisor

        progress_fn = None
        if args.elastic_ckpt_dir:
            if args.pod_coord_dir:
                # pod mode: only ALL-HOSTS-committed tags count as progress
                # (a host-committed tag without a pod manifest is exactly
                # the state the restore path rejects)
                from ..resilience import pod_checkpoint_progress_fn

                progress_fn = pod_checkpoint_progress_fn(args.elastic_ckpt_dir)
            else:
                from ..resilience import checkpoint_progress_fn

                progress_fn = checkpoint_progress_fn(args.elastic_ckpt_dir)
        # every attempt re-runs _dispatch, i.e. re-reads the hostfile /
        # re-discovers the pod — a resized slice relaunches at its new size
        attempt = (_pod_attempt(args) if args.pod_coord_dir
                   else lambda _round: _dispatch(args))
        terminal_rcs = ()
        if args.pod_coord_dir:
            # exit 86 = healthy slice below the elastic floor: permanent by
            # contract (pod_agent.RC_POD_UNRECOVERABLE) — relaunching only
            # burns the backoff schedule and bumps generations pointlessly
            from ..elasticity.pod_agent import RC_POD_UNRECOVERABLE

            terminal_rcs = (RC_POD_UNRECOVERABLE,)
        return Supervisor(attempt,
                          max_restarts=args.elastic_restarts,
                          backoff_s=args.elastic_backoff,
                          backoff_max_s=args.elastic_backoff_max,
                          progress_fn=progress_fn,
                          zero_progress_limit=args.elastic_zero_progress,
                          terminal_rcs=terminal_rcs).run()
    return _dispatch(args)


def _pod_attempt(args):
    """Pod-aware round wrapper: every relaunch bumps the pod generation in
    the coordination store and exports the membership epoch + heartbeat
    contract to the children (docs/POD.md) — training scripts build their
    HeartbeatWatchdog / PodContext from these."""
    from ..elasticity.coordination import FileCoordinationStore, bump_generation

    store = FileCoordinationStore(args.pod_coord_dir)

    def attempt(_round: int) -> int:
        gen = bump_generation(store)
        os.environ["DS_TPU_POD_GENERATION"] = str(gen)
        os.environ["DS_TPU_POD_COORD_DIR"] = args.pod_coord_dir
        os.environ["DS_TPU_POD_LEASE"] = str(args.pod_lease)
        os.environ["DS_TPU_POD_MISS_LIMIT"] = str(args.pod_miss_limit)
        logger.info("launcher: pod generation %d (coordination store %s)",
                    gen, args.pod_coord_dir)
        return _dispatch(args)

    return attempt


def _shrink_to_admitted(active: "OrderedDict[str, List[int]]"
                        ) -> "OrderedDict[str, List[int]]":
    """Pod mode: when the scheduler snapshotted the elastic envelope
    (``DEEPSPEED_ELASTICITY_CONFIG``), trim the healthy pool to the largest
    host count the plan admits BEFORE launching — otherwise an inadmissible
    count (e.g. 3 healthy of a {1,2,4} plan) makes every child fail
    ``ElasticityIncompatibleWorldSize`` and the supervisor crash-loops the
    identical launch.  Without the env var the pool is launched as-is (the
    training script owns the config and the in-job PodSupervisor path does
    its own shrink)."""
    raw = os.environ.get("DEEPSPEED_ELASTICITY_CONFIG")
    if not raw or len(active) <= 1:
        return active
    try:
        from ..elasticity.pod_agent import shrink_to_healthy
        from ..runtime.config import ElasticityConfig

        members, plan = shrink_to_healthy(ElasticityConfig(**json.loads(raw)),
                                          list(active))
    except Exception as e:
        logger.warning("launcher: DEEPSPEED_ELASTICITY_CONFIG unusable for "
                       "pool shrinking (%s: %s); launching every healthy "
                       "host", type(e).__name__, e)
        return active
    if len(members) < len(active):
        # keep the pool's own ordering (coordinator = first ACTIVE host)
        kept = list(active)[:len(members)]
        logger.warning(
            "launcher: elastic plan admits %d of %d healthy host(s) "
            "(valid counts %s); launching %s", len(members), len(active),
            list(plan.valid_device_counts), kept)
        return OrderedDict((h, active[h]) for h in kept)
    return active


def _dispatch(args) -> int:
    """One discovery + launch round (the unit the elastic supervisor
    retries)."""
    if args.simulate > 0:
        return _run_simulate(args, args.simulate)

    pool = fetch_hostfile(args.hostfile)
    pod_info = None
    if args.launcher == "pod" or (not pool and args.launcher in
                                  ("slurm", "openmpi", "impi", "mpich")):
        # discovery-backed pools: TPU-VM/GKE metadata ('pod') or the SLURM
        # allocation env; a hostfile, when present, still wins for the
        # scheduler runners so operators can narrow the allocation
        from .pod import DEFAULT_SOURCES, discover_pod, pod_pool

        # a SLURM launch must get SLURM node names even when TPU metadata
        # is also present (srun rejects the metadata's bare IPs)
        sources = (("slurm", "env", "gce-metadata")
                   if args.launcher == "slurm" else DEFAULT_SOURCES)
        pod_info = discover_pod(coord_port=args.master_port, sources=sources)
        if args.launcher == "pod" and pod_info is None:
            raise RuntimeError(
                "--launcher pod: no pod discovered (need "
                "TPU_WORKER_HOSTNAMES, GCE metadata, or a SLURM "
                "allocation)")
        if pod_info is not None:
            # any discovery source feeds any scheduler runner: an mpi/slurm
            # launch on a TPU-VM pod uses the metadata-discovered hosts
            pool = pod_pool(pod_info)
        elif args.launcher in ("slurm", "openmpi", "impi", "mpich"):
            raise RuntimeError(
                f"--launcher {args.launcher}: no hostfile at "
                f"{args.hostfile!r} and no allocation/pod discovered — an "
                "explicit multi-host launcher must not silently degrade to "
                "a single local process")
    if not pool:
        if args.include or args.exclude or args.num_nodes > 0:
            raise ValueError(
                "host filters given but no hostfile found at "
                f"{args.hostfile!r} (single-host fallback has no pool)")
        pool = OrderedDict([("localhost", args.num_chips if args.num_chips > 0 else 1)])
    if args.pod_coord_dir:
        # shrink-to-healthy at the pool level: hosts a HeartbeatWatchdog
        # declared dead (durable `dead/<host>` markers) are excluded from
        # every later round until cleared (elasticity.clear_dead)
        from ..elasticity.coordination import FileCoordinationStore, dead_set

        dead = set(dead_set(FileCoordinationStore(args.pod_coord_dir)))
        if dead & set(pool):
            logger.warning(
                "launcher: excluding dead host(s) %s from the pool "
                "(pod coordination store %s)", sorted(dead & set(pool)),
                args.pod_coord_dir)
            pool = OrderedDict((h, s) for h, s in pool.items()
                               if h not in dead)
            if not pool:
                # permanent until an operator intervenes: exit with the
                # terminal code so the supervisor stops instead of burning
                # the restart budget re-discovering the same dead pool
                from ..elasticity.pod_agent import RC_POD_UNRECOVERABLE

                logger.error(
                    "every host in the pool is marked dead in the pod "
                    "coordination store — clear the markers once capacity "
                    "returns (elasticity.clear_dead)")
                return RC_POD_UNRECOVERABLE
    active = parse_resource_filter(pool, args.include, args.exclude)
    if args.num_nodes > 0:
        active = OrderedDict(list(active.items())[:args.num_nodes])
    if args.num_chips > 0:
        active = OrderedDict((h, s[:args.num_chips]) for h, s in active.items())
    if args.pod_coord_dir:
        active = _shrink_to_admitted(active)
    if not active:
        raise ValueError("resource filters selected zero hosts")

    hosts = list(active)
    multi = len(hosts) > 1 or args.force_multi
    if not multi and hosts[0] in ("localhost", "127.0.0.1"):
        return _run_local_single(args, active)

    from .multinode_runner import (LocalRunner, MPIRunner, PodRunner,
                                   SlurmRunner, SSHRunner)

    # coordinator = first ACTIVE host (not the discovered pod's worker 0:
    # filters may have excluded it, and every launched process must be able
    # to reach — and one of them bind — this address)
    master = args.master_addr or hosts[0]
    base_env = {
        "COORDINATOR_ADDRESS": f"{master}:{args.master_port}",
        "NUM_PROCESSES": str(len(hosts)),
        "DS_TPU_WORLD_INFO": encode_world_info(active),
    }
    if args.pod_coord_dir:
        # the pod contract must reach REMOTE children too (the supervisor
        # wrapper only set os.environ on the launcher host).  Without
        # --elastic_restarts no wrapper bumped the generation: fall back to
        # the store's current value rather than a silent 0.
        gen = os.environ.get("DS_TPU_POD_GENERATION")
        if not gen:
            from ..elasticity.coordination import (FileCoordinationStore,
                                                   read_generation)

            gen = str(read_generation(
                FileCoordinationStore(args.pod_coord_dir)))
        base_env["DS_TPU_POD_COORD_DIR"] = args.pod_coord_dir
        base_env["DS_TPU_POD_GENERATION"] = gen
        base_env["DS_TPU_POD_LEASE"] = str(args.pod_lease)
        base_env["DS_TPU_POD_MISS_LIMIT"] = str(args.pod_miss_limit)
    base_env.update(fleet_env(args))
    if args.launcher == "pod":
        runner = PodRunner(args, active, base_env, pool=pool, info=pod_info)
    elif args.launcher == "slurm":
        runner = SlurmRunner(args, active, base_env, pool=pool)
    elif args.launcher in ("openmpi", "impi", "mpich"):
        runner = MPIRunner(args, active, base_env, pool=pool)
    elif args.launcher == "ssh":
        runner = SSHRunner(args, active, base_env, pool=pool)
    else:
        runner = LocalRunner(args, active, base_env, pool=pool)
    return runner.launch(_build_user_cmd(args))


if __name__ == "__main__":
    sys.exit(main())
