"""Determinism & replica-consistency debugging (SURVEY §5 aux subsystem:
the reference ships cross-rank desync checks via ``deepspeed/utils/debug.py``
+ distributed norm checks; the TPU build plans its own).

Under single-controller GSPMD one program updates all shards, so classic
replica divergence cannot happen inside a step — the risks that remain are
(a) HOST-side divergence in multi-controller jobs (different processes
feeding different data/rng into what should be identical replicated state)
and (b) silent nondeterminism across reruns.  Both reduce to fingerprinting:

- :func:`checksum_tree` — stable per-leaf fingerprints of any pytree.
- :func:`assert_replicas_consistent` — multi-controller guard: every process
  contributes its fingerprint of process-local (addressable) replicated
  state; mismatch across processes raises before training silently forks.
- :func:`assert_deterministic` — rerun a function twice and require
  bitwise-equal outputs (catches e.g. nondeterministic reductions escaping
  into the training step).
"""
from __future__ import annotations

import hashlib
from typing import Any, Callable, Dict

import numpy as np

import jax

from .logging import log_dist


def _leaf_pieces(x) -> "Dict[str, np.ndarray]":
    """PROCESS-LOCAL data of a leaf as {shard-index-str: host array} —
    globally-sharded arrays (not fully addressable) yield their addressable
    shards, so this never tries to fetch remote shards in a multi-controller
    job; everything else yields one 'full' piece.  Same-index shards on
    multiple LOCAL devices are verified bitwise-equal before deduping — a
    silent dedupe would mask intra-process replica corruption."""
    shards = getattr(x, "addressable_shards", None)
    if shards is None or getattr(x, "is_fully_addressable", True):
        return {"full": np.asarray(jax.device_get(x))}
    pieces: Dict[str, np.ndarray] = {}
    for s in shards:
        idx = str(s.index)
        arr = np.asarray(s.data)
        kept = pieces.setdefault(idx, arr)
        if kept is not arr and kept.tobytes() != arr.tobytes():
            raise RuntimeError(
                f"intra-process replica divergence: local devices disagree "
                f"on shard {idx} of a {x.shape} {x.dtype} leaf")
    return pieces


def _piece_digest(arr: "np.ndarray") -> str:
    h = hashlib.sha256()
    h.update(arr.tobytes() + str(arr.dtype).encode() + str(arr.shape).encode())
    return h.hexdigest()[:16]


def _fingerprint_from_digests(digests: "Dict[str, str]") -> str:
    if set(digests) == {"full"}:
        return digests["full"]
    h = hashlib.sha256()
    for idx in sorted(digests):
        h.update(idx.encode() + digests[idx].encode())
    return h.hexdigest()[:16]


def _leaf_fingerprint(x) -> str:
    pieces = _leaf_pieces(x)
    return _fingerprint_from_digests(
        {idx: _piece_digest(arr) for idx, arr in pieces.items()})


def path_str(path) -> str:
    """'/'-joined name for a jax key path — the one shared spelling of the
    idiom (DictKey .key, SequenceKey .idx, GetAttrKey .name, else str).

    NOTE (intentional spelling change, round 4): GetAttrKey entries render
    as bare ``name`` here, where the pre-round-4 ``str(p)`` fallback rendered
    ``.name``.  Fingerprint KEYS over attr-keyed pytrees (dataclass /
    namedtuple nodes, e.g. optax opt_state) therefore differ from checksums
    recorded before that commit; the VALUES are unchanged.  Nothing in-tree
    persists these keys across versions — they are session-local debug
    fingerprints — so no compatibility alias is kept."""
    parts = []
    for p in path:
        part = getattr(p, "key", None)
        if part is None:
            part = getattr(p, "idx", None)
        if part is None:
            part = getattr(p, "name", None)
        parts.append(str(p if part is None else part))
    return "/".join(parts)


def checksum_tree(tree: Any) -> Dict[str, str]:
    """{'path': sha256-16} per leaf — a stable state fingerprint."""
    out: Dict[str, str] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[path_str(path)] = _leaf_fingerprint(leaf)
    return out


def _split64(hexdigest16: str):
    v = int(hexdigest16, 16)
    return (v >> 32) & 0xFFFFFFFF, v & 0xFFFFFFFF


def _shard_digest_rows(piece_digests) -> "np.ndarray":
    """One uint32 row per (leaf, DISTINCT local shard):
    ``[leaf_id, index_hash_hi, index_hash_lo, data_hash_hi, data_hash_lo]``.
    The index hash identifies WHICH slice of the global array the shard is;
    two processes holding the same (leaf, index) hold replicas of the same
    bytes and must agree.  Replicated-across-local-devices shards dedupe to
    one row so every process contributes the same row count regardless of
    its local device count.  ``piece_digests`` = per-leaf {index: digest}
    (computed once, shared with the local fingerprints)."""
    rows = []
    for li, digests in enumerate(piece_digests):
        for idx_str in sorted(digests):
            ih = _split64(hashlib.sha256(idx_str.encode()).hexdigest()[:16])
            dh = _split64(digests[idx_str])
            rows.append([li, ih[0], ih[1], dh[0], dh[1]])
    return np.asarray(rows, np.uint32).reshape(-1, 5)


def assert_replicas_consistent(tree: Any, name: str = "state") -> Dict[str, str]:
    """Multi-controller desync guard, complete for ARBITRARY shardings:
    every pair of processes holding the same (leaf, shard-index) — fully
    replicated leaves, and the replica groups of partially-sharded ones
    (e.g. dp-replicated × mp-sharded) — must hold identical bytes.  Shards
    that exist on exactly one process have no replica and are implicitly
    clean.  The check all-gathers a small per-shard digest table (uint32
    words — jnp round-trips silently downcast uint64 under the default
    x64-disabled config) and verifies it identically on every process.
    Single-process: a no-op beyond computing the checksum.  Returns the
    local per-leaf checksums."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    # ONE device_get+hash pass serves both the returned fingerprints and the
    # cross-process digest table (state can be multi-GB; fetching it twice
    # per check would double host-transfer and SHA time)
    piece_digests = []
    local: Dict[str, str] = {}
    for p, leaf in flat:
        pieces = _leaf_pieces(leaf)
        digests = {idx: _piece_digest(arr) for idx, arr in pieces.items()}
        piece_digests.append(digests)
        local[path_str(p)] = _fingerprint_from_digests(digests)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        rows = _shard_digest_rows(piece_digests)
        # [nproc, nrows, 5]; requires equal row counts per process — true on
        # symmetric meshes, and an asymmetric topology fails loudly here
        gathered = np.asarray(multihost_utils.process_allgather(rows))
        seen: Dict[tuple, tuple] = {}
        diverged = []
        for proc in range(gathered.shape[0]):
            for li, ih0, ih1, dh0, dh1 in gathered[proc]:
                key = (int(li), int(ih0), int(ih1))
                dig = (int(dh0), int(dh1))
                prev = seen.setdefault(key, (proc, dig))
                if prev[1] != dig:
                    diverged.append((path_str(flat[int(li)][0]), prev[0], proc))
        if diverged:
            uniq = sorted({d[0] for d in diverged})
            pairs = sorted({(a, b) for _, a, b in diverged})
            raise RuntimeError(
                f"replica divergence in {name}: {len(uniq)} leaves hold "
                f"differing replicas across processes (leaves: {uniq[:5]}; "
                f"process pairs: {pairs[:5]})")
    log_dist(f"{name}: {len(local)} leaves replica-consistent", ranks=[0])
    return local


def assert_deterministic(fn: Callable, *args, what: str = "fn") -> Any:
    """Run ``fn`` twice with identical inputs; raise unless outputs are
    bitwise equal.  Returns the (first) output."""
    out1, out2 = fn(*args), fn(*args)
    c1, c2 = checksum_tree(out1), checksum_tree(out2)
    diff = sorted(k for k in c1 if c1[k] != c2.get(k))
    if diff:
        raise RuntimeError(
            f"{what} is nondeterministic: {len(diff)} output leaves changed "
            f"between identical calls (first: {diff[:5]})")
    return out1
