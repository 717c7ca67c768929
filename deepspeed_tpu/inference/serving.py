"""Continuous-batching serving engine: slot-based decode over a paged KV pool.

``InferenceEngine.generate()`` is one-shot: it compiles a program per
``(B, S_pad, max_new)`` tuple, runs the whole batch in lockstep until the
longest request finishes, and admits no new work mid-flight — exactly the
regime Orca (OSDI '22, iteration-level scheduling) and vLLM (SOSP '23,
PagedAttention) showed leaves 2-10x decode throughput on the table under
mixed-length request streams.

:class:`ServingEngine` is the TPU-native redesign:

- a fixed fleet of ``b_slots`` decode slots backed by ONE persistent
  block-paged KV pool (``models.transformer.init_paged_cache``: lane-aligned
  128-token pages, physical page 0 reserved as the trash page);
- an iteration-level loop — each :meth:`step` runs ONE fixed-shape jitted
  decode program over all slots (inactive slots ride along masked), retires
  finished/EOS slots, and admits queued requests into free slots via
  bucketed fixed-shape ``[1, S_pad]`` prefill programs that scatter straight
  into the paged pool;
- exactly ``1 + len(prefill buckets)`` program shapes at steady state
  (:meth:`program_inventory`), so admission NEVER retraces or recompiles and
  short requests no longer convoy behind long ones.

Decode math stays on the XLA einsum path — the Pallas decode kernel was
retired in round 5 on an honest A/B; this win is scheduling, not kernels.

Multi-chip serving (docs/SERVING.md "Multi-chip serving"): the engine is
split into a HOST scheduling half (this class — admission, page tables,
prefix index, deadlines; pure Python over numpy) and a mesh-wide
execution half (:class:`~.execution.MeshExecutor` — the paged KV pool,
its NamedSharding placement, and every jitted fixed-shape program).
With ``mesh=`` the pool shards its KV-head dim over the mesh's
``'model'`` axis and the weights ride the same auto-TP specs
``generate()`` uses, so every steady-state program — decode tick,
bucketed prefill, COW snapshot, speculative draft/verify — is ONE GSPMD
program spanning the whole mesh, token-exact with the unsharded engine,
and per-device KV bytes shrink ~1/tp.  The zero-recompile inventory,
warm-restart program adoption and all the resilience paths below are
mesh-agnostic: they live on the host side of the split.

Scheduling policy (docs/SERVING.md "Scheduling policy"): FIFO admission
with head-of-line blocking (no request skipping, so no starvation), and **a
slot's pages follow its length**.  Admission reserves the pages that hold
the prompt's rows and the row of the first decode tick; a live slot takes
one more page before the tick whose row starts it is launched
(:meth:`ServingEngine._grow_pages`); and when the pool has none, after the
reclaim of cold prefix pages that admission runs too, the slot admitted
last gives its pages up (never the one asking unless it is the youngest):
its request goes back to the HEAD of the queue with the tokens it has
emitted kept beside it, and its readmission rebuilds the rows of prompt and
tokens with the prefill programs already built, in pieces no longer than
the largest of them, and goes on where it stopped — every token emitted
once, one :class:`RequestResult` a ``rid``.  It cannot deadlock: the oldest
slot is never the victim while another is live, one live slot may own the
whole pool, and ``submit`` refuses a request the whole pool cannot hold.
In a pool of the full reservation (the default ``num_pages``) the next page
is always there, nothing is preempted and slots bound admission.  A cache a
tail prefill cannot rebuild (``cache_layout.REFUSED``: a window's rings, a
latent leaf, a state a slot) and a speculative engine, whose verify block
writes rows past a tick's one, keep the whole reservation (prompt +
max_new) taken at admission.

Cross-request KV reuse (docs/SERVING.md "Cross-request KV reuse"): physical
pages are REFCOUNTED and immutable-once-full, and a prefix index
(``prefix_cache.PrefixIndex``: rolling hash over page-aligned token chunks →
physical page) lets a request whose prompt prefix is already resident map
the shared pages into its page table and prefill only the unshared tail —
copy-on-write applies to the one partial boundary page (a fixed-shape
snapshot program; see ``models.transformer.cow_copy_pool``).  Admission
reserves only unshared pages; retirement, expiry and quarantine DROP
refcounts instead of freeing, and the index holds one refcount per cached
page so hot prefixes survive their donors.  The pool invariant becomes
``free + quarantined + referenced == num_pages - 1``
(:meth:`ServingEngine.page_accounting`).  Sharing is pure page-table
indirection: the program inventory is unchanged at steady state and
shared-prefix outputs stay token-exact with the unshared path (K/V at
position ``t`` is a pure function of tokens ``0..t``).

KV-page tiering (docs/SERVING.md "KV-page tiering"): with
``host_tier_pages=N`` the reclaim path DEMOTES cold full prefix pages to a
host-RAM tier (``inference/kv_tiering.py``) instead of evicting them, and a
prefix hit on a demoted entry PROMOTES the page back into a free device
slot before admission maps it — the cache working set is bounded by host
RAM, not HBM.  The tier movers are fixed-shape programs compiled at init
(zero-recompile preserved), the device-pool invariant extends with a
demoted ledger (``demoted == host-tier size``, folded into
``page_accounting()["balanced"]``), and host buffers survive supervisor
warm restarts and ``recycle()`` (:meth:`adopt_host_tier`).

Generation runs per-slot RNG lanes (docs/SERVING.md "Sampling"): each
request may carry a :class:`~.sampling.SamplingParams` (temperature /
top-k / top-p / seed) and the ONE decode program samples with *traced*
per-slot parameter vectors — greedy is just the ``temperature <= 0`` lane
value, so any mix of greedy and sampled slots shares the same compiled
program and admission never recompiles.  Keys are counter-based
(``fold_in(PRNGKey(seed), position)``), which makes sampled streams
engine-independent and replay/failover-exact, and keeps the parity
contract: same seed/params ⇒ serving output token-identical to
``generate(sampling=...)``.  With ``speculative=``
(:class:`~.speculative.SpeculativeConfig`) a small draft model decodes k
candidates per tick against its own mirrored paged pool and the target
verifies all k in one fixed-shape pass — 1..k tokens per slot per tick,
target distribution preserved by in-graph rejection sampling, greedy
speculative token-exact vs non-speculative greedy.  The loop is
host-driven and synchronous: one device program + one [B_slots] token
fetch per tick (k+1 programs per tick under speculation).

Resilience (docs/SERVING.md "Failure handling"): per-request deadlines and a
bounded admission queue with explicit load shedding — expired or shed
requests finish with a typed :class:`RequestResult` (``finish_reason``
``"deadline"`` / ``"shed"``) carrying a ``retry_after_s`` hint instead of
occupying pages forever; a slot whose prefill fails repeatedly is
quarantined (fenced from scheduling, its pages leaked-and-accounted);
:meth:`health` snapshots the loop and :meth:`drain` stops admission,
finishes in-flight work and hands back unserved requests.  Fault-injection
sites: ``serve.tick`` (every tick), ``serve.admit`` (every admission),
``serve.prefill`` / ``serve.decode`` (immediately before the respective
device calls — see resilience/fault_injection.py).  An optional
:class:`~deepspeed_tpu.resilience.HangWatchdog` can be armed around each
device step so a wedged collective becomes a stack report + a
supervisor-recyclable exit instead of a silent forever-hang
(docs/RESILIENCE.md).  :class:`~.serving_supervisor.ServingSupervisor`
wraps this engine with a warm-restart loop that replays the queue and
in-flight requests token-exactly after a poisoned-pool or injected failure.

Observability (docs/OBSERVABILITY.md): every tick/admission/prefill/decode
runs under a ``serve.*`` span on the process-global tracer (no-op when
tracing is disabled), so a flight-recorder dump after a fault covers the
poisoned tick, and :class:`RequestResult` carries a per-request timeline
(``queued_s``, ``ttft_s``, ``decode_ticks``, ``replays``).
"""
from __future__ import annotations

import bisect
import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..models.mixers import MIXERS
from ..models.transformer import PAGE_SIZE
from ..observability.device_profiler import (device_trace_unit,
                                             maybe_capture_from_env)
from ..observability.program_stats import ProgramCatalog
from ..observability.slo import SloEvaluator, SloRule
from ..observability.trace import (get_tracer, new_trace_id, trace_count,
                                   trace_context, trace_span)
from ..resilience import (SITE_SERVE_ADMIT, SITE_SERVE_DECODE,
                          SITE_SERVE_PREFILL, SITE_SERVE_TICK, maybe_fire)
from ..utils.logging import log_dist, logger
from .adapters import AdapterRegistry
from .cache_layout import CacheLayout
from .engine import InferenceEngine
from .execution import MeshExecutor
from .kv_tiering import HostTier
from .page_pool import PagePool
from .prefix_cache import PrefixIndex, PrefixMatch
from .sampling import SamplingParams, as_lanes
from .speculative import SpeculativeConfig, SpeculativeDecoder

_bucket = InferenceEngine._bucket   # shared prompt-length bucketing (pow2>=16)

# a COW boundary match must save at least this much prefill to be worth a
# cross-layer page snapshot — a 1-token match (first tokens coinciding by
# chance, ~1/vocab per prompt pair) would pay a pool-shaped copy to skip one
# token of prefill
MIN_COW_TOKENS = 2


class ServeTimeout(RuntimeError):
    """``run``/``drain`` exceeded its ``max_ticks`` budget.  Deliberately
    NOT retried by :class:`~.serving_supervisor.ServingSupervisor` — a tick
    budget is a test/caller bound, not a fault."""


class PoolConsumedError(RuntimeError):
    """The donated KV pool was consumed by a failed device call — the engine
    cannot continue and must be rebuilt (``ServingSupervisor`` does this
    automatically, replaying queue + in-flight requests)."""


class SlotPrefillError(RuntimeError):
    """A prefill failed in a way attributable to one slot/request; the
    reservation was unwound and the request re-queued.  When the pool
    survived (the failure fired before the device call) the
    engine keeps serving — no restart needed."""

    def __init__(self, msg: str, slot: int, rid: Any, quarantined: bool):
        super().__init__(msg)
        self.slot = slot
        self.rid = rid
        self.quarantined = quarantined


@dataclasses.dataclass
class Request:
    """One generation request.  ``arrival_time`` is seconds relative to the
    start of :meth:`ServingEngine.run` (0 = available immediately);
    ``deadline_s`` is a serving budget measured from arrival — a request
    still queued (or still decoding) past it finishes with
    ``finish_reason="deadline"`` instead of occupying queue/pages forever."""
    rid: Any
    input_ids: np.ndarray
    max_new_tokens: int = 32
    eos_token_id: Optional[int] = None
    arrival_time: float = 0.0
    deadline_s: Optional[float] = None
    # absolute time.monotonic() stamp of when the request FIRST became
    # available, stamped by ServingSupervisor._rebase across a warm restart
    # (None = derive from this engine's clock).  Keeps queued-age gauges,
    # arrival_s/ttft_s stamps and retry hints anchored to the true arrival
    # instead of the replacement engine's reset clock (docs/SERVING.md).
    arrival_epoch_s: Optional[float] = None
    # per-request sampling lane (None = greedy, the historical contract).
    # Counter-based keys (fold_in(PRNGKey(seed), position)) make the
    # sampled stream a pure function of (seed, params, model), so replay,
    # failover resume and cross-engine parity with generate(sampling=...)
    # all stay token-exact (docs/SERVING.md "Sampling").
    sampling: Optional[SamplingParams] = None
    # fleet-wide trace id (docs/OBSERVABILITY.md "Distributed tracing"):
    # one id per REQUEST, assigned at first submission (router or engine)
    # and propagated verbatim through every hop — warm-restart replays,
    # failover re-dispatches and journal reconstructions all continue the
    # SAME trace, so one request is one trace across the whole fleet.
    trace_id: Optional[str] = None
    # tenant adapter (docs/SERVING.md "Multi-tenant adapter serving"):
    # None = the shared base model; an id must be registered with the
    # engine's AdapterRegistry — resolution happens at submission (under
    # the serve.adapter_resolve span) so an unknown tenant is a loud
    # ValueError, never a silently-base-served stream.  The id rides
    # every fleet hop (journal docs, failover re-dispatches) so a resumed
    # stream continues under the SAME tenant weights.
    adapter_id: Optional[str] = None


@dataclasses.dataclass
class RequestResult:
    rid: Any
    input_ids: np.ndarray
    output_ids: np.ndarray          # generated tokens (incl. eos when hit)
    finish_reason: str              # "eos" | "length" | "deadline" | "shed"
    prefill_bucket: int
    # absolute time.monotonic() stamps (arrival = admission availability)
    arrival_s: float = 0.0
    admit_s: float = 0.0
    first_token_s: float = 0.0
    finish_s: float = 0.0
    # set on "shed" and queue-expired "deadline" results: a backlog-derived
    # hint for when a resubmission is likely to be admitted
    retry_after_s: Optional[float] = None
    # ---- per-request timeline (docs/OBSERVABILITY.md): decode program
    # invocations that fed this request, and how many times a warm restart
    # re-prefilled it (ServingSupervisor stamps both when stitching replayed
    # results).  Prefill-emitted tokens (one per incarnation) are not decode
    # ticks, so for any result that generated tokens
    # decode_ticks == len(output_ids) - 1 - replays (less one more for
    # each of `preemptions` whose readmission's prefill emitted a token in
    # a tick's place); empty-output terminals (shed / queue-expired) carry
    # 0/0.
    decode_ticks: int = 0
    replays: int = 0
    # times the request gave its pages up to an older slot and was
    # readmitted (docs/SERVING.md "Scheduling policy"): each readmission
    # rebuilt its rows by prefill and emitted one token from it.  Always 0
    # in a pool of the full reservation.
    preemptions: int = 0
    # prompt tokens served from the prefix index at admission (shared full
    # pages + the COW boundary) instead of being re-prefilled — 0 on a cold
    # admission or when prefix caching is disabled.  For a replayed request
    # this is the LAST incarnation's share (its replay prompt includes the
    # already-generated tokens, which often re-share against the rebuilt
    # index).
    shared_prefix_tokens: int = 0
    # times a fleet router re-routed this request to a surviving engine
    # after its assigned engine's lease lapsed (inference/fleet.py) —
    # distinct from `replays`, which counts SAME-engine warm-restart
    # re-prefills: a failover re-prefills the journaled stream (or, with
    # no journal, the ORIGINAL prompt) on a different engine.
    failovers: int = 0
    # tokens of this output that were RESUMED from the fleet token journal
    # after a failover rather than decoded by the engine that finished the
    # request: the replacement re-prefilled prompt + journaled tokens as
    # pure KV reconstruction and resumed decoding AFTER the last journaled
    # token, so these tokens were never re-emitted (inference/fleet.py).
    # They contribute no decode_ticks (decode_ticks counts the finishing
    # stream's own decode-program invocations).  0 = no mid-stream resume.
    resumed_tokens: int = 0
    # the request's fleet-wide trace id (mirrors Request.trace_id)
    trace_id: Optional[str] = None
    # the tenant adapter this stream was served under (mirrors
    # Request.adapter_id; None = shared base model) — per-tenant
    # token-exactness checks key results by this
    adapter_id: Optional[str] = None
    # structured lifecycle record (docs/OBSERVABILITY.md "Distributed
    # tracing"): ordered (event, t, src) tuples covering
    # queued→admit→[prefix_match/cow]→prefill→first_token→
    # [replay|failover|resume]→finish.  `t` is time.monotonic() on the
    # recording process; `src` is the engine incarnation (int) for
    # engine-recorded events and an engine/router id (str) for
    # fleet-recorded ones.  ServingSupervisor and FleetRouter stitch the
    # record across incarnations and engines exactly like they stitch
    # tokens, so a failed-over request's record reads end to end.
    lifecycle: List = dataclasses.field(default_factory=list)
    # one time.monotonic() stamp per output token (float64, same length as
    # output_ids): the prefill's stamp for token 0, then one clock read per
    # decode tick, taken right after the fetch — a speculative tick gives
    # its 1..k tokens the same stamp.  Non-decreasing, token_s[0] ==
    # first_token_s, token_s[-1] <= finish_s.  Tokens resumed from the
    # fleet journal (which carries no stamps) read NaN; empty for shed and
    # queue-expired results.  np.diff(token_s) is the inter-token latency
    # a streaming client saw (docs/OBSERVABILITY.md "Per-request
    # timelines").
    token_s: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.float64))

    @property
    def ttft_s(self) -> float:
        """Time to first token, from arrival (includes queueing)."""
        return self.first_token_s - self.arrival_s

    @property
    def latency_s(self) -> float:
        return self.finish_s - self.arrival_s

    @property
    def queued_s(self) -> float:
        """Time from arrival to slot admission (pure queueing, no decode)."""
        return self.admit_s - self.arrival_s


@dataclasses.dataclass
class _Slot:
    request: Request
    pages: List[int]            # shared prefix pages first, then private
    tokens: List[int]
    bucket: int
    arrival_s: float
    admit_s: float
    first_token_s: float
    shared_tokens: int = 0      # prompt tokens mapped from the prefix index
    # decode-program invocations that fed this slot (the prefill token is
    # not one).  Without speculation this is len(tokens) - 1; a speculative
    # verify tick emits 1..k+1 tokens per invocation, so it can be less.
    decode_ticks: int = 0
    # lifecycle events recorded so far (moved from _lifecycle_pending at
    # admission; the finish event completes it into RequestResult)
    lifecycle: List = dataclasses.field(default_factory=list)
    # emit stamp of every token in `tokens` (RequestResult.token_s)
    token_s: List[float] = dataclasses.field(default_factory=list)
    # its place in admission order, kept over a readmission: the slot with
    # the highest gives its pages up first (ServingEngine._preempt)
    order: int = 0
    preemptions: int = 0


# decode ticks kept launched ahead of the one being fetched (docs/SERVING.md
# "Decode lookahead"): what the device has queued when the host stands still.
# One is enough to hide the host's own work of a tick, and is all an arrival
# may find ahead of its prefill (a free slot, admission open); eight ride out
# a stall of a few ticks' length where no arrival can be placed anyway
# (PERF.md §6, PR 26: ~115 ms, two to four a run).
LOOKAHEAD_TICKS = 8
# prefills launched whose first token the host has not fetched yet: one runs
# while the next is queued behind it.  A queued prefill's temporaries may be
# held from its enqueue, so no more than the device needs to go from one
# program into the next (PERF.md §6, PR 42)
PREFILLS_IN_FLIGHT = 2


@dataclasses.dataclass
class _Ahead:
    """A decode tick launched before the programs ahead of it were fetched:
    its device output and, slot by slot, the inputs it was launched on.  It
    is taken for every slot whose inputs those still are when its turn comes
    (:meth:`ServingEngine._ahead_slots`), so no path that changes a slot
    between two ticks has to know that it exists."""
    out: Any
    seq: int    # its ``serve.launch``'s, for the ``serve.fetch`` that reads it
    params: Any
    page_table: np.ndarray
    active: np.ndarray      # the slots it computes a token for (its own mask)
    lengths: np.ndarray     # each slot's rows as it starts
    src: np.ndarray     # the launch whose output is each slot's fed token
    owed: np.ndarray    # tokens each slot is still owed once it is taken
    past_end: bool      # its mask is narrower than the slots live at launch


@dataclasses.dataclass
class _FirstToken:
    """A prefill launched and not fetched yet: the admission is booked (slot,
    pages, lengths), its first token lies on the device, and the ticks
    launched behind it take it from there
    (:meth:`~.execution.MeshExecutor.feed_lane`).  The host reads it when
    its turn in launch order comes (:meth:`ServingEngine._first_token`)."""
    out: Any
    seq: int
    program: str
    slot: int
    span: Any       # its ``serve.prefill`` span: the expert counts come late
    live_tokens: int
    st: Optional["_Slot"] = None    # the admission it was booked as
    fed: bool = False       # a tick took its lane on the device


class ServingEngine:
    """Iteration-level scheduler over a fixed slot fleet + paged KV pool.

    ``model`` must expose the paged decode contract (``init_paged_cache`` /
    ``apply_paged`` — see ``models.CausalLM``); ``params`` is the model's
    tree (share ``InferenceEngine.params`` via :meth:`InferenceEngine.serving`
    to keep serving numerics identical to ``generate()``), or a call that
    hands it over.  The engine's own ``params`` is that tree as the executor
    placed it (:class:`~.execution.MeshExecutor`: the same values, held the
    way the decode program reads them).
    """

    def __init__(self, model, params, b_slots: int = 4,
                 page_size: int = PAGE_SIZE, num_pages: Optional[int] = None,
                 max_model_len: Optional[int] = None, monitor=None,
                 watchdog=None, dtype=None, kv_dtype=None, mesh=None,
                 max_queue: Optional[int] = None, quarantine_limit: int = 2,
                 probe_after_ticks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefix_index_entries: int = 4096,
                 host_tier_pages: Optional[int] = None,
                 speculative: Optional[SpeculativeConfig] = None,
                 program_stats_sample_every: int = 0,
                 slo_rules: Optional[List[SloRule]] = None,
                 adapters: Optional[AdapterRegistry] = None,
                 lookahead: bool = True):
        if not hasattr(model, "apply_paged"):
            raise ValueError(
                "ServingEngine needs a model with the paged decode contract "
                "(init_paged_cache/apply_paged) — see models.CausalLM")
        self.model, self.params = model, params
        self.b_slots = int(b_slots)
        self.page_size = int(page_size)
        self.max_model_len = int(max_model_len or model.config.max_seq_len)
        if self.max_model_len > model.config.max_seq_len:
            # forward_paged clamps positions at max_seq_len-1 (a learned
            # pos_embed has no rows past it), so longer slots would emit
            # silently-wrong tokens rather than fail
            raise ValueError(
                f"max_model_len={self.max_model_len} exceeds the model's "
                f"max_seq_len={model.config.max_seq_len}")
        self.pages_per_slot = -(-self.max_model_len // self.page_size)
        # +1: physical page 0 is the reserved trash page
        full = 1 + self.b_slots * self.pages_per_slot
        self.num_pages = int(num_pages) if num_pages is not None else full
        if self.num_pages < 1 + self.pages_per_slot:
            raise ValueError(
                f"num_pages={self.num_pages} cannot hold one full slot "
                f"({self.pages_per_slot} pages of {self.page_size} tokens "
                f"+ the trash page)")
        # what shares, parks or re-reads pages of one K/V pool refuses, by
        # name and before anything is compiled, a cache that is more than that
        # (inference/cache_layout.py); prefix sharing is on everywhere else
        layout = CacheLayout(model.config, self.b_slots, self.page_size,
                             self.pages_per_slot, self.num_pages)
        layout.refuse("prefix sharing (prefix_cache=True)", prefix_cache)
        layout.refuse("KV-page tiering", host_tier_pages is not None)
        layout.refuse("speculative decoding", speculative is not None)
        if prefix_cache is None:
            prefix_cache = layout.allows("prefix sharing (prefix_cache=True)")
        # a slot's pages follow its length wherever a slot that gave them up
        # can be rebuilt by tail prefills and a tick writes one row a slot
        # (a speculative verify block writes k more): elsewhere the whole
        # reservation is taken at admission and the next page is always there
        self._grow = speculative is None and layout.allows(
            "pages that follow a slot's length (recompute preemption)")
        self.monitor = monitor
        self.watchdog = watchdog
        # decode lookahead (docs/SERVING.md "Decode lookahead"): launch tick
        # t+1 on tick t's device-resident tokens before fetching them
        self.lookahead = bool(lookahead)
        self._ahead: Deque[_Ahead] = deque()
        # prefills in flight whose first token is still on the device alone
        self._firsts: Deque[_FirstToken] = deque()
        self._in_run = False
        self.lookahead_launched = 0
        # ticks not used because the weights were swapped under all of them
        self.lookahead_dropped = 0
        # ticks taken for some of their slots only (the others had ended or
        # been taken over since), or fetched for no one
        self.lookahead_stale_taken = 0
        # ticks launched under a mask narrower than the live slots (past a
        # slot's last token, which the host can count), and admissions
        # whose first token reached a tick without the host
        self.lookahead_past_end = 0
        self.prefill_fed_on_device = 0
        # admission passes that left the head of the queue waiting for pages
        # with a slot free (head-of-line: the pool, not the slots, bound the
        # batch), and whether the last one did
        self.page_waits = 0
        self._page_wait = False
        # pages live slots took as they grew into them, slots that gave
        # their pages up to an older one, and the rows their readmissions
        # rebuilt; a request that gave up waits at the head of the queue
        # with what it has emitted (its slot's record, no pages) kept here
        self.page_grows = 0
        self.preemptions = 0
        self.recomputed_tokens = 0
        self._preempted: Dict[Any, _Slot] = {}
        self._admitted = 0      # admissions so far: the next one's `order`
        self._slot_ids = np.arange(self.b_slots)
        self._cols = np.arange(self.pages_per_slot)[None, :]
        # the launch (its seq) whose output is each slot's last token: a
        # tick launched ahead names the token it was fed by it
        self._tok_src = np.zeros((self.b_slots,), np.int64)
        # decode tokens each live slot is still owed (max_new_tokens less
        # the prefill's and those emitted), and live requests that can stop
        # on a token the host has not seen (eos_token_id)
        self._owed = np.zeros((self.b_slots,), np.int32)
        self._eos_live = 0
        # decode and prefill programs launched so far: the k-th launch is
        # the k-th such program the device runs (``seq`` of the
        # ``serve.launch`` / ``serve.fetch`` spans)
        self._launch_seq = 0
        # bounded admission: submissions past max_queue waiting requests are
        # shed with a typed result + retry-after hint (None = unbounded)
        self.max_queue = int(max_queue) if max_queue is not None else None
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(f"max_queue={self.max_queue} must be >= 1")
        # consecutive prefill failures before a slot is fenced
        self.quarantine_limit = int(quarantine_limit)
        if self.quarantine_limit < 1:
            # 0 would mean "never fence": a persistent slot fault then loops
            # forever without ever reaching the all-quarantined terminal
            # error that hands control to the supervisor
            raise ValueError(
                f"quarantine_limit={self.quarantine_limit} must be >= 1")

        # ---- the device half (docs/SERVING.md "Multi-chip serving"): pool
        # placement, auto-TP param sharding, program construction and the
        # zero-recompile inventory live in the MeshExecutor — the scheduling
        # code below never touches a device array directly, so the same
        # loop drives one chip or a tensor-sharded mesh unchanged.
        self.mesh = mesh
        if host_tier_pages is not None:
            if not prefix_cache:
                raise ValueError(
                    "host_tier_pages requires prefix_cache=True — the host "
                    "tier parks demoted PREFIX pages (docs/SERVING.md "
                    "\"KV-page tiering\")")
            if int(host_tier_pages) < 1:
                raise ValueError(
                    f"host_tier_pages={host_tier_pages} must be >= 1")
        # per-program device-time accounting (docs/OBSERVABILITY.md
        # "Per-program accounting"): FLOPs/bytes from lowered cost analysis
        # at each program's first invocation, invocation counts per call,
        # synced wall-time sampling every Nth invocation (default 0 = never
        # — steady-state async pipelining untouched)
        self._catalog = ProgramCatalog(
            sample_every=program_stats_sample_every)
        # SLO rules (docs/OBSERVABILITY.md "SLOs and alerts"): evaluated
        # once per working tick over monitor gauges + span quantiles;
        # firing states in health()["alerts"] and (via the alert{rule=...}
        # gauges) on /metrics as dstpu_alert{rule="..."}
        self._slo = SloEvaluator(slo_rules) if slo_rules else None
        # windowed device-trace capture, env-armed (DS_TPU_DEVICE_TRACE):
        # first engine in the process starts the capture; step() counts
        # the window down one unit per tick
        maybe_capture_from_env()
        self._exec = MeshExecutor(model, params, self.num_pages,
                                  self.page_size, self.b_slots, dtype=dtype,
                                  kv_dtype=kv_dtype, mesh=mesh,
                                  prefix_cache=prefix_cache,
                                  host_tier=host_tier_pages is not None,
                                  catalog=self._catalog, adapters=adapters,
                                  pages_per_slot=self.pages_per_slot)
        # auto-TP-sharded on a mesh, and held as the decode program reads it
        self.params = self._exec.params
        # the executor's own (it knows a state's bytes too), and an allocator
        # a pool of pages it lists, each with the table the programs are fed
        # from: the slots' pages, taken as a request needs them and shared
        # by reference, then what a slot takes a whole row of at admission
        self._layout = self._exec.layout
        self._pools = [PagePool(pages, self.b_slots, per_slot)
                       for pages, per_slot in self._layout.pools]
        self._pages = self._pools[0]
        # ---- multi-tenant adapter serving (docs/SERVING.md "Multi-tenant
        # adapter serving"): with a registry attached, every decode/prefill
        # /verify program takes the per-slot LoRA factor stacks as ONE
        # fixed-shape traced operand — admission of any tenant mix never
        # changes program shape, so the zero-recompile inventory holds
        # bit-identically.  The host stacks mirror the RNG lanes: numpy at
        # rest, device-cached by the executor until a slot flip
        # invalidates them.  Without a registry the programs trace without
        # the operand — byte-identical to the pre-adapter engine.
        self.adapters = adapters
        self._adapter_stacks = (adapters.make_slot_stacks(self.b_slots)
                                if adapters is not None else None)
        # fused-view mode (hot tenant): while set, the engine serves
        # base+adapter FUSED weights under a fresh weight epoch and only
        # this tenant's requests are admissible (their slot delta stays
        # zero — the weights already carry it)
        self.fused_adapter_id: Optional[str] = None
        self._base_params = self.params
        self.adapter_admissions = 0        # adapter-tagged slots admitted
        self._adapter_admit_by_id: Dict[str, int] = {}
        self._adapter_tokens_by_id: Dict[str, int] = {}
        # at-rest storage dtype of the paged pool (docs/SERVING.md
        # "Quantized KV pages"): None = compute dtype, "int8" = quantize-
        # on-store pages + per-page scale rows.  A page is still a page —
        # accounting, prefix sharing, COW, tiering and epoch stamps are
        # dtype-blind
        self.kv_dtype = self._exec.kv_dtype
        self._prefix = (PrefixIndex(self.page_size,
                                    max_entries=prefix_index_entries)
                        if prefix_cache else None)
        # ---- KV-page tiering (docs/SERVING.md "KV-page tiering"): under
        # pool pressure cold FULL prefix pages demote to pinned host
        # buffers instead of being evicted; a prefix hit on a demoted
        # entry promotes the page back into a free device slot before
        # admission maps it.  None = legacy evict-only behavior.
        self.host_tier_pages = (int(host_tier_pages)
                                if host_tier_pages is not None else None)
        self._tier: Optional[HostTier] = None
        if self.host_tier_pages is not None:
            page_bytes = self._exec.pool_bytes["total"] // self.num_pages
            self._tier = HostTier(self.host_tier_pages,
                                  page_bytes=page_bytes)
            # entry removal (eviction, collision subtree, LRU cap) must
            # drop the host buffer in the same step — never strand a slab
            self._prefix.on_drop_host = self._tier.discard
        self.demotions = 0            # pages moved device -> host
        self.promotions = 0           # pages moved host -> device
        self._demoted_hwm = 0         # high-water mark of the demoted ledger
        self._promote_lat_s: Deque[float] = deque(maxlen=2048)
        self._demote_lat_s: Deque[float] = deque(maxlen=2048)
        # ---- weight epochs (docs/HYBRID.md): the live-weight generation
        # this engine is serving.  update_params() advances it and flushes
        # every cached K/V page / prefix entry / host-tier slab (K/V is a
        # pure function of (tokens, params) — a param update makes all of
        # it stale).  Pages are stamped at allocation and admission refuses
        # to map a page from another epoch — the runtime proof that a
        # post-update prefix lookup can never serve pre-update K/V.
        self._weight_epoch = 0
        self.weight_updates = 0       # update_params() calls
        self.kv_flushed_pages = 0     # HBM prefix pages flushed by updates
        self.kv_flushed_slabs = 0     # host-tier slabs flushed by updates
        self._refresh_lat_s: Deque[float] = deque(maxlen=2048)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_shared_tokens = 0
        self.prefix_pages_shared = 0   # full pages mapped instead of prefilled
        self.cow_copies = 0
        self._lengths = np.zeros((self.b_slots,), np.int32)
        self._last_tok = np.zeros((self.b_slots,), np.int32)
        self._active = np.zeros((self.b_slots,), bool)
        # per-slot RNG lanes (docs/SERVING.md "Sampling"): traced parameter
        # vectors the ONE decode program samples with — greedy is just the
        # temperature<=0 lane value, so a heterogeneous request mix never
        # changes program shape.  The seed lane + the slot's position
        # counter (== _lengths) fully determine every sampled token.
        self._lane_temp = np.zeros((self.b_slots,), np.float32)
        self._lane_top_k = np.zeros((self.b_slots,), np.int32)
        self._lane_top_p = np.ones((self.b_slots,), np.float32)
        self._lane_seed = np.zeros((self.b_slots,), np.uint32)
        self.sampled_admissions = 0   # non-greedy requests admitted
        self._slots: List[Optional[_Slot]] = [None] * self.b_slots
        self._queue: Deque[Request] = deque()
        self._pending: List[Request] = []   # arrival-gated, sorted by time
        # queued + pending + in-flight + unclaimed results, for O(1)
        # duplicate-rid rejection (removed when the result is claimed)
        self._live_rids: set = set()
        # which engine incarnation this is under its supervisor (0 = the
        # first build; warm restarts and recycles stamp replacement
        # engines +1) — lifecycle events carry it so a stitched record
        # shows which incarnation served each phase
        self.engine_incarnation = 0
        # rid -> lifecycle events recorded before the request owns a slot
        # (the "queued" stamp); moved into the slot at admission, or
        # flushed into the terminal result for shed/expired requests
        self._lifecycle_pending: Dict[Any, List] = {}
        self._results: Dict[Any, RequestResult] = {}
        self._finished_order: List[Any] = []
        self._tick = 0
        self._tokens_out = 0
        self._t0 = time.monotonic()
        # ---- resilience state (docs/SERVING.md "Failure handling")
        self._quarantined = np.zeros((self.b_slots,), bool)
        self._slot_failures = np.zeros((self.b_slots,), np.int64)
        # background probe/unfence: after `probe_after_ticks` clean ticks
        # (no slot-attributable failure anywhere on the fleet) a fenced
        # slot gets ONE canary prefill; success restores the slot AND its
        # quarantined pages.  None = fenced slots only recover via a full
        # engine rebuild (the pre-probe behavior).
        self.probe_after_ticks = (int(probe_after_ticks)
                                  if probe_after_ticks is not None else None)
        if self.probe_after_ticks is not None and self.probe_after_ticks < 1:
            raise ValueError(
                f"probe_after_ticks={self.probe_after_ticks} must be >= 1")
        self._fence_tick: Dict[int, int] = {}
        self._last_failure_tick = 0
        self.probe_count = 0
        self.unfence_count = 0
        self._draining = False
        # deadline-bearing requests currently waiting (queue + pending):
        # lets _expire skip its O(backlog) queue scan entirely in the
        # common no-deadlines case
        self._waiting_deadlines = 0
        self.shed_count = 0
        self.deadline_count = 0
        self._ema_service_s: Optional[float] = None   # drives retry hints

        # env-gated /metrics endpoint (DS_TPU_METRICS_PORT): process-global,
        # and a taken fixed port falls back to an ephemeral bind instead of
        # failing the Nth engine on a shared host — the ACTUAL bound port is
        # what health() (and the fleet store advertisement) reports
        from ..observability.export import maybe_start_metrics_server

        srv = maybe_start_metrics_server(monitor)
        self.metrics_port = srv.port if srv is not None else None

        # multi-chip gauges are CONSTANT for the engine's lifetime (the
        # pool never reallocates, the mesh never changes) — write them once
        # at init; the Prometheus exposition serves the latest value per
        # name, so /metrics carries them from the first scrape
        info = self._exec.mesh_info()
        if self.monitor is not None:
            pb = self._exec.pool_bytes
            # kvq_* (docs/OBSERVABILITY.md): storage-dtype facts, constant
            # for the engine's lifetime.  scale_bytes_total is the part of
            # kv_pool_bytes_total spent on per-page scale rows (0 on a
            # full-precision pool), page_bytes the all-in per-page cost —
            # the honest denominator of the 2× capacity claim
            scale_bytes = (sum(int(a.nbytes) for a in self._exec.pools[2:])
                           if self._exec.quantized else 0)
            self.monitor.write_events(
                [("serve/mesh_devices", float(info["mesh_devices"]), 0),
                 ("serve/kv_pool_bytes_total", float(pb["total"]), 0),
                 ("serve/kv_pool_bytes_per_device",
                  float(pb["per_device"]), 0),
                 ("serve/kvq_enabled",
                  1.0 if self._exec.quantized else 0.0, 0),
                 ("serve/kvq_scale_bytes_total", float(scale_bytes), 0),
                 ("serve/kvq_page_bytes",
                  float(pb["total"] // self.num_pages), 0),
                 ("serve/state_pool_bytes",
                  float(self._exec.state_bytes), 0),
                 ("serve/loop_passes", float(info["loop_passes"]), 0),
                 ("serve/kv_bytes_per_token",
                  float(info["kv_bytes_per_token"]), 0)]
                + [(f"serve/mesh_axis_{a}", float(s), 0)
                   for a, s in info["mesh_axes"].items()])

        # speculative decoding (docs/SERVING.md "Speculative decoding"): a
        # draft model over its OWN pool with the same page geometry,
        # indexed by the same per-slot page tables — admission prefills
        # both pools, COW snapshots both, page accounting stays the
        # engine's.  Draft decode + verify compile here, at init.
        self._spec: Optional[SpeculativeDecoder] = None
        if speculative is not None:
            speculative.validate(model, self.max_model_len)
            self._spec = SpeculativeDecoder(
                speculative, model, self.num_pages, self.page_size,
                self.b_slots, dtype=dtype, kv_dtype=kv_dtype, mesh=mesh,
                catalog=self._catalog, adapters=adapters,
                target_pool_order=self._exec.pool_order)
            if self._exec._cow_prog is not None:
                # pre-warm the COW jit on the DRAFT pool aval too: a
                # boundary COW at admission must never compile
                self._spec.cow(self._exec._cow_prog, 0, 0)
        log_dist(
            f"serving engine ready: b_slots={self.b_slots} "
            f"pages={self.num_pages}x{self.page_size} "
            f"(max_model_len={self.max_model_len}) "
            f"kv_bytes_per_token={info['kv_bytes_per_token']}"
            + (f" loop_passes={info['loop_passes']}"
               if info["loop_passes"] > 1 else "")
            + (f" mesh={info['mesh_devices']}dev {info['mesh_axes']}"
               if mesh is not None else "")
            + f" weights: {info['weight_leaves_split']} stack(s) held a "
            f"leaf a layer, {info['weight_leaves_relaid']} leaf(s) "
            f"({info['weight_bytes_relaid'] / 1e6:.1f} MB) re-laid out"
            + f" cache={info['cache_kind']} kv_layers={info['kv_layers']}"
            + (f" ring_pages={info['ring_pages']}"
               if info["ring_pages"] else "")
            + "".join(f" state_layers={info['state_layers']} "
                      f"{step}={info[step]}"
                      for step in (m.step_key for m in MIXERS.values())
                      if info[step])
            + "".join(f" {scan}={info[scan]}"
                      for scan in (m.scan_key for m in MIXERS.values())
                      if scan and info[scan])
            + (" layers=" + ",".join(
                f"{kind}:{n}({'+'.join(info['cache_leaves_by_kind'][kind]) or '-'})"
                for kind, n in info["layers_by_kind"].items())
               if len(info["layers_by_kind"]) > 1 else "")
            + " kv_write=" + ",".join(
                f"{leaf}:{path}" for leaf, path in info["kv_write"].items())
            + " kv_read=" + ",".join(
                f"{leaf}:{path}" for leaf, path in info["kv_read"].items())
            + (" expert_matmul=" + ",".join(
                f"{prog}:{path}"
                for prog, path in info["expert_matmul"].items())
               if info["expert_matmul"] else ""),
            ranks=[0])

    def program_inventory(self) -> Dict[str, Any]:
        """The full set of program shapes this engine has built: one decode
        step + one prefill per prompt bucket (+ the one fixed-shape COW
        page copy when prefix caching is on, compiled at init).  Constant
        at steady state — admission never grows it beyond the bucket set."""
        inv = {"decode": 1,
               "prefill_buckets": sorted(self._exec._prefill_progs)}
        if self._exec._cow_prog is not None:
            inv["cow"] = 1
        if self._tier is not None:
            # the tier movers compile at init (traced page ids = one shape
            # each); demote/promote cycling never grows the inventory
            inv["tier"] = {"extract": 1, "inject": 1}
        if self._spec is not None:
            # draft decode + verify compile at init; draft prefills track
            # the target's bucket set — admission (greedy, sampled or
            # speculative mix) never grows any of it
            inv["speculative"] = self._spec.program_inventory()
        return inv

    def program_stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-program accounting table (docs/OBSERVABILITY.md): for every
        program this engine has invoked — decode, each prefill bucket,
        COW, the tier movers, draft/verify under speculation — the
        compile-time FLOPs/bytes, invocation count, executed-FLOPs ledger
        and (when ``program_stats_sample_every`` > 0) sampled device wall
        time.  Mirrored in ``health()["program_stats"]`` and the
        ``serve/program_flops{program=...}`` gauges."""
        return self._catalog.table()

    def slo_states(self) -> Dict[str, Dict[str, Any]]:
        """Per-rule SLO snapshot (empty when no rules are configured)."""
        return self._slo.states() if self._slo is not None else {}

    def adapter_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-tenant admission/token counters, keyed by adapter id
        (empty without a registry) — what the multi-tenant bench reads."""
        if self.adapters is None:
            return {}
        return {aid: {"admissions": self._adapter_admit_by_id.get(aid, 0),
                      "tokens": self._adapter_tokens_by_id.get(aid, 0)}
                for aid in self.adapters.loaded()}

    # ---------------------------------------------------------- scheduling

    def _pages_whole(self, req: Request) -> int:
        """The pages ``req`` holds rows in at its end."""
        return -(-(len(req.input_ids) + req.max_new_tokens) // self.page_size)

    def _emitted(self, req: Request) -> List[int]:
        """The tokens ``req`` emitted before it gave its pages up."""
        st = self._preempted.get(req.rid)
        return st.tokens if st is not None else []

    def _rows_of(self, req: Request) -> np.ndarray:
        """The token rows an admission of ``req`` builds: its prompt, then
        what it had emitted before it gave its pages up."""
        emitted = self._emitted(req)
        if not emitted:
            return req.input_ids
        return np.concatenate([req.input_ids,
                               np.asarray(emitted, np.int32)])

    def _pages_needed(self, req: Request) -> int:
        """The pages an admission of ``req`` takes: those that hold the rows
        it builds (the prompt, and what a preempted request had emitted) and
        the row of the first decode tick; the whole reservation where the
        slot's pages cannot follow its length."""
        if not self._grow:
            return self._pages_whole(req)
        rows = len(req.input_ids) + len(self._emitted(req))
        return -(-(rows + 1) // self.page_size)

    # ------------------------------------------------------- page pools

    def _tables(self, slot: Optional[int] = None):
        """What a paged program takes as its page table: the slots' table
        (one slot's row), beside any other pool's (a window layer's rings)."""
        rows = slice(None) if slot is None else slice(slot, slot + 1)
        tables = tuple(pool.table[rows] for pool in self._pools)
        return tables if len(tables) > 1 else tables[0]

    def _release(self, slot: int, pages: List[int], fence=False) -> None:
        """Give back what ``slot`` holds of each pool: a reference on each of
        ``pages`` of the slots' pool and the whole row of any other.  Or
        (``fence``: ``pages`` a failed attempt's own) leak all of it into the
        quarantine accounts until a canary passes (:meth:`_probe_slot`)."""
        held = [pages] + [pool.row(slot) for pool in self._pools[1:]]
        for pool, mine in zip(self._pools, held):
            pool.table[slot] = 0
            if fence:
                pool.leak(slot, mine)
            else:
                for p in mine:
                    pool.drop(p)

    def page_accounting(self) -> Dict[str, Any]:
        """The refcount pool invariant, one call: every page (minus the
        trash page) is exactly one of free, quarantined, or referenced
        (held by slots and/or the prefix index).  ``balanced`` is what the
        chaos tests assert after every kill; ``cached`` counts pages the
        prefix index pins (a subset of ``referenced``).  With KV-page
        tiering the invariant extends with the DEMOTED ledger: a demoted
        entry holds no device page, so the device equation is untouched,
        but every demoted index entry must have exactly one host-tier
        buffer (``demoted == host tier size``) — ``balanced`` checks both.
        """
        acct = self._pages.accounting()
        demoted = self._prefix.demoted if self._prefix is not None else 0
        # a pool whose pages are never shared: every referenced page is in
        # the row of exactly one slot that holds a request
        rest = [pool.accounting() for pool in self._pools[1:]]
        for pool, a in zip(self._pools[1:], rest):
            a["balanced"] = bool(
                a["balanced"] and not pool.table[~self._active].any()
                and a["referenced"] == int(
                    (pool.table[self._active] > 0).sum()))
        return {
            # the window layers' rings (no pages for any other model)
            "window": rest[0] if rest else PagePool(1, 0, 0).accounting(),
            **acct,
            # entry↔page is one-to-one over HBM entries (PrefixIndex pins
            # each published page until its entry dies or demotes), so the
            # HBM entry count IS the distinct-page count — O(1), and
            # health() polls this per request.  A one-to-one violation
            # still trips the chaos audits: duplicate entries would push
            # cached ABOVE the quiescent referenced count.
            "cached": (self._prefix.hbm_entries()
                       if self._prefix is not None else 0),
            "demoted": demoted,
            "host_tier_bytes": self._tier.bytes() if self._tier is not None
            else 0,
            "balanced": acct["balanced"]
            and demoted == (len(self._tier) if self._tier is not None
                            else 0)
            and all(a["balanced"] for a in rest),
        }

    def _adapter_salt(self, req: Request) -> int:
        """Per-tenant prefix-namespace salt (docs/SERVING.md "Multi-tenant
        adapter serving"): K/V under tenant weights is a function of
        (tokens, base params, ADAPTER), so two tenants' identical prompts
        must never share pages — every chain walk starts from a
        tenant-salted root.  0 (the unsalted base namespace) for
        adapter-less requests and registry-less engines."""
        if self.adapters is None:
            return 0
        return self.adapters.salt(req.adapter_id)

    def _prefix_lookup(self, req: Request) -> PrefixMatch:
        """Longest resident prefix for ``req`` (capped at prompt-1 so at
        least one token always goes through prefill — the first generated
        token reads off the last real prefill position)."""
        if self._prefix is None or len(self._prefix) == 0:
            return PrefixMatch(pages=[], n_tokens=0)
        ids = self._rows_of(req)
        with trace_span("serve.prefix_match", rid=req.rid):
            m = self._prefix.lookup(ids, limit=len(ids) - 1,
                                    salt=self._adapter_salt(req))
        if m.cow_src is not None and m.cow_valid < MIN_COW_TOKENS:
            # not worth a pool-shaped page snapshot: keep the full-page
            # share, prefill the boundary tokens like any other tail
            return PrefixMatch(pages=m.pages,
                               n_tokens=len(m.pages) * self.page_size,
                               keys=m.keys)
        return m

    def _reclaim_cached(self, n_pages: int) -> None:
        """Pool pressure: reclaim cached-but-idle prefix pages, LRU first,
        until ``n_pages`` more pages are actually free (a reclaimed page
        still held by a decoding slot frees nothing yet — keep going) or
        nothing reclaimable remains.  With a host tier configured, cold
        FULL pages DEMOTE (their K/V parks on the host, the entry stays
        matchable) instead of evicting; partial boundary pages are mutable
        and evict as before."""
        freed = 0
        while freed < n_pages and self._prefix is not None \
                and len(self._prefix):
            before = len(self._pages.free)
            if self._tier is not None:
                if not self._demote_lru_entry():
                    break   # every remaining entry is already on the host
            else:
                for p in self._prefix.evict(1):
                    self._pages.drop(p)
            freed += len(self._pages.free) - before

    # ------------------------------------------------------ KV-page tiering

    def _demote_lru_entry(self) -> bool:
        """One reclaim step under tiering: demote the LRU full HBM entry
        (extract its page to the host tier, free the device page) or evict
        the LRU partial one.  Returns False when no entry holds a device
        page anymore."""
        cand = self._prefix.reclaim_candidate()
        if cand is None:
            return False
        key, e = cand
        if not e.full:
            # a partial boundary page is mutable (its owner may still be
            # appending) — it can never move to the host tier; evict it
            # exactly as the untiered engine would
            p = self._prefix.evict_key(key)
            if p is not None:
                self._pages.drop(p)
            return True
        self._tier_make_room()
        with trace_span("serve.demote", page=int(e.page)):
            t0 = time.monotonic()
            slabs = self._exec.extract(int(e.page))
            self._tier.put(key, *slabs, epoch=self._weight_epoch)
            page = self._prefix.demote(key)
            self._pages.drop(page)
            self._demote_lat_s.append(time.monotonic() - t0)
        self.demotions += 1
        if self._prefix.demoted > self._demoted_hwm:
            self._demoted_hwm = self._prefix.demoted
        return True

    def _tier_make_room(self) -> None:
        """Host-tier capacity: a full tier evicts its LRU buffers FOR REAL
        (the prefix entry dies with its only copy — this is the one place
        tiering still loses cache)."""
        while self._tier.full():
            key = self._tier.oldest_key()
            if key is None:   # pragma: no cover - defensive
                return
            self._prefix.evict_key(key)   # drops the buffer via the hook
            self._tier.discard(key)       # belt-and-suspenders: idempotent

    def _promote_match(self, match: PrefixMatch) -> bool:
        """Promote every demoted chunk of ``match`` back into free device
        pages (the caller checked the free count): inject the host slab,
        flip the index entry hot — the fresh page's first reference IS the
        index's — and patch the match in place so admission maps it like
        any resident page.  Returns False when a host buffer vanished
        (host-capacity eviction raced the lookup): the caller retries the
        head with a fresh, smaller lookup."""
        for i, p in enumerate(match.pages):
            if p >= 0:
                continue
            key = match.keys[i]
            # epoch-gated fetch: a slab extracted under retired weights is
            # treated exactly like a vanished one (docs/HYBRID.md) — the
            # entry dies and the caller retries with a smaller match
            data = self._tier.get(key, epoch=self._weight_epoch)
            if data is None:
                # the tier evicted this entry between lookup and now (or
                # its slab is from another weight epoch); make sure the
                # index agrees, then let the caller re-look-up
                self._prefix.evict_key(key)
                self._tier.discard(key)
                return False
            with trace_span("serve.promote"):
                t0 = time.monotonic()
                (dst,) = self._pages.take(1, self._weight_epoch)
                try:
                    self._exec.inject(data, dst)
                except BaseException:
                    self._pages.drop(dst)
                    raise
                self._prefix.promote(key, dst)
                self._tier.pop(key)
                self._promote_lat_s.append(time.monotonic() - t0)
            match.pages[i] = dst
            self.promotions += 1
        return True

    def tier_latencies(self) -> Dict[str, List[float]]:
        """Recent demote/promote wall times in seconds (bounded windows;
        the tiered bench reads promote p50/p99 from here)."""
        return {"promote_s": list(self._promote_lat_s),
                "demote_s": list(self._demote_lat_s)}

    def residency_digest(self, cap: int = 1024) -> List:
        """Compact prefix-residency digest — ``(chain_key, tier)`` per full
        cached chunk, MRU first — what a fleet member publishes through
        the coordination store so the router can route shared-prefix
        requests to the engine already holding them (docs/FLEET.md)."""
        if self._prefix is None:
            return []
        return self._prefix.digest(cap)

    def adopt_host_tier(self, old: "ServingEngine") -> int:
        """Warm-restart/recycle carry: adopt the dead engine's DEMOTED
        prefix entries and their host buffers.  Host slabs are plain host
        memory, valid even when the old device pool was consumed, and K/V
        is a pure function of (tokens, params) — the factory recreates the
        same params — so the replacement serves promotions from the
        carried cache instead of recomputing.  HBM entries died with the
        pool and rebuild organically through replay.  Returns the entries
        carried."""
        if (self._tier is None or old._tier is None or self._prefix is None
                or old._prefix is None):
            return 0
        keys = self._prefix.adopt_demoted(old._prefix)
        adopted = self._tier.adopt(old._tier, keys=keys)
        if len(adopted) < len(keys):
            # tier capacity clipped the carry: drop the index entries whose
            # buffers did not make it so the demoted ledger stays balanced
            for key in set(keys) - set(adopted):
                self._prefix.evict_key(key)
        if self._prefix.demoted > self._demoted_hwm:
            self._demoted_hwm = self._prefix.demoted
        return len(adopted)

    # ------------------------------------- live weight updates (hybrid)

    @property
    def weight_epoch(self) -> int:
        """The live-weight generation this engine is serving
        (docs/HYBRID.md).  Monotonic; advanced by :meth:`update_params`.
        Setting it directly (the supervisor's epoch carry, the rollout
        factory) re-stamps the prefix index so published entries tag
        correctly."""
        return self._weight_epoch

    @weight_epoch.setter
    def weight_epoch(self, value: int) -> None:
        self._weight_epoch = int(value)
        if self._prefix is not None:
            self._prefix.epoch = self._weight_epoch

    def update_params(self, params, draft_params=None,
                      epoch: Optional[int] = None) -> Dict[str, Any]:
        """Swap the LIVE weights under every compiled program and advance
        the **weight epoch** — the train↔serve handoff of the hybrid
        rollout subsystem (docs/HYBRID.md).

        Params are already program arguments, so the swap is
        zero-recompile by construction: the tree is resharded through the
        shared ``place_params``/``auto_tp_specs`` path and committed to
        the exact shardings the programs compiled against
        (:meth:`MeshExecutor.update_params`); a structurally different
        tree is rejected loudly.

        The hard contract is the flush: every paged K/V page the prefix
        index pins, every COW-donor boundary page, and every demoted
        host-tier slab describes activations of the OLD weights — all of
        it is invalidated here (flush), and everything is epoch-stamped
        (tag) so a stale page could not be served even if one survived.
        The page-accounting ledger stays balanced through the flip
        (flushed pages return to the free list; the demoted ledger drops
        to zero with its slabs).

        Requires no slot in flight (a mid-stream weight change would split
        one request's output across two weight generations); queued and
        pending requests are fine — they prefill from scratch under the
        new epoch.  ``draft_params`` optionally refreshes a speculative
        draft's weights (stale draft weights only cost acceptance rate,
        never correctness).  ``epoch`` overrides the new epoch number (the
        supervisor's restart carry); default is +1.

        Returns the update stats (also mirrored on the ``serve/weight_*``
        gauges): new epoch, flushed HBM pages / host slabs, the refresh
        wall time, and the post-flip ``page_accounting()`` verdict."""
        if self._active.any() or self._preempted:
            # (a request waiting to be readmitted is a live stream too)
            raise RuntimeError(
                f"update_params with "
                f"{int(self._active.sum()) + len(self._preempted)} slot(s) "
                "in flight: a live stream's K/V would straddle two weight "
                "epochs — drain or finish the tick loop first "
                "(RolloutEngine sequences rounds so this cannot happen)")
        # ticks still in flight were launched on the old weights: what they
        # computed is taken under the old epoch before the tree is swapped
        self._settle_ahead()
        t0 = time.monotonic()
        with trace_span("serve.weight_update", epoch=self._weight_epoch + 1):
            # swaps first (each validates BEFORE mutating), flush last, and
            # the DRAFT before the TARGET: any rejection then leaves a
            # correct engine — a draft-only partial swap can only cost
            # acceptance rate, while the target weights, the cache and the
            # epoch move together or not at all (stale cached K/V can never
            # coexist with swapped target weights).
            if draft_params is not None and self._spec is not None:
                self._spec.update_params(draft_params)
            self._exec.update_params(params)
            self.params = self._exec.params
            # the new tree is the serving base: any fused adapter view is
            # over (fuse_adapter() re-stamps both when IT is the caller)
            self._base_params = self.params
            self.fused_adapter_id = None
            flushed_pages, flushed_slabs = self._flush_cached_kv()
            self.weight_epoch = (int(epoch) if epoch is not None
                                 else self._weight_epoch + 1)
        self.weight_updates += 1
        dt = time.monotonic() - t0
        self._refresh_lat_s.append(dt)
        acct = self.page_accounting()
        if not acct["balanced"]:   # pragma: no cover - defensive
            raise RuntimeError(
                f"page accounting unbalanced after weight-epoch flip: "
                f"{acct} — the flush leaked or double-freed")
        if self.monitor is not None:
            self.monitor.write_events([
                ("serve/weight_epoch", float(self._weight_epoch),
                 self._tick),
                ("serve/weight_updates_total", float(self.weight_updates),
                 self._tick),
                ("serve/weight_refresh_s", dt, self._tick),
                ("serve/kv_flushed_pages_total",
                 float(self.kv_flushed_pages), self._tick),
            ])
        log_dist(
            f"serve: weight epoch -> {self._weight_epoch} "
            f"({flushed_pages} cached page(s) + {flushed_slabs} host "
            f"slab(s) flushed, refresh {dt * 1e3:.1f} ms)", ranks=[0])
        return {"weight_epoch": self._weight_epoch,
                "flushed_hbm_pages": flushed_pages,
                "flushed_host_slabs": flushed_slabs,
                "refresh_s": dt,
                "balanced": acct["balanced"]}

    def _flush_cached_kv(self) -> tuple:
        """Release every prefix-cached page and host-tier slab (the
        weight-epoch flip).  Slots are idle (checked by the caller), so
        after the flush the only non-free pages are quarantined ones —
        accounting stays exact."""
        flushed_pages = flushed_slabs = 0
        if self._prefix is not None:
            flushed_slabs = self._prefix.demoted
            for p in self._prefix.flush():
                self._pages.drop(p)
                flushed_pages += 1
        if self._tier is not None and len(self._tier):
            # every demoted entry's removal dropped its slab via the
            # on_drop_host hook; anything left is a stranded-slab bug
            raise RuntimeError(
                f"host tier holds {len(self._tier)} slab(s) after the "
                "prefix flush — stranded buffers (ledger torn)")
        self.kv_flushed_pages += flushed_pages
        self.kv_flushed_slabs += flushed_slabs
        return flushed_pages, flushed_slabs

    def refresh_latencies(self) -> List[float]:
        """Recent ``update_params`` wall times in seconds (bounded window;
        the rollout bench reads weight-refresh p50/p99 from here)."""
        return list(self._refresh_lat_s)

    def fuse_adapter(self, adapter_id: Optional[str] = None,
                     epoch: Optional[int] = None) -> Dict[str, Any]:
        """Fused-view serving for a HOT tenant (docs/SERVING.md
        "Multi-tenant adapter serving"): swap ``base + A@B*scale`` fused
        weights in through the ordinary :meth:`update_params` path —
        zero-recompile (the fused tree has identical avals/shardings) and
        epoch-flipped, so every cached K/V page of the shared-base epoch
        is flushed and stamped unservable before the first fused token.

        While fused, ONLY this tenant's requests are admissible: a base
        or other-tenant request would decode against the wrong weights
        (their per-slot delta assumes the shared base), so :meth:`submit`
        rejects the mix loudly.  The tenant's own slots skip the batched
        delta — the weights already carry it — which is the point: a
        tenant hot enough to dominate the engine stops paying the
        per-token factor matmuls.  ``fuse_adapter(None)`` restores the
        shared base (another epoch flip) and reopens mixed admission.
        Requires idle slots, exactly like any weight update."""
        if self.adapters is None:
            raise RuntimeError(
                "fuse_adapter requires an AdapterRegistry — build the "
                "engine with adapters= (docs/SERVING.md)")
        base = self._base_params
        if adapter_id is None:
            view = base
        else:
            self.adapters.resolve(adapter_id)   # loud UnknownAdapter
            view = self.adapters.fuse(base, adapter_id)
        stats = self.update_params(view, epoch=epoch)
        # update_params made the view the new base and cleared the mode;
        # re-stamp both — the true base survives for the next flip
        self._base_params = base
        self.fused_adapter_id = adapter_id
        stats["fused_adapter_id"] = adapter_id
        log_dist(
            f"serve: fused-view "
            f"{'restored to shared base' if adapter_id is None else f'adapter {adapter_id!r}'} "
            f"at weight epoch {self._weight_epoch}", ranks=[0])
        return stats

    def _arrival_abs(self, req: Request) -> float:
        """Absolute arrival stamp: the rebased epoch when the request rode
        across a warm restart, else this engine's clock.  Everything
        REPORTING an arrival (gauges, RequestResult stamps) reads this;
        admission gating and deadline expiry stay on the engine-relative
        ``arrival_time``/``deadline_s`` pair the supervisor rebases."""
        if req.arrival_epoch_s is not None:
            return req.arrival_epoch_s
        return self._t0 + req.arrival_time

    def _usable_slots(self) -> int:
        return int(self.b_slots - self._quarantined.sum())

    def _retry_after_hint(self) -> float:
        """Backlog-derived resubmission hint: waves of requests ahead times
        the EMA of observed service time (a conservative floor before any
        request has completed)."""
        per_req = self._ema_service_s if self._ema_service_s else 0.25
        backlog = (len(self._queue) + len(self._pending)
                   + int(self._active.sum()))
        lanes = max(1, self._usable_slots())
        waves = max(1, -(-max(backlog, 1) // lanes))
        return round(per_req * waves, 4)

    def _shed(self, request: Request, why: str) -> Any:
        """Terminal "shed" result for a request admission refused: typed,
        counted, and carrying a retry-after hint — never silently dropped,
        never parked on an unbounded queue."""
        t = time.monotonic()
        hint = self._retry_after_hint()
        lc = self._lifecycle_pending.pop(request.rid, [])
        lc.append(("shed", t, self.engine_incarnation))
        self._results[request.rid] = RequestResult(
            rid=request.rid, input_ids=request.input_ids,
            output_ids=np.zeros((0,), np.int32), finish_reason="shed",
            prefill_bucket=0, arrival_s=t, admit_s=t, first_token_s=t,
            finish_s=t, retry_after_s=hint, trace_id=request.trace_id,
            lifecycle=lc)
        self._finished_order.append(request.rid)
        self._live_rids.add(request.rid)
        self.shed_count += 1
        logger.warning("serve: shed request %r (%s); retry_after=%.3fs",
                       request.rid, why, hint)
        return request.rid

    def _expire(self, now: float) -> None:
        """Finish every request whose deadline (arrival + deadline_s) has
        passed: queued requests exit with an empty "deadline" result and a
        retry hint; in-flight requests retire with the tokens generated so
        far and give their slot + pages back this tick.  The queue scan is
        skipped outright while no waiting request carries a deadline (the
        common case must not pay O(backlog) per tick)."""
        if self._queue and self._waiting_deadlines:
            keep: Deque[Request] = deque()
            for req in self._queue:
                if (req.deadline_s is not None
                        and now >= req.arrival_time + req.deadline_s):
                    self._waiting_deadlines -= 1
                    t = time.monotonic()
                    lc = self._lifecycle_pending.pop(req.rid, [])
                    lc.append(("deadline", t, self.engine_incarnation))
                    result = RequestResult(
                        rid=req.rid, input_ids=req.input_ids,
                        output_ids=np.zeros((0,), np.int32),
                        finish_reason="deadline", prefill_bucket=0,
                        arrival_s=self._arrival_abs(req), admit_s=t,
                        first_token_s=t, finish_s=t,
                        retry_after_s=self._retry_after_hint(),
                        trace_id=req.trace_id, lifecycle=lc)
                    gave_up = self._preempted.pop(req.rid, None)
                    if gave_up is not None:
                        # waiting to be readmitted: what it had emitted
                        result = dataclasses.replace(
                            result, prefill_bucket=gave_up.bucket,
                            output_ids=np.asarray(gave_up.tokens, np.int32),
                            token_s=np.asarray(gave_up.token_s, np.float64),
                            admit_s=gave_up.admit_s,
                            first_token_s=gave_up.first_token_s,
                            decode_ticks=gave_up.decode_ticks,
                            preemptions=gave_up.preemptions)
                    self._results[req.rid] = result
                    self._finished_order.append(req.rid)
                    self.deadline_count += 1
                    logger.warning("serve: request %r expired in queue "
                                   "(deadline %.3fs)", req.rid, req.deadline_s)
                else:
                    keep.append(req)
            self._queue = keep
        for slot in np.flatnonzero(self._active):
            req = self._slots[slot].request
            if (req.deadline_s is not None
                    and now >= req.arrival_time + req.deadline_s):
                logger.warning("serve: request %r expired in flight after "
                               "%d token(s) (deadline %.3fs)", req.rid,
                               len(self._slots[slot].tokens), req.deadline_s)
                self._finish(slot, "deadline")

    def submit(self, request: Request) -> Any:
        """Queue a request (FIFO).  Validates it can ever be served.

        Admission control: while the engine is draining, or the bounded
        queue (``max_queue``) is full, the request is SHED — it still gets
        a terminal :class:`RequestResult` (``finish_reason="shed"``, with a
        ``retry_after_s`` hint) rather than an unbounded queue growing
        until every deadline in it is dead on arrival."""
        ids = np.asarray(request.input_ids, np.int32).reshape(-1)
        # flatten BEFORE validating: _pages_needed counts len(input_ids),
        # which on a [1, S] prompt would count rows, not tokens
        request = dataclasses.replace(request, input_ids=ids)
        if ids.size == 0:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = ids.size + request.max_new_tokens
        if total > self.max_model_len:
            raise ValueError(
                f"request {request.rid!r}: prompt {ids.size} + max_new "
                f"{request.max_new_tokens} exceeds max_model_len "
                f"{self.max_model_len}")
        if self._pages_whole(request) > self.num_pages - 1:
            raise ValueError(
                f"request {request.rid!r} needs {self._pages_whole(request)} "
                f"pages but the pool holds {self.num_pages - 1}")
        if request.deadline_s is not None and request.deadline_s <= 0:
            raise ValueError(
                f"request {request.rid!r}: deadline_s={request.deadline_s} "
                "must be > 0 (measured from arrival)")
        if request.sampling is not None:
            request.sampling.validate()
        if request.adapter_id is not None:
            # tenant resolution happens HERE, not at slot admission: an
            # unknown adapter must bounce at the door (a loud error to the
            # submitter) rather than fail a prefill attempt later and
            # count against the slot's quarantine budget
            if self.adapters is None:
                raise ValueError(
                    f"request {request.rid!r} names adapter "
                    f"{request.adapter_id!r} but this engine has no "
                    "AdapterRegistry — build it with adapters= "
                    "(docs/SERVING.md \"Multi-tenant adapter serving\")")
            with trace_span("serve.adapter_resolve", rid=request.rid,
                            adapter=request.adapter_id):
                self.adapters.resolve(request.adapter_id)   # UnknownAdapter
        if (self.fused_adapter_id is not None
                and request.adapter_id != self.fused_adapter_id):
            raise ValueError(
                f"request {request.rid!r} (adapter "
                f"{request.adapter_id!r}) rejected: the engine is serving "
                f"a FUSED view of adapter {self.fused_adapter_id!r} — "
                "only that tenant is admissible until fuse_adapter(None) "
                "restores the shared base (docs/SERVING.md)")
        rid = request.rid
        if rid in self._live_rids:
            raise ValueError(
                f"request id {rid!r} is already queued, in flight, or has "
                f"an unclaimed result — rids must be unique")
        if request.trace_id is None:
            # first hop of a standalone engine: assign the fleet-wide
            # trace id here (a FleetRouter assigns before dispatch, and
            # replays/failovers arrive with the original id — accepted
            # verbatim so the request stays ONE trace end to end)
            request = dataclasses.replace(request, trace_id=new_trace_id())
        backlog = len(self._queue) + len(self._pending)
        if self._draining or (self.max_queue is not None
                              and backlog >= self.max_queue):
            return self._shed(request,
                              "draining" if self._draining else "queue full")
        self._live_rids.add(rid)
        self._lifecycle_pending[rid] = [
            ("queued", time.monotonic(), self.engine_incarnation)]
        if request.deadline_s is not None:
            self._waiting_deadlines += 1
        if request.arrival_time > 0:
            bisect.insort(self._pending, request,
                          key=lambda r: r.arrival_time)
        else:
            self._queue.append(request)
        return request.rid

    def _admit(self, now: float) -> None:
        k = bisect.bisect_right(self._pending, now,
                                key=lambda r: r.arrival_time)
        if k:
            self._queue.extend(self._pending[:k])
            del self._pending[:k]
        self._page_wait = False
        while self._queue:
            req = self._queue[0]
            try:
                slot = next(i for i in range(self.b_slots)
                            if not self._active[i]
                            and not self._quarantined[i])
            except StopIteration:
                break
            if (len(self._firsts) >= PREFILLS_IN_FLIGHT
                    and not self._first_token_due()):
                # PREFILLS_IN_FLIGHT unfetched behind a tick still to be
                # fetched: the slot is filled once that tick has been
                break
            admitted = freed_pins = promote_retry = False
            # the owning request's trace context (docs/OBSERVABILITY.md
            # "Distributed tracing"): every span this admission opens —
            # prefix_match, demote/promote under reclaim, COW, admit,
            # prefill — inherits the request's trace_id/rid tags
            with trace_context(req.trace_id, req.rid):
                match = self._prefix_lookup(req)
                # pin the matched DEVICE pages (incl. the COW source) for
                # the span of this admission: reclaim below — or a
                # concurrent eviction by the index's own LRU cap — must
                # never free a matched page back into the pool it is about
                # to be mapped from.  Demoted chunks (-1) have no device
                # page to pin; their host buffers are LRU-touched instead
                # so a capacity eviction during reclaim prefers other
                # victims.
                pinned = [p for p in match.pages if p >= 0]
                if match.cow_src is not None:
                    pinned.append(match.cow_src)
                for p in pinned:
                    self._pages.share(p)
                n_demoted = sum(1 for p in match.pages if p < 0)
                if n_demoted and self._tier is not None:
                    for i, p in enumerate(match.pages):
                        if p < 0:
                            self._tier.touch(match.keys[i])
                try:
                    # demoted chunks each need one free device page for
                    # their promotion on top of the private remainder
                    need = self._pages_needed(req) - len(match.pages)
                    if len(self._pages.free) < need + n_demoted:
                        # reclaim (demote/evict) cached-but-idle prefix
                        # pages before blocking: a cache must never starve
                        # admission
                        self._reclaim_cached(need + n_demoted
                                             - len(self._pages.free))
                    if len(self._pages.free) >= need + n_demoted:
                        if n_demoted and not self._promote_match(match):
                            # a matched host buffer vanished (host-capacity
                            # eviction raced the lookup): retry with a
                            # fresh, strictly smaller lookup
                            promote_retry = True
                        else:
                            # a first token whose turn has come, with a tick
                            # queued behind its prefill for the device to go
                            # on with: read before this admission's own
                            # spans open (the wait is no part of them)
                            if self._first_token_due() and self._ahead:
                                with self._armed("serve.first_token"):
                                    while (self._first_token_due()
                                           and self._ahead):
                                        self._first_token(
                                            self._firsts.popleft())
                            # ``emitted``: above 0 for a readmission, whose
                            # prefills build the prompt's rows and theirs
                            with trace_span("serve.admit", rid=req.rid,
                                            slot=slot, pages=need,
                                            emitted=len(self._emitted(req))):
                                self._admit_one(req, slot, match, need, now)
                            admitted = True
                finally:
                    # the slot takes its own references inside _admit_one;
                    # the lookup pins existed only to survive reclaim.  If
                    # reclaim evicted the head's OWN matched entries, our
                    # pins are now the last references — dropping them
                    # frees the pages.
                    if not admitted:
                        freed_pins = any(self._pages.refcount[p] == 1
                                         for p in pinned)
                    for p in pinned:
                        self._pages.drop(p)
            if admitted:
                continue
            if freed_pins or promote_retry:
                # pool pressure evicted the head's own matched prefix from
                # the index (or its host buffer from the tier), and either
                # the pages came free the instant the pins dropped or the
                # match must shrink — retry the head with a fresh lookup
                # instead of misreading this as head-of-line blocking.
                # Terminates: each retry means the index strictly shrank.
                continue
            # head-of-line: wait for retirements, a slot free and the pool
            # short of the head's pages
            self._page_wait = True
            self.page_waits += 1
            break

    def _admit_one(self, req: Request, slot: int, match: PrefixMatch,
                   need: int, now: float) -> None:
        """Pop the queue head into ``slot`` and prefill its unshared tail
        (one admission — the ``serve.admit`` span/fault unit).  ``match``
        is the resident prefix (``need`` excludes its full pages): the
        slot takes one reference per shared page and allocates only the
        private remainder."""
        # fire BEFORE the pop: a raise-kind injected fault must leave the
        # request queued (recoverable), not silently dropped
        maybe_fire(SITE_SERVE_ADMIT, rid=req.rid, slot=slot)
        self._queue.popleft()
        self._lifecycle_pending.setdefault(req.rid, []).append(
            ("admit", time.monotonic(), self.engine_incarnation))
        if req.deadline_s is not None:
            self._waiting_deadlines -= 1
        shared = list(match.pages)
        for p in shared:
            self._pages.share(p)
        pages = self._pages.take(need, self._weight_epoch)
        for pool in self._pools[1:]:
            pool.table[slot] = pool.take(pool.table.shape[1],
                                         self._weight_epoch)
        try:
            self._prefill(slot, req, shared, pages, match, now)
        except BaseException as e:
            # a failed prefill (transient device error, injected fault)
            # must not leak its reservation or drop the request.  If the
            # slot never registered, unwind — request back at the head —
            # and count the failure against the slot: quarantine_limit
            # consecutive failures fence it, with THIS attempt's PRIVATE
            # pages leaked into the quarantine account (suspect contents
            # are never recycled) and scheduling continuing on the rest
            # of the fleet.  Shared pages were read-only in the attempt
            # and other slots may be decoding through them right now —
            # they are never quarantined, their references just drop.
            # If the slot did register (failure in the post-launch
            # bookkeeping), it owns the pages and the next run continues
            # it.  NOTE: the pool is donated, so a failed DEVICE call also
            # consumes the pool — step() then refuses with
            # PoolConsumedError; the unwind still leaves the queue
            # replayable (ServingSupervisor rebuilds + replays).
            if self._slots[slot] is None:
                self._queue.appendleft(req)
                if req.deadline_s is not None:
                    self._waiting_deadlines += 1
                for p in shared:
                    self._pages.drop(p)
                if not isinstance(e, Exception):
                    # KeyboardInterrupt/SystemExit is the operator, not
                    # the slot: plain unwind, no quarantine accounting
                    self._release(slot, pages)
                    raise
                self._slot_failures[slot] += 1
                self._last_failure_tick = self._tick
                fails = int(self._slot_failures[slot])
                fenced = fails >= self.quarantine_limit
                self._release(slot, pages, fence=fenced)
                if fenced:
                    self._quarantined[slot] = True
                    self._fence_tick[slot] = self._tick
                    logger.error(
                        "serve: slot %d quarantined after %d consecutive "
                        "prefill failures; %d page(s) leaked-and-"
                        "accounted, %d slot(s) remain", slot, fails,
                        len(pages), self._usable_slots())
                raise SlotPrefillError(
                    f"prefill failed in slot {slot} for request "
                    f"{req.rid!r} (failure {fails}/"
                    f"{self.quarantine_limit}"
                    f"{', slot quarantined' if fenced else ''}): "
                    f"{e}", slot=slot, rid=req.rid,
                    quarantined=fenced) from e
            raise

    def _prefill(self, slot: int, req: Request, shared: List[int],
                 private: List[int], match: PrefixMatch, now: float) -> None:
        """Prefill ``req`` into ``slot``: the page-table row maps the
        shared prefix pages first, then the private allocation; only the
        UNSHARED tail of the prompt runs through the prefill program
        (``start`` = shared token count), attending to the shared pages
        through the ordinary causal gather.  When the match ends mid-page,
        the donor's partial boundary page is first snapshotted into this
        slot's own boundary page (copy-on-write)."""
        ids = self._rows_of(req)
        S = len(ids)
        n_shared = match.n_tokens
        pages = shared + private
        # weight-epoch invariant (docs/HYBRID.md): a mapped shared page (or
        # COW donor) must carry K/V of the CURRENT weights.  The prefix
        # index and host tier already refuse stale entries, so this firing
        # means the flush-or-tag machinery has a hole — fail loudly rather
        # than emit tokens conditioned on retired weights.
        suspects = shared + ([match.cow_src]
                             if match.cow_src is not None else [])
        stale = self._pages.stale(suspects, self._weight_epoch)
        if stale:
            raise RuntimeError(
                f"weight-epoch invariant violated: request {req.rid!r} "
                f"would map page(s) {stale} stamped "
                f"{[int(self._pages.epoch[p]) for p in stale]} at weight "
                f"epoch {self._weight_epoch} — pre-update K/V must never "
                "be served (docs/HYBRID.md)")
        # the unshared tail (>= 1 token: lookup is capped at the last), in
        # one launch; a readmission's in the programs already built
        old = self._preempted.get(req.rid)
        pieces = self._prefill_pieces(S - n_shared, old is not None)
        self._pages.table[slot, :] = 0
        self._pages.table[slot, :len(pages)] = pages
        lanes = as_lanes(req.sampling)
        adapter_row = None
        if self.adapters is not None:
            # install the tenant's factors into this slot of the host
            # stacks BEFORE the device calls: the prefill program reads the
            # one-slot row slice now and the next decode tick re-uploads
            # the full stacks.  Under a fused view the slot stays zero —
            # the swapped weights already carry the delta.  A base-model
            # request (adapter_id=None) also clears the slot: zero factors
            # make the traced delta exactly zero.
            ad = (None if self.fused_adapter_id is not None
                  else self.adapters.resolve(req.adapter_id))
            self.adapters.write_slot(self._adapter_stacks, slot, ad)
            self._exec.invalidate_adapters()
            adapter_row = self._exec.adapter_row(self._adapter_stacks, slot)
        start = n_shared
        for i, (n_tok, s_pad) in enumerate(pieces):
            toks = np.zeros((1, s_pad), np.int32)
            toks[0, :n_tok] = ids[start:start + n_tok]
            with trace_span("serve.prefill", rid=req.rid, slot=slot,
                            bucket=s_pad, tokens=n_tok,
                            shared_tokens=start) as sp:
                if get_tracer().enabled:
                    # what the call reads of the slot's cache
                    sp.set(**self._layout.prefill_attrs(s_pad, n_tok, start))
                if i == 0:
                    maybe_fire(SITE_SERVE_PREFILL, rid=req.rid, slot=slot)
                with self._armed(f"serve.prefill rid={req.rid!r}"):
                    # no more than PREFILLS_IN_FLIGHT unfetched: the oldest's
                    # turn has come (_admit starts no admission where it has
                    # not), and the wait for it is a wait for this one's
                    # start
                    while len(self._firsts) >= PREFILLS_IN_FLIGHT:
                        self._first_token(self._firsts.popleft())
                    sp.set(queued_behind=len(self._ahead) + len(self._firsts))
                    if i == 0 and match.cow_src is not None:
                        # COW the partial boundary page: private[0] is the
                        # boundary logical page (shared full pages cover
                        # exactly len(shared) logical pages before it).
                        # Rows past cow_valid in the snapshot are donor
                        # garbage the tail prefill/decode overwrites before
                        # causality can expose them.
                        self._exec.cow(match.cow_src, private[0])
                        self.cow_copies += 1
                        if self._spec is not None:
                            # mirror the snapshot in the draft pool — the
                            # sharer's draft-side boundary must hold the
                            # same donor prefix its target-side boundary
                            # does
                            self._spec.cow(self._exec._cow_prog,
                                           match.cow_src, private[0])
                    out, seq, pt_row, toks_j = self._launch_prefill(
                        s_pad, slot, toks, n_tok, start, *lanes, adapter_row)
                    if self._spec is not None:
                        # draft-pool prefill of the same tail (same bucket,
                        # page-table row, start) — the draft emits nothing
                        self._spec.prefill(s_pad, pt_row, toks_j, n_tok,
                                           start)
                    start += n_tok
                    if start < S:
                        # a piece of a readmission: the next starts behind
                        # its rows, and the token it sampled is no one's
                        continue
                    first = _FirstToken(out, seq, f"prefill_{s_pad}", slot,
                                        sp, n_tok)
                    # read here and now only where something reads it on
                    # the host at once, a stop on that very token included
                    # (the fetch lands inside the watchdog window)
                    tok = (None if self._runs_ahead()
                           and req.eos_token_id is None
                           else self._fetch_first(first))
        # the admission is booked from what the host knows without the
        # device's answer: the slot, its pages and lengths, the tokens it is
        # owed.  Its first token is recorded when its turn in launch order
        # comes (_first_token)
        t = time.monotonic()
        self._slot_failures[slot] = 0   # quarantine counts CONSECUTIVE fails
        lc = self._lifecycle_pending.pop(req.rid, [])
        inc = self.engine_incarnation
        if n_shared > 0:
            lc.append(("prefix_match", t, inc))
        if match.cow_src is not None:
            lc.append(("cow", t, inc))
        lc.append(("prefill", t, inc))
        if old is None:
            st = _Slot(
                request=req, pages=pages, tokens=[], bucket=s_pad,
                arrival_s=self._arrival_abs(req), admit_s=self._t0 + now,
                first_token_s=t, shared_tokens=n_shared, lifecycle=lc,
                order=self._admitted)
            self._admitted += 1
        else:
            # what the request has emitted, its stamps and its place in
            # admission order, in a record of its own: a prefill of the old
            # one may still be in flight, and gives its token to no one
            del self._preempted[req.rid]
            st = dataclasses.replace(old, pages=pages, lifecycle=lc,
                                     shared_tokens=n_shared)
            self.recomputed_tokens += S - n_shared
        first.st = self._slots[slot] = st
        self._lengths[slot] = S
        self._last_tok[slot] = 0
        self._active[slot] = True
        self._tok_src[slot] = seq
        self._owed[slot] = req.max_new_tokens - len(st.tokens) - 1
        self._eos_live += req.eos_token_id is not None
        (self._lane_temp[slot], self._lane_top_k[slot],
         self._lane_top_p[slot], self._lane_seed[slot]) = lanes
        self._exec.invalidate_lanes()
        if req.sampling is not None and not req.sampling.greedy:
            self.sampled_admissions += 1
        if req.adapter_id is not None:
            self.adapter_admissions += 1
            self._adapter_admit_by_id[req.adapter_id] = (
                self._adapter_admit_by_id.get(req.adapter_id, 0) + 1)
        reason = None
        if tok is None:
            self._firsts.append(first)
        else:
            reason = self._record_first(first, tok)
        if self._prefix is not None:
            if n_shared > 0:
                self.prefix_hits += 1
                self.prefix_shared_tokens += n_shared
                self.prefix_pages_shared += len(shared)
            else:
                self.prefix_misses += 1
            # publish this prompt's chunks (full pages + the partial
            # boundary) so later requests can share them; the index takes
            # one reference per new entry.  Shared chunks just LRU-touch
            # their existing entries.  The pages are written by the program
            # enqueued above, ahead of any program that maps them later.
            # Host time AFTER a first token's stamp where that was read.
            with trace_span("serve.publish", rid=req.rid):
                newly, released = self._prefix.publish(
                    ids, pages, salt=self._adapter_salt(req))
                for p in newly:
                    self._pages.share(p)
                for p in released:
                    self._pages.drop(p)
        if reason is not None:
            self._finish(slot, reason)

    def _prefill_pieces(self, n: int, rebuilt: bool) -> List[tuple]:
        """``(tokens, bucket)`` of the prefill launches that build ``n``
        rows of a slot: one, in the bucket of its length.  A readmission's
        (``rebuilt``) run in the programs the engine has built and compile
        nothing: pieces of the largest bucket, each after the first a tail
        prefill behind the rows before it, the last in the smallest bucket
        that holds it."""
        built = sorted(self._exec._prefill_progs)
        if not rebuilt or not built:
            return [(n, _bucket(n))]
        pieces = []
        while n > built[-1]:
            pieces.append((built[-1], built[-1]))
            n -= built[-1]
        return pieces + [(n, next(b for b in built if b >= n))]

    def _runs_ahead(self) -> bool:
        """The engine launches from what the host knows without the device's
        answer: not the plain loop (``lookahead=False``), not with a
        speculative tick or a sampled catalog dispatch, which read a
        program's output on the host as they launch it."""
        catalog = self._exec.catalog
        return (self.lookahead and self._spec is None
                and not (catalog is not None and catalog.sample_every))

    def _first_token_due(self) -> bool:
        """The oldest program in flight is a prefill: its first token's
        turn has come."""
        return bool(self._firsts) and (
            not self._ahead or self._firsts[0].seq < self._ahead[0].seq)

    def _fetch_first(self, first: _FirstToken) -> int:
        """The blocking read of a prefill's output (the caller holds a
        watchdog window): the first token; an MoE model's expert counts go
        to the prefill's own span."""
        out, counts = self._exec.split_counts(
            self._fetch(first.out, first.program, first.seq))
        if counts is not None and get_tracer().enabled:
            self._set_moe_attrs(first.span, counts, first.live_tokens,
                                first.program)
        return int(out.flat[0])

    def _first_token(self, first: _FirstToken) -> None:
        """The second half of an admission, when its prefill's turn in
        launch order has come: fetch, stamp and record the first token, and
        finish a request that asked for one token or stops on this one."""
        reason = self._record_first(first, self._fetch_first(first))
        if reason is not None:
            self._finish(first.slot, reason)

    def _record_first(self, first: _FirstToken, tok: int) -> Optional[str]:
        """Stamp and record an admission's first token; the reason its
        request ends with it, if it does.  A slot that ended while its
        prefill was in flight (a deadline) has no one to give the token
        to."""
        st, slot = first.st, first.slot
        if self._slots[slot] is not st:
            return None
        req = st.request
        t = time.monotonic()
        st.tokens.append(tok)
        st.token_s.append(t)
        self._last_tok[slot] = tok
        self._tokens_out += 1
        if req.adapter_id is not None:
            self._adapter_tokens_by_id[req.adapter_id] = (
                self._adapter_tokens_by_id.get(req.adapter_id, 0) + 1)
        if len(st.tokens) == 1:
            # not a readmission's: the request's first keeps its stamp
            st.first_token_s = t
            st.lifecycle.append(("first_token", t, self.engine_incarnation))
            if self.monitor is not None:
                self.monitor.write_events([
                    ("serve/ttft_s", t - self._arrival_abs(req),
                     self._tick)])
        if req.eos_token_id is not None and tok == req.eos_token_id:
            return "eos"
        return "length" if len(st.tokens) >= req.max_new_tokens else None

    def _slot_rid_map(self) -> Dict[str, str]:
        """Active slot → rid, stringified for trace-event ``args`` (only
        built when tracing is enabled — the disabled tick never pays it)."""
        return {str(int(s)): str(self._slots[s].request.rid)
                for s in np.flatnonzero(self._active)}

    def _armed(self, label: str):
        """Watchdog deadline around a device call (+ its host fetch), or a
        no-op context when no watchdog is attached."""
        if self.watchdog is not None:
            return self.watchdog.armed(label)
        import contextlib

        return contextlib.nullcontext()

    def _launch_decode(self, lengths, fed, active, lanes, adapters,
                       ahead: int, firsts=()):
        """Enqueue one decode program over the slots ``active`` under a
        ``serve.launch`` span: ``(its device output, its seq)``.  ``ahead``:
        ticks in flight behind the one being fetched once this launch has
        returned.  ``firsts``: the prefills in flight whose token ``fed``
        does not hold yet; each one's lane is taken from its output on the
        device, inside the launch."""
        with trace_span("serve.launch", program="decode",
                        seq=self._launch_seq + 1, ahead=ahead):
            for first in firsts:
                fed = self._exec.feed_lane(fed, first.out, first.slot)
            out = self._exec.decode(self._tables(), lengths, fed, active,
                                    lanes, adapters=adapters)
        self._launch_seq += 1
        return out, self._launch_seq

    def _launch_prefill(self, s_pad: int, slot: int, toks: np.ndarray,
                        n_real: int, start: int, *lane_and_adapter):
        """Upload ``slot``'s page-table row and the padded prompt and
        enqueue the bucket's prefill program, all under one
        ``serve.launch`` span (the uploads are part of what a launch
        costs): ``(its device output, its seq, the row and the tokens as
        uploaded)``."""
        with trace_span("serve.launch", program=f"prefill_{s_pad}",
                        seq=self._launch_seq + 1, ahead=0):
            pt_row = jax.tree_util.tree_map(jnp.asarray, self._tables(slot))
            toks_j = jnp.asarray(toks)
            out = self._exec.prefill(s_pad, pt_row, toks_j, n_real, start,
                                     *lane_and_adapter, slot=slot)
        self._launch_seq += 1
        return out, self._launch_seq, pt_row, toks_j

    @staticmethod
    def _fetch(out, program: str, seq: int) -> np.ndarray:
        """The blocking read of a program's output (host fetch = device
        sync) under a ``serve.fetch`` span that names the launch whose
        output it reads."""
        with trace_span("serve.fetch", program=program, seq=seq):
            return np.asarray(out)

    def _lanes_jnp(self):
        return self._exec.lanes(self._lane_temp, self._lane_top_k,
                                self._lane_top_p, self._lane_seed)

    def _adapter_operand(self):
        """Device-cached per-slot adapter factor stacks (None without a
        registry — the programs then traced without the operand)."""
        if self.adapters is None:
            return None
        return self._exec.adapter_stacks(self._adapter_stacks)

    def _set_moe_attrs(self, sp, counts: np.ndarray, live_tokens: int,
                       program: str) -> None:
        """What the router did in one call of ``program`` of an MoE model,
        on the call's span: from the rows each expert of each layer computed
        (``counts [L, E]``, fetched with the tokens) and the real tokens
        the call was given; and the grouped products the program holds, by
        the way they went (``moe_kernel_products`` / ``moe_ragged_products``:
        static a program), with the rows they sorted and the rows the way in
        moved (``moe_sorted_rows`` / ``moe_moved_rows``).  Experts in a
        latent (``moe_latent_size``): ``moe_latent_rows``, the token rows
        that went through each of the two projections, a row a real token
        an expert layer."""
        cfg = self.model.config
        pairs = live_tokens * cfg.moe_top_k * counts.shape[0]
        rows, touched = int(counts.sum()), int((counts > 0).sum())
        latent = ({"moe_latent_rows": live_tokens * counts.shape[0]}
                  if cfg.moe_latent_size else {})
        sp.set(moe_live_rows=pairs, moe_rows=rows, **latent,
               moe_experts_touched=touched,
               moe_max_load=int(counts.max()),
               # the (token, expert) pairs the routers chose, those whose
               # expert is held here (all of them unless the model holds a
               # share, ``moe_experts_held``), and the experts held, a layer
               moe_pairs=pairs, moe_local_pairs=rows,
               moe_experts_held=int(counts.size),
               # the same three by what they count, for readers that
               # take any model with a held share
               pairs_total=pairs, pairs_held=rows,
               experts_touched_held=touched,
               **self._exec.expert_product_attrs(program),
               # the sorted rows and those the way in filled: all of them
               # on the ragged_dot side, the live ones in whole tiles on
               # the kernel's
               **self._exec.expert_row_attrs(program, counts, live_tokens))

    def _arrival_waiting(self, now: float) -> bool:
        """A request is due, a usable slot is free and a prefill may be
        launched: the next admission call would try to place it."""
        return ((bool(self._queue) or (bool(self._pending)
                 and self._pending[0].arrival_time <= now))
                and int(self._active.sum()) < self._usable_slots()
                and len(self._firsts) < PREFILLS_IN_FLIGHT)

    def _placeable(self) -> bool:
        """A slot that comes free could be given to a request: admission is
        open, and this ``run()`` has something queued or still to arrive
        (outside ``run()`` anything may be submitted)."""
        return not self._draining and not (
            self._in_run and not self._queue and not self._pending)

    def _lookahead_depth(self) -> int:
        """How many ticks may be in flight behind the one being fetched:
        each launched from what the host knows without the device's answer
        (a slot without ``eos_token_id`` ends at a tick the host can count;
        a tick past that point runs under the mask of the slots that go
        on).  How many, by what an arrival would find on the device:

        - no request could be given a slot (:meth:`_placeable` is false),
          or every usable slot is busy: up to ``LOOKAHEAD_TICKS``, of which
          at most one lies past a slot's end while a request could take the
          slot (:meth:`_launch_ahead`): a slot stands empty for one tick
          between two requests, and the device never stands still;
        - a slot is free and admission is open, and nothing waits for it
          (``_decode_tick`` follows ``step()``'s admission call, so a
          request still queued is one the pool cannot hold yet): one, the
          most an arrival's prefill is ever launched behind.

        None where a live request can stop on a token the host has not
        seen yet, or a sampled catalog dispatch syncs on its own output.
        Nothing is launched over a request that can be placed: ``step()``
        puts its admission after the tick in flight and ``_decode_tick``
        then skips the launch.  A guess, not a guard: what was launched is
        checked again, slot by slot, when its turn comes."""
        if not self._runs_ahead() or self._eos_live:
            return 0
        if (int(self._active.sum()) >= self._usable_slots()
                or not self._placeable()):
            return LOOKAHEAD_TICKS
        return 1

    def _next_inputs(self):
        """``(active, lengths, src, owed)`` of the tick to launch next: the
        host's own state where no tick is in flight, else what the last one
        launched leaves if it is taken for every slot it was launched on;
        a slot admitted since then comes with the host's (its prefill was
        launched behind that tick).  ``owed``: before this tick."""
        if not self._ahead:
            lengths, src, owed = (self._lengths.copy(), self._tok_src.copy(),
                                  self._owed)
        else:
            last = self._ahead[-1]
            since = self._tok_src > last.seq
            lengths = np.where(since, self._lengths,
                               last.lengths + last.active)
            src = np.where(since, self._tok_src,
                           np.where(last.active, last.seq, last.src))
            owed = np.where(since, self._owed, last.owed)
        return self._active & (owed > 0), lengths, src, owed

    def _launch_ahead(self, lanes, adapters, depth: int) -> int:
        """Top the queue of launched ticks up to the one to fetch and
        ``depth`` behind it, and say how many were launched: each on the
        output of the one before it where it lies on the device (the host's
        last tokens where none is in flight), a slot admitted since on its
        prefill's, each under its own mask and lengths, so the device goes
        from one program into the next while the host fetches, emits and
        admits."""
        launched = 0
        while len(self._ahead) <= depth:
            active, lengths, src, owed = self._next_inputs()
            if not self._grow_pages(active, lengths):
                continue    # a slot gave its pages up: the inputs are others
            if not active.any():
                break
            # under a mask narrower than the live slots: past a slot's last
            # token.  One such tick behind the one being fetched is all a
            # request that could take the slot is made to wait for
            past_end = bool((self._active & ~active).any())
            if (past_end and self._placeable()
                    and any(a.past_end for a in
                            itertools.islice(self._ahead, 1, None))):
                break
            last = self._ahead[-1] if self._ahead else None
            firsts = [f for f in self._firsts
                      if last is None or f.seq > last.seq]
            out, seq = self._launch_decode(
                lengths, self._last_tok if last is None else last.out, active,
                lanes, adapters, ahead=len(self._ahead), firsts=firsts)
            for first in firsts:
                self.prefill_fed_on_device += not first.fed
                first.fed = True
            self._ahead.append(_Ahead(
                out, seq, self._exec.params, self._pages.table.copy(), active,
                lengths, src, owed - active, past_end))
            self.lookahead_launched += len(self._ahead) > 1
            self.lookahead_past_end += past_end
            launched += 1
        return launched

    def _grow_pages(self, active: np.ndarray, lengths: np.ndarray) -> bool:
        """Before the tick over the slots ``active`` at ``lengths`` is
        launched: a slot whose row of this tick starts a page takes that
        page, so no launched tick writes into a page its slot does not own.
        Where the pool has none, cold prefix pages are reclaimed as
        admission does, and then the slot admitted last gives its pages up
        (:meth:`_preempt`), the one asking only where it is that one, until
        a page is free.  False where a slot gave up: the tick's inputs are
        others now.  In a pool of the full reservation, and for a request
        that holds its whole reservation, the page is always there."""
        page = np.minimum(lengths // self.page_size, self.pages_per_slot - 1)
        short = active & (self._pages.table[self._slot_ids, page] == 0)
        if not short.any():
            return True
        gave_up = False
        # the oldest first: whoever gives up is younger than who takes
        for slot in sorted(np.flatnonzero(short),
                           key=lambda i: self._slots[i].order):
            st = self._slots[slot]
            while self._active[slot] and len(st.pages) <= page[slot]:
                if not self._pages.free:
                    self._reclaim_cached(1)
                if not self._pages.free:
                    self._preempt(max(np.flatnonzero(self._active),
                                      key=lambda i: self._slots[i].order))
                    gave_up = True
                    continue
                st.pages += self._pages.take(1, self._weight_epoch)
                self._pages.table[slot, len(st.pages) - 1] = st.pages[-1]
                self.page_grows += 1
        return not gave_up

    def _preempt(self, slot: int) -> None:
        """``slot`` gives its pages up to an older one (recompute
        preemption: the slot with the least to redo).  Its request goes back
        to the head of the queue with what it has emitted kept beside it
        (``_preempted``): nothing is emitted or counted twice, and its
        readmission builds prompt and tokens anew and goes on behind them
        (:meth:`_prefill`).  Its lanes of ticks already launched are no
        one's (:meth:`_ahead_slots`), and a first token still in flight is
        given to no one (:meth:`_record_first`)."""
        st = self._slots[slot]
        req = st.request
        with trace_span("serve.preempt", rid=req.rid, slot=slot,
                        tokens=len(st.tokens), pages=len(st.pages)):
            self._vacate(slot)
            st.pages = []
            st.preemptions += 1
            self._preempted[req.rid] = st
            self._lifecycle_pending[req.rid] = st.lifecycle + [
                ("preempt", time.monotonic(), self.engine_incarnation)]
            self._queue.appendleft(req)
            if req.deadline_s is not None:
                self._waiting_deadlines += 1
        self.preemptions += 1

    def _ahead_slots(self, ahead: _Ahead) -> np.ndarray:
        """The slots ``[b_slots]`` (bool) for which the launched tick
        ``ahead`` computed the token the host would ask for now: live then
        and now, fed the token the slot holds as its last (by the launch
        that made it: the same admission, the same position), at the same
        length, over the same pages as far as its length reaches (the page
        of the row it wrote the last: a page the slot has taken since lies
        behind every row the tick read or wrote)."""
        reach = self._cols <= (ahead.lengths // self.page_size)[:, None]
        return (ahead.active & self._active
                & (ahead.src == self._tok_src)
                & (ahead.lengths == self._lengths)
                & ((ahead.page_table == self._pages.table) | ~reach
                   ).all(axis=1))

    def _take_ahead(self):
        """``(the launched tick whose turn has come, the slots it is
        emitted for)``, or ``None`` where no tick in flight is anyone's.
        What lies ahead of it in launch order is read first: an admission's
        first token (:meth:`_first_token`), a tick no slot goes on under
        (fetched and read by no one).

        **One rule for every model: a launched tick is taken, slot by slot,
        for every slot whose own inputs it was launched on**
        (:meth:`_ahead_slots`), since no slot's arithmetic depends on
        another's.  It is never un-launched for a slot that goes on: for a
        model with a state a slot a tick has advanced every live slot's
        recurrent state, and one launched in its place would advance it
        again.  A slot that ended under it (a deadline; an end by length is
        under no later tick's mask) left a K/V row past its length, and
        where its pages went to a request admitted since, the order of the
        programs on the device keeps them right: the stale tick's row lands
        first, the new request's prefill, launched after it, over it, and a
        row past its prompt is one no read reaches before the slot's own
        decode writes it; its state is reset by that prefill.  The same
        holds for a slot that gave its pages up (:meth:`_preempt`) and a
        page a live slot then took as it grew (:meth:`_grow_pages`): the
        stale row lies at or past the new owner's length, and the owner's
        own write of that row, in a tick launched later, lands on it before
        any read reaches it.

        What per-slot validity cannot save drops the whole queue
        (``lookahead_dropped``): ticks launched on other weights
        (``update_params`` settles the queue first, :meth:`_settle_ahead`)."""
        while self._ahead or self._firsts:
            if self._first_token_due():
                self._first_token(self._firsts.popleft())
                continue
            ahead = self._ahead.popleft()
            own = self._ahead_slots(ahead)
            if ahead.params is not self._exec.params:
                self.lookahead_dropped += 1 + len(self._ahead)
                self._ahead.clear()
                continue
            self.lookahead_stale_taken += not np.array_equal(own,
                                                             ahead.active)
            if own.any():
                return ahead, own
            self._fetch(ahead.out, "decode", ahead.seq)
        return None

    def _settle_ahead(self) -> None:
        """Before the weights change: what is in flight is read in launch
        order.  No slot is live (``update_params`` refuses otherwise), so
        :meth:`_take_ahead` finds every tick no one's (counted as taken, not
        dropped) and gives a first token whose slot has ended to no one."""
        with self._armed("serve.settle"):
            self._take_ahead()

    def _decode_tick(self, rid_map: Optional[Dict[str, str]] = None,
                     held: bool = False) -> None:
        """One decode step over the live slots: top the queue of launched
        ticks up (only to this tick itself where ``step()`` holds an
        admission back behind it, ``held``), read what lies ahead of this
        tick in launch order, fetch it, emit."""
        if self._spec is not None:
            self._spec_tick(rid_map)
            return
        lanes = self._lanes_jnp()
        adapters = self._adapter_operand()
        own = np.zeros((self.b_slots,), bool)
        with trace_span("serve.decode", tick=self._tick) as sp:
            t_open = time.monotonic() if rid_map is not None else 0.0
            # tick-level slot→rid map (docs/OBSERVABILITY.md "Distributed
            # tracing"): a decode tick serves many requests at once, so
            # instead of one owning context the span is tagged with every
            # slot's rid — a poisoned-tick flight dump names exactly the
            # streams it was serving.  Built once per tick by step()
            # (None while tracing is off).
            if rid_map is not None:
                # ``ahead``: ticks in flight as the span opens (0: this
                # tick's program is launched inside the span)
                sp.set(slot_rids=rid_map, ahead=len(self._ahead))
            maybe_fire(SITE_SERVE_DECODE, tick=self._tick)
            with self._armed(f"serve.decode tick {self._tick}"):
                self._launch_ahead(lanes, adapters,
                                   0 if held else self._lookahead_depth())
                if rid_map is not None:
                    # the launches have returned; what is left of the span
                    # is waiting for the device
                    sp.set(dispatch_ms=(time.monotonic() - t_open) * 1e3)
                taken = self._take_ahead()
                if taken is None and self._launch_ahead(lanes, adapters, 0):
                    # every tick in flight was no one's: this one is
                    # launched on what the host holds now
                    taken = self._take_ahead()
                if taken is not None:
                    tick, own = taken
                    if rid_map is not None:
                        # rows the tick's slots hold against rows its read
                        # covers (each slot's own pages, the row it writes
                        # too), by its own mask and lengths; ``own_slots``:
                        # the slots it is emitted for
                        live = tick.lengths[tick.active]
                        sp.set(own_slots=int(own.sum()),
                               live_rows=int(live.sum()),
                               **self._layout.decode_attrs(live + 1,
                                                           self.b_slots))
                    # host fetch = device sync; an MoE model's expert counts
                    # come with the tokens
                    nxt, counts = self._exec.split_counts(
                        self._fetch(tick.out, "decode", tick.seq))
                    if counts is not None and rid_map is not None:
                        self._set_moe_attrs(sp, counts,
                                            int(tick.active.sum()), "decode")
        t_tok = time.monotonic()   # every token of this tick: its emit stamp
        active_slots = np.flatnonzero(own)
        trace_count("serve.tokens", float(len(active_slots)))
        with trace_span("serve.emit", tick=self._tick) as sp:
            for slot in active_slots:
                st = self._slots[slot]
                req = st.request
                tok = int(nxt[slot])
                st.tokens.append(tok)
                st.token_s.append(t_tok)
                st.decode_ticks += 1
                self._lengths[slot] += 1
                self._last_tok[slot] = tok
                self._tok_src[slot] = tick.seq
                self._owed[slot] -= 1
                self._tokens_out += 1
                if req.adapter_id is not None:
                    self._adapter_tokens_by_id[req.adapter_id] = (
                        self._adapter_tokens_by_id.get(req.adapter_id, 0) + 1)
                if req.eos_token_id is not None and tok == req.eos_token_id:
                    self._finish(slot, "eos")
                elif len(st.tokens) >= req.max_new_tokens:
                    self._finish(slot, "length")
            sp.set(emitted=len(active_slots))

    def _spec_tick(self, rid_map: Optional[Dict[str, str]] = None) -> None:
        """Speculative decode tick: k draft proposals + one verify-k pass,
        then per-slot host bookkeeping consuming 1..k emitted tokens
        (truncated by the slot's own eos / remaining budget — rejected or
        over-budget draft K/V past the consumed length is causally
        invisible garbage the next tick's writes overwrite)."""
        with trace_span("serve.decode", tick=self._tick,
                        speculative=self._spec.k) as sp:
            if rid_map is not None:
                sp.set(slot_rids=rid_map)
            maybe_fire(SITE_SERVE_DECODE, tick=self._tick)
            with self._armed(f"serve.decode tick {self._tick} "
                             f"(speculative k={self._spec.k})"):
                emitted, n_emit, self._exec.pools = self._spec.tick(
                    self.params, self._exec.pools,
                    self._pages.table, self._lengths, self._last_tok,
                    self._active, *self._lanes_jnp(),
                    adapters=self._adapter_operand())
        t_tok = time.monotonic()   # the tick's 1..k tokens share one stamp
        active_slots = np.flatnonzero(self._active)
        total = 0
        with trace_span("serve.emit", tick=self._tick) as sp:
            for slot in active_slots:
                st = self._slots[slot]
                req = st.request
                consumed = 0
                finish = None
                for j in range(int(n_emit[slot])):
                    tok = int(emitted[slot, j])
                    st.tokens.append(tok)
                    st.token_s.append(t_tok)
                    consumed += 1
                    self._tokens_out += 1
                    if (req.eos_token_id is not None
                            and tok == req.eos_token_id):
                        finish = "eos"
                        break
                    if len(st.tokens) >= req.max_new_tokens:
                        finish = "length"
                        break
                st.decode_ticks += 1
                total += consumed
                if req.adapter_id is not None and consumed:
                    self._adapter_tokens_by_id[req.adapter_id] = (
                        self._adapter_tokens_by_id.get(req.adapter_id, 0)
                        + consumed)
                self._spec.emitted_tokens += consumed
                self._lengths[slot] += consumed
                self._last_tok[slot] = st.tokens[-1]
                if finish is not None:
                    self._finish(slot, finish)
            sp.set(emitted=total)
        trace_count("serve.tokens", float(total))

    def _finish(self, slot: int, reason: str) -> None:
        st = self._slots[slot]
        finish_t = time.monotonic()
        st.lifecycle.append(("finish", finish_t, self.engine_incarnation))
        result = RequestResult(
            rid=st.request.rid, input_ids=st.request.input_ids,
            output_ids=np.asarray(st.tokens, np.int32),
            finish_reason=reason, prefill_bucket=st.bucket,
            arrival_s=st.arrival_s, admit_s=st.admit_s,
            first_token_s=st.first_token_s, finish_s=finish_t,
            # the prefill produced tokens[0]; every later token came from a
            # decode-program invocation (== len(tokens) - 1 without
            # speculation; a speculative verify tick emits several)
            decode_ticks=st.decode_ticks, preemptions=st.preemptions,
            shared_prefix_tokens=st.shared_tokens,
            trace_id=st.request.trace_id,
            adapter_id=st.request.adapter_id, lifecycle=st.lifecycle,
            token_s=np.asarray(st.token_s, np.float64))
        if reason == "deadline":
            self.deadline_count += 1
        else:
            # served-to-completion service time (admit -> finish) feeds the
            # retry-after hint; expired requests would bias it short
            dt = max(result.finish_s - result.admit_s, 1e-6)
            self._ema_service_s = (dt if self._ema_service_s is None
                                   else 0.8 * self._ema_service_s + 0.2 * dt)
        self._results[st.request.rid] = result
        self._finished_order.append(st.request.rid)
        self._vacate(slot)

    def _vacate(self, slot: int) -> None:
        """``slot``'s request leaves it, at its end or to be readmitted."""
        st = self._slots[slot]
        # drop one reference per page — shared pages stay resident for
        # their other readers (and the prefix index), private pages whose
        # last reference this was return to the free list
        self._release(slot, st.pages)
        self._slots[slot] = None
        self._active[slot] = False
        self._lengths[slot] = 0
        self._last_tok[slot] = 0
        self._owed[slot] = 0
        self._eos_live -= st.request.eos_token_id is not None
        self._lane_temp[slot] = 0.0
        self._lane_top_k[slot] = 0
        self._lane_top_p[slot] = 1.0
        self._lane_seed[slot] = 0
        self._exec.invalidate_lanes()
        if self.adapters is not None and st.request.adapter_id is not None:
            # retire the tenant's factors with the slot — a later base
            # admission must decode against zeros, not a stale delta
            self.adapters.clear_slot(self._adapter_stacks, slot)
            self._exec.invalidate_adapters()

    # ----------------------------------------------------- probe / unfence

    def _probe_quarantined(self) -> None:
        """Background unfence path: for each fenced slot, once
        ``probe_after_ticks`` ticks have passed with no slot-attributable
        failure anywhere (clean ticks — a fleet still throwing faults must
        not be probed into), run one canary prefill on the slot.  Success
        restores the slot and returns its quarantined pages to the free
        pool (free + quarantined == pool stays exact); failure re-fences
        and restarts the clean-tick clock."""
        for slot in np.flatnonzero(self._quarantined):
            slot = int(slot)
            since = self._tick - max(self._fence_tick.get(slot, 0),
                                     self._last_failure_tick)
            if since >= self.probe_after_ticks:
                self._probe_slot(slot)

    def _probe_slot(self, slot: int) -> None:
        pages = self._pages.fenced.get(slot)
        if not pages:
            return   # fenced without a page record (defensive): stay fenced
        self.probe_count += 1
        s_pad = _bucket(1)
        # one-token canary through the slot's own quarantined pages: the
        # same program shape real admissions use, against the same page row
        toks = np.zeros((1, s_pad), np.int32)
        for pool in self._pools:
            fenced = pool.fenced.get(slot, [])
            pool.table[slot, :len(fenced)] = fenced
        try:
            with trace_span("serve.probe", slot=slot):
                maybe_fire(SITE_SERVE_PREFILL, rid="__canary__", slot=slot)
                with self._armed(f"serve.probe slot={slot}"):
                    # greedy lane — the same program shape admissions use;
                    # the host fetch means the probe must really complete
                    out, seq, *_ = self._launch_prefill(
                        s_pad, slot, toks, 1, 0, 0.0, 0, 1.0, 0)
                    self._fetch(out, f"prefill_{s_pad}", seq)
        except BaseException as e:
            self._fence_tick[slot] = self._tick
            self._last_failure_tick = self._tick
            if not isinstance(e, Exception):
                raise   # operator interrupt, not a probe verdict
            logger.warning(
                "serve: canary probe of quarantined slot %d failed "
                "(%s: %s); slot stays fenced", slot, type(e).__name__, e)
            if not self.pool_alive():
                # the pool is donated: a failed probe device call ALSO consumed the
                # pool: abort THIS tick — letting it continue into _admit
                # would feed deleted arrays to a healthy slot's prefill and
                # misattribute the failure to it.  The supervisor rebuilds,
                # the right escalation for a fault that still reproduces
                # after probe_after_ticks.
                raise PoolConsumedError(
                    f"KV pool consumed by the failed canary probe of "
                    f"quarantined slot {slot}; rebuild the engine "
                    "(ServingSupervisor automates this)") from e
            return
        finally:
            for pool in self._pools:
                pool.table[slot] = 0
        for pool in self._pools:
            pool.restore(slot)
        self._quarantined[slot] = False
        self._slot_failures[slot] = 0
        self._fence_tick.pop(slot, None)
        self.unfence_count += 1
        logger.info(
            "serve: slot %d passed its canary probe after quarantine; "
            "restored with %d page(s) (%d slot(s) usable)", slot,
            len(pages), self._usable_slots())

    # ------------------------------------------------------------ the loop

    def pool_alive(self) -> bool:
        """False once a failed donated device call consumed the pool
        buffers (the speculative draft pool counts: a consumed draft pool
        poisons every subsequent verify) — the engine can no longer decode
        and must be rebuilt."""
        if not self._exec.pool_alive():
            return False
        return self._spec is None or self._spec.pool_alive()

    def step(self, now: Optional[float] = None) -> int:
        """One scheduler tick: expire dead deadlines, admit into free
        slots, then ONE fixed-shape decode step over all active slots.
        Returns the number of requests still in flight or queued."""
        if not self.pool_alive():
            # a failed DONATED device call consumed the pool buffers (the
            # admission unwind preserved queue/page accounting, but in-
            # flight KV is gone) — fail loudly instead of feeding deleted
            # arrays to the next program
            raise PoolConsumedError(
                "KV pool was consumed by a failed donated device call; "
                "rebuild the ServingEngine and resubmit — queued requests "
                "were preserved by the admission unwind (ServingSupervisor "
                "automates the rebuild and replays in-flight work)")
        self._tick += 1
        with trace_span("serve.tick", tick=self._tick) as sp:
            maybe_fire(SITE_SERVE_TICK, tick=self._tick)
            if now is None:
                now = time.monotonic() - self._t0
            self._expire(now)
            if (self.probe_after_ticks is not None and not self._draining
                    and self._quarantined.any()):
                self._probe_quarantined()
            # an arrival that finds one tick in flight is placed by the
            # admission call that follows that tick, and nothing is launched
            # over it: its prefill waits for one decode program at most
            # (more than one in flight: every usable slot was busy when they
            # were launched, and the prefill goes behind them now)
            held = (not self._draining and len(self._ahead) == 1
                    and self._arrival_waiting(now))
            if not self._draining and not held:
                self._admit(now)
            if self._active.any():
                rid_map = (self._slot_rid_map() if get_tracer().enabled
                           else None)
                if rid_map is not None:
                    # tick span carries the slot→rid map it decoded under
                    sp.set(slot_rids=rid_map)
                    sp.set(**self._layout.tick_attrs(self._pools,
                                                     self._page_wait))
                self._decode_tick(rid_map, held)
                if rid_map is not None:
                    # how often a slot's pages followed its length so far,
                    # and how often the pool had none (totals)
                    sp.set(page_grows=self.page_grows,
                           preemptions=self.preemptions,
                           recomputed_tokens=self.recomputed_tokens)
                # refill slots the decode just retired — the queue head
                # starts its prefill this tick instead of idling one
                # scheduler round — and place what was held back behind
                # the tick in flight
                if not self._draining:
                    self._admit(now)
                with trace_span("serve.gauges"):
                    # SLO evaluation per working tick (monitor-independent
                    # — alerts must fire even when no gauge backend is
                    # attached)
                    if self._slo is not None:
                        self._slo.evaluate(monitor=self.monitor,
                                           tracer=get_tracer())
                    # gauges only on working ticks: idle arrival-wait ticks
                    # would otherwise dilute occupancy stats and spam csv
                    # backends
                    self._write_gauges()
                # windowed device capture (docs/OBSERVABILITY.md
                # "Device-time correlation"): one WORKING tick = one
                # capture unit — idle arrival-wait ticks must not burn the
                # window before any decode/prefill lands in the trace.
                # A global None check when no capture is armed.
                device_trace_unit()
        return (int(self._active.sum()) + len(self._queue)
                + len(self._pending))

    def run(self, requests: Optional[List[Request]] = None,
            max_ticks: Optional[int] = None,
            resume: bool = False) -> List[RequestResult]:
        """Serve ``requests`` (plus anything already submitted) to
        completion; returns results in completion order.  ``arrival_time``
        offsets gate admission against the wall clock measured from this
        call.  Results finished during a previous run() that raised (e.g.
        ``max_ticks``, an injected fault) are still in the completion log
        and are returned by the next run() alongside its own.

        ``resume=True`` continues a previous run() of THIS engine that was
        interrupted by a fault, WITHOUT re-anchoring the arrival/deadline
        clock or the tokens/sec accounting — the supervisor uses it so a
        continued stream's deadlines are not silently extended."""
        if not resume:
            self._t0 = time.monotonic()
            self._tokens_out = 0   # per-run: the tokens/sec gauge divides
                                   # by elapsed-since-_t0
        start_tick = self._tick    # max_ticks bounds THIS run on a reused engine
        for req in requests or []:
            self.submit(req)
        self._in_run = True    # nothing arrives that is not in _pending
        try:
            self._run_loop(start_tick, max_ticks)
        finally:
            self._in_run = False
        return self.take_results()

    def _run_loop(self, start_tick: int, max_ticks: Optional[int]) -> None:
        while True:
            pending = self.step()
            if pending == 0:
                break
            if max_ticks is not None and self._tick - start_tick >= max_ticks:
                raise ServeTimeout(
                    f"serve loop exceeded max_ticks={max_ticks} with "
                    f"{pending} request(s) outstanding")
            if not self._active.any():
                if self._draining:
                    # admission is closed: with no slot active this loop
                    # could never serve the waiters — without this guard a
                    # queued request would read as a bogus admission
                    # deadlock and pending-only work would spin forever
                    raise RuntimeError(
                        "engine is draining: admission is closed and "
                        f"{len(self._queue) + len(self._pending)} "
                        "request(s) remain unserved — call drain() to "
                        "finish in-flight work and hand them back")
                if self._pending and not self._queue:
                    # idle until the next arrival is due: the loop is
                    # single-threaded, nothing can change while we sleep
                    wait = (self._pending[0].arrival_time
                            - (time.monotonic() - self._t0))
                    if wait > 0:
                        with trace_span("serve.idle", wait_s=wait):
                            time.sleep(wait)
                elif self._queue:
                    if self._usable_slots() == 0:
                        # every slot fenced: nothing can ever be admitted
                        # again on this engine — terminal for the engine,
                        # recoverable via a supervisor warm restart
                        raise RuntimeError(
                            f"all {self.b_slots} slots quarantined with "
                            f"{len(self._queue)} request(s) queued; rebuild "
                            "the engine (ServingSupervisor restarts + "
                            "replays automatically)")
                    # the step above ended with every usable slot free and
                    # STILL could not admit the head (after prefix-cache
                    # reclaim): the pool genuinely cannot hold it —
                    # quarantined slots leaked enough pages, or (a bug)
                    # references leaked silently
                    req = self._queue[0]
                    acct = self.page_accounting()
                    raise RuntimeError(
                        f"admission deadlock: request {req.rid!r} needs "
                        f"{self._pages_needed(req)} pages, "
                        f"{acct['free']} free ({acct['quarantined']} "
                        f"quarantined, {acct['referenced']} referenced) "
                        f"with no slot active")

    def take_results(self) -> List[RequestResult]:
        """Claim every finished result (completion order) and release their
        rids for reuse.  :meth:`run` calls this on a clean drain; after a
        fault it lets a supervisor harvest what finished before the crash."""
        order, self._finished_order = self._finished_order, []
        self._live_rids.difference_update(order)
        return [self._results.pop(rid) for rid in order]

    # ------------------------------------------------------- health / drain

    def _oldest_age_s(self, now_abs: float) -> float:
        """Age of the oldest queued or in-flight request (0 when idle);
        pending requests that have not arrived yet clamp to 0.  O(b_slots),
        not O(backlog) — this runs every working tick for the gauge: the
        queue is FIFO (head oldest) and ``_pending`` is sorted by arrival."""
        arrivals = [st.arrival_s for st in self._slots if st is not None]
        if self._queue:
            arrivals.append(self._arrival_abs(self._queue[0]))
        if self._pending:
            arrivals.append(self._arrival_abs(self._pending[0]))
        return max(0.0, now_abs - min(arrivals)) if arrivals else 0.0

    def health(self) -> Dict[str, Any]:
        """One-call snapshot of loop health — what an external load
        balancer / readiness probe polls.  Mirrors the ``serve/*`` gauges
        plus the resilience counters and page accounting."""
        now = time.monotonic()
        acct = self.page_accounting()
        info = self._exec.mesh_info()
        pb = self._exec.pool_bytes
        return {
            "tick": self._tick,
            "pool_alive": self.pool_alive(),
            # multi-chip serving (docs/SERVING.md): the mesh this engine's
            # programs span, and the per-device KV-pool footprint — on a
            # tp-sharded mesh bytes_per_device is ~total/tp (heads over
            # 'model'), the number HBM capacity planning reads; and what
            # the executor's placement did to the weights it was given
            # (docs/SERVING.md "Weight placement")
            **info,
            "kv_pool_bytes_total": pb["total"],
            "kv_pool_bytes_per_device": pb["per_device"],
            # at-rest pool storage dtype (docs/SERVING.md "Quantized KV
            # pages"): None = compute dtype; "int8" pools include their
            # scale rows in every byte figure above
            "kv_dtype": self.kv_dtype,
            "draft_pool_bytes_per_device": (
                self._spec.pool_bytes["per_device"]
                if self._spec is not None else 0),
            "draining": self._draining,
            "queue_depth": len(self._queue) + len(self._pending),
            "active_slots": int(self._active.sum()),
            "usable_slots": self._usable_slots(),
            "quarantined_slots": int(self._quarantined.sum()),
            "free_pages": acct["free"],
            "quarantined_pages": acct["quarantined"],
            # occupancy for capacity sizing: current referenced pages and
            # the high-water mark — operators size num_pages off these
            # (surfaced on /metrics via the serve/* gauges too)
            "referenced_pages": acct["referenced"],
            "cached_pages": acct["cached"],
            "pages_hwm": self._pages.hwm,
            "shed_total": self.shed_count,
            "deadline_expired_total": self.deadline_count,
            "probes_total": self.probe_count,
            "unfenced_total": self.unfence_count,
            "prefix_hits_total": self.prefix_hits,
            "prefix_misses_total": self.prefix_misses,
            "prefix_shared_tokens_total": self.prefix_shared_tokens,
            "prefix_pages_shared_total": self.prefix_pages_shared,
            "prefix_evictions_total": (self._prefix.evictions
                                       if self._prefix is not None else 0),
            "prefix_index_entries": (len(self._prefix)
                                     if self._prefix is not None else 0),
            "cow_copies_total": self.cow_copies,
            # decode lookahead: ticks launched before the one ahead of them
            # was fetched; those dropped whole (the weights swapped under
            # them); those taken for some of their slots only (the others
            # ended under them) or read by no one; those launched under a
            # mask narrower than the live slots (past a slot's last token);
            # admissions whose first token reached a tick without the host,
            # and prefills in flight whose first token is not fetched yet
            "lookahead_launched_total": self.lookahead_launched,
            "lookahead_dropped_total": self.lookahead_dropped,
            "lookahead_stale_taken_total": self.lookahead_stale_taken,
            "lookahead_past_end_total": self.lookahead_past_end,
            "prefill_fed_on_device_total": self.prefill_fed_on_device,
            "first_tokens_in_flight": len(self._firsts),
            # admission passes that ended with a slot free and the head of
            # the queue waiting for pages (num_pages under the full
            # reservation: pages, not slots, bound the batch)
            "admission_page_waits_total": self.page_waits,
            # a slot's pages follow its length (docs/SERVING.md "Scheduling
            # policy"): pages live slots took at a page boundary, slots
            # that gave their pages up to an older one when the pool had
            # none, the rows their readmissions rebuilt by prefill, and
            # the requests waiting to be readmitted now.  The last three
            # stay 0 in a pool of the full reservation
            "page_grows_total": self.page_grows,
            "preemptions_total": self.preemptions,
            "recomputed_tokens_total": self.recomputed_tokens,
            "preempted_waiting": len(self._preempted),
            # the bytes of the cache's leaves indexed by slot, of a model
            # with a state a slot (counted in kv_pool_bytes_* too)
            "state_pool_bytes": self._exec.state_bytes,
            # what the cache is made of: its kind and how many layers its
            # paged leaves and its slot-indexed leaves are deep
            "cache_kind": self._layout.kind,
            "kv_layers": self._layout.kv_layers,
            "state_layers": self._layout.state_layers,
            # KV-page tiering (docs/SERVING.md "KV-page tiering"): the
            # demoted ledger and host-tier footprint, plus the cumulative
            # movement counters — what capacity planning reads to size the
            # host tier against the prefix working set
            "demoted_pages": acct["demoted"],
            "host_tier_bytes": acct["host_tier_bytes"],
            "host_tier_capacity_pages": self.host_tier_pages or 0,
            "demotions_total": self.demotions,
            "promotions_total": self.promotions,
            "demoted_pages_hwm": self._demoted_hwm,
            # weight epochs (docs/HYBRID.md): the live-weight generation
            # being served plus the flush counters — a rollout controller
            # reads these to confirm the train↔serve flip landed and the
            # stale-KV flush balanced
            "weight_epoch": self._weight_epoch,
            "weight_updates_total": self.weight_updates,
            "kv_flushed_pages_total": self.kv_flushed_pages,
            "kv_flushed_slabs_total": self.kv_flushed_slabs,
            # sampling / speculative (docs/SERVING.md): non-greedy
            # admissions, and — with a draft configured — the verify-tick
            # economics operators size k from (mean accepted length > 1
            # means the draft pays for itself)
            "sampled_admissions_total": self.sampled_admissions,
            # multi-tenant adapter serving (docs/SERVING.md): the loaded
            # inventory a fleet member advertises for adapter-affinity
            # routing, the resolution counters, and the fused-view mode
            "adapters_loaded": (self.adapters.loaded()
                                if self.adapters is not None else []),
            "adapter_admissions_total": self.adapter_admissions,
            "adapter_resolve_total": (self.adapters.resolve_total
                                      if self.adapters is not None else 0),
            "adapter_resolve_miss_total": (
                self.adapters.resolve_miss_total
                if self.adapters is not None else 0),
            "adapter_bytes": (self.adapters.nbytes()
                              if self.adapters is not None else 0),
            "fused_adapter_id": self.fused_adapter_id,
            "speculative_k": self._spec.k if self._spec is not None else 0,
            "spec_verify_slot_ticks_total": (self._spec.verify_slot_ticks
                                             if self._spec is not None
                                             else 0),
            "spec_emitted_tokens_total": (self._spec.emitted_tokens
                                          if self._spec is not None else 0),
            "spec_drafted_tokens_total": (self._spec.drafted_tokens
                                          if self._spec is not None else 0),
            "spec_mean_accepted_len": round(
                self._spec.mean_accepted_len(), 4) if self._spec is not None
            else 0.0,
            "oldest_request_age_s": round(self._oldest_age_s(now), 4),
            "retry_after_hint_s": self._retry_after_hint(),
            "unclaimed_results": len(self._finished_order),
            # per-program device-time accounting + SLO firing states
            # (docs/OBSERVABILITY.md): the fleet advertisement carries
            # alerts so the router can roll up fleet/alerts_firing
            "program_stats": self.program_stats(),
            "alerts": (self._slo.firing() if self._slo is not None
                       else []),
            # the bound /metrics port (None = endpoint not enabled): with N
            # engines on one host each process binds its OWN port (ephemeral
            # fallback), so a scraper discovers endpoints from health/fleet
            # advertisements instead of assuming the configured port
            "metrics_port": self.metrics_port,
        }

    def drain(self, max_ticks: Optional[int] = None) -> List[Request]:
        """Stop admission, finish in-flight work, hand back the unserved
        queue (admission order) for hand-off to another engine.  Finished
        results stay claimable via :meth:`take_results`; later ``submit()``
        calls are shed.  Deadlines keep being enforced while draining."""
        self._draining = True
        start = self._tick
        while self._active.any():
            self.step()
            if max_ticks is not None and self._tick - start >= max_ticks:
                raise ServeTimeout(
                    f"drain exceeded max_ticks={max_ticks} with "
                    f"{int(self._active.sum())} slot(s) still decoding")
        unserved = list(self._queue)
        unserved.extend(self._pending)
        self._queue.clear()
        self._pending.clear()
        self._waiting_deadlines = 0
        self._live_rids.difference_update(r.rid for r in unserved)
        for r in unserved:
            # the hand-off target's submit() starts a fresh queued stamp;
            # keeping these would leak entries for requests we no longer own
            # (one that gave its pages up is handed back whole: the target
            # emits it from its first token)
            self._lifecycle_pending.pop(r.rid, None)
            self._preempted.pop(r.rid, None)
        log_dist(f"serve: drained — {len(unserved)} unserved request(s) "
                 f"handed back, {len(self._finished_order)} result(s) "
                 "claimable", ranks=[0])
        return unserved

    def _write_gauges(self) -> None:
        if self.monitor is None:
            return
        active = float(self._active.sum())
        elapsed = max(time.monotonic() - self._t0, 1e-9)
        self.monitor.write_events([
            ("serve/queue_depth",
             float(len(self._queue) + len(self._pending)), self._tick),
            ("serve/active_slots", active, self._tick),
            ("serve/slot_occupancy", active / self.b_slots, self._tick),
            ("serve/free_pages", float(len(self._pages.free)), self._tick),
            ("serve/admission_page_waits_total", float(self.page_waits),
             self._tick),
            ("serve/page_grows_total", float(self.page_grows), self._tick),
            ("serve/preemptions_total", float(self.preemptions), self._tick),
            ("serve/recomputed_tokens_total", float(self.recomputed_tokens),
             self._tick),
            ("serve/tokens_per_sec", self._tokens_out / elapsed, self._tick),
            ("serve/shed_total", float(self.shed_count), self._tick),
            ("serve/deadline_expired_total", float(self.deadline_count),
             self._tick),
            ("serve/quarantined_slots", float(self._quarantined.sum()),
             self._tick),
            ("serve/quarantined_pages",
             float(len(self._pages.quarantined)), self._tick),
            ("serve/probes_total", float(self.probe_count), self._tick),
            ("serve/unfenced_total", float(self.unfence_count), self._tick),
            ("serve/referenced_pages", float(self._pages.referenced()),
             self._tick),
            ("serve/pages_hwm", float(self._pages.hwm), self._tick),
            ("serve/prefix_hits_total", float(self.prefix_hits), self._tick),
            ("serve/prefix_misses_total", float(self.prefix_misses),
             self._tick),
            ("serve/prefix_shared_tokens_total",
             float(self.prefix_shared_tokens), self._tick),
            ("serve/prefix_index_entries",
             float(len(self._prefix) if self._prefix is not None else 0),
             self._tick),
            ("serve/prefix_evictions_total",
             float(self._prefix.evictions if self._prefix is not None
                   else 0), self._tick),
            ("serve/cow_copies_total", float(self.cow_copies), self._tick),
            ("serve/lookahead_stale_taken_total",
             float(self.lookahead_stale_taken), self._tick),
            ("serve/lookahead_past_end_total",
             float(self.lookahead_past_end), self._tick),
            ("serve/prefill_fed_on_device_total",
             float(self.prefill_fed_on_device), self._tick),
            ("serve/sampled_admissions_total",
             float(self.sampled_admissions), self._tick),
            ("serve/weight_epoch", float(self._weight_epoch), self._tick),
            ("serve/oldest_request_age_s",
             self._oldest_age_s(time.monotonic()), self._tick),
        ])
        if self._tier is not None:
            self.monitor.write_events([
                ("serve/tier_demoted_pages", float(self._prefix.demoted),
                 self._tick),
                ("serve/tier_host_bytes", float(self._tier.bytes()),
                 self._tick),
                ("serve/tier_demotions_total", float(self.demotions),
                 self._tick),
                ("serve/tier_promotions_total", float(self.promotions),
                 self._tick),
            ])
        if self._spec is not None:
            self.monitor.write_events([
                ("serve/spec_emitted_tokens_total",
                 float(self._spec.emitted_tokens), self._tick),
                ("serve/spec_mean_accepted_len",
                 self._spec.mean_accepted_len(), self._tick),
            ])
        if self.adapters is not None:
            # per-tenant accounting (docs/SERVING.md "Multi-tenant adapter
            # serving"): the {adapter=...} suffix rides the flat monitor
            # stream like the program gauges and renders as a real
            # Prometheus label — one admissions/tokens series per tenant
            ad_active = sum(
                1 for s in np.flatnonzero(self._active)
                if self._slots[s].request.adapter_id is not None)
            ad_events = [
                ("serve/adapter_loaded",
                 float(len(self.adapters.loaded())), self._tick),
                ("serve/adapter_active_slots", float(ad_active), self._tick),
                ("serve/adapter_resolve_miss_total",
                 float(self.adapters.resolve_miss_total), self._tick),
            ]
            for aid, n in self._adapter_admit_by_id.items():
                ad_events.append(
                    (f"serve/adapter_admissions_total{{adapter={aid}}}",
                     float(n), self._tick))
            for aid, n in self._adapter_tokens_by_id.items():
                ad_events.append(
                    (f"serve/adapter_tokens_total{{adapter={aid}}}",
                     float(n), self._tick))
            self.monitor.write_events(ad_events)
        # per-program accounting gauges (docs/OBSERVABILITY.md): the
        # {program=...} suffix rides the flat monitor stream and the
        # Prometheus exposition renders it as a real label
        # (dstpu_serve_program_flops{program="decode"}).
        # device_seconds_total is 0 until synced sampling is enabled.
        # gauge_rows() is the flat fast path — no table build per tick.
        prog_events = []
        for name, flops_total, device_s in self._catalog.gauge_rows():
            prog_events.append((f"serve/program_flops{{program={name}}}",
                                float(flops_total), self._tick))
            prog_events.append(
                (f"serve/device_seconds_total{{program={name}}}",
                 float(device_s), self._tick))
        if prog_events:
            self.monitor.write_events(prog_events)
        # SLO firing states as alert{rule=...} gauges -> dstpu_alert{...}
        if self._slo is not None:
            self.monitor.write_events(self._slo.gauge_events(self._tick))
