"""Continuous-batching slot scheduler over the compiled decode engine.

The device-facing half of the serving subsystem (docs/Serving.md): a
fixed grid of ``max_slots`` decode slots over one paged KV block pool.
Every scheduler tick:

1. **retire** active slots whose per-request deadline passed;
2. **admit** queued requests into free slots — prefill the prompt
   through the engine's existing bucketed prefill programs and queue
   the prompt remainder for replay. `slot_prefill_len` says which
   program runs and how much of it is kept, by the rule the engine
   reads off the model (`ceiling_prefill`, never an option): where no
   row of the prefill's cache depends on the tokens after it (causal
   attention, per-token feed-forwards and dropless experts) and nothing
   is held once a slot, all of the prompt but its last token, padded to
   the bucket ABOVE it (no kept row sees the pad under the causal mask;
   the blocks past the kept rows aim at the trash block, and what of
   the pad shares the last owned block lies past the slot's length,
   where the slot writes as it grows), so ONE token replays; otherwise
   the largest bucket BELOW the prompt, kept whole, and the rest
   replayed a token a tick (`/stats` ``prefills_ceiling``,
   ``prefills_floor``, ``prefill_pad_tokens``; ``bucket`` and ``kept``
   on the `serving/prefill` span).
   With **chunked prefill** (``prefill_chunk`` > 0) the
   blocking prefill program is skipped entirely — the slot installs
   immediately and the whole prompt queues as pending tokens that the
   windowed step replays ``prefill_chunk`` at a time, interleaved with
   decode under ``prefill_budget_per_tick``, so admission never stalls
   the decode tick (docs/Serving.md "Chunked prefill");
3. **launch** the next step of ALL slots, one token in ONE compiled
   program: replaying slots force their next prompt token (no RNG
   consumed — the split chain stays bit-aligned with `generate_legacy`),
   emitting slots feed back their last token on the device, free slots
   ride along masked off;
4. **read** the step launched a tick before (the tick's one host sync)
   and hand its tokens on, with the device under the step just launched:
   a pipeline one step deep (`_step`), emptied by name (`_settle`)
   wherever a slot is needed as the device left it;
5. **retire** slots whose read token was their eos or their
   max_new_tokens-th, pushing their slot back on the free-list —
   reusable on the next tick, so decode work for in-flight requests
   never waits for a batch to drain (continuous batching, not static
   batching).

The KV cache is ONE global pool of fixed-size blocks
(`make_paged_pool`) plus per-slot block tables, gathered/scattered
inside the compiled `paged_step`/`pack_prefill` programs. Freeing a
slot is O(blocks) host-side free-list bookkeeping
(`serving/paging.py`) — no device eviction program at all — and a
**prefix cache** maps requests sharing a prompt prefix onto
refcounted shared blocks instead of re-running prefill. Admission
reserves every block a request can ever need (prompt + max_new - 1
tokens) up front, so decode never stalls mid-request; when the pool
cannot cover the next request, admission *holds* it (LRU-evicting
prefix entries first) until retirements free blocks — or, with a
host tier configured (``kv_host_blocks`` > 0), **suspends** the
lowest-SLO-tier active stream instead: its KV blocks bulk-gather
through the engine's `extract_blocks` program, `device_get` to a
:class:`HostBlockStore`, and scatter back through `inject_blocks`
when retirements free capacity (FIFO within tier) — the resumed
stream is BIT-IDENTICAL to an uninterrupted run (replay consumes no
RNG; the slot's rng row is saved/restored; prefix-shared blocks are
never swapped, they re-attach through the normal lookup). The fp
path is BIT-IDENTICAL to `generate_legacy`.

The scheduler is a pure host-side state machine: its only device
contract is the engine's slot methods, so the unit tests drive it with
fake engines and assert the tick-by-tick trace deterministically.
"""

from __future__ import annotations

import collections
import logging
import statistics
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from tf_yarn_tpu import telemetry
from tf_yarn_tpu.telemetry import spans
from tf_yarn_tpu.models.spec import make_drafter, plan_window
from tf_yarn_tpu.serving.paging import (
    TRASH_BLOCK,
    BlockPool,
    HostBlockStore,
    PrefixCache,
)
from tf_yarn_tpu.serving.request import (
    DEFAULT_TIER,
    FINISH_DEADLINE,
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_LENGTH,
    FINISH_SHUTDOWN,
    AdmissionQueue,
    QueueFull,
    Request,
    Response,
    RetryAfterEstimator,
    SamplingParams,
    tier_rank,
)

_logger = logging.getLogger(__name__)

# How long the scheduler loop sleeps between ticks when nothing is
# active or queued; a submit wakes it immediately, so this only bounds
# deadline-expiry latency for queued-but-idle states.
IDLE_POLL_S = 0.05

# A tick's `serving/step` is "slow" when it takes more than
# SLOW_STEP_FACTOR times the running median of the last SLOW_STEP_WINDOW
# of them (judged once SLOW_STEP_MIN_HISTORY exist). Only ticks whose read
# had nothing queued ahead of it are judged and remembered: a read behind
# an admission's prefill is long by that prefill, which the `ahead_*`
# counters hold.
SLOW_STEP_FACTOR = 2.0
SLOW_STEP_WINDOW = 256
SLOW_STEP_MIN_HISTORY = 8
# `finish` of the `serving/request` record of a request `submit` turned
# away (queue full, tier cap, unservable): it never had a Response.
REFUSED = "refused"

# A `serving/step_sync` longer than this waited for the device
# (`paced="device"`); a shorter one found the result there already, and the
# host set that tick's pace (`paced="host"`). Measured on a TPU v5 lite
# (PR 39's chip runs): the read of a result known to be ready (the real
# scheduler and engine driven by hand, the step in flight waited for and
# 20 ms more before each tick) takes 43 us at the median and 93 us at most
# for one array (the tokens; 220 reads), 65 us and 126 us for three (tokens,
# expert counts, cache reads; 228 reads); in the benchmark's Mistral cells
# the reads under this constant took 30-98 us (447 of them in one window),
# and of the reads that waited the shortest hundredth took 1.7 ms. Twice
# the longest ready read, a sixth of the shortest wait.
SYNC_READY_S = 0.00025

DECODE_ATTENTION = ("gather", "fused")
# Why the one-token pipeline was emptied (`/stats` `pipeline_settles`):
# a tick found every slot's last token in flight already, a suspension or
# block shipping needed the slots as the device left them, the grid was
# shut down or its tick failed.
SETTLE_REASONS = ("nothing_to_launch", "suspend", "control_op",
                  FINISH_SHUTDOWN, FINISH_ERROR)


class _Slot:
    """Host-side state of one occupied decode slot.

    A slot with non-empty ``pending`` is in its PREFILLING phase: the
    step program is still consuming prompt tokens (the blocking path's
    short bucket remainder, or — chunked prefill — the whole prompt).
    It transitions to DECODING the tick its last pending token is
    consumed, with no host-visible state change beyond the deque
    emptying."""

    __slots__ = ("request", "response", "pending", "last_token", "emitted",
                 "launched", "refeed", "blocks", "context", "prompt_filled",
                 "registered_blocks", "last_emit_at", "kv_len", "prefilled",
                 "hit_tokens", "replay", "queue_wait_s", "prefill_s",
                 "admitted_clock", "replay_s")

    def __init__(self, request: Request, response: Response,
                 pending: List[int], blocks: Optional[List[int]] = None):
        self.request = request
        self.response = response
        # Prompt tokens still to replay through the step program; the
        # LAST one's step output is the first generated token.
        self.pending: Deque[int] = collections.deque(pending)
        self.last_token = 0
        self.emitted = 0
        # Sampled one-token steps launched for this slot: `emitted` plus
        # the one whose token the host has not read yet. At
        # `max_new_tokens` the slot is left out of the next launch.
        self.launched = 0
        # A resumed stream's next step takes `last_token` from the host:
        # the device's fed-back token is another request's.
        self.refeed = False
        # The physical block ids this slot holds one reference on
        # (shared prefix blocks included).
        self.blocks = blocks
        # The request's full token history (prompt + emissions) — the
        # speculative drafter's lookup corpus. Appended to only on the
        # windowed path.
        self.context: List[int] = list(request.prompt)
        # Prompt tokens with valid KV (prefilled/hit + replayed so far);
        # drives the chunked path's incremental prefix registration.
        self.prompt_filled = len(request.prompt) - len(self.pending)
        # Whole prompt blocks already offered to the prefix cache
        # (chunked path only).
        self.registered_blocks = 0
        # monotonic time of the last token push — the inter-token
        # latency histogram's reference point.
        self.last_emit_at: Optional[float] = None
        # Tokens with valid KV in this slot's blocks (its `_lengths`
        # row): what a length-aware attention would have to read.
        self.kv_len = self.prompt_filled
        # The request's time to its first token, in the parts
        # `_record_admission` and `_observe_ttft` fill in (span clock):
        # tokens through the blocking prefill program / taken from the
        # prefix cache / left to replay, submit -> admission, admission
        # (its blocking prefill), admission's end -> first token.
        self.prefilled = 0
        self.hit_tokens = 0
        self.replay = len(self.pending)
        self.queue_wait_s = 0.0
        self.prefill_s = 0.0
        self.admitted_clock = 0.0
        self.replay_s: Optional[float] = None


class _Suspended:
    """A stream parked on the host tier: its _Slot state (pending
    replay, emission counts, drafter context) plus everything a resume
    must restore exactly — the slot's rng row (bit-identity: resume
    must NOT re-derive it from the seed), the valid KV length, and how
    many leading blocks the swap payload covers. The payload itself
    lives in the HostBlockStore keyed by request id."""

    __slots__ = ("state", "rng", "length", "n_valid", "suspended_at")

    def __init__(self, state: _Slot, rng: np.ndarray, length: int,
                 n_valid: int, suspended_at: float):
        self.state = state
        self.rng = rng
        self.length = length
        self.n_valid = n_valid
        self.suspended_at = suspended_at

    @property
    def request(self) -> Request:
        return self.state.request


class _Flight:
    """A one-token step that was launched and not read yet: its results,
    still on the device, and what the launch knew of each slot it stepped
    (`stepped`: (slot, the `_Slot` it held, whether it sampled)). A slot
    that holds another `_Slot` at the read was retired meanwhile, and its
    result is dropped. `step` is the model step's number, which its launch,
    sync and emit spans share across two ticks; `ahead` is what the
    scheduler's thread dispatched to the device between the launch before
    and this one, and so runs before this step: (programs, prompt tokens of
    blocking prefills)."""

    __slots__ = ("emitted", "counts", "reads", "stepped", "prefill_tokens",
                 "step", "ahead")

    def __init__(self, emitted, counts, reads, stepped, prefill_tokens,
                 step, ahead):
        self.emitted = emitted
        self.counts = counts
        self.reads = reads
        self.stepped = stepped
        self.prefill_tokens = prefill_tokens
        self.step = step
        self.ahead = ahead


class _ControlOp:
    """One cross-thread request into the scheduler thread (prefix
    export/import for the fleet warm-start path). The caller blocks on
    `done`; the scheduler services queued ops at the top of each tick —
    the paging classes stay scheduler-thread-only, no new locks."""

    __slots__ = ("kind", "arg", "done", "result", "error")

    def __init__(self, kind: str, arg):
        self.kind = kind
        self.arg = arg
        self.done = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None


class SlotScheduler:
    """Continuous batching over a fixed slot grid (module docstring).

    `temperature`/`top_k`/`top_p` configure the ONE compiled step
    program the grid runs; requests whose SamplingParams disagree are
    rejected at submit with ValueError (the HTTP frontend's 400).

    Pool knobs: ``block_size`` tokens per KV block; ``num_blocks``
    physical blocks in the pool (default: ``max_slots * max_seq_len /
    block_size + 1``, every slot at full context — shrink it to realize
    the HBM saving); ``prefix_cache_capacity``
    entries in the shared-prefix LRU (0 disables prefix sharing);
    ``max_seq_len`` overrides the engine-derived context bound (fake
    engines in tests have no model config).

    Speculative knobs (docs/Serving.md "Speculative decoding"):
    ``spec_k`` drafts per slot per tick (0 = the exact paths above);
    ``spec_draft`` the proposer ("ngram" self-draft, or a callable
    ``(context, k) -> tokens`` — the draft-model hook);
    ``decode_attention`` = "gather" (reference) or "fused" (paged int8
    pools read directly by the pallas kernel inside the verify
    forward). Emitted streams are identical to the exact path; each
    tick just advances 1..spec_k+1 tokens per slot, and
    ``context_limit`` shrinks by ``spec_k`` (window scratch headroom).

    Chunked prefill (docs/Serving.md "Chunked prefill"):
    ``prefill_chunk`` > 0 replaces the blocking admission prefill with
    teacher-forced windows of that many prompt tokens riding the SAME
    windowed step program decode runs — admit installs the slot
    immediately and every tick mixes chunking and decoding slots in one
    compiled program ("auto" = the engine's largest prompt bucket, or
    the spec window when larger; 0/None = the blocking path).
    ``prefill_budget_per_tick`` caps the prompt tokens replayed per
    tick across all slots — over-budget slots pause (masked off,
    consuming nothing) in round-robin order, so a burst of long
    prompts cannot monopolize the window while decode slots ride the
    same program untouched. Emitted streams stay BIT-IDENTICAL to the
    blocking path (replay consumes no RNG either way), and
    ``context_limit`` reserves ``window - 1`` positions of KV headroom.

    KV oversubscription (docs/Serving.md "KV oversubscription & SLO
    tiers"): ``kv_host_blocks`` > 0 backs the
    device pool with that many host-RAM blocks; under pool pressure
    the scheduler SUSPENDS the lowest-tier active stream (swap out)
    instead of holding the new admission, and resumes it — bit-
    identically — once capacity frees. ``tier_caps`` maps tier name ->
    max in-system requests (queued + active + suspended); a tier at
    its cap rejects with QueueFull (HTTP 429), keeping batch floods
    from ever crowding the interactive tier's queue.

    Per-slot state (docs/Serving.md "State held once a slot", "Latent,
    index and window leaves"): a model whose cache has leaves held once a
    slot (a recurrent state, a window layer's ring; the engine's
    ``slot_state_leaves``) gets arrays ``[max_slots, ...]`` beside the
    block pool. Admission writes the prefill's final state, or
    zeros, into the slot before its first replayed token, and the step
    reads and writes the state in place. Everything that moves keys and
    values WITHOUT the state steps aside by name: the prefix cache
    neither registers nor hits (``prefix_skipped_stateful`` counts), and
    the host swap tier, chunked prefill, the
    speculative / fused window, tensor parallelism and prefix export /
    import are refused with an error naming the feature and the leaves.

    Which step a model gets is another question (the engine's
    ``counted_step``): a model whose layers count what they routed and
    read is stepped by ``paged_state_step`` whether or not it holds
    anything once a slot. Where every leaf is paged (docs/Serving.md "A
    model whose every leaf is paged and latent") the state is empty, the
    counts still come back with the tokens, and the prefix cache, the
    host swap tier and prefix export / import, which move whole blocks
    and nothing else, serve it as they serve keys and values. Such a
    server proves the step its ticks will take at construction, the
    windowed one where one is asked for, so that a model that refuses
    it does so by name before anything is served.
    """

    def __init__(
        self,
        engine,
        params,
        max_slots: int = 8,
        *,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        queue_capacity: int = 64,
        retry_after_s: float = 1.0,
        trace_len: int = 4096,
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        prefix_cache_capacity: int = 256,
        max_seq_len: Optional[int] = None,
        spec_k: int = 0,
        spec_draft="ngram",
        decode_attention: str = "gather",
        prefill_chunk=None,
        prefill_budget_per_tick: Optional[int] = None,
        kv_host_blocks: int = 0,
        tier_caps: Optional[Dict[str, int]] = None,
    ):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if decode_attention not in DECODE_ATTENTION:
            raise ValueError(
                f"decode_attention must be one of {DECODE_ATTENTION}, "
                f"got {decode_attention!r}"
            )
        # Tensor-parallel decode rides entirely inside the engine's
        # compiled programs — the scheduler's tick logic is unchanged —
        # but the one composition that CANNOT shard fails here, loudly,
        # before any pool is allocated.
        self.tp_degree = int(getattr(engine, "tp_degree", 1) or 1)
        if decode_attention == "fused" and self.tp_degree > 1:
            raise ValueError(
                "decode_attention='fused' cannot run tensor-parallel "
                f"(engine tp={self.tp_degree}): the paged-int8 pallas "
                "kernel cannot read a sharded block pool yet; use "
                "decode_attention='gather' or tp=1"
            )
        self.engine = engine
        self.params = params
        self.max_slots = max_slots
        # Cache leaves the model holds once a slot (fake engines in tests
        # have none to name).
        leaves_of = getattr(engine, "slot_state_leaves", None)
        self._state_leaves: Tuple[str, ...] = tuple(
            leaves_of(params)) if leaves_of else ()
        # Whether the one-token step is `paged_state_step`: asked apart
        # from whether anything is held once a slot.
        counted_of = getattr(engine, "counted_step", None)
        self._counted_step = bool(counted_of(params)) if counted_of \
            else bool(self._state_leaves)
        # Whether an admission prefills the bucket above its prompt and
        # keeps the true length: the engine's reading of the model.
        ceiling_of = getattr(engine, "ceiling_prefill", None)
        self._ceiling_prefill = bool(ceiling_of and ceiling_of(params))
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.spec_k = int(spec_k)
        self.decode_attention = decode_attention
        # Speculative decoding (docs/Serving.md): window width = the
        # last token (or replay prefix) + spec_k drafts. The windowed
        # tick also carries the fused-attention path at width 1, so
        # decode_attention="fused" alone routes through it.
        self._spec_width = self.spec_k + 1
        # Chunked prefill (docs/Serving.md "Chunked prefill"): resolve
        # the chunk width, widen the window to cover it, and route the
        # tick through the windowed program.
        if prefill_chunk in (None, 0):
            chunk = 0
        elif prefill_chunk == "auto":
            buckets = getattr(engine, "prompt_buckets", None) or ()
            chunk = max([self._spec_width] + [int(b) for b in buckets])
        else:
            chunk = int(prefill_chunk)
            if chunk < 1:
                raise ValueError(
                    "prefill_chunk must be >= 1, 'auto', or 0/None "
                    f"(blocking admission), got {prefill_chunk!r}"
                )
        self.prefill_chunk = chunk
        self._chunked = chunk > 0
        self._window_width = max(self._spec_width, chunk) \
            if self._chunked else self._spec_width
        self._windowed = (
            self.spec_k > 0 or decode_attention == "fused" or self._chunked
        )
        if prefill_budget_per_tick is not None:
            if not self._chunked:
                raise ValueError(
                    "prefill_budget_per_tick needs chunked prefill "
                    "(prefill_chunk >= 1 or 'auto'); with blocking "
                    "admission there is no per-tick prefill to budget"
                )
            budget = int(prefill_budget_per_tick)
            if budget < self._window_width:
                raise ValueError(
                    f"prefill_budget_per_tick ({budget}) must be >= the "
                    f"window width ({self._window_width}, i.e. "
                    "max(prefill_chunk, spec_k + 1)) or no chunking slot "
                    "could ever advance"
                )
            prefill_budget_per_tick = budget
        self.prefill_budget_per_tick = prefill_budget_per_tick
        self._drafter = make_drafter(spec_draft) if self.spec_k > 0 else None
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._prefill_tokens = 0
        self._decode_tokens = 0
        # Work counters where the work happens (/stats, docs/Serving.md).
        self._prefilled_tokens = 0
        # Blocking prefills by rule, and the pad rows the device ran beside
        # the kept ones (bucket - kept, summed).
        self._prefills_ceiling = 0
        self._prefills_floor = 0
        self._prefill_pad_tokens = 0
        # Query-key pairs, a head, over the blocking prefills' attention
        # layers: those the scores were formed over and those a prompt row
        # can see (`DecodeEngine.prefill_key_pairs`; 0 on an engine
        # without it).
        self._prefill_key_pairs = getattr(
            engine, "prefill_key_pairs", lambda bucket, kept: (0, 0))
        self._prefill_keys_formed = 0
        self._prefill_keys_visible = 0
        self._kv_token_steps = 0
        self._kv_read_token_steps = 0
        self._slot_steps = 0
        self._step_seconds: Deque[float] = collections.deque(
            maxlen=SLOW_STEP_WINDOW)
        self._slow_steps = 0
        self._slow_step_seconds = 0.0
        self._slowest_step: Optional[Dict] = None
        # The last step's (launch, sync, emit) spans, for the tally.
        self._step_parts: Tuple = ()
        kv_host_blocks = int(kv_host_blocks or 0)
        if kv_host_blocks < 0:
            raise ValueError(
                f"kv_host_blocks must be >= 0, got {kv_host_blocks}"
            )
        self.kv_host_blocks = kv_host_blocks
        self.tier_caps: Dict[str, int] = {}
        for name, cap in dict(tier_caps or {}).items():
            tier_rank(name)  # unknown tier names fail loudly here
            if int(cap) < 0:
                raise ValueError(
                    f"tier_caps[{name!r}] must be >= 0, got {cap}"
                )
            self.tier_caps[name] = int(cap)
        # Load-aware backpressure: retirements feed the sliding-window
        # rate, 429s carry depth_ahead / rate (floored at the static
        # retry_after_s hint).
        self._estimator = RetryAfterEstimator(floor_s=retry_after_s)
        self.queue = AdmissionQueue(
            queue_capacity, retry_after_s, estimator=self._estimator
        )
        self._tier_lock = threading.Lock()
        self._tier_inflight: Dict[str, int] = {}
        # Streams parked on the host tier, in suspension order; resume
        # picks the highest tier first, FIFO within a tier.
        self._suspended: List[_Suspended] = []
        self._suspends = 0
        self._resumes = 0
        self._swap_out_blocks = 0
        self._swap_in_blocks = 0
        self._peak_streams = 0
        # The rng row each slot was admitted or resumed with (the windowed
        # step: the row it holds now, read back every tick).
        self._rngs = np.zeros((max_slots, 2), np.uint32)
        # Refused here, at start-up, and not inside a tick at every
        # admission: a PRNG implementation whose keys the grid cannot hold.
        _prng_key(0)
        # The one-token pipeline (docs/Serving.md "Where a tick's time
        # goes"): the newest step's `emitted` and `rngs`, which the next
        # launch takes as they are, on the device; the launched step the
        # host has not read; requests a settle outside a tick retired.
        self._fed = (np.zeros((max_slots,), np.int32),
                     np.zeros((max_slots, 2), np.uint32))
        self._flight: Optional[_Flight] = None
        self._carried_retired: List = []
        self._steps = 0
        self._steps_ahead = 0
        # Programs (and prompt tokens of blocking prefills among them)
        # dispatched since the last launch: the next flight's `ahead`.
        self._ahead = [0, 0]
        # A tick's account of itself (docs/Serving.md "Where a tick's time
        # goes"), all from the spans' own start and duration. The thread's
        # time in three parts: under `step_sync`, under `idle_wait`, and
        # the host's (the rest of its top-level spans).
        self._steps_read = 0
        self._steps_host_paced = 0
        self._host_seconds = 0.0
        self._sync_wait_seconds = 0.0
        self._idle_wait_seconds = 0.0
        self._tick_sync_s = 0.0  # `step_sync` under this round's spans
        # Read end to read end (`_note_read`): one model step's device
        # time where both reads waited for the device and nothing was
        # queued ahead of the second (`clean`), one step and what an
        # admission put before it where something was (`ahead`).
        self._clean_intervals = 0
        self._clean_interval_seconds = 0.0
        self._ahead_intervals = 0
        self._ahead_interval_seconds = 0.0
        self._ahead_prefill_tokens = 0
        # When the last read ended (None once the pipeline was emptied),
        # and whether it waited for the device.
        self._read_end: Optional[float] = None
        self._read_waited = False
        # Every reason from the start: `stats()` copies it on other threads.
        self._settles: Dict[str, int] = dict.fromkeys(SETTLE_REASONS, 0)
        self._slots: List[Optional[_Slot]] = [None] * max_slots
        self._free: Deque[int] = collections.deque(range(max_slots))
        self._used_before = [False] * max_slots
        self.trace: Deque[Dict] = collections.deque(maxlen=trace_len)
        # request.id -> cross-task trace id (the router's X-Request-Id)
        # for requests still in flight; written by submit() on any
        # thread, read by the tick when stamping trace-ring entries,
        # pruned at retirement. Own lock: submit() must not contend on
        # tick-internal state.
        self._trace_ids: Dict[int, str] = {}
        self._trace_id_lock = threading.Lock()
        self._ticks = 0
        self._draining = False
        # Pending cross-thread control ops (prefix export/import),
        # serviced by the scheduler thread at the top of each tick.
        self._control: Deque[_ControlOp] = collections.deque()
        self._control_lock = threading.Lock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._lifecycle = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._registry = registry = telemetry.get_registry()
        self._tracer = telemetry.get_tracer()
        # What every tick observes or sets, looked up once (a lookup is a
        # label key and the registry's lock).
        self._tick_seconds = registry.histogram("serving/tick_seconds")
        self._ticks_total = registry.counter("serving/ticks_total")
        self._token_gaps = registry.histogram(
            "serving/inter_token_latency_ms")
        self._gauges = {
            name: registry.gauge("serving/" + name)
            for name in ("active_slots", "free_slots", "queue_depth",
                         "block_pool_used_blocks", "block_pool_free_blocks",
                         "prefix_cache_entries", "prefix_cache_blocks",
                         "prefix_cache_hit_rate")
        }
        self._suspended_gauges: Dict[str, object] = {}
        # max context the model's KV cache can hold, when the engine
        # exposes a config (the fake engines in tests need not) or the
        # caller says so explicitly.
        if max_seq_len is None:
            max_seq_len = getattr(
                getattr(engine, "model", None), "config", None
            )
            max_seq_len = getattr(max_seq_len, "max_seq_len", None)
        self._max_seq_len = max_seq_len
        # A request the pool could not cover yet: admitted before the
        # queue on the next tick, once retirements free blocks.
        self._held: Optional[Tuple[Request, Response]] = None
        self._state = None
        self._state_bytes = 0
        self._cache_bytes_by_kind: Dict[str, int] = {}
        # What the attention layers counted (a model with `cache_stats`),
        # under the names of the model's contract (`reads`).
        self._cache_reads: Dict[str, int] = {}
        self._state_resets = 0
        self._prefix_skipped_stateful = 0
        self._prefix_capacity = int(prefix_cache_capacity or 0)
        # What the expert layers counted (a model with `moe_stats`).
        self._moe = {"assignments": 0, "assignments_here": 0,
                     "layer_steps": 0, "experts_touched": 0,
                     "experts_streamed": 0,
                     "load_max_sum": 0, "load_mean_sum": 0.0}
        if self._state_leaves:
            self._refuse_for_state(kv_host_blocks)

        if self._max_seq_len is None:
            raise ValueError(
                "the paged KV pool needs max_seq_len (engine.model."
                "config.max_seq_len or the max_seq_len= argument)"
            )
        if self._max_seq_len % block_size:
            raise ValueError(
                f"block_size={block_size} must divide "
                f"max_seq_len={self._max_seq_len}"
            )
        self._block_size = int(block_size)
        self._blocks_per_slot = self._max_seq_len // self._block_size
        if num_blocks is None:
            # Every slot at full context (+ the trash block); shrink
            # for the actual HBM saving.
            num_blocks = max_slots * self._blocks_per_slot + 1
        self._pool = engine.make_paged_pool(
            params, num_blocks, self._block_size
        )
        self._blocks = BlockPool(num_blocks, self._block_size)
        self._prefix = PrefixCache(self._blocks, prefix_cache_capacity)
        self._host_store = (
            HostBlockStore(kv_host_blocks, self._block_size)
            if kv_host_blocks else None
        )
        if self._host_store is not None:
            for name in ("host_blocks_used", "host_blocks_free"):
                self._gauges[name] = registry.gauge("serving/" + name)
        self._tables = np.zeros(
            (max_slots, self._blocks_per_slot), np.int32
        )
        self._lengths = np.zeros((max_slots,), np.int32)
        kv_bytes = _cache_nbytes(self._pool)
        if self._counted_step:
            try:
                # All None where every leaf is paged.
                self._state = engine.make_slot_state(params, max_slots)
            except Exception as exc:
                raise RuntimeError(
                    "serving cannot start: no room for the state of "
                    f"{max_slots} slots "
                    f"({', '.join(self._state_leaves)}) beside "
                    f"{kv_bytes} bytes of KV pool: "
                    f"{type(exc).__name__}: {exc}"
                ) from exc
            self._state_bytes = _cache_nbytes(self._state)
            self._cache_bytes_by_kind = engine.cache_bytes_by_kind(
                params, self._pool, self._state)
        self._kv_bytes = kv_bytes
        # Per-DEVICE residency: under tp sharding each device holds 1/tp
        # of every slot's KV (global bytes above are unchanged) — the
        # capacity-per-chip number the HBM planning reads.
        self._kv_bytes_per_device = _cache_nbytes_per_device(
            self._pool
        ) or kv_bytes
        # The label stays on the wire (docs/Serving.md): a fleet registry
        # or a dashboard may key on it.
        self._registry.gauge(
            "serving/kv_cache_hbm_bytes", layout="paged"
        ).set(kv_bytes)
        self._registry.gauge(
            "serving/kv_cache_hbm_bytes_per_device", layout="paged"
        ).set(self._kv_bytes_per_device)
        self._registry.gauge("serving/tp_degree").set(self.tp_degree)
        self._registry.gauge("serving/state_hbm_bytes").set(self._state_bytes)
        if self._counted_step:
            self._prove_step()

    def _refuse_for_state(self, kv_host_blocks: int) -> None:
        """A model with state held once a slot: every feature that would
        move or replay keys and values without that state is refused here,
        by name, before anything is allocated."""
        leaves = ", ".join(self._state_leaves)
        refused = {
            "the host swap tier (kv_host_blocks: suspend / resume)":
                kv_host_blocks > 0,
            "chunked prefill (prefill_chunk)": self._chunked,
            "the speculative step (spec_k)": self.spec_k > 0,
            "decode_attention='fused'": self.decode_attention == "fused",
            "tensor-parallel decode": self.tp_degree > 1,
        }
        for feature, asked in refused.items():
            if asked:
                raise ValueError(
                    f"{feature} does not carry the state this model holds "
                    f"once a slot ({leaves}) and would serve wrong tokens; "
                    "it is refused until it does (docs/Serving.md \"State "
                    "held once a slot\")"
                )

    def _prove_step(self) -> None:
        """Compile and run once on the empty grid the step the ticks will
        take, so that state slots that do not fit, or a step the compiler
        or the model refuses (a window of tokens through a layer that
        reads one a slot), stop the server at start-up with the reason —
        not every request with `error` under a /healthz that says ok. Free
        slots' rows go to the trash block and admission rewrites a slot's
        state, so the run leaves nothing behind."""
        import jax

        sampling = dict(block_size=self._block_size,
                        temperature=self.temperature, top_k=self.top_k,
                        top_p=self.top_p)
        idle = np.zeros((self.max_slots,), bool)
        try:
            if self._windowed:
                width = self._window_width
                self._pool, emitted, *_ = self.engine.paged_spec_step(
                    self.params, self._pool, self._tables, self._lengths,
                    np.zeros((self.max_slots, width), np.int32),
                    np.zeros((self.max_slots,), np.int32),
                    np.full((self.max_slots,), -1, np.int32), self._rngs,
                    idle, decode_attention=self.decode_attention, **sampling)
            else:
                from tf_yarn_tpu.models.decode_engine import all_forced

                self._pool, self._state, emitted, *_ = \
                    self.engine.paged_state_step(
                        self.params, self._pool, self._state, self._tables,
                        self._lengths,
                        *all_forced(np.zeros((self.max_slots,), np.int32),
                                    self._rngs),
                        idle, **sampling)
            jax.block_until_ready(emitted)
        except Exception as exc:
            held = (f"per-slot state ({', '.join(self._state_leaves)}; "
                    f"{self._state_bytes} bytes of state for "
                    f"{self.max_slots} slots beside {self._kv_bytes} bytes "
                    "of KV pool)") if self._state_leaves else (
                f"every cache leaf paged ({self._kv_bytes} bytes of pool "
                f"for {self.max_slots} slots)")
            family = "windowed" if self._windowed else "paged"
            raise RuntimeError(
                f"serving cannot start: the {family} step of a model with "
                f"{held} did not compile or run: {type(exc).__name__}: {exc}"
            ) from exc

    # -- submission (any thread) -------------------------------------------

    @property
    def context_limit(self) -> int:
        """Max prompt + max_new_tokens this grid can serve. The windowed paths
        reserve ``window - 1`` positions of KV headroom per slot: a
        window writes all its rows before acceptance is known, so the
        last tick's rejected (or paused-garbage) rows must still land
        inside the cache. window = max(spec_k + 1, prefill_chunk), so
        the exact path loses nothing and the spec path loses spec_k
        exactly as before."""
        return self._max_seq_len - (self._window_width - 1)

    def submit(
        self,
        prompt,
        params: Optional[SamplingParams] = None,
        priority: int = 0,
        timeout_s: Optional[float] = None,
        tier: str = DEFAULT_TIER,
        trace_id: Optional[str] = None,
    ) -> Response:
        """Admit one request; returns its streaming Response. Raises
        ValueError for requests this grid cannot serve (an unknown
        `tier` included) and QueueFull when the bounded queue — or the
        request's tier cap — is at capacity (backpressure). `trace_id`
        (the router's X-Request-Id) tags this request's trace-ring
        entries so one id joins router span → queue wait → ticks."""
        began = spans.now()
        try:
            return self._submit(prompt, params, priority, timeout_s, tier,
                                trace_id)
        except Exception:
            # Turned away (unservable, queue full, tier at its cap):
            # still one `serving/request` record under the caller's id.
            self._tracer.record(
                "serving/request", began, spans.now() - began,
                request_id=trace_id, finish=REFUSED,
                prompt_tokens=len(prompt) if hasattr(prompt, "__len__")
                else None,
            )
            raise

    def _submit(self, prompt, params, priority, timeout_s, tier,
                trace_id) -> Response:
        params = params or SamplingParams(
            temperature=self.temperature, top_k=self.top_k, top_p=self.top_p
        )
        if (params.temperature, params.top_k, params.top_p) != (
            self.temperature, self.top_k, self.top_p,
        ):
            raise ValueError(
                "this serving grid runs temperature="
                f"{self.temperature}, top_k={self.top_k}, "
                f"top_p={self.top_p}; per-request sampling overrides are "
                "not supported (the config is baked into the compiled "
                "step program)"
            )
        request = Request(
            prompt=tuple(prompt), params=params, priority=priority,
            timeout_s=timeout_s, tier=tier, trace_id=trace_id,
        )
        if len(request.prompt) + params.max_new_tokens > self.context_limit:
            headroom = (
                f" minus the {self._window_width - 1}-token window "
                "headroom (max(spec_k, prefill_chunk - 1))"
                if self._window_width > 1 else ""
            )
            raise ValueError(
                f"prompt ({len(request.prompt)}) + max_new_tokens "
                f"({params.max_new_tokens}) exceeds the model's "
                f"max_seq_len ({self._max_seq_len}){headroom} — the slot "
                "KV size"
            )
        need = self._blocks_needed(request)
        if need > self._blocks.num_blocks - 1:
            raise ValueError(
                f"request needs {need} KV blocks but the pool holds "
                f"{self._blocks.num_blocks - 1} — it can never be "
                "admitted; raise num_blocks or shorten the request"
            )
        try:
            # Tier-cap + queue admission under one lock: the cap bounds
            # the tier's whole in-system footprint (queued + active +
            # suspended), so a batch flood 429s at its own cap instead
            # of consuming queue capacity the interactive tier needs.
            with self._tier_lock:
                cap = self.tier_caps.get(request.tier)
                inflight = self._tier_inflight.get(request.tier, 0)
                if cap is not None and inflight >= cap:
                    raise QueueFull(inflight, self.queue.retry_hint(request))
                response = self.queue.submit(request)
                self._tier_inflight[request.tier] = inflight + 1
        except Exception:
            self._registry.counter("serving/requests_rejected_total").inc()
            raise
        if trace_id is not None:
            with self._trace_id_lock:
                self._trace_ids[request.id] = trace_id
        self._registry.counter("serving/requests_total").inc()
        self._gauges["queue_depth"].set(self.queue.depth)
        self._work.set()
        return response

    def _blocks_needed(self, request: Request) -> int:
        # Cache occupancy over the request's whole lifetime: the prompt
        # plus every fed-back generated token (the last emitted token is
        # never fed back, so max_new - 1).
        total = len(request.prompt) + request.params.max_new_tokens - 1
        return -(-total // self._block_size)

    # -- the tick (scheduler thread) ----------------------------------------

    def tick(self) -> bool:
        """One scheduling round; returns whether any work happened (the
        loop idles when it returns False)."""
        # The scheduler's thread is always under a named span: control
        # ops, the tick, what follows it, the idle wait (docs/Serving.md
        # "Where a tick's time goes").
        self._tick_sync_s = 0.0
        with telemetry.span("serving/control_ops") as control_span:
            self._run_control_ops()
        now = time.monotonic()
        admitted: List[int] = []
        # With whatever a settle outside a tick retired.
        retired, self._carried_retired = self._carried_retired, []
        tick_no = self._ticks + 1  # its number, if any work happens
        with telemetry.span("serving/tick", tick=tick_no) as tick_span:
            with telemetry.span("serving/retire"):
                self._retire_deadlines(now, retired)
            if self._suspended:
                with telemetry.span("serving/resume"):
                    self._resume_suspended(now, admitted)
            with telemetry.span("serving/admit"):
                self._admit_queued(now, admitted, retired)
            active = [s for s in range(self.max_slots) if self._slots[s]]
            accepts = step_span = None
            # A step in flight is read even where every slot it stepped has
            # gone (deadlines): its counts are the device's work.
            if active or self._flight is not None:
                # The annotation (only while a capture started through
                # telemetry.profile runs) puts the tick's number into the
                # profiler's own host plane, beside jit_step's executions.
                with telemetry.span(
                    "serving/step", active=len(active), tick=tick_no
                ) as step_span, telemetry.profile.annotation(
                    "serving/step", tick=tick_no
                ):
                    if self._windowed:
                        accepts = self._step_spec(active, retired)
                    else:
                        self._step(active, retired)
        with telemetry.span("serving/publish") as publish_span:
            worked = self._publish(
                tick_span, step_span, active, admitted, retired, accepts
            )
        # The host's part of this round: its three spans less the waits
        # for the device under them.
        self._host_seconds += (
            control_span.duration + tick_span.duration
            + publish_span.duration - self._tick_sync_s)
        return worked

    def _publish(self, tick_span, step_span, active, admitted, retired,
                 accepts) -> bool:
        """What a tick leaves behind once `serving/tick` has closed: the
        slow-step tally, the tick histogram, the trace ring's entry, the
        gauges."""
        if step_span is not None:
            self._note_step_seconds(step_span.duration, self._ticks + 1)
        worked = bool(active or admitted or retired)
        streams = len([s for s in self._slots if s is not None]) \
            + len(self._suspended)
        self._peak_streams = max(self._peak_streams, streams)
        if worked:
            self._ticks += 1
            self._tick_seconds.observe(tick_span.duration)
            self._ticks_total.inc()
            entry = {
                "tick": self._ticks,
                "admitted": admitted,
                "retired": [(rid, reason) for rid, reason in retired],
                "active": len([s for s in self._slots if s is not None]),
                "queued": self.queue.depth,
            }
            if accepts is not None:
                # Tokens emitted per request this tick (1 = the exact
                # step's pace; > 1 = accepted drafts landed).
                entry["accepted"] = accepts
            touched = set(admitted)
            touched.update(rid for rid, _ in retired)
            if touched:
                with self._trace_id_lock:
                    trace_map = {
                        rid: self._trace_ids[rid]
                        for rid in touched if rid in self._trace_ids
                    }
                    for rid, _ in retired:
                        self._trace_ids.pop(rid, None)
                if trace_map:
                    # Cross-task join: request.id -> the router's
                    # X-Request-Id, for every request admitted or
                    # retired this tick.
                    entry["trace"] = trace_map
            self.trace.append(entry)
        gauges = self._gauges
        gauges["active_slots"].set(
            len([s for s in self._slots if s is not None]))
        gauges["free_slots"].set(len(self._free))
        gauges["queue_depth"].set(self.queue.depth)
        gauges["block_pool_used_blocks"].set(self._blocks.used_blocks)
        gauges["block_pool_free_blocks"].set(self._blocks.free_blocks)
        gauges["prefix_cache_entries"].set(self._prefix.entries)
        gauges["prefix_cache_blocks"].set(self._prefix.cached_blocks)
        gauges["prefix_cache_hit_rate"].set(self._prefix.hit_rate)
        if self._host_store is not None:
            gauges["host_blocks_used"].set(self._host_store.used_blocks)
            gauges["host_blocks_free"].set(self._host_store.free_blocks)
            counts: Dict[str, int] = {}
            for entry in self._suspended:
                tier = entry.request.tier
                counts[tier] = counts.get(tier, 0) + 1
            for tier in self.tier_caps:
                counts.setdefault(tier, 0)
            counts.setdefault(DEFAULT_TIER, 0)
            for tier, count in counts.items():
                gauge = self._suspended_gauges.get(tier)
                if gauge is None:
                    gauge = self._suspended_gauges[tier] = \
                        self._registry.gauge(
                            "serving/suspended_streams", tier=tier)
                gauge.set(count)
        return worked

    def _retire_deadlines(self, now: float, retired: List) -> None:
        for slot in range(self.max_slots):
            state = self._slots[slot]
            if state is not None and state.request.expired(now):
                self._retire(slot, FINISH_DEADLINE, retired)
        for entry in [e for e in self._suspended
                      if e.request.expired(now)]:
            self._finish_suspended(entry, FINISH_DEADLINE, retired)

    def _finish_unadmitted(self, response: Response, reason: str) -> None:
        """A request that dies without ever occupying a slot."""
        self._tier_dec(response.request)
        response._finish(reason)
        self._record_request(response.request, reason)
        self._registry.counter(
            "serving/requests_completed_total", reason=reason
        ).inc()

    def _tier_dec(self, request: Request) -> None:
        tier = getattr(request, "tier", DEFAULT_TIER)
        with self._tier_lock:
            count = self._tier_inflight.get(tier, 0)
            if count > 0:
                self._tier_inflight[tier] = count - 1

    def _finish_suspended(self, entry: _Suspended, reason: str,
                          retired: List) -> None:
        """A stream that dies while parked on the host tier: drop its
        payload (freeing host capacity) and finish the response — it
        holds no slot and no device blocks."""
        self._suspended.remove(entry)
        if entry.request.id in self._host_store:
            self._host_store.pop(entry.request.id)
        self._tier_dec(entry.request)
        entry.state.response._finish(reason)
        self._record_request(entry.request, reason, entry.state)
        retired.append((entry.request.id, reason))
        self._registry.counter(
            "serving/requests_completed_total", reason=reason
        ).inc()
        self._registry.histogram("serving/request_seconds").observe(
            time.monotonic() - entry.request.submitted_at
        )

    def _admit_queued(self, now: float, admitted: List[int],
                      retired: List) -> None:
        while self._free:
            if self._held is not None:
                item, self._held = self._held, None
            else:
                item = self.queue.pop()
            if item is None:
                break
            request, response = item
            if request.expired(now):
                # Died in the queue: never occupies a slot.
                self._finish_unadmitted(response, FINISH_DEADLINE)
                continue
            ok = self._admit(request, response, now, admitted)
            # Pool exhausted: with a host tier, park lower-SLO-tier
            # active streams (swap their blocks out) until this
            # request fits or no eligible victim remains.
            while not ok and self._suspend_victim_below(request, retired):
                ok = self._admit(request, response, now, admitted)
            if not ok:
                # Hold the request (FIFO head) until retirements
                # free blocks — admission order is preserved,
                # decode of in-flight requests continues.
                self._held = (request, response)
                break

    def _queued(self, programs: int, prefill_tokens: int = 0) -> None:
        """The scheduler's thread has just dispatched `programs` to the
        device (a blocking prefill of `prefill_tokens`, its pack, a state
        write, a block extract or inject): they run ahead of the next
        step launched, whose read will wait for them (`_Flight.ahead`)."""
        self._ahead[0] += programs
        self._ahead[1] += prefill_tokens

    def _take_ahead(self) -> Tuple[int, int]:
        """What was dispatched since the launch before, for the step being
        launched: it runs before this step, whose read waits for it too."""
        ahead, self._ahead = tuple(self._ahead), [0, 0]
        return ahead

    def _record_admission(self, slot: int, state: _Slot, now: float,
                          admitted: List[int], began: float,
                          prefilled: int = 0, hit_tokens: int = 0) -> None:
        """Every admission path ends here (blocking prefill, prefix hit,
        chunked). `began` is the span clock when the request took its
        slot, before any blocking prefill: the zero-length
        `serving/admission` record stands at that instant, and the
        request's wait in the queue ends there."""
        request = state.request
        self._registry.histogram("serving/queue_wait_seconds").observe(
            now - request.submitted_at
        )
        if self._used_before[slot]:
            self._registry.counter("serving/slot_reuse_total").inc()
        self._used_before[slot] = True
        self._rngs[slot] = _prng_key(request.params.seed)
        admitted.append(request.id)
        self._registry.counter("serving/requests_admitted_total").inc()
        state.prefilled = prefilled
        state.hit_tokens = hit_tokens
        state.queue_wait_s = began - request.submitted_clock
        state.admitted_clock = spans.now()
        state.prefill_s = state.admitted_clock - began
        self._prefilled_tokens += prefilled
        self._tracer.record(
            "serving/admission", began, 0.0,
            request_id=request.public_id, slot=slot,
            prompt_tokens=len(request.prompt), prefilled=prefilled,
            hit_tokens=hit_tokens, replay=state.replay,
            queue_wait_ms=state.queue_wait_s * 1e3,
            prefill_ms=state.prefill_s * 1e3,
        )

    def _admit(self, request: Request, response: Response,
               now: float, admitted: List[int]) -> bool:
        """Reserve blocks (sharing a cached prefix when one matches),
        prefill-or-replay, and install the block table. Returns False —
        without consuming a slot — when the pool cannot cover the
        request yet."""
        prompt = request.prompt
        n_total = self._blocks_needed(request)
        # The step consuming the LAST prompt token samples the first
        # generated token, so at most len(prompt) - 1 tokens may come
        # from the prefix cache.
        if self._state_leaves:
            # A hit would hand over keys and values and no state: the
            # prefix cache stands aside (neither hits nor registers).
            hit_tokens, hit_ids = 0, []
        else:
            hit_tokens, hit_ids = self._prefix.lookup(
                prompt, len(prompt) - 1)
        if hit_ids:
            # Protect the matched blocks before any eviction can run.
            self._blocks.retain(hit_ids)
        need = n_total - len(hit_ids)
        if need > self._blocks.free_blocks:
            self._prefix.evict_for(need)
        owned = self._blocks.allocate(need)
        if owned is None:
            if hit_ids:
                self._blocks.release(hit_ids)
            return False
        blocks = hit_ids + owned
        slot = self._free.popleft()
        began = spans.now()
        prefilled = 0
        if hit_tokens:
            prefill_len = hit_tokens
            self._registry.counter("serving/prefix_cache_hits_total").inc()
        elif self._chunked:
            # Chunked prefill: blocks are reserved exactly as above, but
            # nothing prefills at admission — the whole prompt queues as
            # pending replay and the windowed tick appends K/V rows to
            # this slot's blocks chunk by chunk, registering each
            # completed whole block with the prefix cache as it fills.
            prefill_len = 0
        else:
            # The program that runs (its bucket) and the rows of it that
            # are the prompt's: all of them under the floor rule, all of
            # the prompt but its last token under the ceiling rule.
            bucket, kept = self.engine.slot_prefill_len(
                len(prompt), self._ceiling_prefill)
            prefill_len = prefilled = kept
            formed, visible = self._prefill_key_pairs(bucket, kept) \
                if bucket else (0, 0)
            with telemetry.span(
                "serving/prefill", request=request.id,
                request_id=request.public_id, prefill=kept,
                bucket=bucket, kept=kept, prefill_keys_formed=formed,
                prefill_keys_visible=visible,
            ):
                row_cache = None
                if bucket > 0:
                    # Rows past `kept` hold the pad: no kept row sees them
                    # under the causal mask, their blocks' ids aim at the
                    # trash block, what of them lands in the last owned
                    # block lies past the slot's length, where the slot
                    # writes as it grows, and a ring is written from the
                    # rows that end at `kept` (the prefill is told it).
                    tokens = np.zeros((1, bucket), np.int32)
                    tokens[0, :kept] = prompt[:kept]
                    row_cache, _logits = self.engine.prefill(
                        self.params, tokens, kept)
                    n_owned = -(-kept // self._block_size)
                    ids = np.full((-(-bucket // self._block_size),),
                                  TRASH_BLOCK, np.int32)
                    ids[:n_owned] = blocks[:n_owned]
                    self._pool = self.engine.pack_prefill(
                        self._pool, ids, row_cache, bucket,
                        self._block_size,
                    )
                    self._queued(2, bucket)
                    self._prefill_keys_formed += formed
                    self._prefill_keys_visible += visible
                    if self._ceiling_prefill and kept == len(prompt) - 1:
                        self._prefills_ceiling += 1
                        self._prefill_pad_tokens += bucket - kept
                    else:
                        self._prefills_floor += 1
                    if not self._state_leaves:
                        # Offer the full-block prefix for sharing; the
                        # partial tail block stays private (the replay
                        # writes it).
                        self._prefix.register(prompt, kept, blocks)
                if self._state_leaves:
                    # The prefill's final state, or zeros where nothing
                    # was prefilled, before the first replayed token: a
                    # reused slot never runs on its predecessor's state.
                    self._state = self.engine.write_slot_state(
                        self._state, slot, row_cache
                    )
                    self._queued(1)
                    self._state_resets += 1
                    if self._prefix_capacity:
                        self._prefix_skipped_stateful += 1
        self._tables[slot, :] = 0
        self._tables[slot, :len(blocks)] = blocks
        self._lengths[slot] = prefill_len
        state = _Slot(
            request, response, list(prompt[prefill_len:]), blocks=blocks
        )
        # Whole blocks already covered (prefix hit or blocking prefill's
        # registration above): the chunked incremental registration
        # starts past them.
        state.registered_blocks = prefill_len // self._block_size
        self._slots[slot] = state
        self._record_admission(slot, state, now, admitted, began,
                               prefilled=prefilled, hit_tokens=hit_tokens)
        return True

    # -- host-tier swap: suspend / resume ------------------------------------

    def _suspend_victim_below(self, request: Request, retired: List) -> bool:
        """Park one active stream of a tier STRICTLY below `request`'s
        to free its slot and blocks — lowest tier first, youngest
        within a tier (the least sunk prefill work). Returns False when
        no host tier is configured, no lower-tier stream is active, or
        the host store cannot hold any candidate's valid blocks. A
        suspension saves a stream as the host knows it (its last token,
        its count, its rng row), so a step in flight is read first: what
        it retires may itself make the room."""
        if self._host_store is None:
            return False
        rank = request.tier_rank
        candidates = [
            slot for slot in range(self.max_slots)
            if self._slots[slot] is not None
            and self._slots[slot].request.tier_rank < rank
        ]
        if candidates and self._flight is not None:
            # True without a victim: the caller tries its admission again,
            # and the next call finds nothing in flight.
            self._settle("suspend", retired)
            return True
        candidates.sort(key=lambda slot: (
            self._slots[slot].request.tier_rank,
            -self._slots[slot].request.submitted_at,
        ))
        bs = self._block_size
        for slot in candidates:
            n_valid = -(-int(self._lengths[slot]) // bs)
            if self._host_store.can_hold(n_valid):
                self._suspend_slot(slot)
                return True
        return False

    def _suspend_slot(self, slot: int) -> None:
        """Swap one active slot out to the host tier: bulk-gather its
        valid blocks (`extract_blocks` + one `device_get`), release ALL
        its block references — private blocks return to the free list,
        prefix-shared blocks survive on the cache's own reference and
        re-attach on resume through the normal lookup — and free the
        slot. The rng row is saved verbatim: bit-identity of the
        resumed stream depends on it."""
        state = self._slots[slot]
        length = int(self._lengths[slot])
        n_valid = -(-length // self._block_size)
        started = time.monotonic()
        payload = None
        if n_valid:
            ids = np.full((self._blocks_per_slot,), TRASH_BLOCK, np.int32)
            ids[:n_valid] = state.blocks[:n_valid]
            payload = _to_host(self.engine.extract_blocks(
                self.params, self._pool, ids, self._block_size
            ))
            self._queued(1)
        self._host_store.put(state.request.id, n_valid, payload)
        self._blocks.release(state.blocks)
        state.blocks = None
        self._slots[slot] = None
        self._free.append(slot)
        self._tables[slot, :] = 0
        self._lengths[slot] = 0
        self._suspended.append(_Suspended(
            state, self._rngs[slot].copy(), length, n_valid, started
        ))
        self._suspends += 1
        self._swap_out_blocks += n_valid
        tier = state.request.tier
        self._registry.counter("serving/suspends_total", tier=tier).inc()
        if n_valid:
            self._registry.counter("serving/swap_out_blocks_total").inc(
                n_valid
            )
            self._registry.histogram("serving/swap_seconds").observe(
                time.monotonic() - started
            )

    def _pending_rank(self) -> Optional[int]:
        """Highest tier rank waiting to be admitted (held or queued),
        or None — the bar a resume must meet so parked streams never
        jump a higher-tier admission (which would only re-suspend them:
        swap thrash)."""
        ranks = []
        if self._held is not None:
            ranks.append(self._held[0].tier_rank)
        queued = self.queue.peek_rank()
        if queued is not None:
            ranks.append(queued)
        return max(ranks) if ranks else None

    def _resume_suspended(self, now: float, admitted: List[int]) -> None:
        """Bring parked streams back while free slots and blocks allow:
        highest tier first, FIFO within a tier (the first suspended is
        the first back)."""
        while self._free and self._suspended:
            best = None
            for entry in self._suspended:
                if best is None or \
                        entry.request.tier_rank > best.request.tier_rank:
                    best = entry
            barrier = self._pending_rank()
            if barrier is not None and best.request.tier_rank < barrier:
                return
            if not self._try_resume(best, now, admitted):
                return

    def _try_resume(self, entry: _Suspended, now: float,
                    admitted: List[int]) -> bool:
        """Re-reserve the stream's full block budget, scatter its swap
        payload back (`inject_blocks`), and reinstall the slot exactly
        as suspended — saved length, saved rng row, pending replay
        untouched. Shared prefix blocks re-attach through the normal
        lookup, CAPPED at the saved length: a longer cached prefix
        would park shared blocks at positions this slot will write,
        violating the no-copy-on-write sharing invariant. Returns False
        (stream stays parked) when the pool cannot cover it yet."""
        request = entry.request
        state = entry.state
        prompt = request.prompt
        n_total = self._blocks_needed(request)
        _hit_tokens, hit_ids = self._prefix.lookup(
            prompt, min(len(prompt) - 1, entry.length)
        )
        if hit_ids:
            self._blocks.retain(hit_ids)
        need = n_total - len(hit_ids)
        if need > self._blocks.free_blocks:
            # A parked stream retries every tick. Unlike admission,
            # evict ONLY when eviction can actually cover the deficit:
            # dropping entries whose blocks are slot-held frees nothing
            # and would strip the shared prefix this very resume (or a
            # later admission) could ride.
            deficit_coverable = need <= (
                self._blocks.free_blocks + self._prefix.evictable_blocks()
            )
            if not deficit_coverable:
                if hit_ids:
                    self._blocks.release(hit_ids)
                return False
            self._prefix.evict_for(need)
        owned = self._blocks.allocate(need)
        if owned is None:
            if hit_ids:
                self._blocks.release(hit_ids)
            return False
        blocks = hit_ids + owned
        slot = self._free.popleft()
        started = time.monotonic()
        n_valid, payload = self._host_store.pop(request.id)
        k_hit = len(hit_ids)
        inject_n = max(0, n_valid - k_hit)
        if inject_n:
            # Rows [k_hit, n_valid) land in their new physical blocks;
            # prefix-hit rows (already resident, shared) and the pad
            # tail aim at the trash block.
            ids = np.full((self._blocks_per_slot,), TRASH_BLOCK, np.int32)
            for j in range(k_hit, n_valid):
                ids[j] = blocks[j]
            self._pool = self.engine.inject_blocks(
                self.params, self._pool, ids, payload, self._block_size
            )
            self._queued(1)
        self._suspended.remove(entry)
        self._tables[slot, :] = 0
        self._tables[slot, :len(blocks)] = blocks
        self._lengths[slot] = entry.length
        self._rngs[slot] = entry.rng
        state.refeed = True
        state.blocks = blocks
        self._slots[slot] = state
        if self._used_before[slot]:
            self._registry.counter("serving/slot_reuse_total").inc()
        self._used_before[slot] = True
        admitted.append(request.id)
        self._resumes += 1
        self._swap_in_blocks += inject_n
        tier = request.tier
        self._registry.counter("serving/resumes_total", tier=tier).inc()
        if inject_n:
            self._registry.counter("serving/swap_in_blocks_total").inc(
                inject_n
            )
            self._registry.histogram("serving/swap_seconds").observe(
                time.monotonic() - started
            )
        return True

    # -- prefix warm start (fleet peer transfer) -----------------------------

    def export_hot_prefixes(self, limit: Optional[int] = None,
                            timeout_s: float = 30.0) -> Dict:
        """Snapshot the hottest prefix-cache entries WITH their KV block
        payloads, for priming a freshly (re)admitted peer replica. Wire
        form (JSON-ready once the payload pytree is encoded):
        ``{schema_version, block_size, n_blocks, entries: [{key(hex),
        blocks: [index into the donor block list]}], payload}`` where
        ``payload`` is the `extract_blocks` pytree with leading dim
        ``n_blocks`` — int8 pools ship their int8 rows as-is, the 4x
        wire saving for free. Blocks shared across entries are shipped
        once (the index list dedupes). Runs ON the scheduler thread via
        the control-op queue; any thread may call it."""
        return self._control_call("export", limit, timeout_s)

    def import_prefixes(self, wire: Dict, timeout_s: float = 30.0) -> Dict:
        """Install a peer's `export_hot_prefixes` snapshot: allocate
        local blocks (evicting LRU prefix entries if needed, never
        touching active slots), `inject_blocks` the payload rows, and
        register each entry under its content key — identical prompts
        hash identically, so later admissions hit through the normal
        lookup. Hot-first clipping when the local pool cannot hold the
        whole snapshot. Returns ``{imported_blocks, registered_entries,
        skipped_entries}``."""
        return self._control_call("import", wire, timeout_s)

    def _control_call(self, kind: str, arg, timeout_s: float):
        if self._state_leaves:
            raise ValueError(
                f"prefix {kind} (/v1/blocks) ships keys and values and not "
                "the state this model holds once a slot "
                f"({', '.join(self._state_leaves)}); it is refused until it "
                "does"
            )
        op = _ControlOp(kind, arg)
        with self._control_lock:
            self._control.append(op)
        self._work.set()
        with self._lifecycle:
            loop_running = self._thread is not None
        if not loop_running:
            # No loop thread (tests driving tick() by hand, or a grid
            # not yet started): the caller is the de-facto scheduler
            # thread — service the queue in place.
            self._run_control_ops()
        if not op.done.wait(timeout_s):
            raise TimeoutError(
                f"scheduler did not service {kind} within {timeout_s}s"
            )
        if op.error is not None:
            raise op.error
        return op.result

    def _run_control_ops(self) -> None:
        while True:
            with self._control_lock:
                if not self._control:
                    return
                op = self._control.popleft()
            try:
                # Block shipping reads and hands out pool blocks between
                # ticks: on a pipeline that is empty.
                self._settle("control_op")
                if op.kind == "export":
                    op.result = self._export_prefixes_now(op.arg)
                elif op.kind == "import":
                    op.result = self._import_prefixes_now(op.arg)
                else:
                    raise ValueError(f"unknown control op {op.kind!r}")
            except BaseException as exc:  # delivered to the caller
                op.error = exc
            op.done.set()

    def _export_prefixes_now(self, limit: Optional[int]) -> Dict:
        import jax

        # Snapshot refs on the control path: `export_entries` is only a
        # VIEW of the cache — between it and the device extract below,
        # an eviction (a hand-driven tick, a reentrant control op, or
        # anything the extract itself triggers) can release an entry's
        # blocks, and a subsequent admission can reallocate and pack
        # OVER them: the export would ship freshly-overwritten rows
        # under the old content key. Drop entries whose blocks already
        # hit refcount 0, then retain every surviving donor id for the
        # duration of the extract so no donor block can return to the
        # free list mid-export.
        entries = [
            (key, ids) for key, ids in self._prefix.export_entries(limit)
            if all(self._blocks.refcount(block) > 0 for block in ids)
        ]
        donor_ids: List[int] = []
        index: Dict[int, int] = {}
        wire_entries: List[Dict] = []
        for key, ids in entries:
            for block in ids:
                if block not in index:
                    index[block] = len(donor_ids)
                    donor_ids.append(block)
            wire_entries.append({
                "key": key.hex(),
                "blocks": [index[block] for block in ids],
            })
        # Extract in groups of the block-table width — the SAME compile
        # key as the suspend path. Each group's payload ships verbatim
        # (padded tail rows included) as a FLAT leaf list: the payload
        # pytree mirrors the pool, so the receiver rebuilds it against
        # its own pool's treedef — no structure goes over the wire, and
        # an int8 pool's rows ship as int8.
        self._blocks.retain(donor_ids)
        width = self._blocks_per_slot
        groups: List[Dict] = []
        try:
            for start in range(0, len(donor_ids), width):
                chunk = donor_ids[start:start + width]
                ids_arr = np.full((width,), TRASH_BLOCK, np.int32)
                ids_arr[:len(chunk)] = chunk
                payload = _to_host(self.engine.extract_blocks(
                    self.params, self._pool, ids_arr, self._block_size
                ))
                self._queued(1)
                leaves, _ = jax.tree_util.tree_flatten(
                    payload, is_leaf=_none_leaf
                )
                groups.append({"n_blocks": len(chunk), "leaves": leaves})
        finally:
            self._blocks.release(donor_ids)
        if donor_ids:
            self._registry.counter(
                "serving/prefix_export_blocks_total").inc(len(donor_ids))
        return {
            "schema_version": 1,
            "block_size": self._block_size,
            "group_width": width,
            "n_blocks": len(donor_ids),
            "entries": wire_entries,
            "groups": groups,
        }

    def _import_prefixes_now(self, wire: Dict) -> Dict:
        import jax

        block_size = int(wire.get("block_size") or 0)
        if block_size != self._block_size:
            raise ValueError(
                f"peer block_size {block_size} != local "
                f"{self._block_size}; refusing to import KV blocks"
            )
        n_blocks = int(wire.get("n_blocks") or 0)
        entries = list(wire.get("entries") or [])
        groups = list(wire.get("groups") or [])
        width = int(wire.get("group_width") or 0)
        empty = {"imported_blocks": 0, "registered_entries": 0,
                 "skipped_entries": len(entries)}
        if not n_blocks or not entries or not groups or width < 1:
            return empty
        # Hot-first clipping: take the longest prefix of (hot-ordered)
        # entries whose distinct blocks the pool can cover with free +
        # cache-evictable capacity. Active slots are never raided.
        coverable = (self._blocks.free_blocks
                     + self._prefix.evictable_blocks())
        needed: Dict[int, None] = {}
        selected: List[Dict] = []
        for entry in entries:
            fresh = [i for i in entry["blocks"] if i not in needed]
            if len(needed) + len(fresh) > coverable:
                break
            for i in fresh:
                needed[i] = None
            selected.append(entry)
        if not selected:
            return empty
        self._prefix.evict_for(len(needed))
        owned = self._blocks.allocate(len(needed))
        if owned is None:
            return empty
        mapping = dict(zip(needed, owned))
        # Payload rows keep their donor group/row coordinates; rows we
        # did not select (clipped) aim at the trash block.
        treedef = jax.tree_util.tree_structure(
            self._pool, is_leaf=_none_leaf
        )
        for g, group in enumerate(groups):
            ids_arr = np.full((width,), TRASH_BLOCK, np.int32)
            wanted = False
            for j in range(int(group["n_blocks"])):
                local = mapping.get(g * width + j)
                if local is not None:
                    ids_arr[j] = local
                    wanted = True
            if not wanted:
                continue
            payload = jax.tree_util.tree_unflatten(
                treedef, group["leaves"]
            )
            self._pool = self.engine.inject_blocks(
                self.params, self._pool, ids_arr, payload,
                self._block_size,
            )
            self._queued(1)
        registered = 0
        # Cold-to-hot so the donor's hottest entries land at the MRU
        # end of the local LRU.
        for entry in reversed(selected):
            if self._prefix.register_imported(
                bytes.fromhex(entry["key"]),
                [mapping[i] for i in entry["blocks"]],
            ):
                registered += 1
        # Cache entries hold their own references now; dropping the
        # allocation reference frees any block no registered entry kept.
        self._blocks.release(owned)
        self._registry.counter(
            "serving/prefix_import_blocks_total").inc(len(owned))
        return {
            "imported_blocks": len(owned),
            "registered_entries": registered,
            "skipped_entries": len(entries) - len(selected),
        }

    def _step(self, active: List[int], retired: List) -> None:
        """The one-token tick, a pipeline one step deep: launch step N+1,
        then read step N. Three spans tile it: building the inputs and the
        engine's call for the NEXT step (the device is still under the
        step before), the tick's one host sync (the host waits for the
        step before, which had a head start), the per-slot bookkeeping of
        what was read (the device is under the step just launched). Each
        carries the number of the model step it launches or reads (`step`),
        so one step's three spans join across the two ticks they lie in.
        Where nothing can be launched the step in flight is settled."""
        with telemetry.span("serving/step_launch") as launch_span:
            before = self._flight
            budget = [s for s in active if self._slots[s].launched
                      < self._slots[s].request.params.max_new_tokens]
            step = None
            if budget:
                self._flight = self._launch(active, budget)
                self._steps = step = self._flight.step
                self._steps_ahead += int(before is not None)
            launch_span.args.update(
                slots=len(budget), step=step,
                ahead=int(bool(budget) and before is not None),
            )
        if budget:
            self._step_parts = (launch_span,) + self._land(before, retired)
        else:
            # Every slot's last token is in flight already.
            self._step_parts = (launch_span,) + self._settle(
                "nothing_to_launch", retired)

    def _launch(self, active: List[int], budget: List[int]) -> _Flight:
        """Dispatch one step over the slots of `budget` and advance, from
        what the host knows, everything the step after it needs: lengths,
        the replay queues, the counts of what the step reads. Nothing here
        waits for the device. A slot of `active` outside `budget` (its
        last token is in flight) rides along as a free slot does. Four
        spans tile `serving/step_launch`: the host arrays
        (`serving/launch_inputs`), the engine's two (`decode_engine/
        step_args`, then `decode_engine/paged_step`: the dispatch, which
        waits its turn where the device's queue is full), and what follows
        the call (`serving/launch_account`)."""
        with telemetry.span("serving/launch_inputs"):
            tokens = np.zeros((self.max_slots,), np.int32)
            forced = np.ones((self.max_slots,), bool)
            mask = np.zeros((self.max_slots,), bool)
            # Its own copies: the host arrays move on below, and a backend
            # that aliases host memory may not have consumed them yet.
            tables, lengths = self._tables.copy(), self._lengths.copy()
            rng_rows = self._rngs.copy()
            if len(budget) < len(active):
                for slot in set(active).difference(budget):
                    tables[slot, :] = 0
                    lengths[slot] = 0
            for slot in budget:
                state = self._slots[slot]
                if state.pending:
                    tokens[slot] = state.pending[0]
                    mask[slot] = len(state.pending) == 1
                else:
                    mask[slot] = True
                    if state.refeed:
                        tokens[slot] = state.last_token
                    else:
                        forced[slot] = False  # fed back on the device
            sampling = dict(block_size=self._block_size,
                            temperature=self.temperature, top_k=self.top_k,
                            top_p=self.top_p)
        if self._counted_step:
            launched = self.engine.paged_state_step(
                self.params, self._pool, self._state, tables, lengths,
                *self._fed, tokens, rng_rows, forced, mask, **sampling)
        else:
            launched = self.engine.paged_step(
                self.params, self._pool, tables, lengths, *self._fed,
                tokens, rng_rows, forced, mask, **sampling)
        with telemetry.span("serving/launch_account"):
            counts = reads = None
            if self._counted_step:
                # `reads`: after the five, where the model counts them.
                self._pool, self._state, emitted, rngs, counts, *reads = \
                    launched
                reads = reads[0] if reads else None
            else:
                self._pool, emitted, rngs = launched
            self._fed = (emitted, rngs)
            # Asked for now, so that the copies to the host follow the
            # program with no word from the host in between: one wait
            # under `serving/step_sync` in place of one a result.
            for result in (emitted, counts, reads):
                if hasattr(result, "copy_to_host_async"):  # a device array
                    result.copy_to_host_async()
            # After the call: the engine then knows which implementation
            # the step was compiled with.
            chunk = getattr(self.engine, "paged_attention_chunk", None)
            self._count_step(budget, chunk and chunk(self._block_size),
                             counted_by_model=reads is not None)
            stepped = []
            prefill_tokens = 0
            for slot in budget:
                state = self._slots[slot]
                # Every stepped slot consumes one token (a replayed prompt
                # token or its fed-back emission) and writes its K/V at the
                # old length.
                self._lengths[slot] += 1
                state.kv_len += 1
                state.refeed = False
                if state.pending:
                    state.pending.popleft()
                    state.prompt_filled += 1
                    prefill_tokens += 1
                sampled = bool(mask[slot])
                state.launched += sampled
                stepped.append((slot, state, sampled))
            return _Flight(emitted, counts, reads, stepped, prefill_tokens,
                           self._steps + 1, self._take_ahead())

    def _land(self, flight: Optional[_Flight], retired: List) -> Tuple:
        """Read a launched step (the one host sync) and hand its tokens
        on: tokens, first-token and gap observations, expert counts and
        cache reads are taken here, one step after the launch that knew
        the rest. -> its (sync, emit) spans, empty where nothing was in
        flight."""
        emitted = counts = reads = paced = None
        with telemetry.span("serving/step_sync") as sync_span:
            if flight is not None:
                # Every slot's token in one transfer; the counts are ready
                # with them: the same program returned them.
                emitted = np.asarray(flight.emitted)
                if flight.counts is not None:
                    counts = np.asarray(flight.counts)
                if flight.reads is not None:
                    reads = np.asarray(flight.reads)
                # Who set this tick's pace, said on the span itself (the
                # one clock reading the account takes of its own).
                paced = "device" if spans.now() - sync_span.start \
                    > SYNC_READY_S else "host"
                sync_span.args.update(
                    step=flight.step, ahead_programs=flight.ahead[0],
                    ahead_prefill_tokens=flight.ahead[1], paced=paced)
                # The device's buffers go here, under this span.
                flight.emitted = flight.counts = flight.reads = None
        with telemetry.span("serving/step_emit") as emit_span:
            was_retired = len(retired)
            decode_tokens = dropped = 0
            if flight is not None:
                self._note_read(flight, sync_span, paced == "device")
                now = time.monotonic()
                gaps = self._token_gaps
                for slot, state, sampled in flight.stepped:
                    if self._slots[slot] is not state:
                        # Retired since the launch (a deadline, or its eos
                        # in the step before): the result is nobody's.
                        dropped += 1
                    elif sampled:
                        decode_tokens += 1
                        self._emit(slot, state, int(emitted[slot]), now,
                                   gaps, retired)
                self._account_tokens(flight.prefill_tokens, decode_tokens)
                if counts is not None and counts.size:
                    self._count_experts(counts)
                if reads is not None:
                    self._count_reads(reads)
            emit_span.args.update(
                tokens=decode_tokens, dropped=dropped,
                retired=len(retired) - was_retired,
                step=flight.step if flight is not None else None,
            )
        return sync_span, emit_span

    def _note_read(self, flight: _Flight, sync_span, device_paced: bool
                   ) -> None:
        """One read's place in the tick's account of itself, from the sync
        span's own start and duration. From the end of one read that
        waited for the device to the end of the next that did is what the
        device ran in between: one model step (`clean_*`: the step's
        device time with no profiler). Where programs were queued ahead of
        the step read, the time since the read before, whoever paced that
        one, is one step and what the queued work added to this tick
        (`ahead_*`: the tick that admits is often the host's, and the one
        after it waits behind the prefill). A read that found its result
        ready says nothing of the device; a settle breaks the chain."""
        seconds = sync_span.duration
        self._steps_read += 1
        self._sync_wait_seconds += seconds
        self._tick_sync_s += seconds
        end = sync_span.start + seconds
        if not device_paced:
            self._steps_host_paced += 1
        elif self._read_end is not None:
            if flight.ahead[0]:
                self._ahead_intervals += 1
                self._ahead_interval_seconds += end - self._read_end
                self._ahead_prefill_tokens += flight.ahead[1]
            elif self._read_waited:
                self._clean_intervals += 1
                self._clean_interval_seconds += end - self._read_end
        self._read_end, self._read_waited = end, device_paced

    def _emit(self, slot: int, state: _Slot, token: int, now: float, gaps,
              retired: List) -> None:
        """One read token to its client, and the slot's retirement where
        the token ends the request."""
        state.last_token = token
        state.emitted += 1
        first = state.response.first_token_at is None
        state.response._push(token)
        if first:
            self._observe_ttft(state)
        elif state.last_emit_at is not None:
            gaps.observe((now - state.last_emit_at) * 1e3)
        state.last_emit_at = now
        eos = state.request.params.eos_token
        if eos is not None and token == eos:
            self._retire(slot, FINISH_EOS, retired)
        elif state.emitted >= state.request.params.max_new_tokens:
            self._retire(slot, FINISH_LENGTH, retired)

    def _settle(self, reason: str, retired: Optional[List] = None) -> Tuple:
        """Empty the pipeline: read and emit the step in flight, counted
        under `reason` (`/stats` `pipeline_settles`); nothing, and no span,
        where none is. Whoever needs a slot as the device left it calls
        this first: a suspension, block shipping, shutdown, a tick with
        nothing to launch. It leaves `_rngs` true for every live slot: the
        rows of the slots that sampled are read back here, off the steady
        tick (a slot that has not sampled yet still holds the row it was
        admitted or resumed with). What it retires outside a tick goes
        into the next tick's trace entry."""
        flight, self._flight = self._flight, None
        if flight is None:
            return ()
        self._settles[reason] = self._settles.get(reason, 0) + 1
        parts = self._land(
            flight, self._carried_retired if retired is None else retired)
        self._read_end = None  # the next step starts on an idle device
        rows = np.asarray(self._fed[1])
        for slot, state, sampled in flight.stepped:
            if sampled and self._slots[slot] is state:
                self._rngs[slot] = rows[slot]
        return parts

    def _count_reads(self, reads: np.ndarray) -> None:
        """One step's cache reads as the model's attention layers counted
        them over the active slots, summed over layers, under its contract's
        names (`reads`: rows live and rows read of each leaf, keys
        selected). `kv_read_token_steps` takes the rows read of all leaves
        over the number of attention layers: what a layer read of a slot's
        sequence, to set beside `kv_token_steps`, what was live of it."""
        contract = self.engine.contract
        names = contract.reads
        tally = self._cache_reads
        for name, value in zip(names, reads):
            tally[name] = tally.get(name, 0) + int(value)
        self._kv_read_token_steps += sum(
            int(value) for name, value in zip(names, reads)
            if name.endswith("_read")
        ) // contract.n_attention_layers

    def _count_experts(self, counts: np.ndarray) -> None:
        """One step's `counts`, a row an expert layer, its columns named by
        `moe.split_counts` under the model's contract (`moe.ExpertRow`). A
        layer-step is one expert layer in one step. `/stats` shows the
        tally as `moe_*`, `moe_tokens_by_expert` one number a held expert;
        `moe_experts_streamed` counts the held experts whose matrices a
        layer-step read: the loop's trips, or all of them where one product
        runs over every held expert."""
        from tf_yarn_tpu.models.moe import split_counts

        tally = self._moe
        assignments, load, zero, streamed = split_counts(
            counts, self.engine.contract.experts, self.max_slots)
        tally["experts_streamed"] += int(streamed.sum()) \
            if streamed is not None else load.size
        if zero is not None:
            tally["assignments_zero"] = tally.get("assignments_zero", 0) \
                + int(zero.sum())
        tally["assignments"] += int(assignments.sum())
        tally["assignments_here"] += int(load.sum())
        tally["layer_steps"] += int(load.shape[0])
        tally["experts_touched"] += int((load > 0).sum())
        tally["load_max_sum"] += int(load.max(axis=1).sum())
        tally["load_mean_sum"] += float(load.mean(axis=1).sum())
        # The tokens that reached each held expert, over layers and steps.
        tally["tokens_by_expert"] = tally.get("tokens_by_expert", 0) \
            + load.sum(axis=0)

    def _observe_ttft(self, state) -> None:
        # The unlabeled histogram is the back-compat aggregate; the
        # tier-labeled one feeds per-tier SLO objectives (e.g.
        # interactive_ttft_p95_s) without touching existing keys.
        ttft = state.response.ttft_s
        self._registry.histogram("serving/ttft_seconds").observe(ttft)
        self._registry.histogram(
            "serving/ttft_seconds", tier=state.request.tier
        ).observe(ttft)
        # The same wait on the span clock, in its three parts: a request
        # still decoding when someone reads the spans has no
        # `serving/request` record yet, and its first token is long past.
        clock = spans.now()
        state.replay_s = clock - state.admitted_clock
        self._tracer.record(
            "serving/first_token", clock, 0.0,
            request_id=state.request.public_id,
            **self._ttft_parts_ms(state),
        )

    @staticmethod
    def _ttft_parts_ms(state: _Slot) -> Dict:
        """queue wait + blocking prefill + replay = time to first token
        (ms, span clock); the last two None before a first token."""
        parts = {"queue_wait_ms": state.queue_wait_s * 1e3,
                 "prefill_ms": state.prefill_s * 1e3,
                 "replay_ms": None, "ttft_ms": None}
        if state.replay_s is not None:
            parts["replay_ms"] = state.replay_s * 1e3
            parts["ttft_ms"] = (state.queue_wait_s + state.prefill_s
                                + state.replay_s) * 1e3
        return parts

    def _record_request(self, request: Request, reason: str,
                        state: Optional[_Slot] = None,
                        slot: Optional[int] = None) -> None:
        """The one `serving/request` record of a request the scheduler
        accepted, at its end (every finish reason, admitted or not):
        submit -> finish on the span clock, under the caller's id."""
        clock = spans.now()
        life = clock - request.submitted_clock
        if state is None:  # died in the queue: all of its life was wait
            parts = {"queue_wait_ms": life * 1e3, "prefill_ms": 0.0,
                     "replay_ms": None, "ttft_ms": None, "prefilled": 0,
                     "hit_tokens": 0, "replayed": 0, "emitted": 0}
        else:
            parts = dict(
                self._ttft_parts_ms(state), prefilled=state.prefilled,
                hit_tokens=state.hit_tokens,
                replayed=state.replay - len(state.pending),
                emitted=state.emitted,
            )
        self._tracer.record(
            "serving/request", request.submitted_clock, life,
            request_id=request.public_id, finish=reason,
            prompt_tokens=len(request.prompt), slot=slot, **parts,
        )

    def _count_step(self, active: List[int], chunk=None,
                    counted_by_model: bool = False) -> None:
        """Around a model step: what it reads. `kv_token_steps` over
        `slot_steps` is the mean live KV length a slot-step attends
        over; `kv_read_token_steps` over `kv_token_steps` is how much of
        what the step's attention read was live: each slot reads what it
        holds and this step's own row, rounded up to `chunk` tokens
        (`DecodeEngine.paged_attention_chunk`: the kernel's loop trip;
        None = the gathered view's whole `max_seq_len`, as the
        speculative window and the plain one-token read take it). A model
        that counts its own reads (`_count_reads`) says what was read."""
        chunk = chunk or self._max_seq_len
        self._slot_steps += len(active)
        for slot in active:
            kv_len = self._slots[slot].kv_len
            self._kv_token_steps += kv_len
            if not counted_by_model:
                self._kv_read_token_steps += -(-(kv_len + 1) // chunk) * chunk

    def _note_step_seconds(self, seconds: float, tick: int) -> None:
        """Single ticks of many times the usual length decide whole runs
        (PERF.md): count them, and keep the longest with its parts, which
        say whether the host was stuck before the dispatch, the device
        slow under the sync, or the host stuck after it. A tick whose read
        had programs queued ahead of it (an admission's prefill) is long
        by them and is no stall: it is neither judged nor remembered, and
        the `ahead_*` counters hold its time."""
        parts = self._step_parts
        read = parts[1].args if len(parts) > 1 else {}
        if read.get("ahead_programs"):
            return
        history = self._step_seconds
        if len(history) >= SLOW_STEP_MIN_HISTORY and \
                seconds > SLOW_STEP_FACTOR * statistics.median(history):
            self._slow_steps += 1
            self._slow_step_seconds += seconds
            if self._slowest_step is None or \
                    seconds * 1e3 > self._slowest_step["ms"]:
                # (launch, sync, emit); a tick that had nothing to launch
                # and nothing to read has the first alone. `step` and
                # `paced` are of the step this tick read.
                ms = [part.duration * 1e3 for part in parts]
                ms += [0.0] * (3 - len(ms))
                self._slowest_step = {
                    "tick": tick, "ms": seconds * 1e3,
                    "launch_ms": ms[0], "sync_ms": ms[1], "emit_ms": ms[2],
                    "step": read.get("step"), "paced": read.get("paced"),
                }
        history.append(seconds)

    def _step_spec(self, active: List[int], retired: List) -> Dict[int, int]:
        """The windowed tick: ONE compiled program advances every slot a
        VARIABLE number of tokens — decode slots 1 up to spec_k + 1
        (drafts from the host-side drafter over the slot's own token
        history), PREFILLING slots up to the full window of teacher-
        forced prompt replay (chunked prefill rides here: a chunking
        slot is just a slot whose pending deque still holds its prompt).
        ``prefill_budget_per_tick`` caps the prompt tokens consumed per
        tick: chunking slots past the budget are masked off for the tick
        (they consume nothing, emit nothing, and their cache index/
        length stay put — the window's garbage rows land beyond the
        valid length and are overwritten on resume), with round-robin
        rotation so every chunking slot advances within a bounded number
        of ticks. Decode slots are NEVER paused — that is the no-stall
        contract. Returns {request id: tokens emitted} for the trace
        ring.
        """
        with telemetry.span("serving/step_launch") as launch_span:
            # Serial: launched and read in one tick, so the spans carry the
            # step's number and `paced="serial"` and feed none of the
            # read-to-read counters; what was dispatched since the step
            # before still runs ahead of this one.
            self._steps += 1
            ahead = self._take_ahead()
            launch_span.args.update(step=self._steps)
            self._count_step(active)
            width = self._window_width
            tokens = np.full((self.max_slots, width), -1, np.int32)
            n_known = np.zeros((self.max_slots,), np.int32)
            eos_ids = np.full((self.max_slots,), -1, np.int32)
            mask = np.zeros((self.max_slots,), bool)
            consumed: Dict[int, int] = {}
            proposed: Dict[int, int] = {}
            budget = self.prefill_budget_per_tick
            order = active
            if budget is not None and len(active) > 1:
                # Rotate who claims prefill budget first each tick so a
                # burst of long prompts shares it fairly.
                pivot = self._ticks % len(active)
                order = active[pivot:] + active[:pivot]
            for slot in order:
                state = self._slots[slot]
                need = min(len(state.pending), width)
                if budget is not None and need > 0:
                    if need > budget:
                        # Paused this tick (over budget): stays masked off —
                        # the free-slot convention.
                        consumed[slot] = 0
                        proposed[slot] = 0
                        continue
                    budget -= need
                max_emit = state.request.params.max_new_tokens - state.emitted
                window, known, n_prop = plan_window(
                    state.pending, state.last_token, width, max_emit,
                    state.context, self._drafter, max_drafts=self.spec_k,
                )
                tokens[slot] = window
                n_known[slot] = known
                eos = state.request.params.eos_token
                eos_ids[slot] = -1 if eos is None else eos
                mask[slot] = True
                consumed[slot] = need
                proposed[slot] = n_prop
            self._pool, emitted, counts, rngs = self.engine.paged_spec_step(
                self.params, self._pool, self._tables, self._lengths,
                tokens, n_known, eos_ids, self._rngs, mask,
                block_size=self._block_size,
                temperature=self.temperature, top_k=self.top_k,
                top_p=self.top_p,
                decode_attention=self.decode_attention,
            )
        with telemetry.span("serving/step_sync") as sync_span:
            # The tick's host sync: every slot's window + counts at once.
            emitted = np.asarray(emitted)
            counts = np.asarray(counts)
            self._rngs = np.array(rngs)
            del rngs  # the device buffer goes under this span (see _step)
            sync_span.args.update(
                step=self._steps, ahead_programs=ahead[0],
                ahead_prefill_tokens=ahead[1], paced="serial")
        with telemetry.span("serving/step_emit") as emit_span:
            self._step_parts = (launch_span, sync_span, emit_span)
            was_retired = len(retired)
            now = time.monotonic()
            prefill_tokens = 0
            decode_tokens = 0
            accepts: Dict[int, int] = {}
            for slot in active:
                state = self._slots[slot]
                for _ in range(consumed[slot]):
                    state.pending.popleft()
                state.prompt_filled += consumed[slot]
                prefill_tokens += consumed[slot]
                n = int(counts[slot])
                decode_tokens += n
                state.kv_len += int(n_known[slot]) + n
                # Valid rows this tick: the replayed prefix + the
                # emitted tokens; rejected window rows beyond stay dead.
                self._lengths[slot] += int(n_known[slot]) + n
                if self._chunked and consumed[slot]:
                    self._register_chunk_prefix(state)
                if proposed[slot]:
                    accepted_drafts = min(max(n - 1, 0), proposed[slot])
                    self._spec_proposed += proposed[slot]
                    self._spec_accepted += accepted_drafts
                    self._registry.counter(
                        "serving/spec_proposed_tokens_total"
                    ).inc(proposed[slot])
                    if accepted_drafts:
                        self._registry.counter(
                            "serving/spec_accepted_tokens_total"
                        ).inc(accepted_drafts)
                if n:
                    accepts[state.request.id] = n
                    self._registry.histogram(
                        "serving/accepted_tokens_per_step"
                    ).observe(n)
                for j in range(n):
                    token = int(emitted[slot, j])
                    state.last_token = token
                    state.emitted += 1
                    state.context.append(token)
                    first = state.response.first_token_at is None
                    state.response._push(token)
                    if first:
                        self._observe_ttft(state)
                    elif state.last_emit_at is not None:
                        # Tokens landing in the same tick (accepted drafts)
                        # record a ~0 gap — they really do arrive together.
                        self._registry.histogram(
                            "serving/inter_token_latency_ms"
                        ).observe((now - state.last_emit_at) * 1e3)
                    state.last_emit_at = now
                    eos = state.request.params.eos_token
                    if eos is not None and token == eos:
                        self._retire(slot, FINISH_EOS, retired)
                        break
                    if state.emitted >= state.request.params.max_new_tokens:
                        self._retire(slot, FINISH_LENGTH, retired)
                        break
            if self._spec_proposed:
                self._registry.gauge("serving/spec_accept_rate").set(
                    self._spec_accepted / self._spec_proposed
                )
            self._account_tokens(prefill_tokens, decode_tokens)
            emit_span.args.update(
                tokens=decode_tokens, retired=len(retired) - was_retired,
                step=self._steps,
            )
        return accepts

    def _register_chunk_prefix(self, state: _Slot) -> None:
        """Offer every prompt block a chunk just completed to the prefix
        cache (chunked path). `PrefixCache.register` is idempotent
        per prefix key and takes its OWN reference on newly shared
        blocks, so the slot's one reference (released at retire) is
        never double-counted — a mid-PREFILL eviction releases exactly
        the slot's refs and cached blocks survive for the next hit."""
        whole = state.prompt_filled // self._block_size
        if whole > state.registered_blocks:
            self._prefix.register(
                state.request.prompt, state.prompt_filled, state.blocks
            )
            state.registered_blocks = whole

    def _account_tokens(self, prefill_tokens: int, decode_tokens: int) -> None:
        """Per-tick token throughput split: prompt tokens consumed
        (prefill/replay) vs tokens emitted (decode)."""
        self._prefill_tokens += prefill_tokens
        self._decode_tokens += decode_tokens
        if prefill_tokens:
            self._registry.counter("serving/prefill_tokens_total").inc(
                prefill_tokens
            )
        if decode_tokens:
            self._registry.counter("serving/decode_tokens_total").inc(
                decode_tokens
            )

    def _retire(self, slot: int, reason: str, retired: List) -> None:
        state = self._slots[slot]
        self._slots[slot] = None
        self._free.append(slot)
        # O(blocks) bookkeeping, no device program: shared prefix
        # blocks survive (the prefix cache holds its own reference),
        # exclusively-owned blocks return to the free list. The
        # stale pool content needs no zeroing — gathers mask
        # positions beyond each slot's length, and reallocation
        # overwrites.
        self._blocks.release(state.blocks)
        self._tables[slot, :] = 0
        self._lengths[slot] = 0
        self._tier_dec(state.request)
        self._estimator.record_retire(
            getattr(state.request, "tier", DEFAULT_TIER)
        )
        state.response._finish(reason)
        self._record_request(state.request, reason, state, slot)
        retired.append((state.request.id, reason))
        self._registry.counter(
            "serving/requests_completed_total", reason=reason
        ).inc()
        self._registry.histogram("serving/request_seconds").observe(
            time.monotonic() - state.request.submitted_at
        )

    # -- loop ---------------------------------------------------------------

    def start(self) -> None:
        with self._lifecycle:
            if self._thread is not None:
                raise RuntimeError("scheduler already started")
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="serving-scheduler", daemon=True
            )
            self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                worked = self.tick()
            except Exception:
                # A tick must never kill the serving loop (a malformed
                # request slipping past admission used to): fail the
                # in-flight work visibly and keep serving new requests.
                _logger.exception(
                    "scheduler tick failed; failing in-flight requests"
                )
                self._registry.counter("serving/tick_errors_total").inc()
                self._fail_inflight(FINISH_ERROR)
                continue
            if not worked:
                with telemetry.span("serving/idle_wait") as idle_span:
                    self._work.wait(IDLE_POLL_S)
                self._idle_wait_seconds += idle_span.duration
                self._work.clear()

    def _fail_inflight(self, reason: str) -> None:
        retired: List = []
        try:
            # What the device has finished is the clients' before the
            # reason is. (The read that failed a tick is not read again:
            # `_settle` let go of it first.)
            self._settle(reason, retired)
        except Exception:
            _logger.exception("the step in flight could not be read")
        if self._held is not None:
            _request, response = self._held
            self._held = None
            self._finish_unadmitted(response, reason)
        for _request, response in self.queue.drain():
            self._finish_unadmitted(response, reason)
        for entry in list(self._suspended):
            self._finish_suspended(entry, reason, retired)
        for slot in range(self.max_slots):
            if self._slots[slot] is not None:
                self._retire(slot, reason, retired)

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self) -> None:
        """Mark this grid as draining (preemption notice, planned
        shutdown): surfaced in `stats()` and the frontend's `/healthz`
        so load balancers — the fleet router's registry in particular —
        eject the replica from rotation BEFORE it stops accepting.
        Scheduling itself continues until `close()`."""
        if not self._draining:
            self._draining = True
            _logger.info("scheduler marked draining")

    def close(self) -> None:
        """Stop the loop; fail queued and in-flight requests as
        `shutdown` so no client blocks forever on a dead grid."""
        self._draining = True
        self._stop.set()
        self._work.set()
        # Snapshot-under-lock: concurrent close() calls each either own
        # the loop thread (and join it) or see None — the PR 9 orbax
        # check-then-join shape, fixed at the source this time. The join
        # stays outside the lock so a wedged loop can't deadlock start().
        with self._lifecycle:
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=30.0)
        self._fail_inflight(FINISH_SHUTDOWN)

    # -- introspection -------------------------------------------------------

    def stats(self) -> Dict:
        """Host-side snapshot for /stats and the task's flushed metrics."""
        snap = {
            "max_slots": self.max_slots,
            "active_slots": len([s for s in self._slots if s is not None]),
            "free_slots": len(self._free),
            "queue_depth": self.queue.depth,
            "queue_capacity": self.queue.capacity,
            "ticks": self._ticks,
            "temperature": self.temperature,
            "top_k": self.top_k,
            "top_p": self.top_p,
            # One layout; the key stays for whoever reads it off the wire.
            "kv_layout": "paged",
            "kv_cache_hbm_bytes": self._kv_bytes,
            "kv_cache_hbm_bytes_per_device": self._kv_bytes_per_device,
            "tp_degree": self.tp_degree,
            "draining": self._draining,
            "spec_k": self.spec_k,
            "decode_attention": self.decode_attention,
            "prefill_chunk": self.prefill_chunk,
            "prefill_budget_per_tick": self.prefill_budget_per_tick,
            # prefill_tokens: prompt tokens REPLAYED through the step
            # program (one a tick, or a chunk); prefilled_tokens: prompt
            # tokens through the blocking prefill programs.
            "prefill_tokens": self._prefill_tokens,
            "decode_tokens": self._decode_tokens,
            "prefilled_tokens": self._prefilled_tokens,
            # Blocking prefills that ran the bucket above the prompt and
            # kept the true length, those that ran the bucket below and
            # kept it whole, and the pad rows of the former.
            "prefills_ceiling": self._prefills_ceiling,
            "prefills_floor": self._prefills_floor,
            "prefill_pad_tokens": self._prefill_pad_tokens,
            # Query-key pairs a head the prefills' scores were formed over,
            # and those inside the masks of the prompts' rows.
            "prefill_keys_formed": self._prefill_keys_formed,
            "prefill_keys_visible": self._prefill_keys_visible,
            "kv_token_steps": self._kv_token_steps,
            "kv_read_token_steps": self._kv_read_token_steps,
            "slot_steps": self._slot_steps,
            # One-token steps launched; those launched while the step
            # before was still unread; the pipeline emptied, by reason.
            "steps": self._steps,
            "steps_ahead": self._steps_ahead,
            "pipeline_settles": dict(self._settles),
            # The tick's account of itself (docs/Serving.md "Where a
            # tick's time goes"): reads, and those that found their
            # result ready; the scheduler thread's time in three parts;
            # read end to read end, with nothing and with something
            # queued ahead of the step read.
            "steps_read": self._steps_read,
            "steps_host_paced": self._steps_host_paced,
            "host_seconds": round(self._host_seconds, 6),
            "sync_wait_seconds": round(self._sync_wait_seconds, 6),
            "idle_wait_seconds": round(self._idle_wait_seconds, 6),
            "clean_intervals": self._clean_intervals,
            "clean_interval_seconds": round(
                self._clean_interval_seconds, 6),
            "ahead_intervals": self._ahead_intervals,
            "ahead_interval_seconds": round(
                self._ahead_interval_seconds, 6),
            "ahead_prefill_tokens": self._ahead_prefill_tokens,
            # Ticks over twice the median, of those whose read had
            # nothing queued ahead.
            "slow_steps": self._slow_steps,
            "slow_step_seconds": round(self._slow_step_seconds, 6),
            "slowest_step": self._slowest_step,
            "peak_streams": self._peak_streams,
            "retire_rate_per_s": round(self._estimator.retire_rate(), 4),
        }
        with self._tier_lock:
            tier_inflight = {
                tier: count for tier, count in self._tier_inflight.items()
                if count
            }
        snap["tiers"] = {
            "inflight": tier_inflight,
            "caps": dict(self.tier_caps),
        }
        if self._counted_step:
            snap["state_leaves"] = list(self._state_leaves)
            snap["state_bytes"] = self._state_bytes
            snap["cache_bytes_by_kind"] = dict(self._cache_bytes_by_kind)
            snap["cache_hbm_bytes"] = self._kv_bytes + self._state_bytes
            snap["state_resets"] = self._state_resets
            snap["prefix_skipped_stateful"] = self._prefix_skipped_stateful
        snap.update(
            {name + "_token_steps": value
             for name, value in self._cache_reads.items()})
        if self._moe["layer_steps"]:
            tally = self._moe
            snap.update({
                "moe_" + key: value.tolist() if hasattr(value, "tolist")
                else value for key, value in tally.items()})
            snap["moe_load_max_over_mean"] = round(
                tally["load_max_sum"] / tally["load_mean_sum"], 4
            ) if tally["load_mean_sum"] else None
            snap["moe_experts_touched_per_layer_step"] = round(
                tally["experts_touched"] / tally["layer_steps"], 4)
            snap["moe_experts_streamed_per_layer_step"] = round(
                tally["experts_streamed"] / tally["layer_steps"], 4)
        if self._windowed:
            snap["spec"] = {
                "proposed_tokens": self._spec_proposed,
                "accepted_tokens": self._spec_accepted,
                "accept_rate": round(
                    self._spec_accepted / self._spec_proposed, 4
                ) if self._spec_proposed else None,
            }
        snap["block_size"] = self._block_size
        snap["block_pool"] = {
            "num_blocks": self._blocks.num_blocks,
            "used_blocks": self._blocks.used_blocks,
            "free_blocks": self._blocks.free_blocks,
        }
        snap["prefix_cache"] = {
            "entries": self._prefix.entries,
            "cached_blocks": self._prefix.cached_blocks,
            "hits": self._prefix.hits,
            "misses": self._prefix.misses,
            "hit_rate": round(self._prefix.hit_rate, 4),
        }
        if self._host_store is not None:
            suspended_by_tier: Dict[str, int] = {}
            for entry in self._suspended:
                tier = entry.request.tier
                suspended_by_tier[tier] = \
                    suspended_by_tier.get(tier, 0) + 1
            snap["host_block_store"] = {
                "capacity_blocks": self._host_store.capacity_blocks,
                "used_blocks": self._host_store.used_blocks,
                "free_blocks": self._host_store.free_blocks,
                "entries": self._host_store.entries,
            }
            snap["suspended_streams"] = suspended_by_tier
            snap["swap"] = {
                "suspends": self._suspends,
                "resumes": self._resumes,
                "swap_out_blocks": self._swap_out_blocks,
                "swap_in_blocks": self._swap_in_blocks,
            }
        engine_stats = getattr(self.engine, "stats", None)
        if isinstance(engine_stats, dict):
            snap["decode_engine"] = dict(engine_stats)
        return snap


def _cache_nbytes(tree) -> int:
    """Resident bytes of a cache pytree; tolerates fake engines' plain
    numpy (or scalar-free) stand-ins."""
    try:
        from tf_yarn_tpu.models.decode_engine import cache_nbytes

        return cache_nbytes(tree)
    except Exception:
        return 0


def _cache_nbytes_per_device(tree) -> int:
    """Per-device resident bytes (sharded leaves count one shard); same
    fake-engine tolerance as `_cache_nbytes`."""
    try:
        from tf_yarn_tpu.models.decode_engine import tree_nbytes_per_device

        return tree_nbytes_per_device(tree)
    except Exception:
        return 0


def _prng_key(seed: int) -> np.ndarray:
    """generate_legacy's `jax.random.PRNGKey(seed)` as host uint32[2] for
    the rng grid row, made here: on the device it was read back behind the
    prefill just dispatched, 85-97 ms an admission on a v5e, and would
    empty the pipeline. The two words are threefry's seeding: the seed as
    a 64-bit integer where JAX holds one, its lower 32 bits alone where
    it does not (tests/test_serving.py holds them equal)."""
    import jax

    impl = jax.config.jax_default_prng_impl
    if impl != "threefry2x32":
        raise ValueError(
            f"the serving grid holds threefry2x32 rng rows (uint32[2]); "
            f"jax_default_prng_impl is {impl!r}"
        )
    seed = int(seed) + int(
        getattr(jax.config, "jax_random_seed_offset", 0) or 0)
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)


def _to_host(tree):
    """One bulk device->host transfer of a swap payload. `device_get`
    passes plain numpy through untouched, so fake engines' host pools
    ride the same path."""
    import jax

    return jax.device_get(tree)


def _none_leaf(x) -> bool:
    """is_leaf predicate keeping None leaves (a pool's index leaves) in
    flattened swap payloads, mirroring the engine's own tree_maps."""
    return x is None
