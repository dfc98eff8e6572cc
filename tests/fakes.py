"""Deterministic fake engines for the scheduler's tests: no device.

The :class:`SlotScheduler` is a pure host-side state machine whose only
device contract is the engine's paged methods, so its unit tests drive
it with these. One arithmetic everywhere: the pool is a ``(num_blocks,
block_size)`` int64 token store gathered through the block table exactly
like the real program, a slot's "cache" is the sum of the tokens it
consumed, and a sampled position emits ``sum % 97`` — so the tests can
precompute every stream, and a table / length / registration bug changes
an emission and fails them. Every call is logged for ordering assertions.
Last, one drive that the serving tests of every model share.
"""

import time

import numpy as np

from tf_yarn_tpu.serving import SlotScheduler


class FakePagedEngine:
    """The exact step, admission and the swap programs."""

    def __init__(self, buckets=(4, 8), max_seq_len=32, ceiling=False):
        self.prompt_buckets = tuple(sorted(buckets))
        self.max_seq_len = max_seq_len
        self.ceiling = ceiling
        self.calls = []

    def ceiling_prefill(self, params):
        return self.ceiling

    def slot_prefill_len(self, prompt_len, ceiling=False):
        kept = prompt_len - 1
        if ceiling:
            above = [b for b in self.prompt_buckets
                     if kept <= b <= self.max_seq_len]
            if above and kept > 0:
                return above[0], kept
        best = 0
        for bucket in self.prompt_buckets:
            if bucket <= kept:
                best = bucket
        return best, best

    def make_paged_pool(self, params, num_blocks, block_size):
        self.calls.append(("make_pool", num_blocks, block_size))
        return np.zeros((num_blocks, block_size), np.int64)

    def prefill(self, params, prompt, length=None):
        self.calls.append(("prefill", prompt.shape))
        return np.asarray(prompt[0], np.int64), None

    def pack_prefill(self, pool, block_ids, row_cache, prefill_len,
                     block_size):
        self.calls.append(("pack", tuple(int(b) for b in block_ids)))
        pool = pool.copy()
        for pos in range(prefill_len):
            block = block_ids[pos // block_size]
            pool[block, pos % block_size] = row_cache[pos]
        return pool

    def paged_step(self, params, pool, tables, lengths, emitted, rngs,
                   tokens, rng_rows, forced, sample_mask, block_size,
                   temperature=0.0, top_k=None, top_p=None):
        # The real program's feed: the step before's outputs unless the
        # host forces its own.
        forced = np.asarray(forced)
        tokens = np.where(forced, tokens, emitted)
        rngs = np.where(forced[:, None], rng_rows, rngs)
        self.calls.append(
            ("paged_step", tuple(int(t) for t in np.asarray(tokens)),
             tuple(bool(m) for m in np.asarray(sample_mask)))
        )
        pool = np.array(pool)
        tables = np.asarray(tables)
        lengths = np.asarray(lengths)
        emitted = np.array(tokens, np.int32)
        for s in range(len(tokens)):
            length = int(lengths[s])
            # Every slot writes its token at its length — inactive rows
            # (all-zero table) land in the trash block, like the real
            # program.
            pool[tables[s, length // block_size],
                 length % block_size] = tokens[s]
            if sample_mask[s]:
                total = 0
                for pos in range(length + 1):
                    total += pool[tables[s, pos // block_size],
                                  pos % block_size]
                emitted[s] = total % 97
        return pool, emitted, rngs

    def extract_blocks(self, params, pool, block_ids, block_size):
        self.calls.append(
            ("extract", tuple(int(b) for b in np.asarray(block_ids)))
        )
        return np.asarray(pool)[np.asarray(block_ids)].copy()

    def inject_blocks(self, params, pool, block_ids, payload, block_size):
        self.calls.append(
            ("inject", tuple(int(b) for b in np.asarray(block_ids)))
        )
        pool = np.array(pool)
        payload = np.asarray(payload)
        for j, block in enumerate(np.asarray(block_ids)):
            pool[block] = payload[j]
        return pool


class FakePagedWindowedEngine(FakePagedEngine):
    """BOTH the exact and the windowed step, so one class drives the
    blocking reference and the chunked / speculative run: a draft is
    accepted iff it equals the emission of the position before it."""

    def paged_spec_step(self, params, pool, tables, lengths, tokens,
                        n_known, eos_ids, rngs, active, block_size,
                        temperature=0.0, top_k=None, top_p=None,
                        decode_attention="gather"):
        tokens = np.asarray(tokens)
        slots, width = tokens.shape
        self.calls.append(("paged_spec_step", tokens.copy(),
                           np.asarray(n_known).copy(),
                           np.asarray(active).copy()))
        pool = np.array(pool)
        tables = np.asarray(tables)
        lengths = np.asarray(lengths)
        emitted = np.zeros((slots, width), np.int32)
        counts = np.zeros((slots,), np.int32)
        for s in range(slots):
            if not active[s]:
                continue
            length = int(lengths[s])
            total = 0
            for pos in range(length):
                total += pool[tables[s, pos // block_size],
                              pos % block_size]
            out_prev, alive = None, True
            n = 0
            for i in range(width):
                if i > int(n_known[s]):
                    alive = alive and tokens[s, i] == out_prev \
                        and out_prev != eos_ids[s]
                if i >= int(n_known[s]) and not alive:
                    break
                pos = length + i
                pool[tables[s, pos // block_size],
                     pos % block_size] = tokens[s, i]
                total += int(tokens[s, i])
                if i >= int(n_known[s]):
                    out_prev = int(total % 97)
                    emitted[s, n] = out_prev
                    n += 1
                    if out_prev == eos_ids[s]:
                        break
            counts[s] = n
        return pool, emitted, counts, rngs


class FakePagedSpecEngine(FakePagedWindowedEngine):
    """The windowed step only: a speculative grid that ran the exact step
    would fail here. Emissions are always < 97, so token 98 is a
    guaranteed-reject draft and the accept-rate-0 worst case is
    constructible exactly."""

    def paged_step(self, *args, **kwargs):
        raise AssertionError("a windowed grid must not run the exact step")


class _Later:
    """A device result that is ready at `ready_at` (`time.perf_counter`):
    reading it on the host (`np.asarray`) waits until then, as a read of a
    device array does."""

    def __init__(self, value, ready_at):
        self.value = value
        self.ready_at = ready_at

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        return np.asarray(self.value, dtype)


class FakeAsyncEngine(FakePagedEngine):
    """`FakePagedEngine` with a device that takes its time: every call
    returns at once, and the device works through what it was handed in
    order, `step_s` a step and `prefill_s` a prefill. A step's tokens
    come back as a `_Later`, so the read of a step waits for that step
    and for whatever was queued before it; the next step takes them as
    they are, on the device."""

    def __init__(self, step_s=0.0, prefill_s=0.0, **kwargs):
        super().__init__(**kwargs)
        self.step_s = step_s
        self.prefill_s = prefill_s
        self.free_at = 0.0  # when the device has done all it was handed

    def _enqueue(self, seconds):
        self.free_at = max(self.free_at, time.perf_counter()) + seconds
        return self.free_at

    def prefill(self, params, prompt, length=None):
        self._enqueue(self.prefill_s)
        return super().prefill(params, prompt, length)

    def paged_step(self, params, pool, tables, lengths, emitted, rngs,
                   *args, **kwargs):
        if isinstance(emitted, _Later):
            emitted = emitted.value  # fed back on the device: no wait
        pool, emitted, rngs = super().paged_step(
            params, pool, tables, lengths, emitted, rngs, *args, **kwargs)
        return pool, _Later(emitted, self._enqueue(self.step_s)), rngs


def fake_scheduler(engine, max_slots=2, **kwargs):
    """A `SlotScheduler` over a fake: blocks of 4 tokens, the engine's
    context (the fakes have no model config to read it from)."""
    kwargs.setdefault("block_size", 4)
    kwargs.setdefault("max_seq_len", engine.max_seq_len)
    return SlotScheduler(engine, params=None, max_slots=max_slots, **kwargs)


# --------------------------------------------------------------------------
# A drive shared by the serving tests of every model
# --------------------------------------------------------------------------

def admit_prefill(engine, params, pool, prompt, blocks, block_size,
                  ceiling, pad=0):
    """The device half of `SlotScheduler._admit`'s blocking prefill, on
    the engine itself: the bucket and the kept rows by the engine's rule
    (`ceiling`: what `engine.ceiling_prefill(params)` said), the prompt
    padded to the bucket with `pad`, the kept rows' blocks from `blocks`
    and every block past them aimed at the trash block. Returns (pool,
    the prefill's row cache or None, bucket, kept)."""
    bucket, kept = engine.slot_prefill_len(len(prompt), ceiling)
    if not bucket:
        return pool, None, 0, 0
    tokens = np.full((1, bucket), pad, np.int32)
    tokens[0, :kept] = np.asarray(prompt[:kept])
    row, _logits = engine.prefill(params, tokens, kept)
    owned = -(-kept // block_size)
    ids = np.zeros((-(-bucket // block_size),), np.int32)
    ids[:owned] = np.asarray(blocks[:owned])
    pool = engine.pack_prefill(pool, ids, row, bucket, block_size)
    return pool, row, bucket, kept


def mixed_trace_streams(scheduler, settle_every_tick, eos_token=None,
                        vocab=256):
    """Hand-drive one trace through `scheduler` and return
    ({name: tokens}, {name: finish reason}). Five requests over the grid's
    slots (two, for the mix to happen): `long` (prefill + one replayed
    token + decode to `max_new_tokens`), `replayed` (too short for a
    prefill bucket: its whole prompt replays), `eos` (ends at `eos_token`
    if one is given), `queued` (admitted into a freed slot) and `doomed`,
    whose deadline falls once it has two tokens, so with a step of its own
    in flight where the pipeline runs. `settle_every_tick` empties the
    pipeline after each tick: the serial order on the same program, every
    token read before anything else is decided."""
    from tf_yarn_tpu.serving import SamplingParams

    rng = np.random.RandomState(11)
    responses = {}
    for seed, (name, length, max_new) in enumerate(
            [("long", 9, 7), ("replayed", 3, 3), ("eos", 5, 8),
             ("queued", 6, 4), ("doomed", 5, 12)], start=1):
        responses[name] = scheduler.submit(
            rng.randint(1, vocab, length).tolist(),
            SamplingParams(
                max_new_tokens=max_new, seed=seed,
                eos_token=eos_token if name == "eos" else None,
                temperature=scheduler.temperature, top_k=scheduler.top_k,
                top_p=scheduler.top_p))
    doomed = responses["doomed"]
    for _ in range(400):
        scheduler.tick()
        if settle_every_tick:
            scheduler._settle("test")
        if len(doomed.token_times) >= 2 and doomed.request.timeout_s is None:
            doomed.request.timeout_s = 1e-9
        if all(response.done for response in responses.values()):
            break
    else:
        raise AssertionError("the trace did not drain in 400 ticks")
    return ({name: response.result(timeout=1)
             for name, response in responses.items()},
            {name: response.finish_reason
             for name, response in responses.items()})


def assert_pipelined_equals_settled(scheduler, vocab=256):
    """The pipelined scheduler and one settled after every tick give the
    same streams on the same compiled program: `mixed_trace_streams` three
    times through one scheduler (settled without an eos, to learn a token
    to end on; settled and pipelined with it)."""
    learned, _ = mixed_trace_streams(scheduler, True, vocab=vocab)
    stream = learned["eos"]
    # The first token that did not occur before it: the stream ends there.
    cut = next((i for i in range(1, len(stream))
                if stream[i] not in stream[:i]), 0)
    opened = scheduler.stats()
    settled, reasons = mixed_trace_streams(
        scheduler, True, stream[cut], vocab=vocab)
    between = scheduler.stats()
    pipelined, pipelined_reasons = mixed_trace_streams(
        scheduler, False, stream[cut], vocab=vocab)
    closed = scheduler.stats()
    assert pipelined == settled and pipelined_reasons == reasons
    assert reasons == {"long": "length", "replayed": "length", "eos": "eos",
                       "queued": "length", "doomed": "deadline"}
    assert settled["eos"] == stream[:cut + 1]
    assert len(settled["doomed"]) == 2
    for name in ("long", "replayed", "queued"):
        assert settled[name] == learned[name]
    # Settled after every tick no launch finds a step unread; left alone
    # nearly every launch does.
    assert between["steps_ahead"] == opened["steps_ahead"]
    steps = closed["steps"] - between["steps"]
    assert closed["steps_ahead"] - between["steps_ahead"] >= 0.8 * steps
    return closed
