"""Deterministic fake engines for the scheduler's tests: no device.

The :class:`SlotScheduler` is a pure host-side state machine whose only
device contract is the engine's paged methods, so its unit tests drive
it with these. One arithmetic everywhere: the pool is a ``(num_blocks,
block_size)`` int64 token store gathered through the block table exactly
like the real program, a slot's "cache" is the sum of the tokens it
consumed, and a sampled position emits ``sum % 97`` — so the tests can
precompute every stream, and a table / length / registration bug changes
an emission and fails them. Every call is logged for ordering assertions.
"""

import numpy as np

from tf_yarn_tpu.serving import SlotScheduler


class FakePagedEngine:
    """The exact step, admission and the swap programs."""

    def __init__(self, buckets=(4, 8), max_seq_len=32):
        self.prompt_buckets = tuple(sorted(buckets))
        self.max_seq_len = max_seq_len
        self.calls = []

    def slot_prefill_len(self, prompt_len):
        best = 0
        for bucket in self.prompt_buckets:
            if bucket <= prompt_len - 1:
                best = bucket
        return best

    def make_paged_pool(self, params, num_blocks, block_size):
        self.calls.append(("make_pool", num_blocks, block_size))
        return np.zeros((num_blocks, block_size), np.int64)

    def prefill(self, params, prompt):
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

    def paged_step(self, params, pool, tables, lengths, tokens, rngs,
                   sample_mask, block_size, temperature=0.0, top_k=None,
                   top_p=None):
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


def fake_scheduler(engine, max_slots=2, **kwargs):
    """A `SlotScheduler` over a fake: blocks of 4 tokens, the engine's
    context (the fakes have no model config to read it from)."""
    kwargs.setdefault("block_size", 4)
    kwargs.setdefault("max_seq_len", engine.max_seq_len)
    return SlotScheduler(engine, params=None, max_slots=max_slots, **kwargs)

