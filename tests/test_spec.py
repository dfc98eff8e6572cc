"""Speculative decoding: drafter units, the traced window verifier, the
scheduler's variable-tokens-per-tick path, and the real-engine
acceptance bars.

Three layers, matching the subsystem's seams:

* **Host units** — the n-gram/prompt-lookup drafter and `plan_window`
  are pure host code with exact expected outputs.
* **Fake engine** — the scheduler's windowed tick is driven with a
  deterministic fake windowed step (tests/fakes.py: sum%97 arithmetic
  over windows), pinning variable tokens/tick, draft capping at max_new,
  eos-in-window retirement, the trace ring's `accepted` records, and
  the accept-rate-0 worst case (exactly one token per step).
* **Real engine on CPU** — the acceptance bars: greedy speculative
  streams are IDENTICAL to `generate_legacy` (prefix-cache hits,
  whole-prompt replay, early EOS inside an
  accepted window included), the sampled path preserves the per-request
  RNG chain bit-for-bit, no recompiles tick-to-tick, e2e through the
  HTTP server, and the fused paged-int8 decode attention agrees with
  the gather path within quantization tolerance.
"""

import http.client
import json
import threading

import numpy as np
import pytest

from tests.fakes import FakePagedSpecEngine, fake_scheduler
from tf_yarn_tpu.models.spec import (
    NGramDrafter,
    make_drafter,
    ngram_propose,
    plan_window,
)
from tf_yarn_tpu.serving import SamplingParams, ServingServer, SlotScheduler


# --------------------------------------------------------------------------
# drafter + window planning (host units)
# --------------------------------------------------------------------------

def test_ngram_propose_copies_after_most_recent_match():
    # trailing 2-gram (5, 6) occurred earlier; the 3 tokens after it
    # are the proposal.
    assert ngram_propose([5, 6, 7, 8, 9, 5, 6], 3) == [7, 8, 9]
    # Longest n-gram wins: trailing (1, 2, 3) matches the first copy.
    assert ngram_propose([1, 2, 3, 9, 1, 2, 3], 2) == [9, 1]
    # Most RECENT occurrence wins over an older one.
    assert ngram_propose([4, 7, 4, 8, 4], 1, max_ngram=1) == [8]


def test_ngram_propose_bounds_and_no_structure():
    assert ngram_propose([1, 2, 3, 4], 3) == []  # no repeats
    assert ngram_propose([1, 2], 0) == []
    assert ngram_propose([], 3) == []
    # k larger than what follows the match: returns what exists.
    assert ngram_propose([3, 1, 3], 5) == [1, 3]


def test_ngram_drafter_validates_and_make_drafter_resolves():
    with pytest.raises(ValueError, match="min_ngram"):
        NGramDrafter(max_ngram=1, min_ngram=2)
    assert isinstance(make_drafter("ngram"), NGramDrafter)
    fn = lambda context, k: [1] * k  # noqa: E731 - the draft_model hook
    assert make_drafter(fn) is fn
    assert make_drafter(None) is None
    with pytest.raises(ValueError, match="spec_draft"):
        make_drafter("bigmodel")


def test_plan_window_pure_decode_and_fill():
    tokens, n_known, n_prop = plan_window(
        pending=[], last_token=42, width=4, max_emit=10,
        context=[1, 2, 1, 2], drafter=NGramDrafter(),
    )
    assert tokens[0] == 42 and n_known == 0
    # Drafter proposed from the repeated context: the (1, 2)-suffix
    # match yields the 2 tokens that followed it; the unfilled window
    # position is -1 (never matches).
    assert n_prop == 2 and tokens[1:] == [1, 2, -1]


def test_plan_window_replay_prefix_and_draft_room():
    # 2 pending prompt tokens in a width-4 window: positions 0..1 are
    # the replay, n_known = 1 (position 1 is the LAST prompt token —
    # it emits), drafts fill the remaining 2 positions.
    tokens, n_known, n_prop = plan_window(
        pending=[7, 8], last_token=0, width=4, max_emit=10,
        context=[5, 7, 8, 5, 7, 8], drafter=NGramDrafter(),
    )
    assert tokens[:2] == [7, 8] and n_known == 1
    assert n_prop == 2 and tokens[2:] == [5, 7]


def test_plan_window_full_replay_and_max_emit_cap():
    # More pending than the window: all positions replay, no drafts.
    tokens, n_known, n_prop = plan_window(
        pending=[1, 2, 3, 4, 5], last_token=0, width=3, max_emit=10,
        context=[1, 2, 3], drafter=NGramDrafter(),
    )
    assert tokens == [1, 2, 3] and n_known == 3 and n_prop == 0
    # max_emit caps drafting: only max_emit - 1 drafts may ride, the
    # rest of the window is -1 fill (can never match a real token).
    tokens, n_known, n_prop = plan_window(
        pending=[], last_token=9, width=5, max_emit=2,
        context=[9, 9, 9, 9], drafter=NGramDrafter(),
    )
    assert n_prop == 1 and tokens == [9, 9, -1, -1, -1]


def test_verify_window_greedy_accept_truncate_and_eos():
    import jax.numpy as jnp

    from tf_yarn_tpu.models.spec import verify_window

    def logits_for(argmaxes, vocab=8):
        rows = np.zeros((len(argmaxes), vocab), np.float32)
        for i, token in enumerate(argmaxes):
            rows[i, token] = 5.0
        return jnp.asarray(rows)

    rng = jnp.zeros((2,), jnp.uint32)

    def run(argmaxes, tokens, n_known, eos=-1, active=True):
        emitted, count, _rng = verify_window(
            logits_for(argmaxes), jnp.asarray(tokens, jnp.int32),
            jnp.asarray(n_known, jnp.int32), jnp.asarray(eos, jnp.int32),
            rng, jnp.asarray(active), 0.0, None, None,
        )
        count = int(count)
        return [int(t) for t in np.asarray(emitted)[:count]]

    # Pure decode, both drafts match the target's own argmaxes: the
    # window emits target outputs at every position (3 tokens/step).
    assert run([4, 5, 6], [9, 4, 5], n_known=0) == [4, 5, 6]
    # First draft mismatches: exactly one token (the exact step's).
    assert run([4, 5, 6], [9, 7, 5], n_known=0) == [4]
    # Chain dies at the mismatch, later "matching" drafts stay dead.
    assert run([4, 5, 6], [9, 7, 6], n_known=0) == [4]
    # Replay prefix: position 0's successor is known (no emission),
    # position 1 is the last prompt token, draft at position 2 matches.
    assert run([1, 4, 5], [8, 9, 4], n_known=1) == [4, 5]
    # Full-replay window: valid KV, zero emissions.
    assert run([1, 2, 3], [8, 9, 7], n_known=3) == []
    # EOS truncates INSIDE an accepted window: the draft after the
    # emitted eos never lands, even though it matches the argmax.
    assert run([4, 6, 5], [9, 4, 6], n_known=0, eos=6) == [4, 6]
    # Inactive slot: nothing emitted, ever.
    assert run([4, 5, 6], [9, 4, 5], n_known=0, active=False) == []


# --------------------------------------------------------------------------
# scheduler windowed tick over a deterministic fake engine
# --------------------------------------------------------------------------

def _drive(scheduler, responses, max_ticks=200):
    for used in range(1, max_ticks + 1):
        scheduler.tick()
        if all(r.done for r in responses):
            return used
    raise AssertionError(f"not drained after {max_ticks} ticks")


def test_fake_spec_engine_accepts_drafts_variable_tokens_per_tick():
    engine = FakePagedSpecEngine()
    # Oracle drafter for the fake arithmetic: prompt [1..5] -> prefill
    # sum 10, consume 5 -> emit 15, then 30, 60, 23, 46. Proposing the
    # true continuation accepts everything.
    oracle = {0: [15, 30, 60], 1: [30, 60, 23], 4: [46]}

    def drafter(context, k):
        return oracle.get(len(context) - 5, [])[:k]

    scheduler = fake_scheduler(
        engine, max_slots=1, spec_k=3, spec_draft=drafter,
    )
    response = scheduler.submit([1, 2, 3, 4, 5],
                                SamplingParams(max_new_tokens=5))
    ticks = _drive(scheduler, [response])
    assert response.result(timeout=1) == [15, 30, 60, 23, 46]
    # Tick 1: replay 5 + drafts [15, 30, 60] -> 4 emissions; tick 2:
    # feed 23... wait — tick 1 consumes 5 (last prompt token), emits 15
    # and the 3 accepted drafts = 4 tokens; tick 2 feeds 23? No: tick 1
    # emits [15, 30, 60, 23]? The window is [5, d1, d2, d3] = 4 wide:
    # emits 15, then drafts 15, 30, 60 accept -> emits 15, 30, 60, 23?
    # Window width = spec_k + 1 = 4: inputs [5, 15, 30, 60], emissions
    # [15, 30, 60, 23] (position 3's emission is the bonus token).
    # Tick 2: input [23, 46?..] -> emits 46. Total 2 ticks.
    assert ticks == 2
    trace = [t for t in scheduler.trace if t.get("accepted")]
    assert [list(t["accepted"].values()) for t in trace] == [[4], [1]]
    stats = scheduler.stats()
    # Tick 1 proposed 3 drafts (all accepted); tick 2 had max_emit 1 ->
    # no drafts at all.
    assert stats["spec"]["proposed_tokens"] == 3
    assert stats["spec"]["accepted_tokens"] == 3
    assert stats["spec"]["accept_rate"] == 1.0


def test_fake_spec_engine_accept_rate_zero_degrades_to_one_token_per_step():
    engine = FakePagedSpecEngine()
    # 98 can never be emitted (emissions are mod 97): guaranteed reject.
    scheduler = fake_scheduler(
        engine, max_slots=1, spec_k=3,
        spec_draft=lambda context, k: [98] * k,
    )
    response = scheduler.submit([1, 2, 3, 4, 5],
                                SamplingParams(max_new_tokens=4))
    _drive(scheduler, [response])
    # Same stream as the exact path, exactly one token per emitting
    # tick, and the window shape never changed (no recompile pressure:
    # every windowed step saw the same (slots, width)).
    assert response.result(timeout=1) == [15, 30, 60, 23]
    accepted = [list(t["accepted"].values())
                for t in scheduler.trace if t.get("accepted")]
    assert accepted == [[1], [1], [1], [1]]
    shapes = {call[1].shape for call in engine.calls
              if call[0] == "paged_spec_step"}
    assert shapes == {(1, 4)}
    assert scheduler.stats()["spec"]["accept_rate"] == 0.0


def test_fake_spec_engine_eos_inside_accepted_window_retires():
    engine = FakePagedSpecEngine()
    # Emissions: 15, 30, 60, ... — make 30 the eos and propose [15, 30,
    # 60]: the device truncates AT the eos, the request retires with
    # finish_reason eos, and the third (matching) draft never lands.
    scheduler = fake_scheduler(
        engine, max_slots=1, spec_k=3,
        spec_draft=lambda context, k: [15, 30, 60][:k],
    )
    response = scheduler.submit(
        [1, 2, 3, 4, 5],
        SamplingParams(max_new_tokens=10, eos_token=30),
    )
    _drive(scheduler, [response])
    assert response.result(timeout=1) == [15, 30]
    assert response.finish_reason == "eos"


def test_fake_spec_engine_drafts_capped_by_max_new_tokens():
    engine = FakePagedSpecEngine()
    seen_windows = []

    def drafter(context, k):
        seen_windows.append(k)
        return [15, 30, 60][:k]

    scheduler = fake_scheduler(
        engine, max_slots=1, spec_k=3, spec_draft=drafter,
    )
    response = scheduler.submit([1, 2, 3, 4, 5],
                                SamplingParams(max_new_tokens=2))
    _drive(scheduler, [response])
    # Only 2 tokens may ever be emitted -> at most 1 draft requested,
    # and the request never overshoots max_new_tokens.
    assert response.result(timeout=1) == [15, 30]
    assert max(seen_windows) <= 1


def test_scheduler_validates_spec_arguments():
    engine = FakePagedSpecEngine()
    with pytest.raises(ValueError, match="spec_k"):
        fake_scheduler(engine, spec_k=-1)
    with pytest.raises(ValueError, match="decode_attention"):
        fake_scheduler(engine, decode_attention="magic")
    with pytest.raises(ValueError, match="spec_draft"):
        fake_scheduler(engine, spec_k=2, spec_draft="llama")


def test_spec_context_limit_reserves_window_headroom():
    engine = FakePagedSpecEngine()
    scheduler = fake_scheduler(engine, max_slots=1, spec_k=4)
    assert scheduler.context_limit == 28
    with pytest.raises(ValueError, match="headroom"):
        scheduler.submit([1] * 20, SamplingParams(max_new_tokens=9))
    scheduler.submit([1] * 20, SamplingParams(max_new_tokens=8))


# --------------------------------------------------------------------------
# real engine on CPU: the acceptance bars
# --------------------------------------------------------------------------

def _tiny_stack(max_slots=2, kv_cache_dtype="bf16", block_size=8,
                **scheduler_kwargs):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import DecodeEngine

    cfg = transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=64, dtype=jnp.float32,
        kv_cache_dtype=kv_cache_dtype,
    )
    model = transformer.Transformer(cfg)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    )
    engine = DecodeEngine(
        model, batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 16)
    )
    scheduler = SlotScheduler(
        engine, params, max_slots=max_slots, block_size=block_size,
        **scheduler_kwargs
    )
    return model, params, engine, scheduler


def _legacy_stream(model, params, prompt, max_new, eos=None, **sampling):
    import jax.numpy as jnp

    from tf_yarn_tpu.models.generate import generate_legacy

    out = generate_legacy(
        model, params, jnp.asarray([prompt], jnp.int32), max_new,
        eos_token=eos, **sampling,
    )
    row = np.asarray(out)[0, len(prompt):].tolist()
    if eos is not None and eos in row:
        row = row[:row.index(eos) + 1]
    return row


def _oracle_drafter(model, params, prompts, max_new):
    """The perfect drafter: proposes the target's own greedy
    continuation (precomputed via generate_legacy), matched to the
    request by its prompt prefix — every draft accepts, so emitting
    ticks land the full window deterministically."""
    table = {
        tuple(p): _legacy_stream(model, params, p, max_new)
        for p in prompts
    }

    def drafter(context, k):
        for prompt, stream in table.items():
            if tuple(context[:len(prompt)]) == prompt:
                pos = len(context) - len(prompt)
                return stream[pos:pos + k]
        return []

    return drafter


@pytest.mark.parametrize("scheduler_kwargs, kv_cache_dtype", [
    ({}, "bf16"),
    ({"decode_attention": "fused"}, "int8"),
])
def test_greedy_spec_streams_identical_to_legacy(scheduler_kwargs,
                                                 kv_cache_dtype):
    """The tentpole bar: greedy speculative streams are IDENTICAL to
    generate_legacy, through the gather verify and the fused int8 one —
    with the n-gram self-drafter live, concurrent mixed-length
    requests, and a whole-prompt-replay short prompt in the mix."""
    model, params, engine, scheduler = _tiny_stack(
        max_slots=2, kv_cache_dtype=kv_cache_dtype, spec_k=3,
        **scheduler_kwargs,
    )
    try:
        rng = np.random.RandomState(0)
        motif = rng.randint(0, 256, (3,)).tolist()
        prompts = [
            rng.randint(0, 256, (5,)).tolist(),
            (motif * 4)[:9],           # repeated structure: drafts land
            rng.randint(0, 256, (2,)).tolist(),  # whole-prompt replay
        ]
        max_news = (8, 14, 6)
        responses = [
            scheduler.submit(p, SamplingParams(max_new_tokens=m))
            for p, m in zip(prompts, max_news)
        ]
        _drive(scheduler, responses, max_ticks=500)
        for prompt, max_new, response in zip(prompts, max_news, responses):
            assert response.result(timeout=1) == _legacy_stream(
                model, params, prompt, max_new
            )
        # ONE windowed program compiled for the whole run — variable
        # accepts tick-to-tick never recompile.
        assert engine.stats["paged_spec_step_compiles"] == 1
    finally:
        scheduler.close()


def test_spec_accepts_multiple_tokens_per_tick_with_oracle_drafter():
    """With a perfect drafter every emitting tick lands the full
    window: accepted-tokens/step goes to spec_k + 1, the tick count
    collapses accordingly, and the stream still equals legacy."""
    model, params, engine, scheduler = _tiny_stack(max_slots=1)
    prompt = list(np.random.RandomState(1).randint(0, 256, (5,)))
    prompt = [int(t) for t in prompt]
    max_new = 12
    scheduler.close()
    model, params, engine, scheduler = _tiny_stack(
        max_slots=1, spec_k=3,
        spec_draft=_oracle_drafter(model, params, [prompt], max_new),
    )
    try:
        response = scheduler.submit(
            prompt, SamplingParams(max_new_tokens=max_new)
        )
        ticks = _drive(scheduler, [response], max_ticks=100)
        assert response.result(timeout=1) == _legacy_stream(
            model, params, prompt, max_new
        )
        # 12 tokens at 4/tick = 3 emitting ticks (prefill covers the
        # prompt remainder inside the first window).
        assert ticks <= 4
        accepted = [n for t in scheduler.trace
                    for n in t.get("accepted", {}).values()]
        assert max(accepted) == 4
        assert sum(accepted) == max_new
        assert scheduler.stats()["spec"]["accept_rate"] == 1.0
    finally:
        scheduler.close()


def test_spec_accept_rate_zero_real_engine_one_token_per_tick():
    """The worst case on the REAL engine: a drafter that always
    proposes the wrong token degrades to exactly one token per emitting
    tick — same stream, one compiled program, no recompiles."""
    model, params, _engine, probe = _tiny_stack(max_slots=1)
    prompt = [int(t) for t in np.random.RandomState(2).randint(0, 256, (5,))]
    max_new = 8
    stream = _legacy_stream(model, params, prompt, max_new)
    probe.close()

    def wrong_drafter(context, k):
        pos = len(context) - len(prompt)
        return [
            (stream[pos + i] + 1) % 256 if pos + i < len(stream) else 0
            for i in range(k)
        ]

    model, params, engine, scheduler = _tiny_stack(
        max_slots=1, spec_k=3, spec_draft=wrong_drafter,
    )
    try:
        response = scheduler.submit(
            prompt, SamplingParams(max_new_tokens=max_new)
        )
        _drive(scheduler, [response], max_ticks=100)
        assert response.result(timeout=1) == stream
        accepted = [n for t in scheduler.trace
                    for n in t.get("accepted", {}).values()]
        assert accepted == [1] * max_new
        assert scheduler.stats()["spec"]["accept_rate"] == 0.0
        assert engine.stats["paged_spec_step_compiles"] == 1
    finally:
        scheduler.close()


def test_spec_early_eos_inside_accepted_window_matches_legacy():
    """EOS emitted mid-window: acceptance truncates at the eos, the
    request retires as `eos`, and the stream equals legacy's (which
    stops there too) — accepted tokens past the eos are discarded."""
    model, params, _engine, probe = _tiny_stack(max_slots=1)
    prompt = [int(t) for t in np.random.RandomState(6).randint(0, 256, (5,))]
    full = _legacy_stream(model, params, prompt, 12)
    eos = full[2]  # the third greedy token becomes the eos
    # The prompt is chosen for this: a stream that repeats its third
    # token earlier (seed 3's does) would stop at the repeat.
    assert eos not in full[:2], full
    probe.close()
    model, params, engine, scheduler = _tiny_stack(
        max_slots=1, spec_k=3,
        spec_draft=_oracle_drafter(model, params, [prompt], 12),
    )
    try:
        response = scheduler.submit(
            prompt, SamplingParams(max_new_tokens=12, eos_token=eos)
        )
        _drive(scheduler, [response], max_ticks=100)
        expected = _legacy_stream(model, params, prompt, 12, eos=eos)
        assert response.result(timeout=1) == expected
        assert response.finish_reason == "eos"
        assert expected[-1] == eos and len(expected) == 3
    finally:
        scheduler.close()


def test_sampled_spec_preserves_rng_stream_bitwise():
    """The sampled contract: temperature > 0 speculative streams equal
    generate_legacy token-for-token — acceptance is token-matching
    against the request's OWN seeded sampling chain, so the chain
    advances exactly one split per emitted token, drafts or not."""
    model, params, engine, scheduler = _tiny_stack(
        max_slots=2, spec_k=3, temperature=0.8, top_k=20,
    )
    try:
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 256, (5,)).tolist(),
                   rng.randint(0, 256, (9,)).tolist()]
        seeds = [3, 11]
        responses = [
            scheduler.submit(p, SamplingParams(
                max_new_tokens=10, temperature=0.8, top_k=20, seed=s))
            for p, s in zip(prompts, seeds)
        ]
        _drive(scheduler, responses, max_ticks=500)
        for prompt, seed, response in zip(prompts, seeds, responses):
            assert response.result(timeout=1) == _legacy_stream(
                model, params, prompt, 10,
                temperature=0.8, top_k=20, seed=seed,
            )
    finally:
        scheduler.close()


def test_paged_spec_prefix_cache_hit_stream_identical():
    """Prefix-cache hits compose with speculation: the second request
    with the same prompt admits through the shared blocks (no second
    prefill) and its speculative stream still equals legacy."""
    model, params, engine, scheduler = _tiny_stack(
        max_slots=1, spec_k=3,
    )
    try:
        prompt = [int(t) for t in
                  np.random.RandomState(6).randint(0, 256, (9,))]
        first = scheduler.submit(prompt, SamplingParams(max_new_tokens=6))
        _drive(scheduler, [first], max_ticks=200)
        prefills = engine.stats["prefill_compiles"] \
            + engine.stats["prefill_cache_hits"]
        second = scheduler.submit(prompt, SamplingParams(max_new_tokens=6))
        _drive(scheduler, [second], max_ticks=200)
        assert engine.stats["prefill_compiles"] \
            + engine.stats["prefill_cache_hits"] == prefills
        expected = _legacy_stream(model, params, prompt, 6)
        assert first.result(timeout=1) == expected
        assert second.result(timeout=1) == expected
        assert scheduler.stats()["prefix_cache"]["hits"] >= 1
    finally:
        scheduler.close()


def test_fused_decode_attention_matches_gather_within_tolerance():
    """The fused-kernel flag's tolerance bar, at the engine seam: one
    identical paged-int8 state steps through decode_attention='gather'
    and 'fused'. Emitted tokens and counts must be identical, and the
    K/V rows the window wrote into the slot's own blocks must agree to
    quantization tolerance — the two paths differ only in attention
    reduction order (the kernel's online softmax vs the gathered
    view's xla reduction). Trash-block garbage is excluded by construction:
    writes there are unordered across colliding slots."""
    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu

    def run(mode):
        model, params, engine, scheduler = _tiny_stack(
            max_slots=2, kv_cache_dtype="int8", spec_k=2,
            decode_attention=mode,
        )
        scheduler.close()
        prompt = [int(t) for t in
                  np.random.RandomState(8).randint(0, 256, (9,))]
        bs, width = 8, 3
        pool = engine.make_paged_pool(params, 9, bs)
        tables = np.zeros((2, 64 // bs), np.int32)
        lengths = np.zeros((2,), np.int32)
        row, _ = engine.prefill(
            params, np.asarray(prompt[:8], np.int32)[None, :]
        )
        pool = engine.pack_prefill(
            pool, np.asarray([1], np.int32), row, 8, bs
        )
        tables[0, :2] = [1, 2]
        lengths[0] = 8
        tokens = np.full((2, width), -1, np.int32)
        tokens[0, 0] = prompt[8]  # the last prompt token; no drafts
        n_known = np.zeros((2,), np.int32)
        eos = np.full((2,), -1, np.int32)
        rngs = np.zeros((2, 2), np.uint32)
        active = np.asarray([True, False])
        pool, emitted, counts, _rngs = engine.paged_spec_step(
            params, pool, tables, lengths, tokens, n_known, eos, rngs,
            active, block_size=bs, decode_attention=mode,
        )
        # The window wrote slot 0's rows at logical positions 8..10 ->
        # block 2 (table[1]), offsets 0..2. Extract them dequantized.
        rows = {}
        leaves = jtu.tree_flatten_with_path(
            pool, is_leaf=lambda x: x is None
        )[0]
        named = {jtu.keystr(path): leaf for path, leaf in leaves}
        for name, leaf in named.items():
            if leaf is None or "scale" in name:
                continue
            scale = named[name.replace("key'", "key_scale'")
                          .replace("value'", "value_scale'")]
            values = np.asarray(leaf)
            scales = np.asarray(scale)
            # leaf [1, NB, bs, Hkv, D] (block axis after the batch-1
            # axis): block 2, offsets 0..2.
            deq = values[:, 2, :3].astype(np.float32) * scales[:, 2, :3]
            rows[name] = deq
        return (np.asarray(emitted), np.asarray(counts), rows)

    g_emitted, g_counts, g_rows = run("gather")
    f_emitted, f_counts, f_rows = run("fused")
    np.testing.assert_array_equal(g_counts, f_counts)
    assert int(g_counts[0]) == 1
    np.testing.assert_array_equal(g_emitted, f_emitted)
    assert set(g_rows) == set(f_rows) and len(g_rows) >= 2
    for name in g_rows:
        np.testing.assert_allclose(
            g_rows[name], f_rows[name], atol=0.1, rtol=0.05,
            err_msg=name,
        )


def test_spec_http_end_to_end_matches_legacy_and_reports_stats():
    """The e2e acceptance bar: speculative decoding on through the real
    HTTP server — streams bit-identical to generate_legacy, /stats
    reporting the spec section, and accepted-tokens/step > 1 on the
    oracle-drafted request."""
    model, params, _engine, probe = _tiny_stack(max_slots=2)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 256, (5,)).tolist(),
               rng.randint(0, 256, (9,)).tolist()]
    probe.close()
    model, params, engine, scheduler = _tiny_stack(
        max_slots=2, spec_k=3,
        spec_draft=_oracle_drafter(model, params, prompts, 12),
    )
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        results = {}

        def call(index):
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.port, timeout=300
            )
            try:
                conn.request(
                    "POST", "/v1/generate",
                    json.dumps({"prompt": prompts[index],
                                "max_new_tokens": 12}),
                    {"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                results[index] = (resp.status, resp.read())
            finally:
                conn.close()

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(2)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=300)
        for index, prompt in enumerate(prompts):
            status, raw = results[index]
            assert status == 200, raw
            assert json.loads(raw)["tokens"] == _legacy_stream(
                model, params, prompt, 12
            )

        conn = http.client.HTTPConnection(
            "127.0.0.1", server.port, timeout=30
        )
        conn.request("GET", "/stats")
        stats = json.loads(conn.getresponse().read())
        conn.close()
        assert stats["spec_k"] == 3
        assert stats["spec"]["accept_rate"] == 1.0
        assert stats["decode_engine"]["paged_spec_step_compiles"] == 1
        accepted = [n for t in scheduler.trace
                    for n in t.get("accepted", {}).values()]
        assert max(accepted) > 1
    finally:
        server.stop()
        scheduler.close()


def test_serving_experiment_spec_fields_validate():
    from tf_yarn_tpu.experiment import ServingExperiment

    with pytest.raises(ValueError, match="spec_k"):
        ServingExperiment(model=None, model_dir="x", spec_k=-1)
    with pytest.raises(ValueError, match="spec_draft"):
        ServingExperiment(model=None, model_dir="x", spec_draft="gpt")
    with pytest.raises(ValueError, match="decode_attention"):
        ServingExperiment(model=None, model_dir="x",
                          decode_attention="magic")
    experiment = ServingExperiment(
        model=None, model_dir="x", spec_k=4,
        spec_draft=lambda context, k: [],
        decode_attention="fused",
    )
    assert experiment.spec_k == 4
