"""A served model holds each matrix in the type its step reads it in
(`models/param_types.py`, `DecodeEngine.hold_params`; docs/Serving.md
"Parameters as held").

The rule is read off the model's jaxpr, so these tests hold it to the
leaves it has to find in the models this repo serves, to the leaves it has
to leave alone, and to the one property that makes the conversion free of
any change of result: `bf16(w)` is what the step computed from `w` at
every read, so computing it once gives every later step the same bits.
"""

import http.client
import json
import threading

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from tf_yarn_tpu.models import decode_engine, param_types, transformer
from tf_yarn_tpu.models.trunk import ServingContract

BF16 = np.dtype(jnp.bfloat16)


def _two_argument_prefill(self):
    """The probes' contract: the rule traces `build_prefill_fn`, which asks
    a model whether its prefill takes a length."""
    return ServingContract(
        leaf_kinds={"seen": ("index", None)}, prefill_layers=(),
        rows_causal=False, takes_prompt_len=False, counts=False)


def _tiny(**overrides):
    defaults = dict(scan_layers=False, remat=False, max_seq_len=32)
    defaults.update(overrides)
    return transformer.Transformer(
        transformer.TransformerConfig.tiny(**defaults))


def _init(model, seed=0):
    return nn.meta.unbox(
        model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _named(model, variables):
    """{leaf path: the type the rule narrows it to, or None}."""
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(variables)]
    return dict(zip(paths, param_types.narrow_types(model, variables)))


def _is_matrix(path: str) -> bool:
    return path.endswith(("['kernel']", "['lora_a']", "['lora_b']",
                          "['embedding']", "['lm_head']", "['w_gate']",
                          "['w_up']", "['w_down']"))


@pytest.mark.parametrize("overrides, narrowed", [
    (dict(), 16),                      # 7 matrices x 2 layers + table + head
    (dict(scan_layers=True), 9),       # the 7 stacked, read inside the scan
    (dict(scan_layers=True, remat=True), 9),
    (dict(lora_rank=4), 44),           # + lora_a, lora_b of each kernel
    (dict(moe_experts=4), 16),         # the experts' stacks; not the router
], ids=["layers", "scanned", "scanned_remat", "lora", "switch_moe"])
def test_rule_picks_every_matrix_of_a_transformer_and_no_norm_scale(
        overrides, narrowed):
    model = _tiny(**overrides)
    found = _named(model, _init(model))
    assert {p for p, to in found.items() if to is not None} == \
        {p for p in found if _is_matrix(p)}
    assert all(to == BF16 for to in found.values() if to is not None)
    assert sum(to is not None for to in found.values()) == narrowed
    kept = [p for p, to in found.items() if to is None]
    assert kept and all("norm" in p or "router" in p for p in kept)


class _BiasedDense(nn.Module):
    """A `LoraDense` with its bias and LoRA factors under a norm, in the
    form `build_prefill_fn` applies a model in."""

    config: transformer.TransformerConfig
    serving_contract = _two_argument_prefill

    @nn.compact
    def __call__(self, tokens, decode=False):
        self.variable("cache", "seen", lambda: jnp.zeros((), jnp.int32))
        x = jax.nn.one_hot(tokens, 8, dtype=self.config.dtype)
        x = transformer.LoraDense(
            64, (transformer.EMBED, transformer.MLP), self.config,
            use_bias=True, name="dense")(x)
        return transformer.RMSNorm(self.config, name="norm")(x)


def test_rule_takes_a_bias_and_lora_factors_with_the_kernel():
    model = _BiasedDense(transformer.TransformerConfig.tiny(lora_rank=2))
    found = _named(model, {"params": _init(model)["params"]})
    assert found == {
        "['params']['dense']['bias']": BF16,
        "['params']['dense']['kernel']": BF16,
        "['params']['dense']['lora_a']": BF16,
        "['params']['dense']['lora_b']": BF16,
        "['params']['norm']['scale']": None,
    }


class _Probe(nn.Module):
    """One matrix read the way `read` says, after a table that is only
    ever converted to bfloat16."""

    read: str
    serving_contract = _two_argument_prefill

    @nn.compact
    def __call__(self, tokens, decode=False):
        self.variable("cache", "seen", lambda: jnp.zeros((), jnp.int32))
        table = self.param("table", nn.initializers.normal(1.0), (16, 8),
                           jnp.float32)
        w = self.param("w", nn.initializers.normal(1.0), (8, 8), jnp.float32)
        x = table.astype(jnp.bfloat16)[tokens]
        y = x @ w.astype(jnp.bfloat16)
        if self.read == "own_type_too":
            y = y + (x.astype(jnp.float32) @ w).astype(jnp.bfloat16)
        elif self.read == "two_types":
            y = y + (x.astype(jnp.float16)
                     @ w.astype(jnp.float16)).astype(jnp.bfloat16)
        elif self.read == "unknown_call":
            y = y + jax.lax.cond(tokens[0, 0] > 0, lambda m: m,
                                 lambda m: -m, w).astype(jnp.bfloat16)[0]
        elif self.read == "same_width":
            y = x.astype(jnp.float32) @ w.astype(jnp.float32)
        return y.astype(jnp.float32)


@pytest.mark.parametrize("read, to", [
    ("convert_only", BF16), ("own_type_too", None), ("two_types", None),
    ("unknown_call", None), ("same_width", None)])
def test_rule_leaves_alone_what_it_cannot_classify(read, to):
    """A leaf read in its own type as well, converted to two types, handed
    to an equation the walk does not look into, or converted to nothing
    narrower stays as restored; the table beside it is still narrowed."""
    model = _Probe(read)
    found = _named(model, {"params": _init(model)["params"]})
    assert found == {"['params']['table']": BF16, "['params']['w']": to}


@pytest.mark.parametrize("which", ["hybrid", "latent"])
def test_rule_finds_nothing_where_the_matrices_are_stored_narrow(which):
    """The hybrid and latent models keep their matrices in bfloat16 and
    read every float32 leaf (norms, router bias, `A_log`, `D`, `dt_bias`,
    the convolution) in float32: nothing to convert, nothing touched."""
    if which == "hybrid":
        from tf_yarn_tpu.models.hybrid import HybridConfig, HybridLM

        model = HybridLM(HybridConfig.tiny())
    else:
        from tf_yarn_tpu.models.latent import LatentConfig, LatentLM

        model = LatentLM(LatentConfig.tiny())
    variables = _init(model)
    assert not any(_named(model, variables).values())
    leaves = jax.tree_util.tree_leaves(variables)
    held, narrowed, before, after = param_types.narrow(model, variables)
    assert narrowed == 0 and before == after
    assert all(a is b for a, b in
               zip(leaves, jax.tree_util.tree_leaves(held)))


def _prefill_logits(model, params):
    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    _, logits = jax.jit(decode_engine.build_prefill_fn(model))(params, prompt)
    return np.asarray(logits)


def _paged_step_logits(model, params):
    """One paged step over three slots (free, 5 and 21 tokens held) of a
    random pool."""
    rng = np.random.RandomState(7)
    row = decode_engine._decode_cache_aval(model, params)
    block, slots = 4, 3
    per_slot = model.config.max_seq_len // block
    pool = jax.tree_util.tree_map(
        lambda aval: None if aval is None else jnp.asarray(
            rng.randn(*aval.shape), aval.dtype),
        decode_engine.paged_pool_avals(model, row, slots * per_slot + 1,
                                       block),
        is_leaf=lambda x: x is None)
    tables = (rng.permutation(slots * per_slot) + 1).reshape(
        slots, per_slot).astype(np.int32)
    tables[0] = 0
    step = jax.jit(decode_engine.build_paged_step_fn(
        model, block, 0.0, None, None, with_logits=True))
    out = step(params, pool, tables, np.asarray([0, 5, 21], np.int32),
               *decode_engine.all_forced(np.asarray([3, 4, 5], np.int32),
                                         np.zeros((slots, 2), np.uint32)),
               np.ones((slots,), bool))
    return np.asarray(out[-1])


@pytest.mark.parametrize("logits_of", [_prefill_logits, _paged_step_logits],
                         ids=["prefill", "paged_step"])
@pytest.mark.parametrize("to, same", [(None, True), (jnp.float16, False)],
                         ids=["as_the_rule_says", "float16_instead"])
def test_logits_from_the_held_tree_equal_the_float32_trees_bit_for_bit(
        logits_of, to, same):
    """Why nothing may differ: every read of a narrowed leaf `w` was
    `w.astype(bfloat16)`, and the held leaf *is* `w.astype(bfloat16)`, on
    which the step's own convert is the identity: the same program on the
    same values. A tree narrowed to float16 instead hands the step
    `bf16(f16(w))`, other values, and fails the same comparison."""
    model = _tiny()
    wide = _init(model, seed=3)
    expected = logits_of(model, wide)
    if to is None:
        held, narrowed, _, _ = param_types.narrow(model, _copy(wide))
        assert narrowed == 16
    else:
        types = param_types.narrow_types(model, wide)
        leaves, treedef = jax.tree_util.tree_flatten(wide)
        held = treedef.unflatten([
            leaf if t is None else leaf.astype(to)
            for leaf, t in zip(leaves, types)])
    got = logits_of(model, held)
    assert got.dtype == expected.dtype == np.float32
    assert np.array_equal(got, expected) == same


def test_the_wide_originals_are_gone_and_host_leaves_convert_the_same():
    model = _tiny()
    wide = _init(model)
    host = jax.tree_util.tree_map(np.asarray, wide)  # what a restore gives
    types = param_types.narrow_types(model, wide)
    leaves = jax.tree_util.tree_leaves(wide)
    held, narrowed, before, after = param_types.narrow(model, wide)
    held_leaves = jax.tree_util.tree_leaves(held)
    for leaf, got, to in zip(leaves, held_leaves, types):
        if to is None:
            assert got is leaf and not leaf.is_deleted()
        else:
            assert leaf.is_deleted() and got.dtype == to
    assert narrowed == 16
    wide_bytes = sum(l.size * 4 for l in leaves)
    saved = sum(l.size * 2 for l, to in zip(leaves, types) if to is not None)
    assert (before, after) == (wide_bytes, wide_bytes - saved)
    from_host, *counts = param_types.narrow(model, host)
    assert counts == [narrowed, before, after]
    for a, b in zip(held_leaves, jax.tree_util.tree_leaves(from_host)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_under_a_mesh_each_converted_leaf_keeps_its_sharding():
    from jax.sharding import NamedSharding

    from tf_yarn_tpu import inference
    from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(tp=2), jax.devices()[:2])
    model = _tiny()
    placed = inference.shard_restored_params(model, _init(model), mesh)
    before = [leaf.sharding for leaf in jax.tree_util.tree_leaves(placed)]
    assert any(not s.is_fully_replicated for s in before)
    engine = decode_engine.DecodeEngine(model, mesh=mesh)
    held = engine.hold_params(placed)
    assert engine.stats["params_narrowed"] == 16
    for leaf, sharding in zip(jax.tree_util.tree_leaves(held), before):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding == sharding
    # Already where the engine places them: nothing moves again.
    again = engine._place_params(held)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(again),
                                      jax.tree_util.tree_leaves(held)))


def test_training_still_starts_from_float32_masters():
    """Training, evaluation and checkpoints never pass through
    `hold_params`; `param_dtype`'s default is what it was."""
    from tf_yarn_tpu import training

    model = _tiny()
    decode_engine.DecodeEngine(model).hold_params(_init(model))
    assert transformer.TransformerConfig().param_dtype == jnp.float32
    state = training._default_init_fn(model)(
        jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 8), jnp.int32)})
    floats = [leaf for leaf in jax.tree_util.tree_leaves(nn.meta.unbox(state))
              if jnp.issubdtype(leaf.dtype, jnp.floating)]
    assert floats and all(leaf.dtype == jnp.float32 for leaf in floats)


def test_hold_params_reports_a_span_and_two_stats_keys():
    from tf_yarn_tpu import telemetry
    from tf_yarn_tpu.serving import SlotScheduler

    model = _tiny()
    engine = decode_engine.DecodeEngine(
        model, batch_buckets=(1, 2), prompt_buckets=(4, 8, 16))
    assert "param_bytes" not in engine.stats
    telemetry.get_tracer().clear()
    held = engine.hold_params(_init(model))
    (span,) = [s for s in telemetry.get_tracer().records()
               if s.name == "serving/cast_params"]
    assert span.args["leaves"] == 16
    assert span.args["bytes_before"] > span.args["bytes_after"] > 0
    scheduler = SlotScheduler(engine, held, max_slots=2, block_size=8)
    try:
        reported = scheduler.stats()["decode_engine"]
    finally:
        scheduler.close()
    assert reported["params_narrowed"] == 16
    assert reported["param_bytes"] == span.args["bytes_after"] == sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(held))


def test_run_serving_holds_the_narrow_tree_and_says_so_on_stats(monkeypatch):
    """The task body end to end on a bfloat16 model handed float32
    parameters: the conversion comes before the first program, so one set
    of programs is compiled, `/stats` carries the two keys, and the stream
    is the one the float32 tree gives through the same engine."""
    from tf_yarn_tpu import inference as inference_mod
    from tf_yarn_tpu import preemption
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.experiment import ServingExperiment
    from tf_yarn_tpu.serving.server import run_serving
    from tf_yarn_tpu.topologies import TaskKey

    model = _tiny(max_seq_len=64)
    wide = _init(model)
    prompt = [1, 2, 3, 4, 5]
    decode_engine.clear_engines()
    expected = np.asarray(decode_engine.get_engine(model).generate(
        wide, jnp.asarray([prompt], jnp.int32), 4))[0, len(prompt):].tolist()
    decode_engine.clear_engines()
    monkeypatch.setattr(inference_mod, "_restore_params",
                        lambda model_dir, step: (_copy(wide), 3))

    class _Runtime:
        kv = InProcessKV()
        task_key = TaskKey("serving", 0)
        task = "serving:0"

    runtime = _Runtime()
    experiment = ServingExperiment(
        model=model, model_dir="/nonexistent-restore-is-patched",
        host="127.0.0.1", max_slots=2)
    result = {}
    thread = threading.Thread(target=lambda: result.update(
        stats=run_serving(experiment, runtime=runtime)))
    thread.start()
    try:
        endpoint = runtime.kv.wait_str("serving:0/serving_endpoint",
                                       timeout=60)
        host, port = endpoint.rsplit(":", 1)
        conn = http.client.HTTPConnection("127.0.0.1", int(port), timeout=120)
        conn.request("POST", "/v1/generate", json.dumps(
            {"prompt": prompt, "max_new_tokens": 4}),
            {"Content-Type": "application/json"})
        tokens = json.loads(conn.getresponse().read())["tokens"]
        conn.request("GET", "/stats")
        live = json.loads(conn.getresponse().read())["decode_engine"]
        conn.close()
    finally:
        preemption.request()
        thread.join(timeout=120)
        preemption.reset()
    assert not thread.is_alive()
    assert tokens == expected
    final = result["stats"]["decode_engine"]
    assert live["params_narrowed"] == final["params_narrowed"] == 16
    assert live["param_bytes"] == final["param_bytes"]
    assert final["paged_step_compiles"] == 1
    decode_engine.clear_engines()
