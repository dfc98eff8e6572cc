"""jaxpr engine: abstract-trace exported entry points and verify them.

Where the AST engine reads *source*, this engine reads the *program*:
each registered entry point is traced with `jax.make_jaxpr` over
`ShapeDtypeStruct` inputs (no devices touched, no FLOPs spent — safe on
a laptop and in CI) and the resulting jaxpr is walked recursively:

* a trace failure is itself a finding (TYA101) — the same exception
  would otherwise first fire on hardware, at step 0;
* every collective primitive's axis names must lie inside the axis
  environment the entry point declares it runs under (TYA102) — the
  jaxpr-level twin of the AST engine's literal check, and the one that
  catches axes smuggled in through variables;
* host-callback / device-transfer primitives in hot paths are flagged
  (TYA103) — a `jax.debug.print` left in a kernel is a host round-trip
  per step;
* per-entry primitive counts are reported, so a review diff that
  silently doubles the `mul`s or drops a fused kernel's `custom_vjp`
  shows up as a number.

Entry points cover the surfaces ROADMAP cares about: the ops kernels,
the `parallel.collectives` wrappers, ring/Ulysses attention bodies, and
the flagship model's forward+backward.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from tf_yarn_tpu.analysis.findings import Finding

# Primitive names whose params carry mesh-axis names, and the param keys
# they use (jax spells it 'axes' for reductions, 'axis_name' elsewhere).
_AXIS_PARAM_KEYS = ("axes", "axis_name")
_COLLECTIVE_PRIMITIVES = {
    "psum", "pmin", "pmax", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "axis_index",
}
_HOST_CALLBACK_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback", "outside_call", "device_put",
}


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One abstractly-traceable surface.

    `build` returns (fn, args_tuple, kwargs) — deferred so importing the
    engine never imports jax-heavy modules. `axis_env` is the (name,
    size) environment the trace runs under AND the vocabulary its
    collectives are verified against; `expected_axes` narrows that
    further when the entry should only ever touch a subset (ring
    attention has no business reducing over `tp`). `hot` marks per-step
    code where a host callback is a finding, not a curiosity.
    `requires` names runtime capabilities (see `capabilities()`) the
    entry needs: where they are lacking the entry is *skipped* with a
    visible notice, not failed — a one-device process cannot lower a
    tp=2 program.
    """

    name: str
    build: Callable[[], Tuple[Callable, tuple, dict]]
    axis_env: Tuple[Tuple[str, int], ...] = ()
    expected_axes: Optional[Tuple[str, ...]] = None
    hot: bool = True
    requires: Tuple[str, ...] = ()
    # Per-entry rule suppression — the traced-program twin of the AST
    # engine's `# noqa` (a jaxpr finding has no source line to comment
    # on). Codes listed here are filtered from failures but surfaced as
    # notices in the CLI output, so an `allow` never silently rots.
    allow: Tuple[str, ...] = ()


def capabilities() -> frozenset:
    """What this process can lower, probed once."""
    global _CAPABILITIES
    if _CAPABILITIES is not None:
        return _CAPABILITIES
    import jax

    caps = set()
    # The sharded decode entries lower tp=2 programs and need two real
    # devices (a CPU rig gets them via
    # --xla_force_host_platform_device_count); single-device installs
    # skip those entries with a notice instead of failing them.
    if len(jax.devices()) >= 2:
        caps.add("multi_device")
    _CAPABILITIES = frozenset(caps)
    return _CAPABILITIES


_CAPABILITIES: Optional[frozenset] = None


def _walk_jaxpr(jaxpr) -> Iterable:
    """Yield every eqn in `jaxpr` and all nested jaxprs (cond branches,
    scan/while bodies, pjit/shard_map calls, custom_vjp closures)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in _nested_jaxprs(value):
                yield from _walk_jaxpr(sub)


def _nested_jaxprs(value) -> Iterable:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _nested_jaxprs(item)


def _axis_names(eqn) -> List[str]:
    names: List[str] = []
    for key in _AXIS_PARAM_KEYS:
        value = eqn.params.get(key)
        if value is None:
            continue
        if isinstance(value, (tuple, list)):
            names.extend(v for v in value if isinstance(v, str))
        elif isinstance(value, str):
            names.append(value)
    return names


def check_entry(entry: EntryPoint) -> Tuple[List[Finding], Dict[str, int]]:
    """Trace one entry point; returns (findings, primitive counts)."""
    import jax

    findings: List[Finding] = []
    counts: collections.Counter = collections.Counter()
    try:
        fn, args, kwargs = entry.build()
        closed = jax.make_jaxpr(
            lambda *a: fn(*a, **kwargs), axis_env=list(entry.axis_env)
        )(*args)
    except Exception as exc:  # the finding IS the failure
        findings.append(
            Finding(
                "TYA101",
                f"entry point `{entry.name}` failed to trace: "
                f"{type(exc).__name__}: {exc}",
                entry.name,
            )
        )
        return findings, {}

    allowed = {name for name, _ in entry.axis_env}
    expected = (
        set(entry.expected_axes) if entry.expected_axes is not None else None
    )
    for eqn in _walk_jaxpr(closed.jaxpr):
        prim = eqn.primitive.name
        counts[prim] += 1
        if prim in _COLLECTIVE_PRIMITIVES:
            for axis in _axis_names(eqn):
                if axis not in allowed:
                    findings.append(
                        Finding(
                            "TYA102",
                            f"`{entry.name}`: collective `{prim}` names "
                            f"axis {axis!r}, outside its declared axis "
                            f"environment {sorted(allowed)}",
                            entry.name,
                        )
                    )
                elif expected is not None and axis not in expected:
                    findings.append(
                        Finding(
                            "TYA102",
                            f"`{entry.name}`: collective `{prim}` names "
                            f"axis {axis!r}, outside the axes this entry "
                            f"is documented to use {sorted(expected)}",
                            entry.name,
                        )
                    )
        if entry.hot and prim in _HOST_CALLBACK_PRIMITIVES:
            findings.append(
                Finding(
                    "TYA103",
                    f"`{entry.name}`: host-callback/device-transfer "
                    f"primitive `{prim}` in a hot path — a host "
                    "round-trip per step",
                    entry.name,
                )
            )
    return findings, dict(counts)


def run(
    entries: Optional[Sequence[EntryPoint]] = None,
) -> Tuple[List[Finding], Dict[str, Dict[str, int]], List[str],
           List[Finding]]:
    """Check every entry; returns (findings, {entry: primitive counts},
    skipped-entry notices, suppressed findings). Suppressed findings
    matched an entry's `allow=` list: they are not failures, but the
    CLI surfaces them as notices so suppressions stay visible."""
    if entries is None:
        entries = default_entry_points()
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    all_counts: Dict[str, Dict[str, int]] = {}
    skipped: List[str] = []
    caps = capabilities()
    for entry in entries:
        missing = [r for r in entry.requires if r not in caps]
        if missing:
            skipped.append(
                f"{entry.name}: this process lacks {', '.join(missing)}"
            )
            continue
        entry_findings, counts = check_entry(entry)
        allowed = set(entry.allow)
        for finding in entry_findings:
            (suppressed if finding.code in allowed else findings).append(
                finding
            )
        if counts:
            all_counts[entry.name] = counts
    return findings, all_counts, skipped, suppressed


# --------------------------------------------------------------------------
# The repo's entry-point registry
# --------------------------------------------------------------------------

def _f32(*shape):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _ops_entries() -> List[EntryPoint]:
    def attention_xla():
        from tf_yarn_tpu.ops.attention import xla_attention

        return (
            lambda q, k, v: xla_attention(q, k, v, causal=True),
            (_f32(2, 8, 4, 16), _f32(2, 8, 2, 16), _f32(2, 8, 2, 16)),
            {},
        )

    def rmsnorm():
        from tf_yarn_tpu.ops.rmsnorm import rmsnorm

        # interpret=True: tracing must not require a TPU lowering path.
        return (
            lambda x, s: rmsnorm(x, s, interpret=True),
            (_f32(8, 128), _f32(128)),
            {},
        )

    def rmsnorm_grad():
        import jax

        from tf_yarn_tpu.ops.rmsnorm import rmsnorm

        def loss(x, s):
            return rmsnorm(x, s, interpret=True).sum()

        return jax.grad(loss, argnums=(0, 1)), (_f32(8, 128), _f32(128)), {}

    def layernorm():
        from tf_yarn_tpu.ops.layernorm import layernorm

        return (
            lambda x, s, b: layernorm(x, s, b, interpret=True),
            (_f32(8, 128), _f32(128), _f32(128)),
            {},
        )

    def quantize():
        from tf_yarn_tpu.ops.quantize import dequantize_int8, quantize_int8

        def roundtrip(x):
            values, scales = quantize_int8(x, interpret=True)
            return dequantize_int8(values, scales)

        return roundtrip, (_f32(8, 128),), {}

    return [
        EntryPoint("ops.attention.xla_attention", attention_xla),
        EntryPoint("ops.rmsnorm.rmsnorm", rmsnorm),
        EntryPoint("ops.rmsnorm.rmsnorm_grad", rmsnorm_grad),
        EntryPoint("ops.layernorm.layernorm", layernorm),
        EntryPoint("ops.quantize.int8_roundtrip", quantize),
    ]


def _collective_entries() -> List[EntryPoint]:
    """The parallel.collectives wrappers, each traced under the canonical
    mesh axes (parallel.mesh.MeshSpec) so a wrapper that hardcodes or
    mangles an axis name fails TYA102 here, not on a pod."""
    from tf_yarn_tpu.parallel import mesh as mesh_lib

    axis_env = tuple(
        (name, 2)
        for name in (
            mesh_lib.AXIS_DP, mesh_lib.AXIS_FSDP, mesh_lib.AXIS_TP,
            mesh_lib.AXIS_SP, mesh_lib.AXIS_EP, mesh_lib.AXIS_PP,
        )
    )

    def wrapper(fn_name: str, axis: str):
        def build():
            from tf_yarn_tpu.parallel import collectives

            fn = getattr(collectives, fn_name)
            return (lambda x: fn(x, axis)), (_f32(4, 8),), {}

        return build

    entries = []
    for fn_name in ("all_reduce_mean", "all_reduce_sum", "reduce_scatter",
                    "all_gather", "ring_shift"):
        entries.append(
            EntryPoint(
                f"parallel.collectives.{fn_name}",
                wrapper(fn_name, mesh_lib.AXIS_DP),
                axis_env=axis_env,
                expected_axes=(mesh_lib.AXIS_DP,),
            )
        )
    return entries


def _parallel_entries() -> List[EntryPoint]:
    from tf_yarn_tpu.parallel import mesh as mesh_lib

    sp_env = ((mesh_lib.AXIS_SP, 2),)

    def ring():
        from tf_yarn_tpu.parallel.ring_attention import ring_attention

        return (
            lambda q, k, v: ring_attention(q, k, v, causal=True),
            (_f32(2, 8, 4, 16), _f32(2, 8, 2, 16), _f32(2, 8, 2, 16)),
            {},
        )

    def ulysses():
        from tf_yarn_tpu.parallel.ulysses import ulysses_attention

        return (
            lambda q, k, v: ulysses_attention(q, k, v, causal=True),
            (_f32(2, 8, 4, 16), _f32(2, 8, 2, 16), _f32(2, 8, 2, 16)),
            {},
        )

    return [
        EntryPoint(
            "parallel.ring_attention.ring_attention", ring,
            axis_env=sp_env, expected_axes=(mesh_lib.AXIS_SP,),
        ),
        EntryPoint(
            "parallel.ulysses.ulysses_attention", ulysses,
            axis_env=sp_env, expected_axes=(mesh_lib.AXIS_SP,),
        ),
    ]


def _model_entries() -> List[EntryPoint]:
    def transformer_fwd_bwd():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models import common
        from tf_yarn_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
        )

        from tf_yarn_tpu.parallel import sharding as sharding_lib

        config = TransformerConfig.tiny()
        model = Transformer(config)
        tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params = sharding_lib.unbox_params(
            jax.eval_shape(lambda r, t: model.init(r, t), rng, tokens)
        )

        def loss_and_grad(params, tokens, rng):
            def loss(p):
                value, _aux = common.lm_loss(
                    model, p, {"tokens": tokens}, rng, train=False
                )
                return value

            return jax.value_and_grad(loss)(params)

        return loss_and_grad, (params, tokens, rng), {}

    return [
        EntryPoint("models.transformer.fwd_bwd", transformer_fwd_bwd),
    ]


def _decode_entries() -> List[EntryPoint]:
    """The compiled decode engine's two programs (models/decode_engine.py):
    the bucketed prefill and the on-device while_loop decode. Both are
    hot — the decode loop runs once per generated token, so a host
    callback or device transfer smuggled into either is exactly the
    per-token round-trip the engine exists to eliminate."""

    def _engine_avals():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.decode_engine import build_prefill_fn
        from tf_yarn_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
        )
        from tf_yarn_tpu.parallel import sharding as sharding_lib

        config = TransformerConfig.tiny()
        model = Transformer(config)
        prompt = jax.ShapeDtypeStruct((2, 8), jnp.int32)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params = sharding_lib.unbox_params(
            jax.eval_shape(lambda r, t: model.init(r, t), rng, prompt)
        )
        cache = jax.eval_shape(build_prefill_fn(model), params, prompt)[0]
        return model, params, prompt, cache

    def prefill():
        from tf_yarn_tpu.models.decode_engine import build_prefill_fn

        model, params, prompt, _cache = _engine_avals()
        return build_prefill_fn(model), (params, prompt), {}

    def decode_loop():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.decode_engine import build_decode_fn

        model, params, _prompt, cache = _engine_avals()
        fn = build_decode_fn(
            model, temperature=0.0, top_k=None, top_p=None,
            has_eos=True, has_rest=True,
        )
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        args = (
            params, cache,
            jax.ShapeDtypeStruct((2, 8), jnp.int32),   # rest buffer
            scalar,                                     # rest_len
            scalar,                                     # num_new
            jax.ShapeDtypeStruct((2,), jnp.uint32),     # rng
            scalar,                                     # eos_id
            jax.ShapeDtypeStruct((2, 16), jnp.int32),   # out buffer
        )
        return fn, args, {}

    def _paged_avals(block_size=8, slots=2, num_blocks=9):
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.decode_engine import (
            _decode_cache_aval,
            paged_pool_avals,
        )

        model, params, _prompt, _cache = _engine_avals()
        row = _decode_cache_aval(model, params)
        pool = paged_pool_avals(model, row, num_blocks, block_size)
        max_blocks = model.config.max_seq_len // block_size
        tables = jax.ShapeDtypeStruct((slots, max_blocks), jnp.int32)
        lengths = jax.ShapeDtypeStruct((slots,), jnp.int32)
        return model, params, pool, tables, lengths, slots

    def paged_step():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.decode_engine import (
            build_paged_step_fn,
            feed_avals,
        )

        model, params, pool, tables, lengths, slots = _paged_avals()
        fn = build_paged_step_fn(
            model, block_size=8, temperature=0.0, top_k=None, top_p=None
        )
        # the step before's token and rng row, the host's, the mask between
        args = (params, pool, tables, lengths) + feed_avals(slots) + (
            jax.ShapeDtypeStruct((slots,), jnp.bool_),     # sample mask
        )
        return fn, args, {}

    def _window_avals(slots, width):
        """What the scheduler uploads for one windowed tick, after the
        tables and lengths."""
        import jax
        import jax.numpy as jnp

        return (
            jax.ShapeDtypeStruct((slots, width), jnp.int32),  # window
            jax.ShapeDtypeStruct((slots,), jnp.int32),        # n_known
            jax.ShapeDtypeStruct((slots,), jnp.int32),        # eos ids
            jax.ShapeDtypeStruct((slots, 2), jnp.uint32),     # rngs
            jax.ShapeDtypeStruct((slots,), jnp.bool_),        # active
        )

    def chunk_apply():
        """The windowed tick at the chunk-apply width (prefill_chunk=8,
        teacher-forced prompt replay) over the gathered cache view. Same
        program builder as the speculative tick — width is a compile-key
        dimension, nothing else changes."""
        from tf_yarn_tpu.models.decode_engine import build_paged_spec_step_fn

        model, params, pool, tables, lengths, slots = _paged_avals()
        width = 8
        fn = build_paged_spec_step_fn(
            model, 8, width, temperature=0.0, top_k=None, top_p=None
        )
        args = (params, pool, tables, lengths) + _window_avals(slots, width)
        return fn, args, {}

    def paged_spec_step():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.decode_engine import (
            _decode_cache_aval,
            build_paged_spec_step_fn,
            paged_pool_avals,
        )
        from tf_yarn_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
        )
        from tf_yarn_tpu.parallel import sharding as sharding_lib

        # The FUSED verify forward: decode attention reads the int8
        # block pool directly through the paged pallas kernel — the
        # exact program the satellite guardrail pins host-callback-free.
        config = TransformerConfig.tiny(kv_cache_dtype="int8")
        model = Transformer(config)
        prompt = jax.ShapeDtypeStruct((2, 8), jnp.int32)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        params = sharding_lib.unbox_params(
            jax.eval_shape(lambda r, t: model.init(r, t), rng, prompt)
        )
        block_size, slots, width = 8, 2, 3
        row = _decode_cache_aval(model, params)
        pool = paged_pool_avals(model, row, 9, block_size)
        max_blocks = model.config.max_seq_len // block_size
        fn = build_paged_spec_step_fn(
            model, block_size, width, temperature=0.0, top_k=None,
            top_p=None, decode_attention="fused",
        )
        args = (
            params, pool,
            jax.ShapeDtypeStruct((slots, max_blocks), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),        # lengths
        ) + _window_avals(slots, width)
        return fn, args, {}

    def paged_prefill():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.decode_engine import (
            build_pack_prefill_fn,
            build_prefill_fn,
        )

        model, params, pool, _tables, _lengths, _slots = _paged_avals()
        prefill_fn = build_prefill_fn(model)
        pack_fn = build_pack_prefill_fn(model, block_size=8, prefill_len=8)

        def prefill_and_pack(params, prompt, pool, block_ids):
            row_cache, _logits = prefill_fn(params, prompt)
            return pack_fn(pool, block_ids, row_cache)

        args = (
            params,
            jax.ShapeDtypeStruct((1, 8), jnp.int32),
            pool,
            jax.ShapeDtypeStruct((1,), jnp.int32),  # block ids (traced)
        )
        return prefill_and_pack, args, {}

    def extract_blocks():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.decode_engine import (
            _decode_cache_aval,
            build_extract_blocks_fn,
        )

        model, params, pool, _tables, _lengths, _slots = _paged_avals()
        row = _decode_cache_aval(model, params)
        max_blocks = model.config.max_seq_len // 8
        fn = build_extract_blocks_fn(model, row)
        args = (
            pool,
            jax.ShapeDtypeStruct((max_blocks,), jnp.int32),  # block ids
        )
        return fn, args, {}

    def inject_blocks():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.decode_engine import (
            _decode_cache_aval,
            build_extract_blocks_fn,
            build_inject_blocks_fn,
        )

        model, params, pool, _tables, _lengths, _slots = _paged_avals()
        row = _decode_cache_aval(model, params)
        max_blocks = model.config.max_seq_len // 8
        ids = jax.ShapeDtypeStruct((max_blocks,), jnp.int32)
        # The payload pytree is whatever extract produces for this pool
        # layout — swap-in replays swap-out's shapes exactly.
        payload = jax.eval_shape(
            build_extract_blocks_fn(model, row), pool, ids
        )
        fn = build_inject_blocks_fn(model, row)
        return fn, (pool, ids, payload), {}

    def _tp_sharded(build, uploads, small_outs, donate):
        """A TENSOR-PARALLEL serving tick, lowered exactly as the engine
        lowers it: params placed by the logical-axis rules, the block
        pool sharded by kv-heads over `tp`, everything the scheduler
        uploads each tick replicated, pool + rngs donated. `build(model,
        block_size)` makes the step, `uploads(slots)` its avals after
        the tables and lengths; the pool comes back first, then
        `small_outs` replicated results. The TP collectives themselves
        are inserted by the XLA partitioner at compile (they are not
        jaxpr primitives), so the entries verify what the trace CAN see
        — any named-axis collective stays inside the declared tp axis
        env, and the program is host-callback-free; the compiled-HLO
        all-reduce presence is pinned by tests/test_tp_serving.py."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from tf_yarn_tpu.models.decode_engine import (
            _decode_cache_aval,
            cache_layout,
            paged_pool_avals,
            pool_partition_spec,
        )
        from tf_yarn_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
        )
        from tf_yarn_tpu.parallel import sharding as sharding_lib
        from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh

        tp = 2
        config = TransformerConfig.tiny()
        model = Transformer(config)
        mesh = build_mesh(MeshSpec(tp=tp), jax.devices()[:tp])
        rep = NamedSharding(mesh, PartitionSpec())
        abstract = jax.eval_shape(
            lambda r, t: model.init(r, t),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((1, 8), jnp.int32),
        )
        param_sh = sharding_lib.tree_shardings(mesh, abstract)
        params = sharding_lib.unbox_params(abstract)
        slots, block_size = 2, 8
        row = _decode_cache_aval(model, params)
        pool = paged_pool_avals(model, row, 9, block_size)
        pool_sh = jax.tree_util.tree_map(
            lambda aval, r, lay: (
                None if aval is None else NamedSharding(
                    mesh, pool_partition_spec(tuple(r.shape), lay, tp),
                )
            ),
            pool, row, cache_layout(model, row),
            is_leaf=lambda x: x is None,
        )
        max_blocks = config.max_seq_len // block_size
        args = (
            params, pool,
            jax.ShapeDtypeStruct((slots, max_blocks), jnp.int32),  # tables
            jax.ShapeDtypeStruct((slots,), jnp.int32),             # lengths
        ) + uploads(slots)
        fn = jax.jit(
            build(model, block_size),
            in_shardings=(param_sh, pool_sh) + (rep,) * (len(args) - 2),
            out_shardings=(pool_sh,) + (rep,) * small_outs,
            # Mirrored from the engine so the HLO engine's TYA202
            # verifies the aliasing on the lowering serving actually runs.
            donate_argnums=donate,
        )
        return fn, args, {}

    def sharded_paged_step():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.decode_engine import (
            build_paged_step_fn,
            feed_avals,
        )

        return _tp_sharded(
            lambda model, block_size: build_paged_step_fn(
                model, block_size=block_size, temperature=0.0,
                top_k=None, top_p=None,
            ),
            lambda slots: feed_avals(slots) + (
                jax.ShapeDtypeStruct((slots,), jnp.bool_),     # sample mask
            ),
            small_outs=2, donate=(1, 5),  # DecodeEngine.paged_step
        )

    def sharded_chunk_apply():
        """The TP chunk-apply: the windowed program at the chunked width
        (8). Chunked prefill admits prompts through THIS program tick by
        tick, so it gets the same host-callback and axis-vocabulary pins
        as the sharded decode tick."""
        from tf_yarn_tpu.models.decode_engine import build_paged_spec_step_fn

        width = 8
        return _tp_sharded(
            lambda model, block_size: build_paged_spec_step_fn(
                model, block_size, width, temperature=0.0, top_k=None,
                top_p=None,
            ),
            lambda slots: _window_avals(slots, width),
            small_outs=3, donate=(1, 7),  # DecodeEngine.paged_spec_step
        )

    from tf_yarn_tpu.parallel.mesh import AXIS_TP

    return [
        EntryPoint("models.decode_engine.prefill", prefill),
        EntryPoint("models.decode_engine.decode_loop", decode_loop),
        # The serving tick's device program (continuous batching):
        # gather-by-block-table, model step, and scatter-append all in
        # one program. It runs once per generated token across the whole
        # slot grid, so a host callback smuggled in here is a per-token
        # round-trip for every in-flight request at once, and the table
        # indirection must stay on device too.
        EntryPoint("models.decode_engine.paged_step", paged_step),
        # Paged admission's device work: bucketed prefill + block splice.
        EntryPoint("models.decode_engine.paged_prefill", paged_prefill),
        # The KV-oversubscription swap programs: extract gathers a
        # suspended slot's pool rows for the bulk device_get (read-only
        # — the one PLANNED host transfer lives in the scheduler, not
        # the program), inject scatters them back on resume (pool
        # donated). Both take traced block ids at the fixed table
        # width, so suspend/resume churn adds ZERO compile keys — and
        # neither may smuggle in a host callback, or every swap becomes
        # a per-leaf sync instead of one bulk copy.
        EntryPoint("models.decode_engine.extract_blocks", extract_blocks),
        EntryPoint("models.decode_engine.inject_blocks", inject_blocks),
        # The SPECULATIVE tick: one windowed verify forward advances
        # every slot up to spec_k + 1 tokens. The accept/reject masking
        # must be entirely traced — a host callback here would sync the
        # grid once per window position, not once per tick. This is the
        # fused verify: decode attention streams the int8 block pool
        # through the pallas kernel (scalar-prefetched block tables) and
        # scatters the window's quantized K/V rows.
        EntryPoint("models.decode_engine.paged_spec_step", paged_spec_step),
        # The CHUNK-APPLY: the windowed program over the gathered cache
        # view at the chunked width (8) — admission replays prompt chunks
        # through it teacher-forced (n_known == W, zero emissions), interleaved
        # with decode slots in the one tick program. A host callback
        # here would stall every decode slot once per admitted chunk.
        EntryPoint("models.decode_engine.chunk_apply", chunk_apply),
        # The TENSOR-PARALLEL serving tick (tp=2): params placed by
        # LOGICAL_RULES, the block pool sharded by heads, explicit in/out
        # shardings — traced under the declared tp axis env so any
        # named-axis collective that appears is vocabulary-checked, and
        # host-callback-freedom is asserted like every tick program.
        # Needs >= 2 devices (skipped with a notice on 1-device rigs).
        EntryPoint(
            "models.decode_engine.sharded_paged_step", sharded_paged_step,
            axis_env=((AXIS_TP, 2),), expected_axes=(AXIS_TP,),
            requires=("multi_device",),
        ),
        # The sharded chunk-apply twin, pinned like sharded_paged_step so
        # the chunked-admission program keeps the same collective census
        # and donation aliasing under tp=2 as the decode tick it
        # interleaves with.
        EntryPoint(
            "models.decode_engine.sharded_chunk_apply", sharded_chunk_apply,
            axis_env=((AXIS_TP, 2),), expected_axes=(AXIS_TP,),
            requires=("multi_device",),
        ),
    ]


def _rank_entries() -> List[EntryPoint]:
    """The ranking tick's device program (models/rank_engine.py): one
    bucketed DLRM forward per micro-batch. Hot — the scheduler
    dispatches it once per tick under a request deadline, so a host
    callback here turns the single planned host sync (the score
    readback) into several."""

    def _dlrm_avals():
        import jax
        import jax.numpy as jnp

        from tf_yarn_tpu.models.dlrm import DLRM, DLRMConfig

        config = DLRMConfig.tiny()
        model = DLRM(config)
        cat = jax.ShapeDtypeStruct(
            (8, len(config.table_sizes)), jnp.int32
        )
        dense = jax.ShapeDtypeStruct((8, config.n_dense), jnp.float32)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        abstract = jax.eval_shape(
            lambda r, c, d: model.init(r, c, d), rng, cat, dense
        )
        return model, abstract, cat, dense

    def forward():
        from tf_yarn_tpu.models.rank_engine import build_rank_fn
        from tf_yarn_tpu.parallel import sharding as sharding_lib

        model, abstract, cat, dense = _dlrm_avals()
        params = sharding_lib.unbox_params(abstract)
        return (
            build_rank_fn(model, has_dense=True),
            (params, cat, dense),
            {},
        )

    def sharded_forward():
        """The EMBEDDING-SHARDED forward, lowered exactly as RankEngine
        lowers it under a mesh: params placed by RANKING_RULES (tables
        1/tp per device), replicated features in, replicated scores
        out. The embedding all-gather is inserted by the XLA
        partitioner at compile — the HLO engine's TYA201 manifest pins
        its census; this entry verifies the traced program is
        host-callback-free and any named-axis collective stays in the
        tp vocabulary."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from tf_yarn_tpu.models.rank_engine import build_rank_fn
        from tf_yarn_tpu.parallel import sharding as sharding_lib
        from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh

        tp = 2
        model, abstract, cat, dense = _dlrm_avals()
        mesh = build_mesh(MeshSpec(tp=tp), jax.devices()[:tp])
        rep = NamedSharding(mesh, PartitionSpec())
        param_sh = sharding_lib.tree_shardings(
            mesh, abstract, rules=sharding_lib.RANKING_RULES
        )
        params = sharding_lib.unbox_params(abstract)
        fn = jax.jit(
            build_rank_fn(model, has_dense=True),
            in_shardings=(param_sh, rep, rep),
            out_shardings=rep,
        )
        return fn, (params, cat, dense), {}

    from tf_yarn_tpu.parallel.mesh import AXIS_TP

    return [
        EntryPoint("models.rank_engine.forward", forward),
        EntryPoint(
            "models.rank_engine.sharded_forward", sharded_forward,
            axis_env=((AXIS_TP, 2),), expected_axes=(AXIS_TP,),
            requires=("multi_device",),
        ),
    ]


def default_entry_points() -> List[EntryPoint]:
    return (
        _ops_entries()
        + _collective_entries()
        + _parallel_entries()
        + _model_entries()
        + _decode_entries()
        + _rank_entries()
    )
