"""Compile the flagship's whole programs for a described TPU v5e, without
the chip: the step before any chip call (on-chip-measurement guide §2.3).

    JAX_PLATFORMS=cpu python benchmarks/compile_for_chip.py            # all
    JAX_PLATFORMS=cpu python benchmarks/compile_for_chip.py train4 tp4

libtpu is installed on the CPU rig, so the chip's own compiler runs here and
raises what the chip would raise: a vector layout Mosaic refuses, a kernel
that outgrows VMEM, a program that does not fit 16 GB, a custom call nothing
can partition. `tests/test_tpu_compile.py` keeps the single kernels in
tier-1 (~10 s); these whole steps take a few minutes and stay out of it.
Nothing runs, so nothing here is a result or a time of the device. Programs:

    train   the train step of chip_smoke.py (flash + fused norms, unrolled),
            batch 8 x seq 1024, one chip
    train4  the same step sharded fsdp=2 x tp=2 over the 2x2 host
    bf16 | int8 | fused
            prefill (buckets 32/64/128), pack and the paged decode step at
            max_slots=8, block 16: the one-token step over a bf16 and an
            int8 pool, the speculative window's fused int8 step
    tp4     prefill and paged step of a tp=4 replica (bf16, plain read)
    latent  the benchmark's dots3_note share at its own sizes
            (cellbench/configs/dots3_note_serve_1chip.json): prefill at the
            2048 and 4096 buckets (told the prompt's length, as a model
            with rings is), pack, and the 64-slot paged state step
            (latent and index rows off the pool, rings held once a slot)
    longcat the benchmark's LongCat-Flash share at its own sizes
            (cellbench/configs/longcat_flash_serve_1chip.json): prefill at
            the 512, 1024 and 2048 buckets (2048: what an admission of a
            1026-1536 token prompt runs), pack, and the 64-slot paged state
            step
            (every leaf paged, the latent rows read by the paged kernel)
    laguna  the benchmark's Laguna-XS.2 stage at its own sizes
            (cellbench/configs/laguna_xs2_serve_1chip.json): prefill at the
            512, 1024, 2048 and 4096 buckets (from 1024 on the tokens sorted
            by expert over all 256 of a layer; 4096: what an admission of a
            2050-4096 token prompt runs, told the prompt's length), pack,
            and the 64-slot paged state step
            (the full layers' keys and values read by the paged kernel at 6
            query heads a KV head, the sliding layers' rings held once a
            slot)
    dsv32   the benchmark's DeepSeek-V3.2 share at its own sizes
            (cellbench/configs/deepseek_v32_serve_1chip.json): prefill at
            the 1024 and 2048 buckets (no query of either has more than
            `index_topk` keys: the prefill never selects), pack, and the
            32-slot paged state step over tables of 768 blocks (12,288
            tokens a slot: five layers score, sort and gather off the pool)

Each line: how many Mosaic kernels (`tpu_custom_call`) and collectives the
compiler emitted, and the bytes one device needs (temporaries + arguments).
The run fails if anything is refused, needs more than the chip has, or lacks
a kernel where one is expected.
"""

from __future__ import annotations

import collections
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from tf_yarn_tpu import training  # noqa: E402
from tf_yarn_tpu.models import common, decode_engine as de  # noqa: E402
from tf_yarn_tpu.models.transformer import (  # noqa: E402
    Transformer,
    TransformerConfig,
)
from tf_yarn_tpu.ops import _rowwise  # noqa: E402
from tf_yarn_tpu.parallel import mesh as mesh_lib  # noqa: E402
from tf_yarn_tpu.parallel import sharding as sharding_lib  # noqa: E402

FLAGSHIP = dict(
    vocab_size=32000, d_model=1024, n_layers=8, n_heads=16, n_kv_heads=8,
    d_ff=4096, max_seq_len=2048, remat=False, scan_layers=False,
)
BATCH, SEQ, SLOTS, BLOCK = 8, 1024, 8, 16
HBM_BYTES = 16e9  # one v5e chip
GIB = 2 ** 30
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def _is_none(x) -> bool:
    return x is None


def _abstract(avals, shardings):
    """ShapeDtypeStructs placed by the matching tree of shardings: there
    is no device to hold an array, so every argument is a shape."""
    return jax.tree_util.tree_map(
        lambda a, s: None if a is None
        else jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        avals, shardings, is_leaf=_is_none,
    )


def report(name, compiled, began, want_kernels) -> None:
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    kernels = text.count("tpu_custom_call")
    need = memory.temp_size_in_bytes + memory.argument_size_in_bytes
    print(
        f"{name}: compiled ({time.monotonic() - began:.0f}s on this host), "
        f"{kernels} Mosaic kernels, collectives "
        f"{dict(collections.Counter(_COLLECTIVE.findall(text)))}, "
        f"temp {memory.temp_size_in_bytes / GIB:.2f} GiB + args "
        f"{memory.argument_size_in_bytes / GIB:.2f} GiB per device",
        flush=True,
    )
    if want_kernels and not kernels:
        raise AssertionError(f"{name}: no Mosaic kernel in the program")
    if need > HBM_BYTES:
        raise AssertionError(f"{name}: needs {need / GIB:.1f} GiB per device")


def compile_train(devices, axes) -> None:
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(**axes), devices)
    model = Transformer(TransformerConfig(
        **FLAGSHIP, attention_impl="flash", fused_norms=True))
    optimizer = common.adamw_with_decay_mask(3e-4)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)

    def init(unbox):
        def init_state(rng, tokens):
            variables = model.init(rng, tokens)
            if unbox:
                variables = sharding_lib.unbox_params(variables)
            return training.TrainState(
                np.int32(0), variables, optimizer.init(variables))
        return init_state

    # As training.train_and_evaluate places its state: shardings from the
    # boxed (annotated) tree, applied to the unboxed one.
    shardings = training._named_shardings(
        mesh, jax.eval_shape(init(False), rng, tokens))
    state = _abstract(jax.eval_shape(init(True), rng, tokens), shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(
        tokens.shape, tokens.dtype, sharding=mesh_lib.batch_sharding(mesh, 1))}
    key = jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=mesh_lib.replicated_sharding(mesh))
    step = training.build_train_step(model, common.lm_loss, optimizer)
    began = time.monotonic()
    with mesh, mesh_lib.use_mesh(mesh):
        compiled = jax.jit(
            step, donate_argnums=(0,), out_shardings=(shardings, None)
        ).lower(state, batch, key).compile()
    report(f"train step {axes or 'one chip'}", compiled, began, True)


def compile_serving(name, devices, kv_cache_dtype, attention, tp) -> None:
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(tp=tp), devices)
    replicated = NamedSharding(mesh, PartitionSpec())
    config = TransformerConfig(**FLAGSHIP, kv_cache_dtype=kv_cache_dtype)
    model = Transformer(config)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    params = _abstract(
        jax.eval_shape(lambda r, t: nn.meta.unbox(model.init(r, t)),
                       rng, tokens),
        sharding_lib.tree_shardings(
            mesh, jax.eval_shape(model.init, rng, tokens)),
    )

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=replicated)

    max_blocks = config.max_seq_len // BLOCK
    prefill = de.build_prefill_fn(model)
    row = jax.eval_shape(prefill, params, arg(jnp.int32, 1, 1))[0]
    pool_avals = de.paged_pool_avals(
        model, row, SLOTS * max_blocks + 1, BLOCK)
    pool_shardings = jax.tree_util.tree_map(
        lambda aval, row_leaf, lay: None if aval is None else NamedSharding(
            mesh, de.pool_partition_spec(tuple(row_leaf.shape), lay, tp)),
        pool_avals, row, de.cache_layout(model, row), is_leaf=_is_none,
    )
    pool = _abstract(pool_avals, pool_shardings)
    int8 = kv_cache_dtype == "int8"

    for bucket in (32, 64, 128):
        prompt = arg(jnp.int32, 1, bucket)
        cache_avals = jax.eval_shape(prefill, params, prompt)[0]
        cache_shardings = jax.tree_util.tree_map(
            lambda a, lay: NamedSharding(mesh, de.kv_partition_spec(
                tuple(a.shape), lay, tp)),
            cache_avals, de.cache_layout(model, cache_avals))
        began = time.monotonic()
        compiled = jax.jit(
            prefill, out_shardings=(cache_shardings, replicated)
        ).lower(params, prompt).compile()
        report(f"{name} prefill[{bucket}]", compiled, began, int8)
        began = time.monotonic()
        compiled = jax.jit(
            de.build_pack_prefill_fn(model, BLOCK, bucket),
            donate_argnums=(0,), out_shardings=pool_shardings,
        ).lower(pool, arg(jnp.int32, -(-bucket // BLOCK)),
                _abstract(cache_avals, cache_shardings)).compile()
        report(f"{name} pack[{bucket}]", compiled, began, False)

    tables, lengths = arg(jnp.int32, SLOTS, max_blocks), arg(jnp.int32, SLOTS)
    rngs, active = arg(jnp.uint32, SLOTS, 2), arg(jnp.bool_, SLOTS)
    began = time.monotonic()
    if attention == "gather":
        compiled = jax.jit(
            # As DecodeEngine.paged_attention_kernel decides: the plain
            # read where the pool is sharded, else by backend and shape
            # (heads of 64: the bf16 kernel stands aside, the int8 one
            # serves).
            de.build_paged_step_fn(model, BLOCK, 0.0, None, None,
                                   paged_kernel=None if tp == 1 else False),
            donate_argnums=(1, 5),
            out_shardings=(pool_shardings, replicated, replicated),
        ).lower(params, pool, tables, lengths,
                *(arg(a.dtype, *a.shape) for a in de.feed_avals(SLOTS)),
                active).compile()
    else:
        compiled = jax.jit(
            de.build_paged_spec_step_fn(
                model, BLOCK, 1, 0.0, None, None, decode_attention="fused"),
            donate_argnums=(1, 7),
            out_shardings=(pool_shardings,) + (replicated,) * 3,
        ).lower(params, pool, tables, lengths, arg(jnp.int32, SLOTS, 1),
                lengths, lengths, rngs, active).compile()
    report(f"{name} paged step", compiled, began, int8)


def compile_latent(devices, name="latent",
                   config="dots3_note_serve_1chip", buckets=(2048, 4096),
                   want_kernel=False) -> None:
    """A latent-attention share as the benchmark serves it: the expanded
    prefill's peak (no [heads, bucket, context] float32 array) and the
    one-token step's (no [slots, context, ...] view of a leaf) are what the
    compiler has to fit beside the weights and the cache (dots3: 5.2 GB and
    1.3 GB; LongCat-Flash: 10.3 GB and 2.7 GB). `want_kernel`: the step
    reads the pool through the paged kernel."""
    import json

    from cellbench import agent

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench", "configs",
                           config + ".json")) as fh:
        sizes = json.load(fh)
    model = agent.build_model(sizes)
    slots = sizes["serving"]["max_slots"]
    one = jax.sharding.SingleDeviceSharding(devices[0])

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: None if a is None else jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one), tree, is_leaf=_is_none)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = place(jax.eval_shape(
        lambda r, t: nn.meta.unbox(model.init(r, t)),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, 8), jnp.int32)))
    prefill = de.build_prefill_fn(model)
    row = jax.eval_shape(prefill, params, arg(jnp.int32, 1, 1))[0]
    layout = de.cache_layout(model, row)
    max_blocks = model.config.max_seq_len // BLOCK
    pool = place(de.paged_pool_avals(model, row, slots * max_blocks + 1, BLOCK))
    state = place(jax.tree_util.tree_map(
        lambda a, lay: jax.ShapeDtypeStruct((slots,) + a.shape, a.dtype)
        if lay.kind in de.HELD_A_SLOT else None, row, layout))
    # A model with rings is told where the prompt ends: a traced scalar.
    length = (arg(jnp.int32),) \
        if model.serving_contract().takes_prompt_len else ()
    for bucket in buckets:
        began = time.monotonic()
        prompt = arg(jnp.int32, 1, bucket)
        compiled = jax.jit(prefill).lower(params, prompt, *length).compile()
        report(f"{name} prefill[{bucket}]", compiled, began, False)
    began = time.monotonic()
    compiled = jax.jit(
        de.build_pack_prefill_fn(model, BLOCK, buckets[0]),
        donate_argnums=(0,),
    ).lower(pool, arg(jnp.int32, buckets[0] // BLOCK),
            place(jax.eval_shape(prefill, params,
                                 arg(jnp.int32, 1, buckets[0]))[0])).compile()
    report(f"{name} pack[{buckets[0]}]", compiled, began, False)
    began = time.monotonic()
    compiled = jax.jit(
        de.build_paged_state_step_fn(model, BLOCK, 0.0, None, None),
        donate_argnums=(1, 2, 6),
    ).lower(params, pool, state, arg(jnp.int32, slots, max_blocks),
            arg(jnp.int32, slots),
            *(arg(a.dtype, *a.shape) for a in de.feed_avals(slots)),
            arg(jnp.bool_, slots)).compile()
    report(f"{name} paged state step", compiled, began, want_kernel)
    context = model.config.max_seq_len
    views = re.findall(rf"\[{slots},{context},\d+\]", compiled.as_text())
    if views:
        raise AssertionError(
            f"the step builds a [slots, context, ...] array: {set(views)}")


def main(argv) -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    # The code under test asks jax.default_backend(), sees this host's CPU
    # and would pick interpret mode; steer it onto the chip's branch here,
    # in the script, not through an option of the program.
    _rowwise.default_interpret = lambda: False
    topology = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    one, four = list(topology.devices[:1]), list(topology.devices)
    programs = {
        "train": lambda: compile_train(one, {}),
        "train4": lambda: compile_train(four, {"fsdp": 2, "tp": 2}),
        "bf16": lambda: compile_serving("bf16", one, "bf16",
                                        "gather", 1),
        "int8": lambda: compile_serving("int8", one, "int8",
                                        "gather", 1),
        "fused": lambda: compile_serving("int8 fused", one, "int8",
                                         "fused", 1),
        "tp4": lambda: compile_serving("tp=4 bf16", four, "bf16",
                                       "gather", 4),
        "latent": lambda: compile_latent(one),
        "longcat": lambda: compile_latent(
            one, "longcat", "longcat_flash_serve_1chip", (512, 1024, 2048),
            True),
        "laguna": lambda: compile_latent(
            one, "laguna", "laguna_xs2_serve_1chip",
            (512, 1024, 2048, 4096), True),
        "dsv32": lambda: compile_latent(
            one, "dsv32", "deepseek_v32_serve_1chip", (1024, 2048)),
    }
    for name in argv or list(programs):
        programs[name]()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
