"""Every pallas kernel under tf_yarn_tpu/ops/, compiled for a described
(not attached) TPU v5e at the flagship shapes — d_model 1024, 16 query /
8 KV heads of 64, batch 8 x seq 1024, a 2048-token cache in 16-token
blocks — and the one-token paged step's bf16 kernel at the two benchmark
cells' own shapes (32 / 8 heads of 128, 16 and 32 slots of 4096 tokens).
Nothing runs: the chip's own compiler (libtpu is installed on
the CPU rig) either accepts the kernel or raises what the chip would
raise. Interpret mode cannot see a refused vector layout, a mis-tiled
block or a kernel that outgrows VMEM; this can, at no chip time.
Results and times still come only from a run on the chip
(`python chip_smoke.py`). One plain-XLA loop is held here too, by its
compiled text: `moe.touched_experts` at the two benchmark shapes that take
it reads each expert's matrices inside its products.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

BATCH, SEQ, D_MODEL = 8, 1024, 1024
N_HEADS, N_KV, HEAD_DIM = 16, 8, 64
CACHE_LEN, BLOCK, SLOTS = 2048, 16, 8
MAX_BLOCKS = CACHE_LEN // BLOCK
NUM_BLOCKS = SLOTS * MAX_BLOCKS + 1  # dense-equivalent pool + trash block


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e 2x2 host, as a sharding."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip; keep the cache out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _flash(grad):
    from tf_yarn_tpu.ops.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    q = ((BATCH, SEQ, N_HEADS, HEAD_DIM), jnp.bfloat16)
    kv = ((BATCH, SEQ, N_KV, HEAD_DIM), jnp.bfloat16)
    if not grad:
        return fwd, [q, kv, kv]
    return (
        jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2)),
        [q, kv, kv],
    )


def _rmsnorm():
    from tf_yarn_tpu.ops.rmsnorm import rmsnorm

    def loss(x, scale):
        return rmsnorm(x, scale, interpret=False, kernel_bwd=True).astype(
            jnp.float32).sum()

    return jax.value_and_grad(loss, argnums=(0, 1)), [
        ((BATCH, SEQ, D_MODEL), jnp.bfloat16), ((D_MODEL,), jnp.float32),
    ]


def _layernorm():
    from tf_yarn_tpu.ops.layernorm import layernorm

    def loss(x, scale, bias):
        return layernorm(
            x, scale, bias, interpret=False, kernel_bwd=True
        ).astype(jnp.float32).sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2)), [
        ((BATCH, SEQ, D_MODEL), jnp.bfloat16), ((D_MODEL,), jnp.float32),
        ((D_MODEL,), jnp.float32),
    ]


def _groupnorm():
    from tf_yarn_tpu.ops.groupnorm import groupnorm

    # The ResNet-50 stage-1 slab (models/resnet.py), 32 groups.
    def loss(x, scale, bias):
        return groupnorm(
            x, scale, bias, 32, interpret=False, kernel_bwd=True
        ).astype(jnp.float32).sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2)), [
        ((8, 56, 56, 256), jnp.bfloat16), ((256,), jnp.float32),
        ((256,), jnp.float32),
    ]


def _quantize():
    from tf_yarn_tpu.ops.quantize import quantize_int8

    # One prefill's K rows, as models/transformer.py quantizes them.
    return functools.partial(quantize_int8, interpret=False), [
        ((1, SEQ, N_KV, HEAD_DIM), jnp.float32),
    ]


def _dense_decode():
    from tf_yarn_tpu.ops.decode_attention import int8_decode_attention

    return functools.partial(int8_decode_attention, interpret=False), [
        ((1, N_HEADS, HEAD_DIM), jnp.bfloat16),
        ((1, CACHE_LEN, N_KV, HEAD_DIM), jnp.int8),
        ((1, CACHE_LEN, N_KV, 1), jnp.float32),
        ((1, CACHE_LEN, N_KV, HEAD_DIM), jnp.int8),
        ((1, CACHE_LEN, N_KV, 1), jnp.float32),
        ((), jnp.int32),
    ]


def _paged_decode(scale_rows, width=None):
    from tf_yarn_tpu.ops import decode_attention

    pool = ((NUM_BLOCKS, BLOCK, N_KV, HEAD_DIM), jnp.int8)
    scale = ((NUM_BLOCKS, scale_rows, N_KV, 1), jnp.float32)
    if width is None:
        fn = decode_attention.paged_int8_decode_attention
        query = ((SLOTS, N_HEADS, HEAD_DIM), jnp.bfloat16)
    else:
        fn = decode_attention.paged_int8_window_attention
        query = ((SLOTS, width, N_HEADS, HEAD_DIM), jnp.bfloat16)
    return functools.partial(fn, interpret=False), [
        query, pool, scale, pool, scale,
        ((SLOTS, MAX_BLOCKS), jnp.int32), ((SLOTS,), jnp.int32),
    ]


def _paged_bf16_decode(slots):
    """The one-token paged step's kernel at a benchmark cell's shapes:
    Mistral's 16 slots and granite's 32, each over its own pool leaf of
    `slots` x 256 blocks of 16 tokens x 8 KV heads of 128 in bf16 (+ the
    trash block), tables [slots, 256]."""
    from tf_yarn_tpu.ops.decode_attention import paged_decode_attention

    heads, kv, dim, table = 32, 8, 128, 4096 // BLOCK
    pool = ((slots * table + 1, BLOCK, kv, dim), jnp.bfloat16)
    return functools.partial(
        paged_decode_attention, kernel=True, interpret=False), [
        ((slots, heads, dim), jnp.bfloat16), pool, pool,
        ((slots, table), jnp.int32), ((slots,), jnp.int32),
    ]


KERNELS = {
    "flash_fwd": lambda: _flash(grad=False),
    "flash_fwd_bwd": lambda: _flash(grad=True),
    "rmsnorm_fwd_dx": _rmsnorm,
    "layernorm_fwd_dx": _layernorm,
    "groupnorm_fwd_dx": _groupnorm,
    "quantize_int8": _quantize,
    "int8_decode_attention": _dense_decode,
    "paged_decode_row_scales": lambda: _paged_decode(BLOCK),
    "paged_decode_block_scales": lambda: _paged_decode(1),
    "paged_window_w4": lambda: _paged_decode(BLOCK, width=4),
    "paged_bf16_decode_mistral_16_slots": lambda: _paged_bf16_decode(16),
    "paged_bf16_decode_granite_32_slots": lambda: _paged_bf16_decode(32),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, chip):
    fn, avals = KERNELS[name]()
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in avals
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{name}: compiled without a Mosaic kernel — the pallas call "
        "was lowered away or ran in interpret mode"
    )


@pytest.mark.parametrize("tokens,d_model,width", [
    (32, 7168, 2048),   # DeepSeek-V3.2's step: 32 slots over 88 MB experts
    (64, 6144, 2048),   # LongCat's: 64 slots over 75 MB experts
])
def test_the_loop_over_touched_experts_reads_its_matrices_in_the_products(
        tokens, d_model, width, chip):
    """`moe.touched_experts` at the two shapes that take it: the loop is a
    `while`, and an iteration's slice of an expert's matrices is an operand
    of the product's fusion. No instruction outside a fusion makes an array
    of one expert's `w_in` or `w_out` (a `copy` or a `dynamic-slice` there
    would read and write 88 MB an iteration before the product reads it
    again: what the loop exists to save)."""
    import re

    from tf_yarn_tpu.models import moe

    held = 16
    compiled = jax.jit(functools.partial(
        moe.touched_experts, dtype=jnp.bfloat16)).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in [
            ((tokens, d_model), jnp.bfloat16),
            ((held, d_model, 2 * width), jnp.bfloat16),
            ((held, width, d_model), jnp.bfloat16),
            ((tokens, held), jnp.float32), ((held,), jnp.bool_)])).compile()
    text = compiled.as_text()
    assert re.search(r"= \([^\n]*\) while\(", text), "no loop was compiled"
    one_expert = re.compile(
        rf"= bf16\[(1,)?({d_model},{2 * width}|{width},{d_model})\]")
    computation, made = "", []
    for line in text.splitlines():
        if line.endswith("{") and "->" in line:
            computation = line.split()[0].lstrip("%")
        elif one_expert.search(line) and "fused_computation" not in computation:
            made.append(line.strip()[:160])
    assert not made, made
