"""Model-FLOPs estimation and MFU accounting.

The reference's only throughput metric is steps/sec (reference:
tensorflow/metrics.py:35-38). On TPU the number that actually says
whether the chip is being used is **MFU** — model FLOPs per second over
the chip's peak. The model-FLOPs estimate comes from XLA's own cost
analysis of the compiled train step (per-device HLO module, i.e.
post-SPMD-partitioning), so it is exact for whatever program actually
runs — remat, grad accumulation, fused kernels and all — instead of a
hand-maintained 6*N*T formula.
"""

from __future__ import annotations

import logging
from typing import Optional

_logger = logging.getLogger(__name__)

# Peak dense bf16 FLOP/s per chip (public spec sheet numbers). Matched
# against `device.device_kind` lowercased, first hit wins — order matters
# ("v5 lite" before "v5").
_PEAK_BF16_FLOPS = (
    ("v6", 918e12),  # Trillium
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

def peak_flops_per_chip(device) -> Optional[float]:
    """Peak bf16 FLOP/s of `device`. None off the TPU (the CPU rig
    reports no utilization); a TPU kind the table does not know is an
    error — a utilization over a guessed peak is not a measurement."""
    kind = getattr(device, "device_kind", "").lower()
    if "tpu" not in kind:
        return None
    for pattern, flops in _PEAK_BF16_FLOPS:
        if pattern in kind:
            return flops
    raise ValueError(
        f"no peak FLOP/s on record for TPU kind {device.device_kind!r}; "
        "add it, with its source, to utils/flops._PEAK_BF16_FLOPS"
    )


def compiled_flops(compiled) -> Optional[float]:
    """FLOPs of one execution of an AOT-compiled jax function (per
    device, post-partitioning), from XLA's cost analysis."""
    try:
        flops = compiled.cost_analysis().get("flops")
        return float(flops) if flops else None
    except Exception as exc:  # cost analysis is best-effort on all backends
        _logger.debug("cost_analysis unavailable: %s", exc)
        return None


def transformer_train_flops(config, batch_size: int, seq_len: int,
                            causal: bool = True) -> float:
    """Analytic model FLOPs for ONE training step of models.transformer
    (matmul flops only, fwd + 2x bwd — the PaLM-appendix accounting).

    This is the MFU denominator of choice for the transformer family:
    XLA's cost analysis counts `lax.scan`/while bodies once regardless of
    trip count (so scan_layers models undercount n_layers-fold) and sees
    zero FLOPs inside pallas kernels — both of which this model uses. The
    causal quadratic term is counted at S^2/2 (the model-required minimum;
    implementations that compute the full square burn hardware FLOPs
    above this denominator, which is exactly what MFU should charge them
    for).
    """
    d, hd = config.d_model, config.head_dim
    attn_params = (
        d * config.n_heads * hd          # wq
        + 2 * d * config.n_kv_heads * hd  # wk, wv
        + config.n_heads * hd * d        # wo
    )
    # SwiGLU: gate + up + down. Switch-MoE routes each token through one
    # expert of the same shape, so per-token matmul flops match dense
    # (router matmul d*E is negligible).
    mlp_params = 3 * d * config.d_ff
    dense_params = config.n_layers * (attn_params + mlp_params)
    dense_params += d * config.vocab_size  # untied lm_head
    tokens = batch_size * seq_len
    fwd = 2.0 * tokens * dense_params
    quad = 4.0 * batch_size * float(seq_len) ** 2 * d * config.n_layers
    if causal:
        quad /= 2.0
    return 3.0 * (fwd + quad)


def model_train_flops(model, batch, compiled=None,
                      n_devices: int = 1) -> Optional[float]:
    """Best-available per-chip model FLOPs for one train step on `batch`.

    The transformer family gets the analytic count (its layer scan and
    grad-accum scan defeat cost analysis's trip-count-blind walk, and
    pallas kernels report zero flops); everything else falls back to the
    compiled program's XLA cost analysis (already per-device).
    """
    cfg = getattr(model, "config", None)
    if (cfg is not None and hasattr(cfg, "scan_layers")
            and hasattr(cfg, "n_kv_heads")):
        samples, tokens = batch_counts(batch)
        if samples and tokens:
            seq = tokens // samples
            return transformer_train_flops(cfg, samples, seq) / n_devices
    return compiled_flops(compiled) if compiled is not None else None


_TOKEN_KEYS = ("tokens", "input_ids", "token_ids")


def batch_counts(batch) -> "tuple[Optional[int], Optional[int]]":
    """(samples, tokens) per global batch. Samples = leading dim of the
    first array leaf; tokens = B*S of a conventionally-named token-id
    entry ("tokens"/"input_ids"/"token_ids" — shape alone can't separate
    token ids from integer feature columns), None otherwise."""
    import jax

    leaves = jax.tree_util.tree_leaves(batch)
    samples = None
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape:
            samples = int(shape[0])
            break
    tokens = None
    if isinstance(batch, dict):
        for key in _TOKEN_KEYS:
            leaf = batch.get(key)
            shape = getattr(leaf, "shape", None)
            if shape is not None and len(shape) >= 2:
                tokens = int(shape[0]) * int(shape[1])
                break
    return samples, tokens


def mfu(flops_per_step: Optional[float], steps_per_sec: float,
        peak: Optional[float]) -> Optional[float]:
    """Per-chip MFU: per-device model FLOP/s over the chip's peak."""
    if not flops_per_step or not peak:
        return None
    return flops_per_step * steps_per_sec / peak
