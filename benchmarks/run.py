"""BASELINE.json benchmark suite: one JSON line per config.

The five configs BASELINE.json tracks (Keras-MNIST-dense, LinearClassifier
clicks, BERT-base, ResNet-50, Llama-LoRA) plus the additions this repo
measures beyond them: dlrm_clicks, vit_base, long_context, decode (bf16
vs int8 KV cache), and the ICI allreduce microbench. Sizes are
TPU-realistic and the run needs a TPU: without one it fails. `--cpu` asks
by name for the CPU rig at toy shapes — a check that the code paths run,
never a speed. Every line names the platform, device kind and device
count it ran on.

    python benchmarks/run.py                 # all configs
    python benchmarks/run.py bert_base       # one config
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _best_of_variants(variants, run_one):
    """Shared A/B sweep shape: run each (name, spec), keep per-variant
    samples/sec + MFU rows, return the best run's full stats with the
    rows attached. One bad variant never kills the sweep."""
    rows, best = {}, None
    for name, spec in variants:
        try:
            stats = run_one(spec)
        except Exception as exc:
            rows[name] = {"error": str(exc)[:160]}
            continue
        rows[name] = {
            "samples_per_sec_per_chip": stats["samples_per_sec_per_chip"],
            "mfu": stats.get("mfu"),
        }
        if best is None or (stats["samples_per_sec_per_chip"]
                            > best["samples_per_sec_per_chip"]):
            best = dict(stats, variant=name)
    if best is None:
        return {"variants": rows}
    best["variants"] = rows
    return best


def bench_mnist_dense(tpu: bool):
    import numpy as np
    import optax

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models import common
    from tf_yarn_tpu.models.mnist import DenseClassifier

    batch = 512 if tpu else 64
    rng = np.random.RandomState(0)
    return measure_throughput(
        DenseClassifier(),
        common.classification_loss,
        optax.adam(1e-3),
        {
            "x": rng.randn(batch, 784).astype(np.float32),
            "y": rng.randint(0, 10, batch).astype(np.int32),
        },
    )


def bench_linear_clicks(tpu: bool):
    import numpy as np
    import optax

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models import common
    from tf_yarn_tpu.models.linear import HashedLinearClassifier, LinearConfig

    config = LinearConfig(n_buckets=2**20 if tpu else 2**12, n_features=26)
    batch = 4096 if tpu else 256
    rng = np.random.RandomState(0)
    model = HashedLinearClassifier(config)
    return measure_throughput(
        model,
        common.binary_logistic_loss,
        optax.adagrad(0.05),
        {
            "x": rng.randint(0, config.n_buckets, (batch, 26)).astype(np.int32),
            "y": rng.randint(0, 2, batch).astype(np.int32),
        },
    )


def bench_bert_base(tpu: bool):
    import numpy as np
    import optax

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models import bert

    # b64 from the round-2 sweep: b16 left the MXU underfed (MFU 0.27 ->
    # 0.46); s128 is the classic fine-tune shape. On TPU the fused pallas
    # LayerNorm (ops/layernorm.py) rides as an A/B variant.
    batch, seq = (64, 128) if tpu else (8, 32)
    rng = np.random.RandomState(0)

    def loss_fn(model, params, batch, rng_, train=True):
        import jax.numpy as jnp

        logits = model.apply(
            params, batch["x"], rngs={"dropout": rng_}, deterministic=not train
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]
        ).mean()
        return loss, {"accuracy": jnp.mean(jnp.argmax(logits, -1) == batch["y"])}

    def run_one(variant):
        from tf_yarn_tpu.benchmark import kernel_bwd_env

        fused, kernel_bwd = variant
        config = (bert.BertConfig.base(fused_norms=fused) if tpu
                  else bert.BertConfig.tiny(fused_norms=fused))
        model = bert.BertClassifier(config)
        with kernel_bwd_env(kernel_bwd):
            return measure_throughput(
                model,
                loss_fn,
                optax.adamw(2e-5),
                {
                    "x": rng.randint(
                        0, config.vocab_size, (batch, seq)).astype(np.int32),
                    "y": rng.randint(
                        0, config.num_classes, batch).astype(np.int32),
                },
                init_fn=lambda r, b: model.init(r, b["x"]),
                steps=10 if tpu else 5,
            )

    # Post-LN BERT is the norm-heaviest family (2 norms/layer + embedding
    # norm): fused_ln_fwd isolates the forward kernel, fused_ln adds the
    # dx backward kernels — the pair answers whether the bwd fusion moves
    # the 0.456 MFU (VERDICT r4 item 8).
    variants = ([("base", (False, False)),
                 ("fused_ln_fwd", (True, False)),
                 ("fused_ln", (True, True))] if tpu
                else [("base", (False, False))])
    return _best_of_variants(variants, run_one)


def bench_resnet50(tpu: bool):
    """A/Bs the stem (classic conv7x7s2 vs space-to-depth) and, on TPU,
    batch 64 vs 128 — the two live hypotheses for the 0.272 MFU
    (docs/ResNetMFU.md). Headline = the best variant; per-variant rows
    ride along so the A/B is captured the moment a chip is reachable."""
    import numpy as np
    import optax

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models import common, resnet

    size = 224 if tpu else 32
    rng = np.random.RandomState(0)

    def run_one(spec):
        from tf_yarn_tpu.benchmark import kernel_bwd_env

        stem, batch, fused, gn_bwd = spec
        config = (
            resnet.ResNetConfig.resnet50(stem=stem, fused_norms=fused)
            if tpu
            else resnet.ResNetConfig.tiny(stem=stem, fused_norms=fused))
        model = resnet.ResNet(config)
        with kernel_bwd_env(gn_bwd):
            return measure_throughput(
                model,
                common.classification_loss,
                optax.sgd(0.1, momentum=0.9),
                {
                    "x": rng.randn(batch, size, size, 3).astype(np.float32),
                    "y": rng.randint(
                        0, config.num_classes, batch).astype(np.int32),
                },
                steps=10 if tpu else 5,
            )

    # The winning s2d+fused config splits fwd-only vs fwd+bwd GroupNorm
    # kernels (VERDICT r4 item 5's A/B, resnet edition).
    variants = (
        [("conv_b64", ("conv", 64, False, False)),
         ("s2d_b64", ("space_to_depth", 64, False, False)),
         ("s2d_b128", ("space_to_depth", 128, False, False)),
         ("s2d_fused_gn_b128", ("space_to_depth", 128, True, False)),
         ("s2d_fused_gn_bwd_b128", ("space_to_depth", 128, True, True))]
        if tpu else [("conv", ("conv", 8, False, False))]
    )
    return _best_of_variants(variants, run_one)


def bench_vit_base(tpu: bool):
    """ViT-B/16 on 224px images — encoder-stack vision throughput
    (transformer-native counterpart of the resnet50 config). On TPU the
    fused pallas LayerNorm rides as an A/B variant."""
    import numpy as np
    import optax

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models import common, vit

    batch = 128 if tpu else 8
    rng = np.random.RandomState(0)

    def run_one(fused):
        config = (vit.ViTConfig.base16(fused_norms=fused) if tpu
                  else vit.ViTConfig.tiny(fused_norms=fused))
        size = config.image_size
        model = vit.ViT(config)
        return measure_throughput(
            model,
            common.classification_loss,
            optax.adamw(3e-4),
            {
                "x": rng.randn(batch, size, size, 3).astype(np.float32),
                "y": rng.randint(
                    0, config.num_classes, batch).astype(np.int32),
            },
            steps=10 if tpu else 5,
        )

    variants = ([("base", False), ("fused_ln", True)] if tpu
                else [("base", False)])
    return _best_of_variants(variants, run_one)


def bench_llama_lora(tpu: bool):
    import numpy as np

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models import common
    from tf_yarn_tpu.models.transformer import (
        Transformer,
        TransformerConfig,
        make_lora_optimizer,
    )

    if tpu:
        # Largest decoder that fits one v5e chip comfortably for a bench.
        # flash attention is what makes it fit: xla attention's saved
        # f32 [B,H,S,S] logits alone exceed HBM at this depth.
        config = TransformerConfig(
            vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=8, d_ff=5632, max_seq_len=2048, lora_rank=16,
            remat=False, attention_impl="flash", fused_norms=True,
            scan_layers=False,
        )
        batch, seq = 4, 1024
    else:
        config = TransformerConfig.tiny(lora_rank=4)
        batch, seq = 8, 32
    rng = np.random.RandomState(0)
    model = Transformer(config)
    return measure_throughput(
        model,
        common.lm_loss,
        make_lora_optimizer(1e-4),
        {"tokens": rng.randint(0, config.vocab_size, (batch, seq)).astype(np.int32)},
        init_fn=lambda r, b: model.init(r, b["tokens"]),
        steps=10 if tpu else 5,
    )


def bench_dlrm_clicks(tpu: bool):
    """Deep CTR on the Criteo-clicks shape: 26 embedding tables stacked
    into one fsdp-sharded param + MXU pairwise interaction."""
    import numpy as np
    import optax

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models.dlrm import DLRM, DLRMConfig, dlrm_loss

    config = DLRMConfig.criteo() if tpu else DLRMConfig.tiny()
    batch = 4096 if tpu else 256
    rng = np.random.RandomState(0)
    sizes = np.asarray(config.table_sizes)
    model = DLRM(config)
    return measure_throughput(
        model,
        dlrm_loss,
        optax.adagrad(1e-3),
        {
            "cat": rng.randint(0, sizes, (batch, len(sizes))).astype(np.int32),
            "dense": rng.randn(batch, config.n_dense).astype(np.float32),
            "y": rng.randint(0, 2, batch).astype(np.int32),
        },
        init_fn=lambda r, b: model.init(r, b["cat"], b["dense"]),
        steps=10 if tpu else 5,
    )


def bench_long_context(tpu: bool):
    """Long-sequence training on one chip: flash attention + chunked-vocab
    loss are what make S=8192 fit (xla attention's f32 logits alone would
    be 32 GiB here). Reported as tokens/sec/chip.

    On TPU this is an A/B matrix targeting the 0.327 MFU hypotheses
    ranked in docs/LongContext.md: `headdim128` (d_head 64 half-fills
    the 128-wide MXU on the ~30%-of-FLOPs attention contractions),
    `fullloss` (the chunked-vocab loss recomputes the head per chunk),
    plus an attention-only block-size microbench (grid overhead vs VMEM
    pressure). The headline stays the base config so cross-round
    comparisons hold."""
    import numpy as np
    import optax

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models import common
    from tf_yarn_tpu.models.transformer import Transformer, TransformerConfig

    base_cfg = dict(
        vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
        n_kv_heads=8, d_ff=4096, max_seq_len=8192, remat=False,
        attention_impl="flash", fused_norms=True, scan_layers=False,
    )
    if tpu:
        batch, seq, steps = 1, 8192, 10
    else:
        batch, seq, steps = 2, 64, 3
    rng = np.random.RandomState(0)

    def run_one(overrides, loss_fn):
        config = (TransformerConfig(**{**base_cfg, **overrides}) if tpu
                  else TransformerConfig.tiny(attention_impl="flash"))
        return measure_throughput(
            Transformer(config),
            loss_fn,
            optax.adamw(1e-4),
            {"tokens": rng.randint(
                0, config.vocab_size, (batch, seq)).astype(np.int32)},
            steps=steps,
        )

    stats = run_one({}, common.lm_loss_chunked)
    stats["tokens_per_sec_per_chip"] = stats["samples_per_sec_per_chip"] * seq
    if not tpu:
        return stats

    variants = [
        # Hypothesis 3 (docs/LongContext.md): chunk recompute cost.
        ("fullloss", {}, common.lm_loss),
        # Hypothesis 1: MXU fill — same d_model, 128-deep head dim.
        ("headdim128", {"n_heads": 8, "n_kv_heads": 8},
         common.lm_loss_chunked),
    ]
    rows = {}
    for name, overrides, loss_fn in variants:
        try:
            v = run_one(overrides, loss_fn)
            rows[name] = {
                "tokens_per_sec_per_chip":
                    round(v["samples_per_sec_per_chip"] * seq, 1),
                "step_time_ms": round(v["step_time_ms"], 2),
                "mfu": round(v["mfu"], 4) if "mfu" in v else None,
            }
        except Exception as exc:  # noqa: BLE001 - record, keep benching
            rows[name] = {"error": f"{type(exc).__name__}: {exc}"}
    stats["variants"] = rows
    stats["attn_microbench"] = _flash_block_microbench(seq)
    return stats


def _flash_block_microbench(seq: int):
    """Attention-only fwd+bwd at S=seq across flash block sizes — the
    direct probe of the flash-grid hypothesis (one number per block
    config, TFLOP/s on the 4·S²·d·0.5 causal attention FLOPs)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tf_yarn_tpu.ops.flash_attention import flash_attention

    b, h, d_head = 1, 16, 64
    rng = np.random.RandomState(0)
    qkv = [
        jnp.asarray(rng.randn(b, h, seq, d_head).astype(np.float32),
                    jnp.bfloat16)
        for _ in range(3)
    ]
    flops = 3 * (4 * seq * seq * d_head * h * b) // 2  # train, causal
    rows = {}
    for block in (256, 512, 1024):
        @jax.jit
        def step(q, k, v, block=block):
            def loss(q):
                out = flash_attention(
                    q, k, v, causal=True, block_q=block, block_k=block)
                return (out.astype(jnp.float32) ** 2).sum()
            return jax.grad(loss)(q)

        try:
            jax.block_until_ready(step(*qkv))  # compile + warm
            t0 = time.perf_counter()
            for _ in range(3):
                g = step(*qkv)
            jax.block_until_ready(g)
            dt = (time.perf_counter() - t0) / 3
            rows[f"block{block}"] = {
                "ms": round(dt * 1e3, 2),
                "tflops": round(flops / dt / 1e12, 1),
            }
        except Exception as exc:  # noqa: BLE001
            rows[f"block{block}"] = {"error": f"{type(exc).__name__}: {exc}"}
    return rows


def _spec_decode_ab(tpu: bool, ks=(2, 4)):
    """Exact vs speculative decoding A/B on ONE seeded repeated-structure
    trace: the same prompts (each tiling a short motif — the shape
    n-gram/prompt-lookup drafting exists for: templated/structured
    traffic) decode through the SAME engine with spec_k = 0 (exact) and
    spec_k in `ks`, reporting end-to-end tokens/s and accepted-tokens
    per emitting step. Streams are asserted identical across rows — the
    speculative path is a latency lever, not a different sampler."""
    import time

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tf_yarn_tpu.models.decode_engine import DecodeEngine
    from tf_yarn_tpu.models.transformer import Transformer, TransformerConfig
    from tf_yarn_tpu.parallel.mesh import select_devices
    from tf_yarn_tpu.serving import SamplingParams, SlotScheduler

    select_devices()
    if tpu:
        config = TransformerConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=4096, max_seq_len=2048, remat=False,
            scan_layers=False,
        )
        n_requests, max_slots, prompt_len, max_new = 16, 8, 64, 128
    else:
        config = TransformerConfig.tiny(scan_layers=False, max_seq_len=128)
        n_requests, max_slots, prompt_len, max_new = 6, 4, 12, 32
    model = Transformer(config)
    rng = np.random.RandomState(7)
    params = nn.meta.unbox(
        model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, prompt_len), jnp.int32)
        )
    )
    engine = DecodeEngine(model)
    # Repeated structure: each prompt tiles a 3-token motif, so the
    # greedy continuation is (near-)periodic and prompt-lookup drafts
    # land. One seeded trace shared by every row.
    prompts = []
    for _ in range(n_requests):
        motif = rng.randint(0, config.vocab_size, (3,))
        prompts.append(
            np.tile(motif, -(-prompt_len // 3))[:prompt_len].tolist()
        )

    def run_row(spec_k):
        scheduler = SlotScheduler(
            engine, params, max_slots=max_slots,
            queue_capacity=n_requests, spec_k=spec_k,
        )
        scheduler.start()
        try:
            # Warmup: compile prefill + the row's step program outside
            # the timed window.
            scheduler.submit(
                prompts[0], SamplingParams(max_new_tokens=2)
            ).result(timeout=600)
            t0 = time.perf_counter()
            responses = [
                scheduler.submit(p, SamplingParams(max_new_tokens=max_new))
                for p in prompts
            ]
            streams = [r.result(timeout=600) for r in responses]
            wall = time.perf_counter() - t0
            # Accepted-tokens per emitting step, from the tick trace
            # (exact rows have no `accepted` entries: by definition 1).
            accepted = [
                n
                for entry in scheduler.trace
                for n in entry.get("accepted", {}).values()
            ]
            per_step = (
                round(sum(accepted) / len(accepted), 3) if accepted else 1.0
            )
            stats = scheduler.stats()
            return streams, {
                "spec_k": spec_k,
                "tokens_per_sec": round(
                    n_requests * max_new / wall, 2
                ),
                "wall_s": round(wall, 3),
                "accepted_tokens_per_step": per_step,
                "accept_rate": (stats.get("spec") or {}).get("accept_rate"),
            }
        finally:
            scheduler.close()

    exact_streams, exact_row = run_row(0)
    rows = {"exact": exact_row}
    for k in ks:
        streams, row = run_row(k)
        row["streams_match_exact"] = streams == exact_streams
        row["speedup_vs_exact"] = (
            round(row["tokens_per_sec"] / exact_row["tokens_per_sec"], 3)
            if exact_row["tokens_per_sec"] else None
        )
        rows[f"k{k}"] = row
    return {
        "requests": n_requests,
        "max_slots": max_slots,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new,
        "rows": rows,
    }


def bench_decode(tpu: bool, spec: bool = False):
    """Autoregressive decode throughput (tokens/sec), bf16 vs int8 KV
    cache. Decode steps are scanned inside ONE jitted program, so the
    number is the device's decode step, not the host's dispatch of it.

    The `engine` vs `percall_jit` pair A/Bs the serving path itself:
    `DecodeEngine` (compile cached across calls, on-device EOS loop,
    donated cache) against the legacy `generate_legacy` host loop (fresh
    jitted step closure per call + one host sync per token). Both time a
    SECOND call end-to-end — exactly what a warm server pays per batch —
    so the engine's cached compile and the legacy path's per-call
    retrace are both visible in the number."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tf_yarn_tpu.models.decode_engine import DecodeEngine
    from tf_yarn_tpu.models.generate import generate_legacy
    from tf_yarn_tpu.models.transformer import Transformer, TransformerConfig
    from tf_yarn_tpu.parallel.mesh import select_devices

    select_devices()

    results = {}
    for cache_dtype in ("bf16", "int8"):
        if tpu:
            config = TransformerConfig(
                vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
                n_kv_heads=8, d_ff=4096, max_seq_len=2048, remat=False,
                scan_layers=False, kv_cache_dtype=cache_dtype,
            )
            batch, prefill_len, decode_tokens = 8, 128, 256
        else:
            config = TransformerConfig.tiny(kv_cache_dtype=cache_dtype,
                                            scan_layers=False)
            batch, prefill_len, decode_tokens = 2, 8, 16
        model = Transformer(config)
        rng = np.random.RandomState(0)
        prompt = jnp.asarray(
            rng.randint(0, config.vocab_size, (batch, prefill_len)), jnp.int32
        )
        params = jax.jit(model.init)(jax.random.PRNGKey(0), prompt)

        def prefill(params, prompt):
            logits, state = model.apply(
                params, prompt, decode=True, mutable=["cache"]
            )
            return state["cache"], jnp.argmax(
                logits[:, -1], axis=-1
            ).astype(jnp.int32)

        def decode_n(params, cache, token):
            def body(carry, _):
                cache, token = carry
                logits, state = model.apply(
                    {**params, "cache": cache}, token[:, None], decode=True,
                    mutable=["cache"],
                )
                return (
                    state["cache"],
                    jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32),
                ), ()
            (cache, token), _ = jax.lax.scan(
                body, (cache, token), None, length=decode_tokens
            )
            return token

        cache, token = jax.jit(prefill)(params, prompt)
        run = jax.jit(decode_n).lower(params, cache, token).compile()
        jax.block_until_ready(run(params, cache, token))  # warmup
        t0 = time.perf_counter()
        jax.block_until_ready(run(params, cache, token))
        elapsed = time.perf_counter() - t0
        results[f"decode_tokens_per_sec_{cache_dtype}"] = round(
            batch * decode_tokens / elapsed, 2
        )
        results[f"decode_ms_per_step_{cache_dtype}"] = round(
            1000 * elapsed / decode_tokens, 3
        )

        def _timed_call(fn):
            # Warm call compiles (engine) / traces (per-call jit); sync
            # it so no async tail leaks into the timed window.
            jax.block_until_ready(fn())
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            return batch * decode_tokens / (time.perf_counter() - t0)

        try:
            engine = DecodeEngine(model)
            results[f"engine_tokens_per_sec_{cache_dtype}"] = round(
                _timed_call(lambda: engine.generate(
                    params, prompt, decode_tokens, temperature=0.0)), 2
            )
            results[f"engine_decode_compiles_{cache_dtype}"] = (
                engine.stats["decode_compiles"]
            )
            results[f"percall_jit_tokens_per_sec_{cache_dtype}"] = round(
                _timed_call(lambda: generate_legacy(
                    model, params, prompt, decode_tokens,
                    temperature=0.0)), 2
            )
        except Exception as exc:  # noqa: BLE001 - record, keep benching
            results[f"engine_error_{cache_dtype}"] = (
                f"{type(exc).__name__}: {exc}"[:160]
            )
    out = {
        "batch": batch, "prefill": prefill_len,
        "decode_tokens": decode_tokens, **results,
    }
    if spec:
        # `decode --spec`: the exact-vs-speculative A/B rides along.
        try:
            out["spec"] = _spec_decode_ab(tpu)
        except Exception as exc:  # noqa: BLE001 - record, keep benching
            out["spec"] = {"error": f"{type(exc).__name__}: {exc}"[:160]}
    return out


def bench_fleet(tpu: bool, replica_counts=(1, 2, 4), n_requests=None,
                autoscale=False):
    """The serving stack behind the fleet router: aggregate tokens/s and
    TTFT p95 vs replica count under the SAME seeded Poisson arrival
    trace, driven end-to-end through the fleet ROUTER (tf_yarn_tpu/fleet/):
    N real serving stacks (scheduler + HTTP frontend) advertise into an
    in-process KV, the replica registry probes them healthy, and every
    request streams through the router's ``/v1/generate`` passthrough —
    TTFT is measured client-side at first token line, so discovery,
    balancing, and the extra hop are all inside the number. The decode
    engine (and its compiled programs) is shared across replicas, so
    the sweep measures the replica axis, not recompilation.

    ``autoscale=True`` (`fleet --autoscale`) switches to the elastic
    A/B instead of the replica sweep: a STATIC 2-replica fleet vs an
    AUTOSCALED one (start 2, max 4, FleetAutoscaler side-car with an
    in-process spawn actuator + real /v1/blocks peer warm start) under
    the SAME seeded Poisson trace with a mid-run rate step, plus one
    injected replica preemption (eject + relaunch on a NEW port, the
    registry re-admit path) in BOTH arms. Reported: per-arm
    SLO-violation rate (client-side TTFT over the threshold), dropped
    in-flight streams (must be 0), and ``streams_match`` — the two
    arms' per-request token sequences compared bit-for-bit (scaling
    must change WHEN tokens arrive, never WHICH). On the CPU rig the
    latency numbers are scheduling evidence only."""
    import threading
    import time

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tf_yarn_tpu import event, telemetry
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.fleet import (
        FleetMonitor,
        ReplicaRegistry,
        RouterServer,
        make_policy,
    )
    from tf_yarn_tpu.models.decode_engine import DecodeEngine
    from tf_yarn_tpu.models.transformer import Transformer, TransformerConfig
    from tf_yarn_tpu.parallel.mesh import select_devices
    from tf_yarn_tpu.serving import SamplingParams, ServingServer, SlotScheduler

    select_devices()
    if tpu:
        config = TransformerConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=4096, max_seq_len=2048, remat=False,
            scan_layers=False,
        )
        default_requests, max_slots, mean_gap_s = 32, 8, 0.02
        prompt_lens, max_new_range = (64, 128, 256), (32, 256)
    else:
        config = TransformerConfig.tiny(scan_layers=False, max_seq_len=64)
        default_requests, max_slots, mean_gap_s = 12, 4, 0.005
        prompt_lens, max_new_range = (5, 9, 14), (2, 16)
    model = Transformer(config)
    rng = np.random.RandomState(0)
    params = nn.meta.unbox(
        model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, max(prompt_lens)), jnp.int32),
        )
    )
    engine = DecodeEngine(model)
    if autoscale:
        return _bench_fleet_autoscale(
            tpu, engine, params, config, max_slots, n_requests)
    n_requests = n_requests or default_requests

    # One seeded Poisson trace, shared by every fleet size.
    gaps = rng.exponential(mean_gap_s, n_requests)
    arrivals = np.cumsum(gaps)
    requests = [
        (
            float(arrivals[i]),
            rng.randint(0, config.vocab_size,
                        rng.choice(prompt_lens)).tolist(),
            int(rng.randint(*max_new_range)),
        )
        for i in range(n_requests)
    ]
    total_tokens = sum(m for _, _, m in requests)

    def stream_through_router(port, offset, prompt, max_new, t0, out):
        import http.client
        import json as json_lib

        lag = t0 + offset - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        try:
            conn.request(
                "POST", "/v1/generate",
                json_lib.dumps({"prompt": prompt,
                                "max_new_tokens": max_new,
                                "stream": True}),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            first = None
            n_tokens = 0
            # Read to EOF (not just the done line): draining the
            # terminal chunk means the router has finished its own
            # accounting for this request before we count it done.
            while True:
                line = resp.readline()
                if not line:
                    break
                payload = json_lib.loads(line)
                if "token" in payload:
                    if first is None:
                        first = time.perf_counter()
                    n_tokens += 1
            out.append({
                "status": resp.status,
                "n_tokens": n_tokens,
                "ttft_s": (first - (t0 + offset))
                if first is not None else None,
            })
        finally:
            conn.close()

    def run_fleet(n_replicas):
        # Reset the process registry so this row's fleet-merged sketch
        # (the in-process replicas share one registry) only holds this
        # row's observations.
        telemetry.get_registry().clear()
        kv = InProcessKV()
        replicas = []
        for index in range(n_replicas):
            scheduler = SlotScheduler(
                engine, params, max_slots=max_slots,
                queue_capacity=n_requests,
            )
            scheduler.start()
            server = ServingServer(scheduler, "127.0.0.1", 0)
            server.start()
            task = f"serving:{index}"
            event.serving_endpoint_event(kv, task, server.endpoint)
            replicas.append((task, scheduler, server))
        registry = ReplicaRegistry(
            kv, tasks=[task for task, _, _ in replicas],
            probe_interval_s=0.2,
        )
        registry.refresh(force=True)
        monitor = FleetMonitor(registry, interval_s=0.2)
        router = RouterServer(
            registry, make_policy("least_loaded"), "127.0.0.1", 0,
            retries=2, monitor=monitor,
        )
        router.start()
        monitor.start()
        try:
            # Warmup compiles every prompt bucket's prefill + the step
            # program outside the timed window (shared engine: paid
            # once across the whole sweep).
            for length in prompt_lens:
                replicas[0][1].submit(
                    [1] * length, SamplingParams(max_new_tokens=2)
                ).result(timeout=600)
            results = []
            threads = []
            t0 = time.perf_counter()
            for offset, prompt, max_new in requests:
                thread = threading.Thread(
                    target=stream_through_router,
                    args=(router.port, offset, prompt, max_new, t0,
                          results),
                )
                thread.start()
                threads.append(thread)
            for thread in threads:
                thread.join(timeout=900)
            wall = time.perf_counter() - t0
            completed = [r for r in results if r["status"] == 200]
            ttfts = sorted(
                r["ttft_s"] for r in completed if r["ttft_s"] is not None
            )
            generated = sum(r["n_tokens"] for r in completed)
            row = {
                "replicas": n_replicas,
                "completed": len(completed),
                "tokens_per_sec": round(generated / wall, 2),
                "wall_s": round(wall, 3),
            }
            if ttfts:
                row["ttft_mean_ms"] = round(
                    1000 * sum(ttfts) / len(ttfts), 2
                )
                row["ttft_p95_ms"] = round(
                    1000 * ttfts[int(0.95 * (len(ttfts) - 1))], 2
                )
            router_stats = router.stats()
            row["healthy_replicas"] = router_stats["healthy_replicas"]
            row["routed_ok"] = sum(
                outcomes.get("ok", 0)
                for outcomes in router_stats["routed_requests"].values()
            )
            # The fleet observability plane's own numbers: the
            # scrape-merged fleet TTFT p95 (server-side, pooled over
            # every replica's sketch — what the autoscaler sees, vs
            # the client-side ttft_p95_ms above which includes the
            # router hop) and the scrape overhead per monitor cycle.
            aggregate = monitor.poll_once()
            if aggregate.get("status") == "ok":
                fleet_ttft = aggregate["histograms"].get(
                    "serving/ttft_seconds", {})
                if "p95" in fleet_ttft:
                    row["fleet_ttft_p95_ms"] = round(
                        1000 * fleet_ttft["p95"], 2)
                row["monitor_cycles"] = aggregate["cycle"]
                row["monitor_scrape_wall_ms"] = round(
                    1000 * aggregate["scrape_wall_s"], 3)
            return row
        finally:
            monitor.stop()
            router.stop()
            for _task, scheduler, server in replicas:
                server.stop()
                scheduler.close()

    rows = {}
    for count in replica_counts:
        try:
            rows[f"r{count}"] = run_fleet(count)
        except Exception as exc:  # noqa: BLE001 - record, keep benching
            rows[f"r{count}"] = {"error": f"{type(exc).__name__}: {exc}"[:160]}
    result = {
        "requests": n_requests,
        "max_slots": max_slots,
        "total_max_new_tokens": total_tokens,
        "rows": rows,
    }
    base = rows.get(f"r{replica_counts[0]}", {}).get("tokens_per_sec")
    for count in replica_counts[1:]:
        top = rows.get(f"r{count}", {}).get("tokens_per_sec")
        if base and top:
            result[f"scaling_r{count}_vs_r{replica_counts[0]}"] = round(
                top / base, 3
            )
    return result


def _bench_fleet_autoscale(tpu, engine, params, config, max_slots,
                           n_requests=None):
    """`fleet --autoscale`: the elastic A/B (see bench_fleet's
    docstring). Static 2-replica arm vs autoscaled arm (start 2, max 4)
    under one seeded rate-step Poisson trace with one injected replica
    preemption + relaunch-on-a-new-port in both arms."""
    import sys
    import threading
    import time

    import numpy as np

    from tf_yarn_tpu import event, telemetry
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.fleet import (
        AutoscalePolicy,
        FleetAutoscaler,
        FleetMonitor,
        ReplicaRegistry,
        RouterServer,
        make_policy,
    )
    from tf_yarn_tpu.serving import SamplingParams, ServingServer, SlotScheduler

    rng = np.random.RandomState(7)
    if tpu:
        n_requests = n_requests or 48
        mean_gap_s, step_factor = 0.05, 4.0
        block_size, prefix_len = 16, 64
        tail_lens, max_new_range = (32, 64, 96), (16, 96)
        slo_ttft_s, interval_s = 0.5, 0.1
        ab_slots = max_slots
    else:
        n_requests = n_requests or 24
        mean_gap_s, step_factor = 0.04, 4.0
        block_size, prefix_len = 8, 16
        tail_lens, max_new_range = (3, 5, 8), (2, 10)
        slo_ttft_s, interval_s = 0.5, 0.05
        # Few slots per replica so TTFT is queue-wait dominated: extra
        # replicas add admission capacity even on a GIL-shared CPU rig.
        ab_slots = min(4, max_slots)

    # ONE seeded trace for both arms: Poisson at the base rate for the
    # first half, then the gaps compress by step_factor (the demand
    # surge the autoscaled arm should absorb). Every prompt opens with
    # a shared prefix so the prefix cache — and the peer warm start
    # that ships it — has something to hit.
    gaps = rng.exponential(mean_gap_s, n_requests)
    gaps[n_requests // 2:] /= step_factor
    arrivals = np.cumsum(gaps)
    shared_prefix = rng.randint(0, config.vocab_size, prefix_len).tolist()
    requests = [
        (
            float(arrivals[i]),
            shared_prefix + rng.randint(
                0, config.vocab_size, rng.choice(tail_lens)).tolist(),
            int(rng.randint(*max_new_range)),
        )
        for i in range(n_requests)
    ]
    kill_at = float(arrivals[n_requests // 3])

    def stream_ab(port, offset, prompt, max_new, t0, out, index):
        import http.client
        import json as json_lib

        lag = t0 + offset - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        try:
            conn.request(
                "POST", "/v1/generate",
                json_lib.dumps({"prompt": prompt,
                                "max_new_tokens": max_new,
                                "stream": True}),
                {"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            first = None
            tokens = []
            dropped = resp.status != 200
            while True:
                line = resp.readline()
                if not line:
                    break
                payload = json_lib.loads(line)
                if "token" in payload:
                    if first is None:
                        first = time.perf_counter()
                    tokens.append(int(payload["token"]))
                if payload.get("error"):
                    dropped = True
            out.append({
                "index": index,
                "status": resp.status,
                "tokens": tokens,
                "dropped": dropped,
                "ttft_s": (first - (t0 + offset))
                if first is not None else None,
            })
        except Exception as exc:  # noqa: BLE001 - record, keep benching
            out.append({"index": index, "status": 0, "tokens": [],
                        "dropped": True, "ttft_s": None,
                        "error": f"{type(exc).__name__}: {exc}"[:120]})
        finally:
            conn.close()

    def run_arm(autoscaled):
        telemetry.get_registry().clear()
        kv = InProcessKV()
        state_lock = threading.Lock()
        replicas = []
        next_id = [0]

        def spawn_replica(task=None):
            with state_lock:
                if task is None:
                    task = f"serving:{next_id[0]}"
                next_id[0] = max(next_id[0],
                                 int(task.split(":", 1)[1]) + 1)
            scheduler = SlotScheduler(
                engine, params, max_slots=ab_slots,
                queue_capacity=max(64, n_requests),
                block_size=block_size,
                prefix_cache_capacity=64,
            )
            scheduler.start()
            server = ServingServer(scheduler, "127.0.0.1", 0)
            server.start()
            # Advertise AFTER the server listens: the registry probes
            # the advertised address on its next refresh pass.
            event.serving_endpoint_event(kv, task, server.endpoint)
            with state_lock:
                replicas.append((task, scheduler, server))
            return task

        for _ in range(2):
            spawn_replica()
        registry = ReplicaRegistry(
            kv, tasks=None, probe_interval_s=interval_s / 2,
        )
        registry.refresh(force=True)
        monitor = FleetMonitor(registry, interval_s=interval_s)
        autoscaler = None
        if autoscaled:
            def actuate(kind, current, target, reason):
                if kind != "generate" or target <= current:
                    return False
                # Idempotent against the registry's lag: `current` is
                # the fleet the registry can SEE, which trails replicas
                # still constructing — spawn toward the target from the
                # count of distinct tasks ever launched, not by delta.
                with state_lock:
                    missing = target - next_id[0]
                if missing <= 0:
                    return False
                # Launch off-thread: real relaunches take seconds and
                # the decision loop must not block on them.
                threading.Thread(
                    target=lambda: [spawn_replica()
                                    for _ in range(missing)],
                    name="bench-scale-out", daemon=True,
                ).start()
                return True

            autoscaler = FleetAutoscaler(
                registry, monitor,
                {"generate": AutoscalePolicy(
                    min_replicas=2, max_replicas=4,
                    scale_out_queue_depth=0.5,
                    scale_out_p95_s=slo_ttft_s,
                    scale_in_load=None, cooldown_cycles=2,
                )},
                actuate=actuate, interval_s=interval_s,
            )
        router = RouterServer(
            registry, make_policy("least_loaded"), "127.0.0.1", 0,
            retries=2, monitor=monitor, autoscaler=autoscaler,
        )
        router.start()
        monitor.start()
        stop = threading.Event()

        def refresh_loop():
            while not stop.is_set():
                registry.refresh()
                stop.wait(interval_s / 2)

        refresher = threading.Thread(
            target=refresh_loop, name="bench-registry-refresh",
            daemon=True,
        )
        refresher.start()
        try:
            # Compile every prompt bucket outside the timed window
            # (shared engine: paid once across both arms).
            for tail in tail_lens:
                replicas[0][1].submit(
                    shared_prefix + [1] * tail,
                    SamplingParams(max_new_tokens=2),
                ).result(timeout=600)
            results = []
            threads = []
            t0 = time.perf_counter()

            def chaos_kill():
                lag = t0 + kill_at - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                with state_lock:
                    victim = replicas[0][0]
                registry.report_failure(
                    victim, ConnectionError("preempted (bench chaos)"),
                )
                # Relaunch under the SAME task name on a NEW port: the
                # registry re-admit path probes the fresh address, and
                # the autoscaled arm warm-starts the cold cache from a
                # live peer over /v1/blocks. In-flight streams on the
                # old server drain to completion (zero dropped).
                spawn_replica(task=victim)

            killer = threading.Thread(
                target=chaos_kill, name="bench-chaos", daemon=True,
            )
            killer.start()
            for index, (offset, prompt, max_new) in enumerate(requests):
                thread = threading.Thread(
                    target=stream_ab,
                    args=(router.port, offset, prompt, max_new, t0,
                          results, index),
                )
                thread.start()
                threads.append(thread)
            # The main thread paces the autoscaler for the trace's
            # duration: production runs autoscaler.start()'s side-car
            # thread, but under a saturated bench GIL a side-car gets
            # starved to a couple of cycles — polling from the load
            # generator's clock keeps the decision cadence honest in
            # both arms' measurement windows.
            deadline = time.perf_counter() + 900
            while any(t.is_alive() for t in threads):
                if time.perf_counter() > deadline:
                    break
                if autoscaler is not None:
                    try:
                        autoscaler.poll_once()
                    except Exception:  # noqa: BLE001 - cycle, not arm
                        pass
                time.sleep(interval_s)
            for thread in threads:
                thread.join(timeout=60)
            killer.join(timeout=60)
            # Ingest any relaunch/scale-out that advertised after the
            # refresher's last pass, then run a final decision cycle: a
            # re-admission that landed after the last in-trace poll
            # still warm-starts (the endpoint-change trigger is
            # stateful, not edge-sampled).
            registry.refresh(force=True)
            if autoscaler is not None:
                autoscaler.poll_once()
            wall = time.perf_counter() - t0
            violated = sum(
                1 for r in results
                if r["dropped"] or r["ttft_s"] is None
                or r["ttft_s"] > slo_ttft_s
            )
            ttfts = sorted(
                r["ttft_s"] for r in results if r["ttft_s"] is not None
            )
            row = {
                "completed": sum(1 for r in results if not r["dropped"]),
                "dropped": sum(1 for r in results if r["dropped"]),
                "wall_s": round(wall, 3),
                "slo_violation_rate": round(
                    violated / max(1, len(results)), 3),
            }
            if ttfts:
                row["ttft_p95_ms"] = round(
                    1000 * ttfts[int(0.95 * (len(ttfts) - 1))], 2)
            snapshot = registry.snapshot()
            row["replicas_final"] = snapshot["healthy_replicas"]
            row["readmissions"] = snapshot["readmissions_total"]
            if autoscaler is not None:
                stats = autoscaler.stats()
                row["autoscaler_cycles"] = stats["cycles"]
                row["scale_events"] = len(stats["scale_events"])
                # pulls = attempts; warm_starts = pulls that shipped
                # blocks (a pull that finds the peer already re-heated
                # organically imports 0 — the fleet healed either way).
                row["warm_start_pulls"] = len(stats["warm_starts"])
                row["warm_starts"] = sum(
                    1 for w in stats["warm_starts"]
                    if w.get("imported_blocks")
                )
                row["warm_start_blocks"] = int(
                    telemetry.get_registry().counter(
                        "fleet/warm_start_blocks_total").value
                )
            streams = {r["index"]: list(r["tokens"]) for r in results}
            return row, streams
        finally:
            stop.set()
            if autoscaler is not None:
                autoscaler.stop()
            monitor.stop()
            router.stop()
            refresher.join(timeout=10)
            with state_lock:
                final = list(replicas)
            for _task, scheduler, server in final:
                server.stop()
                scheduler.close()

    rows = {}
    streams = {}
    switch_interval = sys.getswitchinterval()
    sys.setswitchinterval(0.002)  # fairer GIL handoff under 24+ threads
    try:
        for arm, autoscaled in (("static", False), ("autoscaled", True)):
            try:
                rows[arm], streams[arm] = run_arm(autoscaled)
            except Exception as exc:  # noqa: BLE001 - record, keep benching
                rows[arm] = {"error": f"{type(exc).__name__}: {exc}"[:160]}
    finally:
        sys.setswitchinterval(switch_interval)
    result = {
        "mode": "autoscale_ab",
        "requests": n_requests,
        "max_slots": max_slots,
        "slo_ttft_s": slo_ttft_s,
        "rate_step_factor": step_factor,
        "kill_at_s": round(kill_at, 3),
        "rows": rows,
    }
    if len(streams) == 2:
        # Scaling must change WHEN tokens arrive, never WHICH: the two
        # arms' per-request token sequences must be bit-identical.
        result["streams_match"] = streams["static"] == streams["autoscaled"]
    static_row, auto_row = rows.get("static", {}), rows.get("autoscaled", {})
    if "slo_violation_rate" in static_row \
            and "slo_violation_rate" in auto_row:
        result["violation_delta"] = round(
            static_row["slo_violation_rate"]
            - auto_row["slo_violation_rate"], 3,
        )
    if not tpu:
        result["note"] = (
            "CPU rig: latency rows are scheduling evidence only; the "
            "TPU row is the capacity claim"
        )
    return result


def bench_rank(tpu: bool, waits_ms=(0.0, 2.0, 5.0)):
    """Online-ranking micro-batch bench: ONE seeded Poisson arrival
    trace of feature batches replayed through the fill-or-timeout
    scheduler (tf_yarn_tpu/ranking/) at max_wait_ms ∈ {0, 2, 5} —
    the batching-policy knob's whole trade in three rows. `wait0` is
    tick-on-arrival (best p50, one engine call per request); larger
    waits coalesce rows per compiled forward, buying requests/s with
    queue latency. Every row shares the trace AND the engine, so the
    deltas are policy-only (no recompiles inside the timed window)."""
    import threading
    import time

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tf_yarn_tpu.models.dlrm import DLRM, DLRMConfig
    from tf_yarn_tpu.models.rank_engine import RankEngine
    from tf_yarn_tpu.parallel.mesh import select_devices
    from tf_yarn_tpu.ranking.scheduler import MicroBatchScheduler

    select_devices()
    if tpu:
        config = DLRMConfig.criteo()
        n_requests, mean_gap_s, row_choices = 256, 0.002, (1, 2, 4, 8)
        max_batch, buckets = 64, (1, 2, 4, 8, 16, 32, 64)
    else:
        config = DLRMConfig.tiny()
        n_requests, mean_gap_s, row_choices = 48, 0.003, (1, 2, 4)
        max_batch, buckets = 8, (1, 2, 4, 8)
    model = DLRM(config)
    rng = np.random.RandomState(0)
    sizes = np.asarray(config.table_sizes)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, len(sizes)), jnp.int32),
        jnp.zeros((1, config.n_dense), jnp.float32),
    ))
    engine = RankEngine(model, batch_buckets=buckets)

    # One seeded Poisson trace shared by every max_wait_ms row.
    arrivals = np.cumsum(rng.exponential(mean_gap_s, n_requests))
    trace = []
    for index in range(n_requests):
        rows = int(rng.choice(row_choices))
        trace.append((
            float(arrivals[index]),
            rng.randint(0, sizes, (rows, len(sizes))).astype(np.int32),
            rng.randn(rows, config.n_dense).astype(np.float32),
        ))
    total_rows = sum(cat.shape[0] for _, cat, _ in trace)

    def run_row(max_wait_ms):
        scheduler = MicroBatchScheduler(
            engine, params, max_batch=max_batch,
            max_wait_ms=max_wait_ms, queue_capacity=n_requests,
        )
        # Warmup compiles every bucket outside the timed window (cache
        # hits from the second row on — the engine is shared).
        engine.warmup(scheduler.params, max_batch=max_batch)
        ticks_before = scheduler.stats()["ticks"]
        scheduler.start()
        try:
            latencies = [None] * n_requests

            def client(index, offset, cat, dense, t0):
                lag = t0 + offset - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                scheduler.submit(cat, dense).result(timeout=600)
                # Measured against the TRACE arrival, so queue wait —
                # the cost max_wait_ms deliberately adds — counts.
                latencies[index] = time.perf_counter() - (t0 + offset)

            threads = []
            t0 = time.perf_counter()
            for index, (offset, cat, dense) in enumerate(trace):
                thread = threading.Thread(
                    target=client, args=(index, offset, cat, dense, t0)
                )
                thread.start()
                threads.append(thread)
            for thread in threads:
                thread.join(timeout=900)
            wall = time.perf_counter() - t0
            done = sorted(lat for lat in latencies if lat is not None)
            stats = scheduler.stats()
            ticks = stats["ticks"] - ticks_before
            return {
                "max_wait_ms": max_wait_ms,
                "completed": len(done),
                "requests_per_sec": round(len(done) / wall, 2),
                "rows_per_sec": round(total_rows / wall, 2),
                "latency_p50_ms": round(
                    1000 * done[len(done) // 2], 2),
                "latency_p95_ms": round(
                    1000 * done[int(0.95 * (len(done) - 1))], 2),
                "ticks": ticks,
                "rows_per_tick": round(total_rows / ticks, 2)
                if ticks else None,
            }
        finally:
            scheduler.close()

    rows = {}
    for wait in waits_ms:
        name = f"wait{wait:g}ms"
        try:
            rows[name] = run_row(wait)
        except Exception as exc:  # noqa: BLE001 - record, keep benching
            rows[name] = {"error": f"{type(exc).__name__}: {exc}"[:160]}
    return {
        "requests": n_requests,
        "total_rows": total_rows,
        "max_batch": max_batch,
        "mean_gap_ms": mean_gap_s * 1000,
        "forward_compiles": engine.stats["forward_compiles"],
        "rows": rows,
        "note": (
            "one shared trace + engine per row: requests/s and p95 vs "
            "max_wait_ms is the fill-or-timeout policy trade, nothing "
            "else"
        ),
    }


def bench_ici_allreduce(tpu: bool):
    from tf_yarn_tpu.parallel.collectives import allreduce_bandwidth
    from tf_yarn_tpu.parallel.mesh import select_devices

    return allreduce_bandwidth(
        size_mb=64.0 if tpu else 2.0, iters=10, devices=select_devices()
    )


def bench_analysis(tpu: bool):
    """Wall seconds per static-analysis engine (ast/jaxpr/hlo/concurrency)
    over the repo's own tree — the checker is a tier-1 gate, so its
    budget is a tracked number, not a vibe. Runs the real CLI in a
    subprocess (the exact gate invocation, import cost included) and
    reports the per-engine breakdown the CLI already times.

    Device-independent: the jaxpr/hlo engines trace tiny shapes and the
    lockset scenarios are pure-Python, so the CPU number IS the claim.
    """
    import subprocess
    import time

    env = dict(os.environ, JAX_PLATFORMS="cpu")  # the gate's environment
    started = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "tf_yarn_tpu.analysis", "tf_yarn_tpu",
         "--json"],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    total_s = time.monotonic() - started
    if proc.returncode != 0:
        # A dirty tree is a finding, not a crash: surface it in-band so
        # the bench line records WHY the seconds are missing.
        return {
            "exit_code": proc.returncode,
            "total_s": total_s,
            "error": (proc.stdout or proc.stderr).strip()[:400],
        }
    payload = json.loads(proc.stdout)
    race = payload.get("race_report") or {}
    return {
        "exit_code": proc.returncode,
        "total_s": total_s,
        **{f"{name}_s": secs
           for name, secs in (payload.get("engine_seconds") or {}).items()},
        "n_findings": payload.get("n_findings"),
        "n_suppressed": len(payload.get("suppressed_findings") or ()),
        "race_scenarios": len(race),
        "race_accesses": sum(
            s.get("accesses", 0) for s in race.values()
        ),
        "note": (
            "per-engine wall seconds for the four-engine checker on "
            "tf_yarn_tpu/ (subprocess = gate-identical, interpreter "
            "startup inside total_s only)"
        ),
    }


CONFIGS = {
    "mnist_dense": bench_mnist_dense,
    "linear_clicks": bench_linear_clicks,
    "bert_base": bench_bert_base,
    "dlrm_clicks": bench_dlrm_clicks,
    "resnet50": bench_resnet50,
    "vit_base": bench_vit_base,
    "llama_lora": bench_llama_lora,
    "long_context": bench_long_context,
    "decode": bench_decode,
    "fleet": bench_fleet,
    "rank": bench_rank,
    "ici_allreduce": bench_ici_allreduce,
    "analysis": bench_analysis,
}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("configs", nargs="*", default=list(CONFIGS))
    parser.add_argument(
        "--cpu", action="store_true",
        help="run toy shapes on the CPU rig (correctness only; without "
        "this flag a missing TPU is an error)",
    )
    parser.add_argument(
        "--spec", action="store_true",
        help="decode config: add the exact-vs-speculative (spec_k) A/B",
    )
    parser.add_argument(
        "--autoscale", action="store_true",
        help=(
            "fleet config: run the static-vs-autoscaled elastic A/B "
            "(rate-step trace + injected replica preemption, "
            "SLO-violation rate + streams_match) instead of the "
            "replica sweep"
        ),
    )
    args = parser.parse_args()
    if args.cpu:
        os.environ["TPU_YARN_PLATFORM"] = "cpu"  # explicit flag wins over env
    unknown = [name for name in args.configs if name not in CONFIGS]
    if unknown:
        parser.error(
            f"unknown config(s) {unknown}; choose from {sorted(CONFIGS)}"
        )
    from tf_yarn_tpu import compile_cache
    from tf_yarn_tpu.parallel.mesh import device_report, select_devices

    compile_cache.enable()
    select_devices()  # the TPU, unless --cpu named the CPU; else an error
    tpu = not args.cpu
    device = device_report()
    for name in args.configs:
        if name == "decode":
            result = CONFIGS[name](tpu, spec=args.spec)
        elif name == "fleet":
            result = CONFIGS[name](tpu, autoscale=args.autoscale)
        else:
            result = CONFIGS[name](tpu)
        print(json.dumps({
            "config": name, "tpu": tpu, "platform": device["platform"],
            "device_kind": device["kind"], "n_devices": device["count"],
            **{k: round(v, 4) if isinstance(v, float) else v
               for k, v in result.items()},
        }), flush=True)


if __name__ == "__main__":
    main()
