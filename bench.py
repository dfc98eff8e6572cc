"""Benchmark: flagship training-step throughput on the local TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": N,
   "device": {"platform": ..., "kind": ..., "count": N}, ...}

The reference publishes no numbers, so vs_baseline compares against the
value recorded in BENCH_BASELINE.json when present (our own previous
run), else 1.0. The full per-config suite lives in benchmarks/run.py.

The bench A/Bs the kernel knobs (attention_impl=xla|flash, fused_norms
on/off), adds decode (bf16 vs int8 KV cache), serving, fleet, ranking and
long-context (S=8192) sections, and writes everything to BENCH_AB.json
with measurement provenance (device, git commit, timestamp). The headline
reports the *best* training variant (the unit string names the winning
impl).

It measures the chip or nothing: without a TPU it fails at
`select_devices`, and a variant, section or family that raises is
reported, the others still run, and the exit code is 1. ROADMAP.md (A0,
C1) replaces this runner with one over `workloads`; until then
`python chip_smoke.py` is the proof that the system runs on the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
_AB_PATH = os.path.join(_REPO, "BENCH_AB.json")

# The flagship TPU bench config (chip_smoke.py drives the same model).
_TPU_BASE = dict(
    vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
    n_kv_heads=8, d_ff=4096, max_seq_len=2048, remat=False,
)
_TPU_BATCH, _TPU_SEQ, _TPU_STEPS = 8, 1024, 20


def _config_hash(cfg: dict) -> str:
    import hashlib

    return hashlib.sha256(
        json.dumps(cfg, sort_keys=True, default=str).encode()
    ).hexdigest()[:12]


def _code_hash() -> str:
    """Fingerprint of the kernel + train-loop source a TPU measurement
    depends on, recorded with it: a number in BENCH_AB.json can then be
    told apart from one that measured other code."""
    import glob
    import hashlib

    digest = hashlib.sha256()
    paths = sorted(
        glob.glob(os.path.join(_REPO, "tf_yarn_tpu", "ops", "*.py"))
        # The kernel DISPATCH (attention_impl / fused_norms wiring) lives
        # in the model files — a rewrite there changes what a TPU number
        # measures just as surely as a kernel edit.
        + glob.glob(os.path.join(_REPO, "tf_yarn_tpu", "models", "*.py"))
    )
    paths.append(os.path.join(_REPO, "tf_yarn_tpu", "training.py"))
    paths.append(os.path.join(_REPO, "tf_yarn_tpu", "benchmark.py"))
    paths.append(os.path.join(_REPO, "benchmarks", "run.py"))
    for path in paths:
        try:
            with open(path, "rb") as fh:
                digest.update(os.path.basename(path).encode())
                digest.update(fh.read())
        except OSError:
            digest.update(f"missing:{os.path.basename(path)}".encode())
    return digest.hexdigest()[:12]


def _log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def _git_head() -> str:
    try:
        return subprocess.run(
            ["git", "-C", _REPO, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
    except Exception:
        return ""


def _ab_file_provenance() -> dict:
    """(commit, date) the committed BENCH_AB.json was last touched at —
    the provenance trail for stale reporting when the file predates the
    embedded measured_at/git_commit fields."""
    try:
        out = subprocess.run(
            ["git", "-C", _REPO, "log", "-1", "--format=%h|%cI", "--",
             "BENCH_AB.json"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
        commit, _, date = out.partition("|")
        return {"git_commit": commit, "measured_at": date}
    except Exception:
        return {"git_commit": "", "measured_at": ""}


def _write_ab(table: dict) -> None:
    try:
        with open(_AB_PATH, "w") as fh:
            json.dump(table, fh, indent=1)
        _log(f"A/B table -> {_AB_PATH}")
    except OSError as exc:
        _log(f"could not write A/B table: {exc}")


def _load_bench_suite():
    """benchmarks/run.py as a module (no package __init__ there)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tpu_yarn_bench_suite", os.path.join(_REPO, "benchmarks", "run.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _run_variant(config, batch_size: int, seq_len: int, steps: int,
                 devices):
    import numpy as np
    import optax

    from tf_yarn_tpu.benchmark import measure_throughput
    from tf_yarn_tpu.models import common
    from tf_yarn_tpu.models.transformer import Transformer

    tokens = np.random.RandomState(0).randint(
        0, config.vocab_size, (batch_size, seq_len), dtype=np.int32
    )
    return measure_throughput(
        Transformer(config),
        common.lm_loss,
        optax.adamw(1e-4),
        {"tokens": tokens},
        steps=steps,
        devices=devices,
    )


def bench_flagship_train(failures: list):
    """The flagship variants, then every other section. A section that
    raises is named in `failures` and the rest still run; `main` turns a
    non-empty list into exit code 1."""
    from tf_yarn_tpu.models.transformer import TransformerConfig
    from tf_yarn_tpu.parallel.mesh import device_report, select_devices

    devices = select_devices()  # TPU chips, or an error that says why not
    _log(f"benchmarking on {len(devices)} x {devices[0].device_kind}")

    # remat off: this config's activations fit one chip's HBM, so
    # recompute would only burn MXU cycles.
    base = dict(_TPU_BASE)
    batch_size, seq_len, steps = _TPU_BATCH, _TPU_SEQ, _TPU_STEPS
    # Axes: layer-scan on/off (unrolling lets XLA fuse across layer
    # boundaries), attention xla/flash, fused pallas norms on/off.
    variants = [
        ("xla", dict(attention_impl="xla", fused_norms=False)),
        ("xla+fused_norms", dict(attention_impl="xla", fused_norms=True)),
        ("xla+fused+unroll", dict(attention_impl="xla", fused_norms=True,
                                  scan_layers=False)),
        # fused norms with the recompute backward vs the dx kernels — the
        # rmsnorm-bwd A/B (TPU_YARN_NORM_KERNEL_BWD env seam,
        # docs/Performance.md).
        ("flash+fused+unroll+bwd_recompute",
         dict(attention_impl="flash", fused_norms=True,
              scan_layers=False, _norm_kernel_bwd=False)),
        ("flash+fused+unroll", dict(attention_impl="flash",
                                    fused_norms=True, scan_layers=False)),
    ]

    table = []
    model_desc = None
    for name, overrides in variants:
        overrides = dict(overrides)
        norm_bwd = overrides.pop("_norm_kernel_bwd", True)
        config = TransformerConfig(**{**base, **overrides})
        model_desc = f"d_model={config.d_model}, layers={config.n_layers}"
        from tf_yarn_tpu.benchmark import kernel_bwd_env

        try:
            with kernel_bwd_env(norm_bwd):
                stats = _run_variant(
                    config, batch_size, seq_len, steps, devices
                )
        except Exception as exc:  # the other variants still run
            _log(f"variant {name}: FAILED: {type(exc).__name__}: {exc}")
            table.append({"variant": name, "error": f"{exc}"})
            failures.append(f"variant {name}")
            continue
        row = {
            "variant": name,
            "samples_per_sec_per_chip": round(
                stats["samples_per_sec_per_chip"], 3),
            "step_time_ms": round(stats["step_time_ms"], 2),
            "mfu": round(stats["mfu"], 4) if "mfu" in stats else None,
            "final_loss": round(stats["final_loss"], 4),
        }
        table.append(row)
        _log(f"variant {name}: {row['samples_per_sec_per_chip']} samples/s/chip, "
             f"step {row['step_time_ms']}ms, mfu={row['mfu']}")

    ok_rows = [r for r in table if "error" not in r]
    if not ok_rows:
        raise RuntimeError(
            "every flagship variant failed: "
            + "; ".join(str(r.get("error", ""))[:120] for r in table)
        )
    best = max(ok_rows, key=lambda r: r["samples_per_sec_per_chip"])

    result = {
        "metric": "flagship_train_samples_per_sec_per_chip",
        "value": best["samples_per_sec_per_chip"],
        "unit": f"samples/sec/chip ({model_desc}, seq={seq_len}, "
        f"bf16, tpu, {best['variant']})",
        "device": device_report(),
    }
    if best.get("mfu") is not None:
        result["mfu"] = best["mfu"]

    # --- TPU: persist the A/B table incrementally (flagship first, so a
    # timeout mid-extras still leaves it recorded), then fold in decode
    # and long-context — the driver artifact carries all three surfaces.
    # Previous decode/long-context sections are carried forward with a
    # staleness label until their fresh run succeeds: a failed extra must
    # not erase the last hardware evidence for that surface.
    try:
        with open(_AB_PATH) as fh:
            previous = json.load(fh)
    except (OSError, ValueError):
        previous = {}
    ab = {
        "config": {**base, "batch": batch_size, "seq": seq_len},
        "config_hash": _config_hash({**base, "batch": batch_size,
                                     "seq": seq_len}),
        "code_hash": _code_hash(),
        "device": devices[0].device_kind,
        "git_commit": _git_head(),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "rows": table,
    }
    for section in ("decode", "long_context", "fleet", "rank",
                    "bert_base", "resnet50", "vit_base"):
        if previous.get(section):
            ab[section] = {
                **previous[section],
                # Keep the ORIGINAL measurement commit across repeated
                # carry-forwards — previous.git_commit is only right the
                # first time the section goes stale.
                "stale_from_commit": previous[section].get("stale_from_commit")
                or previous.get("git_commit")
                or _ab_file_provenance()["git_commit"],
            }
    _write_ab(ab)

    suite = _load_bench_suite()
    try:
        decode = suite.bench_decode(tpu=True)
        ab["decode"] = decode
        _write_ab(ab)
        result["decode_tokens_per_sec_bf16"] = decode[
            "decode_tokens_per_sec_bf16"]
        result["decode_tokens_per_sec_int8"] = decode[
            "decode_tokens_per_sec_int8"]
        # Serving-path A/B (DecodeEngine vs per-call jit), when the
        # suite produced it.
        for key in ("engine_tokens_per_sec_bf16",
                    "engine_tokens_per_sec_int8",
                    "percall_jit_tokens_per_sec_bf16",
                    "percall_jit_tokens_per_sec_int8"):
            if key in decode:
                result[key] = decode[key]
        _log(f"decode: {decode}")
    except Exception as exc:
        _log(f"decode bench FAILED: {type(exc).__name__}: {exc}")
        failures.append("decode")
    try:
        fleet = suite.bench_fleet(tpu=True)
        ab["fleet"] = fleet
        _write_ab(ab)
        # Fleet scale-out headline: aggregate tokens/s + tail TTFT
        # through the router per replica count, plus the scaling
        # ratios vs one replica (ROADMAP item 1's named bench).
        for row_name, row in (fleet.get("rows") or {}).items():
            if isinstance(row, dict) and "tokens_per_sec" in row:
                result[f"fleet_{row_name}_tokens_per_sec"] = row[
                    "tokens_per_sec"
                ]
                result[f"fleet_{row_name}_ttft_p95_ms"] = row.get(
                    "ttft_p95_ms"
                )
        for key, value in fleet.items():
            if str(key).startswith("scaling_"):
                result[f"fleet_{key}"] = value
        _log(f"fleet: {fleet}")
    except Exception as exc:
        _log(f"fleet bench FAILED: {type(exc).__name__}: {exc}")
        failures.append("fleet")
    try:
        # Elastic A/B (ROADMAP item 1's autoscaler): static fleet
        # vs autoscaled fleet under the same seeded rate-step trace
        # with one injected preemption + relaunch. Headline: the
        # SLO-violation delta and the bit-identity flag.
        fleet_as = suite.bench_fleet(tpu=True, autoscale=True)
        ab.setdefault("fleet", {})["autoscale"] = fleet_as
        _write_ab(ab)
        for row_name, row in (fleet_as.get("rows") or {}).items():
            if isinstance(row, dict) and "slo_violation_rate" in row:
                result[
                    f"fleet_autoscale_{row_name}_slo_violation_rate"
                ] = row["slo_violation_rate"]
                result[f"fleet_autoscale_{row_name}_ttft_p95_ms"] = (
                    row.get("ttft_p95_ms")
                )
        auto_row = (fleet_as.get("rows") or {}).get("autoscaled") or {}
        for key in ("scale_events", "warm_start_pulls", "warm_starts",
                    "warm_start_blocks"):
            if key in auto_row:
                result[f"fleet_autoscale_{key}"] = auto_row[key]
        for key in ("violation_delta", "streams_match"):
            if key in fleet_as:
                result[f"fleet_autoscale_{key}"] = fleet_as[key]
        _log(f"fleet autoscale: {fleet_as}")
    except Exception as exc:
        _log(f"fleet autoscale bench FAILED: "
             f"{type(exc).__name__}: {exc}")
        failures.append("fleet autoscale")
    try:
        rank = suite.bench_rank(tpu=True)
        ab["rank"] = rank
        _write_ab(ab)
        # Ranking micro-batch headline: requests/s + tail latency
        # per max_wait_ms row — the fill-or-timeout policy trade
        # (docs/Ranking.md) measured on the Criteo-shape DLRM.
        for row_name, row in (rank.get("rows") or {}).items():
            if isinstance(row, dict) and "requests_per_sec" in row:
                result[f"rank_{row_name}_requests_per_sec"] = row[
                    "requests_per_sec"
                ]
                result[f"rank_{row_name}_latency_p95_ms"] = row.get(
                    "latency_p95_ms"
                )
        _log(f"rank: {rank}")
    except Exception as exc:
        _log(f"rank bench FAILED: {type(exc).__name__}: {exc}")
        failures.append("rank")
    try:
        longctx = suite.bench_long_context(tpu=True)
        # Fresh measurement replaces any carried-forward stale section.
        ab["long_context"] = {
            key: longctx[key]
            for key in ("tokens_per_sec_per_chip", "step_time_ms", "mfu",
                        "variants", "attn_microbench")
            if key in longctx
        }
        _write_ab(ab)
        result["longctx_tokens_per_sec"] = longctx["tokens_per_sec_per_chip"]
        if "mfu" in longctx:
            result["longctx_mfu"] = longctx["mfu"]
        _log(f"long_context: {ab['long_context']}")
    except Exception as exc:
        _log(f"long-context bench FAILED: {type(exc).__name__}: {exc}")
        failures.append("long_context")
    # The full model-family A/B matrices run AFTER the headline JSON
    # line prints (main) — a driver timeout mid-matrix must never cost
    # the round its headline record.
    return result, (suite, ab)


def _record_analysis_seconds(result: dict, failures: list) -> None:
    """Per-engine wall seconds for the four-engine static checker
    (ast/jaxpr/hlo/concurrency over tf_yarn_tpu/), folded into the
    headline line as `analysis_*_s` tracked fields. The checker is a
    tier-1 gate, so its budget drifting up is a regression this line
    makes visible round over round. Device-independent (tiny traced
    shapes, pure-Python lockset scenarios), so it runs on every rig.
    TPU_YARN_BENCH_SKIP_ANALYSIS=1 opts out for a quick run."""
    if os.environ.get("TPU_YARN_BENCH_SKIP_ANALYSIS") == "1":
        return
    try:
        suite = _load_bench_suite()
        stats = suite.bench_analysis(tpu=False)
    except Exception as exc:  # the bench headline must still print
        _log(f"analysis bench FAILED: {type(exc).__name__}: {exc}")
        failures.append("analysis")
        return
    for key in ("total_s", "ast_s", "jaxpr_s", "hlo_s", "concurrency_s"):
        if key in stats:
            result[f"analysis_{key}"] = round(float(stats[key]), 4)
    if "exit_code" in stats:
        result["analysis_exit_code"] = stats["exit_code"]
    if "error" in stats:
        result["analysis_error"] = stats["error"]
    _log(f"analysis engine seconds: {stats}")


def _run_family_blitz(suite, ab, failures: list) -> None:
    """The model-family A/B matrices (bert fused-LN fwd/bwd, resnet
    stem/batch, ViT fused-LN), captured in the same command as the
    flagship and incrementally persisted to BENCH_AB.json so a timeout
    mid-matrix keeps the earlier sections.
    TPU_YARN_BENCH_SKIP_FAMILIES=1 opts out for a quick run."""
    if os.environ.get("TPU_YARN_BENCH_SKIP_FAMILIES") == "1":
        return
    for section in ("bert_base", "resnet50", "vit_base"):
        try:
            bench_fn = getattr(suite, f"bench_{section}")
            stats = bench_fn(tpu=True)
            ab[section] = {
                key: stats[key]
                for key in ("samples_per_sec_per_chip",
                            "step_time_ms", "mfu", "variants")
                if key in stats
            }
            _write_ab(ab)
            _log(f"{section}: {ab[section]}")
        except Exception as exc:
            _log(f"{section} bench FAILED: {type(exc).__name__}: {exc}")
            failures.append(section)


def main() -> int:
    from tf_yarn_tpu import compile_cache

    compile_cache.enable()
    failures: list = []
    result, pending_blitz = bench_flagship_train(failures)
    baseline_path = os.path.join(_REPO, "BENCH_BASELINE.json")
    vs_baseline = 1.0
    if os.path.exists(baseline_path):
        try:
            with open(baseline_path) as fh:
                baseline = json.load(fh)
            if baseline.get("metric") == result["metric"] and baseline.get("value"):
                vs_baseline = round(result["value"] / float(baseline["value"]), 3)
        except (ValueError, OSError):
            pass
    result["vs_baseline"] = vs_baseline
    _record_analysis_seconds(result, failures)
    print(json.dumps(result))
    sys.stdout.flush()
    # Post-headline capture: the family matrices only ever ADD to
    # BENCH_AB.json; the one-line stdout contract above is already met.
    _run_family_blitz(*pending_blitz, failures)
    if failures:
        _log(f"FAILED sections: {failures}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
