"""HLO engine: compile registered entries and audit the artifact (TYA2xx).

The third analysis layer. The AST engine reads *source*, the jaxpr
engine reads the *traced program* — but neither can see what the XLA
partitioner actually emits: PR 10's tensor-parallel serving deliberately
delegates all TP communication to GSPMD ("the partitioner inserts the
all-reduces from placements alone"), so a placement typo that silently
inserts a multi-GB all-gather, drops a donation alias, or doubles KV
HBM passes every jaxpr-level gate. This engine closes that hole by
lowering-and-COMPILING every registered entry (`jax.jit(fn).lower(
*avals).compile()` — abstract inputs, no FLOPs, safe on a laptop) and
checking the optimized HLO text against a per-entry declared manifest:

* TYA201 unexpected-collective — census of all-reduce / all-gather /
  reduce-scatter / collective-permute / all-to-all kinds, counts, and
  payload bytes vs the manifest (`sharded_paged_step` must show exactly its
  wo/w_down/embed all-reduces and ZERO all-gathers above the small
  floor);
* TYA202 broken-donation — declared `donate_argnums` must appear as
  `input_output_alias` in the compiled module header, else the KV
  pool/cache double-buffers in HBM;
* TYA203 host-round-trip — infeed/outfeed and host custom-call targets
  at the HLO level (a `pure_callback` that jaxpr tracing was told to
  allow, or one smuggled in below the jaxpr, compiles to
  `custom_call_target="xla_python_cpu_callback"` and friends);
* TYA204 oversized-replication — an input the entry shards elsewhere
  materialized fully-replicated above a byte threshold on a
  multi-device mesh;
* TYA205 recompile-churn — a program-cache-key registry fed by
  `DecodeEngine.program_keys()`: drives a real tiny engine several
  ticks with varying tables/lengths/tokens and flags program kinds
  that compiled more than once (those values are supposed to be
  traced, not baked into cache keys).

Census results persist to the checked-in `hlo_budgets.json` baseline
next to this file; `run()` diffs against it so a collective-count,
payload-bytes, custom-call, or aliasing regression fails tier-1 even
when it stays inside the manifest's explicit assertions. Regenerate
with `python -m tf_yarn_tpu.analysis --update-hlo-budgets` after a
reviewed change.

Entries reuse the jaxpr engine's builders (same surfaces, same avals)
minus the bare collective wrappers (psum et al. need an axis
environment that exists only under `make_jaxpr` — they cannot compile
standalone; the jaxpr engine keeps covering them). Capability gating
(`requires=("multi_device",)`) and per-entry `allow=` suppression work
exactly as in the jaxpr engine.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from tf_yarn_tpu.analysis.findings import Finding
from tf_yarn_tpu.analysis.jaxpr_engine import capabilities

# The checked-in census baseline (see module docstring).
DEFAULT_BUDGET_PATH = Path(__file__).parent / "hlo_budgets.json"

BUDGET_SCHEMA = 1

# HLO op -> canonical collective kind. `-start` variants (async pairs)
# count as the collective; `-done` halves are bookkeeping and skipped.
_COLLECTIVE_OPS = {
    "all-reduce": "all-reduce",
    "all-reduce-start": "all-reduce",
    "all-gather": "all-gather",
    "all-gather-start": "all-gather",
    "reduce-scatter": "reduce-scatter",
    "collective-permute": "collective-permute",
    "collective-permute-start": "collective-permute",
    "all-to-all": "all-to-all",
}

_COLLECTIVE_RE = re.compile(
    r" = (?P<type>.*?) (?P<op>"
    + "|".join(sorted(_COLLECTIVE_OPS, key=len, reverse=True))
    + r")\("
)

# element type -> byte width, for payload-bytes census from HLO shapes.
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4,
    "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z]\d*[a-z0-9]*|pred)\[([0-9,]*)\]")

# `input_output_alias={ {0}: (1, {}, may-alias), ... }` in the module
# header — each tuple's first field is the aliased parameter number.
_ALIAS_RE = re.compile(r"\((\d+),\s*\{\},\s*(?:may|must)-alias\)")

_CUSTOM_CALL_RE = re.compile(r'custom_call_target="([^"]+)"')

# A custom-call target that is host traffic by construction; device
# kernels must be allowlisted in ops.DEVICE_CUSTOM_CALL_TARGETS.
_HOST_TARGET_RE = re.compile(r"callback|python|host|infeed|outfeed", re.I)

_INFEED_RE = re.compile(r" = .* (infeed|outfeed)\(")


@dataclasses.dataclass(frozen=True)
class Manifest:
    """What one entry's compiled artifact is allowed to contain.

    `collectives` maps canonical kind -> EXACT expected count; kinds not
    listed must not appear at all. None means census-only (counts are
    still recorded and budget-diffed, but nothing is asserted — used
    while an entry's communication pattern is still being designed).
    Collectives whose payload is below `small_floor_bytes` are tallied
    separately and exempt from the count assertions: the partitioner
    legitimately emits tiny all-gathers for scalar bookkeeping (the
    argmax over vocab-sharded logits gathers 16 bytes), and treating
    those like a weights-sized transfer would force every manifest to
    chase partitioner minutiae.

    `donate_argnums` declares which positional args the engine donates
    (mirroring models/decode_engine.py) — verified via input_output
    aliasing (TYA202) and applied when the builder returns a bare
    (un-jitted) function. `max_replicated_bytes` arms TYA204.
    """

    collectives: Optional[Dict[str, int]] = None
    small_floor_bytes: int = 64
    donate_argnums: Tuple[int, ...] = ()
    max_replicated_bytes: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class HloEntry:
    """One compile-and-audit surface. `build` returns (fn, args, kwargs)
    exactly like the jaxpr engine's EntryPoint — and the default
    registry reuses those builders verbatim, so both layers audit the
    same lowering. A pre-jitted `fn` (has `.lower`) is compiled as-is;
    a bare fn is wrapped with the manifest's donate_argnums."""

    name: str
    build: Callable[[], Tuple[Callable, tuple, dict]]
    manifest: Manifest = Manifest()
    requires: Tuple[str, ...] = ()
    allow: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class ChurnEntry:
    """One recompile-churn probe (TYA205). `build` returns a zero-arg
    driver that exercises a real engine for several ticks and returns
    its `program_keys()` dict; `expected` caps the distinct compile
    keys per program kind (1 = tables/lengths/tokens are traced, as
    designed — a second key means a tick input leaked into the cache
    key and serving recompiles mid-flight)."""

    name: str
    build: Callable[[], Callable[[], Dict[str, List[tuple]]]]
    expected: Dict[str, int] = dataclasses.field(default_factory=dict)
    requires: Tuple[str, ...] = ()
    allow: Tuple[str, ...] = ()


@dataclasses.dataclass
class HloReport:
    findings: List[Finding]
    suppressed: List[Finding]
    skipped: List[str]
    census: Dict[str, Dict]


# --------------------------------------------------------------------------
# HLO text parsers
# --------------------------------------------------------------------------

def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue
        size = width
        for dim in dims.split(","):
            if dim:
                size *= int(dim)
        total += size
    return total


def collective_census(
    hlo_text: str, small_floor_bytes: int
) -> Tuple[Dict[str, Dict[str, int]], Dict[str, int]]:
    """(big, small) collective tallies from optimized HLO text: big is
    {kind: {count, bytes}} for payloads >= the floor, small is {kind:
    count} below it."""
    big: Dict[str, Dict[str, int]] = {}
    small: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        match = _COLLECTIVE_RE.search(line)
        if not match:
            continue
        kind = _COLLECTIVE_OPS[match.group("op")]
        nbytes = _shape_bytes(match.group("type"))
        if nbytes < small_floor_bytes:
            small[kind] = small.get(kind, 0) + 1
        else:
            entry = big.setdefault(kind, {"count": 0, "bytes": 0})
            entry["count"] += 1
            entry["bytes"] += nbytes
    return big, small


def aliased_params(hlo_text: str) -> frozenset:
    """Parameter numbers that appear in the module's input_output_alias
    header (donated inputs the compiler actually aliased)."""
    for line in hlo_text.splitlines():
        if "input_output_alias=" in line:
            return frozenset(int(n) for n in _ALIAS_RE.findall(line))
    return frozenset()


def custom_call_targets(hlo_text: str) -> Dict[str, int]:
    targets: Dict[str, int] = {}
    for target in _CUSTOM_CALL_RE.findall(hlo_text):
        targets[target] = targets.get(target, 0) + 1
    return targets


# --------------------------------------------------------------------------
# Per-entry checks
# --------------------------------------------------------------------------

def _compile_entry(entry: HloEntry):
    fn, args, kwargs = entry.build()
    if not hasattr(fn, "lower"):
        import jax

        if kwargs:
            inner = fn
            fn = jax.jit(
                lambda *a: inner(*a, **kwargs),
                donate_argnums=entry.manifest.donate_argnums,
            )
        else:
            fn = jax.jit(fn, donate_argnums=entry.manifest.donate_argnums)
        return fn.lower(*args).compile(), args
    return fn.lower(*args, **kwargs).compile(), args


def _donated_leaf_params(
    compiled, args: tuple, donate_argnums: Tuple[int, ...]
) -> Dict[int, List[int]]:
    """{argnum: compiled parameter numbers} for donated args. HLO
    numbers parameters in `tree_leaves(args)` order — over the KEPT
    leaves only: jit drops dead args (e.g. paged_prefill discards the
    prefill logits, so the final-norm/head params never become
    parameters), renumbering everything after them. A donated leaf
    that was dropped has nothing to alias and is excluded."""
    import jax

    offsets = [0]
    for arg in args:
        offsets.append(offsets[-1] + len(jax.tree_util.tree_leaves(arg)))
    kept = None
    try:
        kept = sorted(compiled._executable._kept_var_idx)
    except AttributeError:
        pass
    if kept is None or len(kept) == offsets[-1]:
        kept = list(range(offsets[-1]))
    position = {flat_idx: pos for pos, flat_idx in enumerate(kept)}
    return {
        argnum: [
            position[i]
            for i in range(offsets[argnum], offsets[argnum + 1])
            if i in position
        ]
        for argnum in donate_argnums
        if argnum < len(args)
    }


def check_entry(entry: HloEntry) -> Tuple[List[Finding], Dict]:
    """Compile one entry and audit the artifact; returns (findings,
    census record for the budget file)."""
    from tf_yarn_tpu.ops import DEVICE_CUSTOM_CALL_TARGETS

    findings: List[Finding] = []
    manifest = entry.manifest
    try:
        compiled, args = _compile_entry(entry)
        hlo_text = compiled.as_text()
    except Exception as exc:  # the finding IS the failure (cf. TYA101)
        findings.append(
            Finding(
                "TYA201",
                f"entry `{entry.name}` failed to lower/compile: "
                f"{type(exc).__name__}: {exc}",
                entry.name,
            )
        )
        return findings, {}

    # -- TYA201: collective census vs manifest ---------------------------
    big, small = collective_census(hlo_text, manifest.small_floor_bytes)
    if manifest.collectives is not None:
        for kind, expected in sorted(manifest.collectives.items()):
            actual = big.get(kind, {"count": 0})["count"]
            if actual != expected:
                findings.append(
                    Finding(
                        "TYA201",
                        f"`{entry.name}`: expected exactly {expected} "
                        f"{kind} collective(s) >= "
                        f"{manifest.small_floor_bytes}B in the compiled "
                        f"program, found {actual} "
                        f"({big.get(kind, {}).get('bytes', 0)}B total)",
                        entry.name,
                    )
                )
        for kind, info in sorted(big.items()):
            if kind not in manifest.collectives:
                findings.append(
                    Finding(
                        "TYA201",
                        f"`{entry.name}`: unexpected {kind} in the "
                        f"compiled program ({info['count']} op(s), "
                        f"{info['bytes']}B) — not in this entry's "
                        "manifest; a placement typo can insert one "
                        "silently",
                        entry.name,
                    )
                )

    # -- TYA202: declared donation must appear as aliasing ---------------
    aliased = aliased_params(hlo_text)
    for argnum, leaf_params in sorted(
        _donated_leaf_params(compiled, args, manifest.donate_argnums).items()
    ):
        if leaf_params and not any(p in aliased for p in leaf_params):
            findings.append(
                Finding(
                    "TYA202",
                    f"`{entry.name}`: donated arg {argnum} (parameters "
                    f"{leaf_params}) has no input_output_alias in the "
                    "compiled artifact — the donation was dropped and "
                    "the buffer double-buffers in HBM",
                    entry.name,
                )
            )

    # -- TYA203: host round-trips in the compiled program ----------------
    unknown_calls: Dict[str, int] = {}
    for target, count in sorted(custom_call_targets(hlo_text).items()):
        if target in DEVICE_CUSTOM_CALL_TARGETS:
            continue
        if _HOST_TARGET_RE.search(target):
            findings.append(
                Finding(
                    "TYA203",
                    f"`{entry.name}`: host custom-call "
                    f'`{target}` x{count} in the compiled program — a '
                    "device<->host round-trip per execution (per tick, "
                    "in a serving step)",
                    entry.name,
                )
            )
        else:
            # Backend compute kernels (TopK etc.): not host traffic, but
            # recorded so the budget diff flags a new one appearing.
            unknown_calls[target] = count
    for op_kind in set(_INFEED_RE.findall(hlo_text)):
        findings.append(
            Finding(
                "TYA203",
                f"`{entry.name}`: `{op_kind}` op in the compiled program "
                "— host transfer inside the hot path",
                entry.name,
            )
        )

    # -- TYA204: oversized fully-replicated operands ---------------------
    if manifest.max_replicated_bytes is not None:
        findings.extend(
            _check_replication(
                entry.name, compiled, args, manifest.max_replicated_bytes
            )
        )

    census = {
        "collectives": big,
        "small_collectives": small,
        "custom_calls": unknown_calls,
        "aliased_params": len(aliased),
    }
    return findings, census


def _check_replication(
    name: str, compiled, args: tuple, threshold_bytes: int
) -> List[Finding]:
    import jax

    try:
        in_shardings = compiled.input_shardings[0]
    except Exception:
        return []
    shardings = jax.tree_util.tree_leaves(in_shardings)
    avals = jax.tree_util.tree_leaves(args)
    if len(shardings) != len(avals):
        return []
    findings = []
    for index, (sharding, aval) in enumerate(zip(shardings, avals)):
        shape = tuple(getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", None)
        if dtype is None or not shape:
            continue
        nbytes = int(dtype.itemsize)
        for dim in shape:
            nbytes *= int(dim)
        if nbytes <= threshold_bytes:
            continue
        devices = getattr(sharding, "device_set", None)
        n_devices = (
            len(devices) if devices is not None
            else getattr(sharding, "num_devices", 1)
        )
        if n_devices <= 1:
            continue
        if getattr(sharding, "is_fully_replicated", False):
            findings.append(
                Finding(
                    "TYA204",
                    f"`{name}`: input parameter {index} "
                    f"({dtype.name}{list(shape)}, {nbytes}B) is "
                    f"fully replicated across {n_devices} devices — "
                    f"{nbytes * n_devices}B of HBM for an operand above "
                    f"the {threshold_bytes}B replication budget",
                    name,
                )
            )
    return findings


def check_churn(entry: ChurnEntry) -> List[Finding]:
    findings: List[Finding] = []
    try:
        keys = entry.build()()
    except Exception as exc:
        findings.append(
            Finding(
                "TYA205",
                f"churn probe `{entry.name}` failed to run: "
                f"{type(exc).__name__}: {exc}",
                entry.name,
            )
        )
        return findings
    for kind, max_keys in sorted(entry.expected.items()):
        observed = keys.get(kind, [])
        if len(observed) > max_keys:
            findings.append(
                Finding(
                    "TYA205",
                    f"`{entry.name}`: program kind `{kind}` compiled "
                    f"{len(observed)} distinct cache keys (budget "
                    f"{max_keys}) across ticks whose tables/lengths/"
                    f"tokens should be traced — keys: {observed}",
                    entry.name,
                )
            )
    return findings


# --------------------------------------------------------------------------
# Budget baseline
# --------------------------------------------------------------------------

def load_budget(path: Path) -> Optional[Dict]:
    try:
        data = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError):
        return None
    if data.get("schema") != BUDGET_SCHEMA:
        return None
    return data


def diff_budget(
    census: Dict[str, Dict], budget: Optional[Dict], budget_path: Path
) -> List[Finding]:
    """Findings for census drift vs the checked-in baseline. Field drift
    maps to the rule that owns the field, so a suppression of (say)
    TYA203 on an entry also covers its custom-call budget line."""
    findings: List[Finding] = []
    if budget is None:
        findings.append(
            Finding(
                "TYA201",
                f"no HLO budget baseline at {budget_path} — run "
                "`python -m tf_yarn_tpu.analysis --update-hlo-budgets` "
                "and check the file in",
                str(budget_path),
            )
        )
        return findings
    baseline = budget.get("entries", {})
    field_rule = {
        "collectives": "TYA201",
        "small_collectives": "TYA201",
        "custom_calls": "TYA203",
        "aliased_params": "TYA202",
    }
    for name, record in sorted(census.items()):
        base = baseline.get(name)
        if base is None:
            findings.append(
                Finding(
                    "TYA201",
                    f"`{name}`: no baseline in {budget_path.name} — "
                    "review the census and run --update-hlo-budgets",
                    name,
                )
            )
            continue
        for field, rule in field_rule.items():
            if record.get(field) != base.get(field):
                findings.append(
                    Finding(
                        rule,
                        f"`{name}`: compiled-artifact census drifted "
                        f"from {budget_path.name} — {field}: "
                        f"{base.get(field)!r} -> {record.get(field)!r}; "
                        "if intentional, re-run with "
                        "--update-hlo-budgets and commit the diff",
                        name,
                    )
                )
    return findings


def write_budget(
    census: Dict[str, Dict], path: Path, skipped_names: Sequence[str] = ()
) -> None:
    """Persist the census; entries skipped on THIS rig (capability
    gating) keep their existing baseline so a 1-device update doesn't
    wipe the sharded entries' numbers."""
    existing = load_budget(path)
    entries = dict(existing.get("entries", {})) if existing else {}
    for name in skipped_names:
        entries.setdefault(name, {})
    entries.update(census)
    Path(path).write_text(
        json.dumps(
            {"schema": BUDGET_SCHEMA, "entries": entries},
            indent=1, sort_keys=True,
        )
        + "\n"
    )


# --------------------------------------------------------------------------
# Engine driver
# --------------------------------------------------------------------------

def run(
    entries: Optional[Sequence[HloEntry]] = None,
    churn_entries: Optional[Sequence[ChurnEntry]] = None,
    budget_path: Optional[Path] = DEFAULT_BUDGET_PATH,
    update_budgets: bool = False,
) -> HloReport:
    """Compile-and-audit every entry; returns an HloReport. Pass
    `budget_path=None` to skip the baseline diff (fixture runs);
    `update_budgets=True` rewrites the baseline instead of diffing."""
    if entries is None:
        entries = default_entries()
    if churn_entries is None:
        churn_entries = default_churn_entries()
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    skipped: List[str] = []
    skipped_names: List[str] = []
    census: Dict[str, Dict] = {}
    caps = capabilities()

    def _route(entry_findings, allow):
        allowed = set(allow)
        for finding in entry_findings:
            (suppressed if finding.code in allowed else findings).append(
                finding
            )

    for entry in entries:
        missing = [r for r in entry.requires if r not in caps]
        if missing:
            skipped.append(
                f"{entry.name}: this process lacks {', '.join(missing)}"
            )
            skipped_names.append(entry.name)
            continue
        entry_findings, record = check_entry(entry)
        _route(entry_findings, entry.allow)
        if record:
            census[entry.name] = record

    if budget_path is not None:
        if update_budgets:
            write_budget(census, budget_path, skipped_names)
        else:
            allow_by_entry = {e.name: e.allow for e in entries}
            for finding in diff_budget(
                census, load_budget(budget_path), Path(budget_path)
            ):
                _route([finding], allow_by_entry.get(finding.path, ()))

    for entry in churn_entries:
        missing = [r for r in entry.requires if r not in caps]
        if missing:
            skipped.append(
                f"{entry.name}: this process lacks {', '.join(missing)}"
            )
            continue
        _route(check_churn(entry), entry.allow)

    return HloReport(findings, suppressed, skipped, census)


# --------------------------------------------------------------------------
# The repo's entry registry — reuses the jaxpr builders (same surfaces,
# same avals) so both layers audit the same lowering.
# --------------------------------------------------------------------------

def _jaxpr_builds() -> Dict[str, Callable]:
    from tf_yarn_tpu.analysis import jaxpr_engine

    return {
        e.name: e.build for e in jaxpr_engine.default_entry_points()
    }


# Donation map mirrors models/decode_engine.py's _jit(donate=...) calls
# exactly — TYA202 verifies the aliasing serving actually runs with.
_NO_COLLECTIVES = Manifest(collectives={})


def default_entries() -> List[HloEntry]:
    builds = _jaxpr_builds()

    def _entry(name, manifest=_NO_COLLECTIVES, requires=(), allow=()):
        return HloEntry(
            name, builds[name], manifest=manifest, requires=requires,
            allow=allow,
        )

    replicated_budget = 1 << 20  # tiny-model params are all far below
    return [
        # ops kernels: pure single-device compute, zero collectives.
        _entry("ops.attention.xla_attention"),
        _entry("ops.rmsnorm.rmsnorm"),
        _entry("ops.rmsnorm.rmsnorm_grad"),
        _entry("ops.layernorm.layernorm"),
        _entry("ops.quantize.int8_roundtrip"),
        # train step (fwd+bwd): single-device lowering here; the
        # data-parallel gradient psum lives under shard_map and is
        # covered by the jaxpr layer's axis checks.
        _entry("models.transformer.fwd_bwd"),
        # decode engine programs — donation mirrors DecodeEngine._jit.
        _entry("models.decode_engine.prefill"),
        _entry(
            "models.decode_engine.decode_loop",
            Manifest(collectives={}, donate_argnums=(1, 7)),
        ),
        _entry(
            "models.decode_engine.paged_step",
            Manifest(collectives={}, donate_argnums=(1, 5)),
        ),
        _entry(
            "models.decode_engine.paged_prefill",
            Manifest(collectives={}, donate_argnums=(2,)),
        ),
        # The KV-oversubscription swap programs: extract is a read-only
        # gather (NOT donated — the pool must survive the suspend),
        # inject donates the pool so resume splices in place. Zero
        # collectives: swap traffic is the scheduler's one planned bulk
        # device_get/put, never a cross-device exchange.
        _entry("models.decode_engine.extract_blocks"),
        _entry(
            "models.decode_engine.inject_blocks",
            Manifest(collectives={}, donate_argnums=(0,)),
        ),
        _entry(
            "models.decode_engine.paged_spec_step",
            Manifest(collectives={}, donate_argnums=(1, 7)),
        ),
        # The chunk-apply (the windowed program at the chunked width):
        # admission replays prompt chunks through it interleaved with
        # decode, so it carries the same zero-collective, pool+rngs
        # donation contract as paged_spec_step.
        _entry(
            "models.decode_engine.chunk_apply",
            Manifest(collectives={}, donate_argnums=(1, 7)),
        ),
        # THE headline manifests: the tp=2 serving tick. GSPMD must
        # insert exactly the matmul-partial all-reduces (embed + wo +
        # w_down, fused per scan body) and NO all-gather above the
        # small floor — an all-gather here means a weights- or
        # KV-sized re-materialization per tick. The 16-byte argmax
        # gathers over vocab-sharded logits land in the small census.
        _entry(
            "models.decode_engine.sharded_paged_step",
            Manifest(
                collectives={"all-reduce": 3, "all-gather": 0},
                donate_argnums=(1, 5),
                max_replicated_bytes=replicated_budget,
            ),
            requires=("multi_device",),
        ),
        # The tp=2 chunk-apply: chunked admission shares the decode
        # tick's mesh, so its census is pinned identically — the three
        # matmul-partial all-reduces and NO all-gather above the floor.
        _entry(
            "models.decode_engine.sharded_chunk_apply",
            Manifest(
                collectives={"all-reduce": 3, "all-gather": 0},
                donate_argnums=(1, 7),
                max_replicated_bytes=replicated_budget,
            ),
            requires=("multi_device",),
        ),
        # The ranking tick (models/rank_engine.py): a bucketed DLRM
        # forward, zero collectives single-device.
        _entry("models.rank_engine.forward"),
        # The EMBEDDING-SHARDED ranking forward. GSPMD resolves the
        # lookup into tp-sharded tables as masked partial lookups plus
        # exactly ONE batch-sized all-reduce (the gathered embedding
        # rows: batch x tables x embed_dim floats — 1KB here), and must
        # NOT emit an all-gather above the small floor: an all-gather
        # would re-materialize the full tables per tick, the exact HBM
        # blowup sharding them 1/tp per device exists to avoid.
        _entry(
            "models.rank_engine.sharded_forward",
            Manifest(
                collectives={"all-reduce": 1, "all-gather": 0},
                max_replicated_bytes=replicated_budget,
            ),
            requires=("multi_device",),
        ),
    ]


def _decode_churn_driver() -> Callable[[], Dict[str, List[tuple]]]:
    def drive():
        import jax
        import jax.numpy as jnp
        from flax import linen as nn

        from tf_yarn_tpu.models.decode_engine import DecodeEngine, all_forced
        from tf_yarn_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
        )

        config = TransformerConfig.tiny(
            max_seq_len=32, scan_layers=False, remat=False
        )
        model = Transformer(config)
        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        )
        engine = DecodeEngine(
            model, batch_buckets=(2,), prompt_buckets=(8,)
        )
        slots, block_size = 2, 8
        pool = engine.make_paged_pool(params, 5, block_size)
        rngs = jnp.stack(
            [jax.random.PRNGKey(i) for i in range(slots)]
        )
        mask = jnp.ones((slots,), jnp.bool_)
        max_blocks = config.max_seq_len // block_size
        width = 4  # the chunked/spec window width — a fixed compile key
        eos_ids = jnp.full((slots,), -1, jnp.int32)
        for tick in range(3):
            # Every per-tick input varies: tokens, rngs, block tables,
            # lengths. A cache keyed on any of them recompiles here.
            tokens = jnp.full((slots,), tick + 3, jnp.int32)
            tables = jnp.full(
                (slots, max_blocks), (tick % 3) + 1, jnp.int32
            )
            lengths = jnp.full((slots,), tick + 1, jnp.int32)
            pool, _emitted, rngs = engine.paged_step(
                params, pool, tables, lengths, *all_forced(tokens, rngs),
                mask, block_size=block_size,
            )
            # The windowed tick doubles as chunked prefill's chunk-apply:
            # n_known sweeping 0 -> width (decode-heavy to all-known
            # replay) is traced data, never a compile key (TYA205).
            window = jnp.full((slots, width), tick + 5, jnp.int32)
            n_known = jnp.full((slots,), min(tick * 2, width), jnp.int32)
            pool, _emitted, _counts, rngs = engine.paged_spec_step(
                params, pool, tables, lengths, window, n_known, eos_ids,
                rngs, mask, block_size=block_size,
            )
        return engine.program_keys()

    return drive


def _swap_churn_driver() -> Callable[[], Dict[str, List[tuple]]]:
    def drive():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from flax import linen as nn

        from tf_yarn_tpu.models.decode_engine import DecodeEngine, all_forced
        from tf_yarn_tpu.models.transformer import (
            Transformer,
            TransformerConfig,
        )
        from tf_yarn_tpu.serving.paging import TRASH_BLOCK

        config = TransformerConfig.tiny(
            max_seq_len=32, scan_layers=False, remat=False
        )
        model = Transformer(config)
        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        )
        engine = DecodeEngine(
            model, batch_buckets=(2,), prompt_buckets=(8,)
        )
        slots, block_size = 2, 8
        pool = engine.make_paged_pool(params, 5, block_size)
        rngs = jnp.stack(
            [jax.random.PRNGKey(i) for i in range(slots)]
        )
        mask = jnp.ones((slots,), jnp.bool_)
        max_blocks = config.max_seq_len // block_size
        for tick in range(3):
            # One suspend/resume round per tick, interleaved with the
            # decode tick. Block ids, fill counts, tokens, lengths all
            # vary — every one is traced data, never a compile key.
            tokens = jnp.full((slots,), tick + 3, jnp.int32)
            tables = jnp.full(
                (slots, max_blocks), (tick % 3) + 1, jnp.int32
            )
            lengths = jnp.full((slots,), tick + 1, jnp.int32)
            pool, _emitted, rngs = engine.paged_step(
                params, pool, tables, lengths, *all_forced(tokens, rngs),
                mask, block_size=block_size,
            )
            ids = np.full((max_blocks,), TRASH_BLOCK, np.int32)
            ids[: tick + 1] = np.arange(1, tick + 2, dtype=np.int32)
            payload = jax.device_get(
                engine.extract_blocks(params, pool, ids, block_size)
            )
            pool = engine.inject_blocks(
                params, pool, ids, payload, block_size
            )
        return engine.program_keys()

    return drive


def _rank_churn_driver() -> Callable[[], Dict[str, List[tuple]]]:
    def drive():
        import jax
        import numpy as np

        from tf_yarn_tpu.models.dlrm import DLRM, DLRMConfig
        from tf_yarn_tpu.models.rank_engine import RankEngine

        config = DLRMConfig.tiny()
        model = DLRM(config)
        engine = RankEngine(model, batch_buckets=(4,))
        params = model.init(
            jax.random.PRNGKey(0),
            np.zeros((1, len(config.table_sizes)), np.int32),
            np.zeros((1, config.n_dense), np.float32),
        )
        rng = np.random.default_rng(0)
        for batch in (1, 3, 4, 2):
            # Every per-tick input varies: ids, dense values, batch
            # size (all inside the one bucket). A cache keyed on any
            # of them recompiles here.
            cat = rng.integers(
                0, 64, (batch, len(config.table_sizes))
            ).astype(np.int32)
            dense = rng.standard_normal(
                (batch, config.n_dense)
            ).astype(np.float32)
            engine.rank(params, cat, dense)
        return engine.program_keys()

    return drive


def default_churn_entries() -> List[ChurnEntry]:
    return [
        ChurnEntry(
            "models.decode_engine.tick_churn",
            _decode_churn_driver,
            # One compiled program per kind across 3 ticks of varying
            # tokens/rngs/tables/lengths — those are traced, never keys.
            # paged_spec_step covers the chunk-apply: n_known sweeps the
            # whole decode-to-replay range without minting a second program.
            expected={"paged_step": 1, "paged_spec_step": 1},
        ),
        ChurnEntry(
            "models.decode_engine.swap_churn",
            _swap_churn_driver,
            # Three suspend/resume rounds interleaved with decode ticks:
            # block ids, fill counts, and lengths all vary, yet swap
            # mints exactly ONE extract and ONE inject program (fixed
            # table width; ids are traced) and the decode tick itself
            # never recompiles across the churn.
            expected={"paged_step": 1, "extract": 1, "inject": 1},
        ),
        ChurnEntry(
            "models.rank_engine.rank_churn",
            _rank_churn_driver,
            # Four micro-batches of varying size inside one bucket:
            # ids/values are traced and padding normalizes the shape,
            # so exactly one compiled forward may exist.
            expected={"forward": 1},
        ),
    ]
