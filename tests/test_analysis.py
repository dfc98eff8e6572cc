"""The static checker checks itself: every rule flags its bad fixture,
the clean fixture stays clean (false-positive guard), the repo passes
its own checker (the CI gate — any future PR introducing a flagged
pattern fails here), the jaxpr engine verifies the collectives
wrappers' axis discipline, and the HLO engine detects every seeded
TYA201–205 violation in its compiled-artifact fixtures."""

import importlib.util
import os
import subprocess
import sys

import pytest

from tf_yarn_tpu.analysis.ast_engine import (
    analyze_paths,
    collect_declared_axes,
)
from tf_yarn_tpu.analysis.findings import Finding, noqa_lines
from tf_yarn_tpu.analysis.rules import RULES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")
HLO_FIXTURES = os.path.join(FIXTURES, "hlo")
CONC_FIXTURES = os.path.join(FIXTURES, "concurrency")
RACE_FIXTURES = os.path.join(FIXTURES, "race")

AST_RULES = sorted(code for code, rule in RULES.items() if rule.engine == "ast")
HLO_RULES = sorted(code for code, rule in RULES.items() if rule.engine == "hlo")
# The static half of the concurrency engine (TYA311/312 are dynamic-only
# and exercised through the racecheck scenario tests below).
CONC_STATIC_RULES = ["TYA301", "TYA302", "TYA303"]
SCENARIO_NAMES = {
    "serving.slot_scheduler", "serving.suspend_resume",
    "serving.prefill_ship", "ranking.micro_batch", "fleet.registry",
    "fleet.monitor", "fleet.autoscaler", "telemetry.metrics_spans",
    "checkpoint.writer",
}


# --- AST engine: each rule fires on its fixture, and only its rule -------

@pytest.mark.parametrize("code", AST_RULES)
def test_bad_fixture_flags_exactly_its_rule(code):
    path = os.path.join(FIXTURES, f"bad_{code.lower()}.py")
    findings = analyze_paths([path])
    codes = {f.code for f in findings}
    assert codes == {code}, (
        f"{path} expected only {code}, got {sorted(codes)}: "
        f"{[f.format() for f in findings]}"
    )
    assert len(findings) >= 1


def test_clean_fixture_has_no_findings():
    findings = analyze_paths([os.path.join(FIXTURES, "clean.py")])
    assert findings == [], [f.format() for f in findings]


def test_every_ast_rule_has_a_fixture():
    for code in AST_RULES:
        assert os.path.exists(
            os.path.join(FIXTURES, f"bad_{code.lower()}.py")
        ), f"no fixture for {code}"


def test_noqa_suppresses_matching_code_only(tmp_path):
    src = (
        "import jax\n"
        'a = jax.lax.psum(1.0, "zz")  # noqa: TYA006\n'
        'b = jax.lax.psum(1.0, "qq")  # noqa\n'
        'c = jax.lax.psum(1.0, "ww")  # noqa: TYA001\n'
    )
    path = tmp_path / "noqa_case.py"
    path.write_text(src)
    findings = analyze_paths([str(path)])
    assert [f.code for f in findings] == ["TYA006"]
    assert findings[0].line == 4


def test_noqa_inside_string_literal_is_not_a_suppression():
    sup = noqa_lines('x = "contains # noqa: TYA006 in a string"\n')
    assert sup == {}


def test_declared_axis_collection():
    import ast

    tree = ast.parse(
        'AXIS_X = "xx"\n'
        "from jax.sharding import Mesh\n"
        'm = Mesh(devs, ("aa", "bb"))\n'
        'def f(v, axis="cc"):\n'
        "    return v\n"
        "class S:\n"
        "    @property\n"
        "    def axis_names(self):\n"
        '        return ("dd", "ee")\n'
    )
    assert collect_declared_axes([tree]) == {"xx", "aa", "bb", "cc", "dd", "ee"}


# --- the repo gates itself ------------------------------------------------

def _run_checker(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "tf_yarn_tpu.analysis", *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )


def test_repo_passes_its_own_checker():
    """THE analysis gate: one invocation runs AST + jaxpr + HLO +
    concurrency over the repo, and the per-engine wall time lands in
    the tier-1 log so a creeping analysis budget is visible, not just
    felt."""
    import json

    proc = _run_checker("tf_yarn_tpu", "--json")
    assert proc.returncode == 0, (
        "the checker found problems in tf_yarn_tpu/ — fix them, "
        "suppress with # noqa: TYA0xx / entry allow= / scenario allow=, "
        f"or re-baseline hlo_budgets.json:\n{proc.stdout}\n{proc.stderr}"
    )
    payload = json.loads(proc.stdout)
    assert payload["json_schema_version"] == 3
    seconds = payload["engine_seconds"]
    assert set(seconds) == {"ast", "jaxpr", "hlo", "concurrency"}
    print(
        "analysis engine seconds: "
        + " ".join(f"{k}={v}" for k, v in sorted(seconds.items()))
    )
    # All five lockset scenarios ran over the real hot objects, with
    # zero unsuppressed races (suppressions are justified in
    # docs/StaticAnalysis.md and surface in suppressed_findings).
    race_report = payload["race_report"]
    assert set(race_report) == SCENARIO_NAMES
    for name, scenario in race_report.items():
        assert scenario["races"] == scenario["suppressed"], (name, scenario)
        assert scenario["lock_cycles"] == [], (name, scenario)
        assert scenario["threads"] >= 2, (name, scenario)
    assert any(
        f["code"] == "TYA311" for f in payload["suppressed_findings"]
    ), "expected the advisory-counter suppressions to surface"
    # The headline manifest ran (8 CPU devices are forced in this env):
    # sharded_paged_step's census is present, with its exact all-reduce count
    # and zero above-floor all-gathers baked into the manifest check.
    census = payload["hlo_census"]
    assert "models.decode_engine.sharded_paged_step" in census
    assert (
        census["models.decode_engine.sharded_paged_step"]["collectives"][
            "all-reduce"]["count"] == 3
    )
    assert "all-gather" not in (
        census["models.decode_engine.sharded_paged_step"]["collectives"]
    )


def test_checker_clean_over_telemetry_and_instrumented_sites():
    """The telemetry layer's contract: instrumentation lives strictly
    outside jit bodies. Linting the package plus every instrumented call
    site directly (not just via the whole-tree run) pins the gate — a
    span/clock/registry call smuggled into a jit body fails here."""
    instrumented = [
        "tf_yarn_tpu/telemetry",
        "tf_yarn_tpu/resilience",
        "tf_yarn_tpu/serving",
        "tf_yarn_tpu/ranking",
        "tf_yarn_tpu/fleet",
        "tf_yarn_tpu/training.py",
        "tf_yarn_tpu/inference.py",
        "tf_yarn_tpu/models/decode_engine.py",
        "tf_yarn_tpu/models/rank_engine.py",
        "tf_yarn_tpu/models/spec.py",
        "tf_yarn_tpu/tasks/serving.py",
        "tf_yarn_tpu/tasks/rank.py",
        "tf_yarn_tpu/tasks/router.py",
        "tf_yarn_tpu/tasks/prefill.py",
        "tf_yarn_tpu/checkpoint.py",
        "tf_yarn_tpu/client.py",
        "tf_yarn_tpu/coordination/kv.py",
        "tf_yarn_tpu/data/prefetch.py",
        "tf_yarn_tpu/experiment.py",
        "tf_yarn_tpu/tasks/worker.py",
        "tf_yarn_tpu/event.py",
        "tf_yarn_tpu/utils/metrics.py",
    ]
    paths = [os.path.join(REPO, p) for p in instrumented]
    for path in paths:
        assert os.path.exists(path), path
    findings = analyze_paths(paths)
    assert findings == [], [f.format() for f in findings]


def test_fixtures_fail_the_checker():
    # --no-race: the fixture sweep wants the static lints only (the
    # dynamic scenario suite audits the repo, not fixture files).
    proc = _run_checker(FIXTURES, "--no-jaxpr", "--no-hlo", "--no-race")
    assert proc.returncode == 2, proc.stdout + proc.stderr
    # every AST + static-concurrency rule shows up in the aggregate run
    for code in AST_RULES + CONC_STATIC_RULES:
        assert code in proc.stdout, f"{code} missing from:\n{proc.stdout}"


def test_checker_json_output():
    import json

    proc = _run_checker(
        FIXTURES, "--no-jaxpr", "--no-hlo", "--no-race", "--json"
    )
    assert proc.returncode == 2
    payload = json.loads(proc.stdout)
    assert payload["json_schema_version"] == 3
    assert payload["n_findings"] == len(payload["findings"]) > 0
    assert {f["code"] for f in payload["findings"]} >= set(
        AST_RULES + CONC_STATIC_RULES
    )
    # suppressed findings surface as notices, never silently vanish
    assert "suppressed_findings" in payload


def test_checker_exit_codes_distinguish_findings_from_errors():
    """0 clean / 2 findings / 1 engine or usage error — CI can tell
    'the code has defects' from 'the checker itself broke'."""
    # findings -> 2 (asserted above on the fixtures); usage error -> 1
    proc = _run_checker("--definitely-not-a-flag")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    # engine error (nonexistent path) -> 1, not 2
    proc = _run_checker("no/such/path_anywhere", "--no-jaxpr", "--no-hlo")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "error" in proc.stderr.lower()
    # --help is not an error
    proc = _run_checker("--help")
    assert proc.returncode == 0


# --- jaxpr engine ---------------------------------------------------------

def test_jaxpr_engine_collectives_verify_clean():
    from tf_yarn_tpu.analysis.jaxpr_engine import _collective_entries, run

    findings, counts, skipped, suppressed = run(_collective_entries())
    assert findings == [], [f.format() for f in findings]
    assert skipped == []
    assert suppressed == []
    assert counts["parallel.collectives.all_reduce_sum"]["psum"] == 1
    assert counts["parallel.collectives.ring_shift"]["ppermute"] == 1
    assert counts["parallel.collectives.all_gather"]["all_gather"] == 1


def test_jaxpr_engine_flags_axis_outside_expected():
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.analysis.jaxpr_engine import EntryPoint, check_entry

    def build():
        from tf_yarn_tpu.parallel import collectives

        return (
            lambda x: collectives.all_reduce_sum(x, "tp"),
            (jax.ShapeDtypeStruct((4,), jnp.float32),),
            {},
        )

    entry = EntryPoint(
        "test.wrong_axis", build,
        axis_env=(("dp", 2), ("tp", 2)), expected_axes=("dp",),
    )
    findings, _counts = check_entry(entry)
    assert [f.code for f in findings] == ["TYA102"]
    assert "'tp'" in findings[0].message


def test_jaxpr_engine_flags_unbound_axis_as_trace_failure():
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.analysis.jaxpr_engine import EntryPoint, check_entry

    def build():
        return (
            lambda x: jax.lax.psum(x, "dpp"),  # noqa: TYA006 - deliberate
            (jax.ShapeDtypeStruct((4,), jnp.float32),),
            {},
        )

    entry = EntryPoint("test.typo", build, axis_env=(("dp", 2),))
    findings, _counts = check_entry(entry)
    assert [f.code for f in findings] == ["TYA101"]


def test_jaxpr_engine_flags_host_callback_in_hot_path():
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.analysis.jaxpr_engine import EntryPoint, check_entry

    def build():
        def chatty(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        return chatty, (jax.ShapeDtypeStruct((4,), jnp.float32),), {}

    entry = EntryPoint("test.chatty", build)
    findings, counts = check_entry(entry)
    assert [f.code for f in findings] == ["TYA103"]
    assert counts.get("debug_print") == 1  # what jax.debug.print traces to


def test_jaxpr_engine_default_entries_clean_on_this_build():
    from tf_yarn_tpu.analysis.jaxpr_engine import run

    findings, counts, skipped, _suppressed = run()
    assert findings == [], [f.format() for f in findings]
    # the flagship model traced: lowering regressions show as count diffs
    assert "models.transformer.fwd_bwd" in counts
    assert counts["models.transformer.fwd_bwd"]["dot_general"] > 0
    # the serving path traced: the decode loop is a hot entry, so a host
    # callback smuggled into it fails here, and the while_loop itself
    # must be present (the on-device-EOS-loop contract).
    assert "models.decode_engine.prefill" in counts
    assert counts["models.decode_engine.decode_loop"]["while"] >= 1
    # the continuous-batching slot step traced too: it runs once per
    # generated token across the whole serving grid, so it is exactly
    # where a smuggled host callback would hurt most. It must contain
    # the block-table gather AND the scatter-append (the whole point of
    # the pool) — findings == [] above already asserts both paged
    # entries trace clean.
    assert "models.decode_engine.paged_step" in counts
    paged = counts["models.decode_engine.paged_step"]
    assert paged["dot_general"] > 0
    assert paged.get("gather", 0) > 0
    assert paged.get("scatter", 0) > 0
    assert "models.decode_engine.paged_prefill" in counts
    assert counts["models.decode_engine.paged_prefill"][
        "dynamic_update_slice"] > 0
    # The WINDOWED ticks: the verify over the gathered view (accept/reject
    # masking fully traced; the chunk-apply) and the FUSED verify —
    # findings == [] above
    # already asserts both are host-callback-free; the fused entry must
    # actually contain the pallas kernel call (the paged int8 decode-
    # attention wire-up this gate exists to pin).
    window = counts["models.decode_engine.chunk_apply"]
    assert window["dot_general"] > 0
    assert window.get("gather", 0) > 0
    fused = counts["models.decode_engine.paged_spec_step"]
    assert fused["dot_general"] > 0
    assert fused.get("pallas_call", 0) > 0
    assert fused.get("scatter", 0) > 0


def test_jaxpr_engine_allow_suppresses_and_surfaces():
    """The jaxpr/HLO twin of `# noqa`: an entry-level allow= keeps the
    finding out of failures but surfaces it as a notice."""
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.analysis.jaxpr_engine import EntryPoint, run

    def build():
        def chatty(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        return chatty, (jax.ShapeDtypeStruct((4,), jnp.float32),), {}

    entry = EntryPoint("test.allowed_chatty", build, allow=("TYA103",))
    findings, _counts, _skipped, suppressed = run([entry])
    assert findings == [], [f.format() for f in findings]
    assert [f.code for f in suppressed] == ["TYA103"]


def test_finding_format_and_json_roundtrip():
    finding = Finding("TYA006", "msg", "a/b.py", 3, 7)
    assert finding.format() == "a/b.py:3:7: TYA006 msg"
    assert finding.to_json()["line"] == 3


# --- HLO engine: compiled-artifact audits ---------------------------------

def _load_hlo_fixture(name):
    path = os.path.join(HLO_FIXTURES, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"hlo_fixture_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _run_hlo_fixture(module, **overrides):
    from tf_yarn_tpu.analysis import hlo_engine

    return hlo_engine.run(
        entries=overrides.get("entries", getattr(module, "ENTRIES", [])),
        churn_entries=getattr(module, "CHURN", []),
        budget_path=None,  # fixtures have no baseline; manifests only
    )


@pytest.mark.parametrize("code", ["TYA201", "TYA202", "TYA203", "TYA204",
                                  "TYA205"])
def test_hlo_bad_fixture_flags_exactly_its_rule(code):
    report = _run_hlo_fixture(_load_hlo_fixture(f"bad_{code.lower()}"))
    assert report.skipped == [], report.skipped
    codes = {f.code for f in report.findings}
    assert codes == {code}, (
        f"expected only {code}, got {sorted(codes)}: "
        f"{[f.format() for f in report.findings]}"
    )


def test_hlo_clean_fixture_has_no_findings():
    report = _run_hlo_fixture(_load_hlo_fixture("clean"))
    assert report.findings == [], [f.format() for f in report.findings]
    assert report.suppressed == []
    # the clean entry's donation really aliased (the check has teeth)
    assert report.census["fixture.clean.donated_step"]["aliased_params"] > 0


def test_every_hlo_rule_has_a_fixture():
    for code in HLO_RULES:
        assert os.path.exists(
            os.path.join(HLO_FIXTURES, f"bad_{code.lower()}.py")
        ), f"no fixture for {code}"


def test_hlo_entry_allow_suppresses_and_surfaces():
    import dataclasses

    module = _load_hlo_fixture("bad_tya203")
    allowed = [
        dataclasses.replace(entry, allow=("TYA203",))
        for entry in module.ENTRIES
    ]
    report = _run_hlo_fixture(module, entries=allowed)
    assert report.findings == [], [f.format() for f in report.findings]
    assert [f.code for f in report.suppressed] == ["TYA203"]


def test_hlo_collective_census_parser():
    from tf_yarn_tpu.analysis.hlo_engine import collective_census

    text = (
        "  %ar = f32[2,64]{1,0} all-reduce(%x), replica_groups={{0,1}}\n"
        "  %ag = f32[4]{0} all-gather(%y), dimensions={0}\n"
        "  %ars = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-reduce-start(%a, %b)\n"
        "  %ard = f32[8,8]{1,0} all-reduce-done(%ars)\n"
    )
    big, small = collective_census(text, small_floor_bytes=64)
    assert big["all-reduce"]["count"] == 2  # plain + -start; -done skipped
    assert big["all-reduce"]["bytes"] == 2 * 64 * 4 + 2 * 8 * 8 * 4
    assert small == {"all-gather": 1}  # 16B, below the floor


def test_hlo_alias_parser():
    from tf_yarn_tpu.analysis.hlo_engine import aliased_params

    text = (
        "HloModule jit_step, input_output_alias={ {0}: (1, {}, may-alias),"
        " {2}: (3, {}, must-alias) }, entry_computation_layout=...\n"
        "  %body = ...\n"
    )
    assert aliased_params(text) == frozenset({1, 3})
    assert aliased_params("HloModule jit_f, entry...\n") == frozenset()


def test_hlo_budget_diff_detects_regression(tmp_path):
    from pathlib import Path

    from tf_yarn_tpu.analysis.hlo_engine import (
        diff_budget,
        load_budget,
        write_budget,
    )

    path = Path(tmp_path) / "budgets.json"
    baseline_census = {
        "entry.a": {
            "collectives": {"all-reduce": {"count": 3, "bytes": 1536}},
            "small_collectives": {}, "custom_calls": {},
            "aliased_params": 4,
        },
    }
    write_budget(baseline_census, path)
    budget = load_budget(path)
    # identical census: clean
    assert diff_budget(baseline_census, budget, path) == []
    # a fourth all-reduce appears: TYA201
    drifted = {
        "entry.a": {
            **baseline_census["entry.a"],
            "collectives": {"all-reduce": {"count": 4, "bytes": 2048}},
        },
    }
    codes = [f.code for f in diff_budget(drifted, budget, path)]
    assert codes == ["TYA201"]
    # a donation alias disappears: TYA202
    dropped = {
        "entry.a": {**baseline_census["entry.a"], "aliased_params": 0},
    }
    codes = [f.code for f in diff_budget(dropped, budget, path)]
    assert codes == ["TYA202"]
    # an entry with no baseline at all is itself a finding
    codes = [
        f.code
        for f in diff_budget({"entry.new": {}}, budget, path)
    ]
    assert codes == ["TYA201"]
    # and a missing budget file fails loudly, not silently
    missing = [f.code for f in diff_budget({}, None, path)]
    assert missing == ["TYA201"]


def test_hlo_budget_file_is_checked_in_and_current_schema():
    from tf_yarn_tpu.analysis.hlo_engine import (
        DEFAULT_BUDGET_PATH,
        load_budget,
    )

    budget = load_budget(DEFAULT_BUDGET_PATH)
    assert budget is not None, (
        f"{DEFAULT_BUDGET_PATH} missing or wrong schema — regenerate "
        "with `python -m tf_yarn_tpu.analysis --update-hlo-budgets`"
    )
    entries = budget["entries"]
    # the headline baselines are pinned: the tp=2 serving ticks
    assert entries["models.decode_engine.sharded_paged_step"][
        "collectives"]["all-reduce"]["count"] == 3
    assert "all-gather" not in (
        entries["models.decode_engine.sharded_paged_step"]["collectives"]
    )
    for gone in ("step", "spec_step", "sharded_step"):
        assert "models.decode_engine." + gone not in entries


# --- concurrency engine: static lint (TYA301-303) ------------------------

@pytest.mark.parametrize("code", CONC_STATIC_RULES)
def test_concurrency_bad_fixture_flags_exactly_its_rule(code):
    from tf_yarn_tpu.analysis.concurrency import (
        analyze_paths as analyze_concurrency,
    )

    path = os.path.join(CONC_FIXTURES, f"bad_{code.lower()}.py")
    findings = analyze_concurrency([path])
    codes = {f.code for f in findings}
    assert codes == {code}, (
        f"{path} expected only {code}, got {sorted(codes)}: "
        f"{[f.format() for f in findings]}"
    )


def test_concurrency_clean_fixture_has_no_findings():
    from tf_yarn_tpu.analysis.concurrency import (
        analyze_paths as analyze_concurrency,
    )

    findings = analyze_concurrency(
        [os.path.join(CONC_FIXTURES, "clean.py")]
    )
    assert findings == [], [f.format() for f in findings]


def test_concurrency_repo_lint_is_clean():
    """The in-process half of the gate (the subprocess repo gate above
    covers the CLI): today's tree satisfies its own lock discipline.
    This is also the regression net for the PR 16 fixes — reverting the
    ServingServer/RankServer/RouterServer/SlotScheduler/
    MicroBatchScheduler/Heartbeat stop paths, the KVServer join, or the
    RankEngine stats guard re-flags here."""
    from tf_yarn_tpu.analysis.concurrency import (
        analyze_paths as analyze_concurrency,
    )

    findings = analyze_concurrency([os.path.join(REPO, "tf_yarn_tpu")])
    assert findings == [], [f.format() for f in findings]


def test_concurrency_noqa_suppresses(tmp_path):
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.total = 0\n"
        "    def add(self, n):\n"
        "        with self._lock:\n"
        "            self.total += n\n"
        "    def reset(self):\n"
        "        self.total = 0  # noqa: TYA301\n"
    )
    path = tmp_path / "noqa_conc.py"
    path.write_text(src)
    from tf_yarn_tpu.analysis.concurrency import (
        analyze_paths as analyze_concurrency,
    )

    assert analyze_concurrency([str(path)]) == []


def test_guarded_by_annotation_binds_the_guard(tmp_path):
    """A `# guarded-by: <lock>` annotation makes EVERY unguarded write a
    finding — even when the with-block inference alone would see only
    one guarded site."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.total = 0  # guarded-by: _lock\n"
        "    def reset(self):\n"
        "        self.total = 0\n"
    )
    path = tmp_path / "guarded_by.py"
    path.write_text(src)
    from tf_yarn_tpu.analysis.concurrency import (
        analyze_paths as analyze_concurrency,
    )

    findings = analyze_concurrency([str(path)])
    assert [f.code for f in findings] == ["TYA301"]


# --- concurrency engine: dynamic lockset checker (TYA311/312) ------------


def _load_race_fixture(name):
    path = os.path.join(RACE_FIXTURES, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"race_fixture_{name}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.build_scenario()


def test_seeded_race_fixture_is_flagged():
    from tf_yarn_tpu.analysis.racecheck import run_scenario

    report = run_scenario(_load_race_fixture("racy"))
    assert [f.code for f in report.findings] == ["TYA311"]
    message = report.findings[0].message
    # both call sites ride along in the finding
    assert "counter.value" in message
    assert "race-t" in message
    assert report.n_threads == 3


def test_guarded_race_fixture_is_clean():
    from tf_yarn_tpu.analysis.racecheck import run_scenario

    report = run_scenario(_load_race_fixture("guarded"))
    assert report.findings == [], [f.format() for f in report.findings]
    assert report.races == []
    assert report.n_threads == 3
    assert report.n_accesses > 0  # the tracer did observe the accesses


def test_lock_order_cycle_is_flagged():
    import threading

    from tf_yarn_tpu.analysis.racecheck import (
        RaceTracer, Scenario, run_scenario,
    )

    class TwoLocks:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()

    def drive(tracer):
        obj = TwoLocks()
        tracer.watch(obj, "locks")
        with obj.a:
            with obj.b:
                pass
        with obj.b:
            with obj.a:
                pass

    report = run_scenario(Scenario(name="cycle", run=drive))
    assert [f.code for f in report.findings] == ["TYA312"]
    assert report.cycles, "the a->b->a cycle must be in the report"
    assert "locks.a" in report.findings[0].message
    assert "locks.b" in report.findings[0].message


def test_scenario_suite_zero_unsuppressed_races():
    """The tier-1 lockset gate over the REAL hot objects: a new
    unguarded access to scheduler state, BlockPool/PrefixCache
    refcounts, registry replicas, checkpoint futures, or telemetry
    instruments fails here with both stack traces in the message."""
    from tf_yarn_tpu.analysis import racecheck

    report = racecheck.run()
    assert report.findings == [], [f.format() for f in report.findings]
    assert set(report.report) == SCENARIO_NAMES
    for name, scenario in report.report.items():
        assert scenario["lock_cycles"] == [], (name, scenario)
        assert scenario["threads"] >= 2, (name, scenario)
        assert scenario["accesses"] > 0, (name, scenario)
    # every suppression is a justified TYA311 advisory-counter entry
    assert all(f.code == "TYA311" for f in report.suppressed)
    assert all("allowed:" in f.message for f in report.suppressed)


def test_race_tracer_preserves_scheduler_behavior():
    """Overhead guard: instrumentation must never heisenbug the
    scheduler — the traced run emits the same tokens and the same tick
    trace (modulo global request ids) as the plain run."""
    from tf_yarn_tpu.analysis.racecheck import RaceTracer
    from tf_yarn_tpu.analysis.scenarios import (
        drive_paged_scheduler, make_paged_scheduler,
    )

    prompts = [[1, 2, 3, 4, 5], [2, 3, 4, 5, 6], [7, 8, 9, 10, 11]]

    def shape(scheduler):
        return [
            (
                entry["tick"], len(entry["admitted"]),
                sorted(reason for _, reason in entry["retired"]),
                entry["active"], entry["queued"],
            )
            for entry in scheduler.trace
        ]

    plain = make_paged_scheduler()
    plain_tokens = [
        r.result(5.0) for r in drive_paged_scheduler(plain, prompts)
    ]

    traced = make_paged_scheduler()
    tracer = RaceTracer()
    tracer.watch(traced, "scheduler")
    tracer.watch(traced._blocks, "pool")
    tracer.watch(traced._prefix, "prefix")
    try:
        traced_tokens = [
            r.result(5.0) for r in drive_paged_scheduler(traced, prompts)
        ]
    finally:
        tracer.release()

    assert traced_tokens == plain_tokens
    assert shape(traced) == shape(plain)
    assert tracer.n_accesses > 0
    # and release() restored the real class: no proxy left behind
    assert type(traced).__module__ != "tf_yarn_tpu.analysis.racecheck"


@pytest.mark.slow
def test_scenario_suite_is_deterministic_across_repeats():
    """Heavyweight stability pass (slow rig precedent: PR 12/14): the
    sequential-phase drivers must produce the identical race set every
    run — zero flake by construction."""
    from tf_yarn_tpu.analysis import racecheck

    baseline = None
    for _ in range(3):
        report = racecheck.run()
        assert report.findings == []
        counts = {
            name: (entry["races"], entry["suppressed"])
            for name, entry in report.report.items()
        }
        if baseline is None:
            baseline = counts
        assert counts == baseline


@pytest.mark.slow
def test_registry_scenario_scales_to_a_large_fleet():
    """Heavyweight registry variant: 8 replicas, repeated refresh/fail/
    policy cycles — the fast in-suite representative is the 2-replica
    scenario inside default_scenarios()."""
    import threading

    from tf_yarn_tpu import event
    from tf_yarn_tpu.analysis.racecheck import RaceTracer
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.fleet.policy import LeastLoadedPolicy
    from tf_yarn_tpu.fleet.registry import ReplicaRegistry

    kv = InProcessKV()
    tasks = [f"serving:{i}" for i in range(8)]
    for index, task in enumerate(tasks):
        kv.put_str(
            f"{task}/{event.SERVING_ENDPOINT}", f"127.0.0.1:{9100 + index}"
        )

    def probe(endpoint):
        return {"status": "ok", "queue_depth": int(endpoint[-1]) % 4,
                "active_slots": 1}

    registry = ReplicaRegistry(kv, tasks, probe=probe, probe_interval_s=0.0)
    tracer = RaceTracer()
    tracer.watch(registry, "registry")

    def run_phase(name, body):
        thread = threading.Thread(target=body, name=name, daemon=True)
        thread.start()
        thread.join(timeout=60.0)
        assert not thread.is_alive(), f"phase {name} wedged"

    try:
        run_phase("fleet-refresh-0", lambda: registry.refresh(force=True))
        for task in tasks:
            tracer.watch(registry.get(task), f"replica[{task}]")
        policy = LeastLoadedPolicy()

        def reads():
            for _ in range(8):
                healthy = registry.healthy()
                if healthy:
                    policy.pick(healthy)
                registry.snapshot()

        for round_index in range(4):
            run_phase(
                f"fleet-fail-{round_index}",
                lambda i=round_index: registry.report_failure(
                    tasks[i % len(tasks)], ConnectionError("boom")
                ),
            )
            run_phase(
                f"fleet-refresh-{round_index + 1}",
                lambda: registry.refresh(force=True),
            )
            run_phase(f"fleet-reads-{round_index}", reads)
        races = tracer.races()
        assert races == [], races
        assert tracer.lock_cycles() == []
    finally:
        tracer.release()
