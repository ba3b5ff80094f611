"""fluxlint tests: per-rule positive/negative fixtures, the guard-
deletion and rank-wrap mutation checks (the acceptance contract: these
edits to real hot-path files MUST fail the lint), suppression + baseline
round trips, JSON output, CLI exit codes, and the tier-1 repo-clean
assertion. The analyzer is pure stdlib — no jax needed beyond what the
package import pulls in — so everything here is fast."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from fluxmpi_tpu.analysis import (
    Baseline,
    ProjectContext,
    default_rules,
    lint_repo,
    lint_source,
)
from fluxmpi_tpu.analysis.rules import (
    HandBuiltMesh,
    JaxCompatDrift,
    SpmdDivergentCollective,
    UndocumentedEnvVar,
    UnguardedHotPathInstrumentation,
    UnknownMetricName,
    UnregisteredFaultSite,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CLI = os.path.join(_REPO, "scripts", "fluxlint.py")


def _ctx(**kw):
    """Small synthetic project context for fixture snippets."""
    defaults = dict(
        known_metric_names=frozenset({"train.loss", "fault.injected"}),
        closed_namespaces=("fault.",),
        known_fault_sites=frozenset({"ckpt.write", "data.fetch"}),
        documented_env_vars={"FLUXMPI_TPU_DOCUMENTED": 10},
        tests_corpus="scope('ckpt.write') scope('data.fetch')",
    )
    defaults.update(kw)
    return ProjectContext(**defaults)


def _keys(report, rule):
    return [f.key for f in report.findings if f.rule == rule]


# ---------------------------------------------------------------------------
# Rule 1: spmd-divergent-collective
# ---------------------------------------------------------------------------


def test_spmd_flags_collective_under_rank_branch():
    src = textwrap.dedent(
        """
        import jax
        from . import comm
        def f(x):
            if jax.process_index() == 0:
                comm.allreduce(x)
        """
    )
    r = lint_source(src, "pkg/a.py", _ctx(), rules=[SpmdDivergentCollective()])
    assert _keys(r, "spmd-divergent-collective") == ["f:allreduce:branch"]


def test_spmd_flags_collective_after_rank_early_exit_via_local_bool():
    src = textwrap.dedent(
        """
        import jax
        from . import comm
        def f(x):
            lead = jax.process_index() == 0
            if not lead:
                return
            comm.barrier()
        """
    )
    r = lint_source(src, "pkg/a.py", _ctx(), rules=[SpmdDivergentCollective()])
    assert _keys(r, "spmd-divergent-collective") == ["f:barrier:after-exit"]


def test_spmd_quiet_on_spmd_consistent_twins():
    # All-ranks collective with lead-only *side effects*, and a
    # world-size condition: both fine.
    src = textwrap.dedent(
        """
        import jax
        from . import comm
        def f(x):
            out = comm.allreduce(x)
            if jax.process_index() == 0:
                print(out)
            if jax.process_count() > 1:
                comm.barrier()
            return out
        """
    )
    r = lint_source(src, "pkg/a.py", _ctx(), rules=[SpmdDivergentCollective()])
    assert r.findings == []


def test_spmd_mutation_of_train_loop_fails_the_lint():
    # The acceptance check: wrapping a collective in a
    # process_index()==0 branch in the real dispatch loop must produce a
    # finding (here: the coordination host_allreduce in train_loop).
    path = os.path.join(_REPO, "fluxmpi_tpu", "parallel", "loop.py")
    with open(path, encoding="utf-8") as f:
        src = f.read()
    target = "if coordinate and at_flush and bool("
    assert target in src
    mutated = src.replace(
        target, "if jax.process_index() == 0 and coordinate and bool("
    )
    ctx = ProjectContext.load(_REPO)
    clean = lint_source(
        src, "fluxmpi_tpu/parallel/loop.py", ctx,
        rules=[SpmdDivergentCollective()],
    )
    assert clean.findings == []
    bad = lint_source(
        mutated, "fluxmpi_tpu/parallel/loop.py", ctx,
        rules=[SpmdDivergentCollective()],
    )
    # The coordination collective lives in train_loop's _post_dispatch
    # closure (the shared pipelined/fused boundary hook) — the key names
    # the innermost function, the prefix anchors it to train_loop.
    assert "train_loop._post_dispatch:host_allreduce:shortcircuit" in _keys(
        bad, "spmd-divergent-collective"
    )


# ---------------------------------------------------------------------------
# Rule 2: unguarded-hot-path-instrumentation
# ---------------------------------------------------------------------------

_HOT = (("pkg/hot.py", "hot", "function"),)


def test_hot_path_flags_unguarded_timing_and_handles():
    src = textwrap.dedent(
        """
        import time
        def hot(reg, x):
            t0 = time.perf_counter()
            reg.histogram("train.step_seconds").observe(time.time() - t0)
            return x
        """
    )
    r = lint_source(
        src, "pkg/hot.py", _ctx(),
        rules=[UnguardedHotPathInstrumentation(_HOT)],
    )
    keys = set(_keys(r, "unguarded-hot-path-instrumentation"))
    assert "hot:time.perf_counter" in keys
    assert "hot:histogram" in keys


def test_hot_path_quiet_on_guarded_twin():
    # Both guard idioms: enclosing `if guard:` and the early
    # `if not guard: return` fast path; IfExp guards too.
    src = textwrap.dedent(
        """
        import time
        def hot(reg, tracer, x):
            enabled = reg.enabled or tracer.enabled
            t0 = time.perf_counter() if enabled else 0.0
            if not enabled:
                return x
            reg.histogram("train.step_seconds").observe(
                time.perf_counter() - t0
            )
            return x
        """
    )
    r = lint_source(
        src, "pkg/hot.py", _ctx(),
        rules=[UnguardedHotPathInstrumentation(_HOT)],
    )
    assert r.findings == []


def test_hot_path_guard_polarity_of_negated_local():
    # `off = not reg.enabled` is truthy when instrumentation is OFF:
    # code under `if off:` is the exact contract violation, and an
    # `if off: return` early exit DOES guard what follows.
    src = textwrap.dedent(
        """
        import time
        def hot(reg, x):
            off = not reg.enabled
            if off:
                t0 = time.perf_counter()
            if off:
                return x
            return time.perf_counter()
        """
    )
    r = lint_source(
        src, "pkg/hot.py", _ctx(),
        rules=[UnguardedHotPathInstrumentation(_HOT)],
    )
    flagged = [f for f in r.findings
               if f.rule == "unguarded-hot-path-instrumentation"]
    assert len(flagged) == 1 and flagged[0].line == 6  # only the OFF-path call


def test_hot_path_loops_scope_keeps_guard_context_in_nested_loops():
    hot = (("pkg/hot.py", "drive", "loops"),)
    src = textwrap.dedent(
        """
        import time
        def drive(reg, batches):
            enabled = reg.enabled
            t_start = time.perf_counter()
            for batch in batches:
                if enabled:
                    for part in batch:
                        reg.histogram("train.step_seconds").observe(1.0)
                for part in batch:
                    t = time.perf_counter()
        """
    )
    r = lint_source(
        src, "pkg/hot.py", _ctx(),
        rules=[UnguardedHotPathInstrumentation(hot)],
    )
    # t_start (function level) is out of scope; the guarded nested loop
    # is quiet; the unguarded nested call is reported exactly once.
    flagged = [f for f in r.findings
               if f.rule == "unguarded-hot-path-instrumentation"]
    assert [(f.key, f.line) for f in flagged] == [
        ("drive:time.perf_counter", 11)
    ]


def test_hot_path_ignores_functions_outside_the_hot_set():
    src = "import time\ndef cold():\n    return time.perf_counter()\n"
    r = lint_source(
        src, "pkg/hot.py", _ctx(),
        rules=[UnguardedHotPathInstrumentation(_HOT)],
    )
    assert r.findings == []


def test_hot_path_guard_deletion_in_comm_fails_the_lint():
    # The acceptance check: deleting the _instrumentation_on() guard in
    # comm.py (resolving the fast-guard to a plain True) must produce
    # findings in _run_collective; the committed source must not.
    path = os.path.join(_REPO, "fluxmpi_tpu", "comm.py")
    with open(path, encoding="utf-8") as f:
        src = f.read()
    assert "instrumented = _instrumentation_on()" in src
    mutated = src.replace(
        "instrumented = _instrumentation_on()", "instrumented = True"
    )
    ctx = ProjectContext.load(_REPO)
    rule = [UnguardedHotPathInstrumentation()]
    clean = lint_source(src, "fluxmpi_tpu/comm.py", ctx, rules=rule)
    assert clean.findings == []
    bad = lint_source(mutated, "fluxmpi_tpu/comm.py", ctx, rules=rule)
    keys = set(_keys(bad, "unguarded-hot-path-instrumentation"))
    assert "_run_collective:time.perf_counter" in keys
    assert "_run_collective:_begin_op" in keys


# ---------------------------------------------------------------------------
# Rule 3: unknown-metric-name
# ---------------------------------------------------------------------------


def test_metric_rule_flags_typo_and_suggests():
    src = 'def f(reg):\n    reg.counter("train.losss").inc()\n'
    r = lint_source(src, "pkg/m.py", _ctx(), rules=[UnknownMetricName()])
    (f,) = r.findings
    assert f.key == "train.losss"
    assert "train.loss" in f.message  # nearest-known hint


def test_metric_rule_quiet_on_known_names_and_open_dynamic():
    src = textwrap.dedent(
        """
        def f(reg, key):
            reg.gauge("train.loss").set(1.0)
            reg.gauge(f"device.memory.{key}").set(0.0)
        """
    )
    r = lint_source(src, "pkg/m.py", _ctx(), rules=[UnknownMetricName()])
    assert r.findings == []


def test_metric_rule_flags_closed_namespace_dynamic_prefix():
    src = 'def f(reg, x):\n    reg.counter("fault.bogus_" + x).inc()\n'
    r = lint_source(src, "pkg/m.py", _ctx(), rules=[UnknownMetricName()])
    assert _keys(r, "unknown-metric-name") == ["prefix:fault.bogus_"]


def test_metric_rule_checks_instant_names():
    ctx = _ctx()
    bad = 'def f(t):\n    t.instant("train.explosion", step=1)\n'
    r = lint_source(bad, "pkg/m.py", ctx, rules=[UnknownMetricName()])
    assert _keys(r, "unknown-metric-name") == ["train.explosion"]
    ok = textwrap.dedent(
        """
        def f(t, rule):
            t.instant("train.preemption", step=1)
            t.instant("anomaly." + rule, step=1)
            t.instant("fault.injected", site="x")
        """
    )
    r = lint_source(ok, "pkg/m.py", ctx, rules=[UnknownMetricName()])
    assert r.findings == []


def test_metric_rule_flags_consumer_literal_drift_in_scripts():
    """The consumer half: dashboards under scripts/ read metric keys as
    PLAIN string literals — a drifted key must fail the lint, not fail
    as a silently blank panel at runtime."""
    src = textwrap.dedent(
        """
        def f(flat):
            return flat.get("train.losss")
        """
    )
    r = lint_source(src, "scripts/top.py", _ctx(), rules=[UnknownMetricName()])
    (f,) = r.findings
    assert f.key == "train.losss"
    assert "train.loss" in f.message  # nearest-known hint
    # The SAME literal outside scripts/ is not a consumer read (package
    # producers go through the instrument-call check instead).
    r = lint_source(src, "pkg/m.py", _ctx(), rules=[UnknownMetricName()])
    assert r.findings == []


def test_metric_rule_consumer_scan_allows_known_shapes():
    src = textwrap.dedent(
        '''
        """Docstring naming train.losss is prose, not a read."""

        def f(flat, name):
            a = flat.get("train.loss")           # schema-known
            b = name.startswith("fault.")        # family-prefix idiom
            c = flat.get("not.a.metric.family")  # foreign dotted string
            d = open("some.file.json")           # ditto
            e = flat.get("train.preemption")     # the instant constant
            return a, b, c, d, e
        '''
    )
    r = lint_source(src, "scripts/top.py", _ctx(), rules=[UnknownMetricName()])
    assert r.findings == []


def test_metric_rule_consumer_scan_flags_dead_family_prefix():
    # A dangling "<family>." prefix read matching NOTHING known under it
    # is drift too (ctx has no metric under "train." besides
    # train.loss, so "fault.zzz_" style reads flag via the family).
    src = 'def f(flat):\n    return flat.get("fault.zzz")\n'
    r = lint_source(src, "scripts/top.py", _ctx(), rules=[UnknownMetricName()])
    assert _keys(r, "unknown-metric-name") == ["fault.zzz"]
    # ...including the trailing-dot form: a startswith("train.loss.")
    # read (sub-namespace typo) matches nothing known and must flag,
    # while a live family prefix stays quiet.
    src = textwrap.dedent(
        """
        def f(name):
            a = name.startswith("train.loss.")
            b = name.startswith("fault.injected")
            return a, b
        """
    )
    r = lint_source(src, "scripts/top.py", _ctx(), rules=[UnknownMetricName()])
    assert _keys(r, "unknown-metric-name") == ["prefix:train.loss."]


def test_metric_rule_covers_serving_report_consumer_literals():
    """scripts/serving_report.py names registry twins for its JSONL
    aggregates as plain metric literals — the consumer rule must keep
    them schema-true: the committed file lints clean, a drifted twin
    fails."""
    path = os.path.join(_REPO, "scripts", "serving_report.py")
    with open(path, encoding="utf-8") as f:
        src = f.read()
    target = '"serving.requests_completed"'
    assert target in src
    ctx = ProjectContext.load(_REPO)
    clean = lint_source(
        src, "scripts/serving_report.py", ctx, rules=[UnknownMetricName()]
    )
    assert clean.findings == []
    bad = lint_source(
        src.replace(target, '"serving.requests_completedd"'),
        "scripts/serving_report.py", ctx, rules=[UnknownMetricName()],
    )
    keys = _keys(bad, "unknown-metric-name")
    assert "serving.requests_completedd" in keys
    (f,) = [x for x in bad.findings if x.key == "serving.requests_completedd"]
    assert "serving.requests_completed" in f.message  # nearest-known hint


# ---------------------------------------------------------------------------
# Rule 4: unregistered-fault-site
# ---------------------------------------------------------------------------


def test_fault_site_rule_flags_unregistered_literal_with_nearest():
    src = (
        "from . import faults as _faults\n"
        'def f():\n    _faults.check("ckpt.wrte")\n'
    )
    r = lint_source(src, "pkg/f.py", _ctx(), rules=[UnregisteredFaultSite()])
    found = [f for f in r.findings if f.key == "ckpt.wrte"]
    assert len(found) == 1 and "ckpt.write" in found[0].message


def test_fault_site_rule_quiet_on_registered_and_known_prefix():
    src = textwrap.dedent(
        """
        from . import faults as _faults
        def f(kind):
            _faults.check("ckpt.write")
            _faults.check("data." + kind)
        """
    )
    r = lint_source(src, "pkg/f.py", _ctx(), rules=[UnregisteredFaultSite()])
    assert r.findings == []


def test_fault_site_rule_demands_test_coverage():
    ctx = _ctx(
        known_fault_sites=frozenset({"ckpt.write", "ghost.site"}),
        tests_corpus="only ckpt.write is exercised here",
    )
    r = lint_source("x = 1\n", "pkg/f.py", ctx, rules=[UnregisteredFaultSite()])
    assert _keys(r, "unregistered-fault-site") == ["untested:ghost.site"]


# ---------------------------------------------------------------------------
# Rule 5: hand-built-mesh
# ---------------------------------------------------------------------------


def test_hand_built_mesh_flags_mesh_and_axis_literals():
    src = textwrap.dedent(
        """
        import jax
        from jax.sharding import Mesh, PartitionSpec as P
        def bad(devs, q):
            mesh = Mesh(devs, ("dp", "tp"))
            spec = P("dp", None)
            composed = P(("dp", "fsdp"))
            g = jax.lax.psum(q, "tp")
            h = attend(q, axis_name="sp")
            return mesh, spec, composed, g, h
        """
    )
    ctx = _ctx(axis_name_literals=frozenset({"dp", "fsdp", "tp", "sp"}))
    r = lint_source(
        src, "fluxmpi_tpu/parallel/ring.py", ctx, rules=[HandBuiltMesh()]
    )
    keys = _keys(r, "hand-built-mesh")
    assert "mesh" in keys
    assert keys.count("axis:dp") == 2
    assert "axis:fsdp" in keys and "axis:tp" in keys and "axis:sp" in keys


def test_hand_built_mesh_quiet_on_plan_runtime_and_constants():
    ctx = _ctx(axis_name_literals=frozenset({"dp", "tp"}))
    src = textwrap.dedent(
        """
        from jax.sharding import Mesh, PartitionSpec as P
        def build(devs):
            return Mesh(devs, ("dp",)), P("dp")
        """
    )
    # The plan engine and the runtime ARE where meshes come from.
    for path in ("fluxmpi_tpu/parallel/plan.py", "fluxmpi_tpu/runtime.py"):
        assert not lint_source(src, path, ctx, rules=[HandBuiltMesh()]).findings
    # Outside fluxmpi_tpu/ (scripts, tests, examples) the rule is silent.
    assert not lint_source(
        src, "scripts/demo.py", ctx, rules=[HandBuiltMesh()]
    ).findings
    # The canonical spellings don't trip it.
    good = textwrap.dedent(
        """
        from jax.sharding import PartitionSpec as P
        from fluxmpi_tpu import config
        from fluxmpi_tpu.parallel.plan import plan_axis_name
        def fine(q):
            spec = P(config.DP_AXIS_NAME)
            name = plan_axis_name("sp")
            label = {"axis": "dp"}  # a dict literal is not a spec arg
            return spec, name, label
        """
    )
    r = lint_source(
        good, "fluxmpi_tpu/parallel/ring.py", ctx, rules=[HandBuiltMesh()]
    )
    assert not r.findings


def test_hand_built_mesh_clean_on_repo_and_loaded_registry():
    # The merged tree is clean under the rule, and the axis registry
    # loads from config.py (single-sourced, no copy to drift).
    ctx = ProjectContext.load(_REPO)
    assert {"dp", "fsdp", "tp", "pp", "sp", "ep"} <= set(
        ctx.axis_name_literals
    )
    report = lint_repo(_REPO, ["fluxmpi_tpu"], context=ctx)
    assert not [
        f for f in report.findings if f.rule == "hand-built-mesh"
    ], report.text()


# ---------------------------------------------------------------------------
# Rule 6: undocumented-env-var
# ---------------------------------------------------------------------------


def test_env_rule_flags_both_directions():
    src = 'import os\nv = os.environ.get("FLUXMPI_TPU_MYSTERY_KNOB")\n'
    # faults_path == the scanned file marks the scan as "full", enabling
    # the reverse (documented-but-unread) direction.
    r = lint_source(
        src, "pkg/e.py", _ctx(faults_path="pkg/e.py"),
        rules=[UndocumentedEnvVar()],
    )
    keys = _keys(r, "undocumented-env-var")
    # Read-but-undocumented AND documented-but-unread both fire.
    assert "FLUXMPI_TPU_MYSTERY_KNOB" in keys
    assert "unread:FLUXMPI_TPU_DOCUMENTED" in keys


def test_env_rule_quiet_when_table_matches_and_skips_docstrings():
    src = textwrap.dedent(
        '''
        """Docstring mentioning FLUXMPI_TPU_NOT_A_READ is not a read."""
        import os
        v = os.environ.get("FLUXMPI_TPU_DOCUMENTED")
        '''
    )
    r = lint_source(
        src, "pkg/e.py", _ctx(faults_path="pkg/e.py"),
        rules=[UndocumentedEnvVar()],
    )
    assert r.findings == []


# ---------------------------------------------------------------------------
# Suppressions and baseline
# ---------------------------------------------------------------------------


def test_inline_suppression_trailing_and_own_line():
    src = textwrap.dedent(
        """
        def f(reg):
            reg.counter("bad.one").inc()  # fluxlint: disable=unknown-metric-name
            # fluxlint: disable=unknown-metric-name
            reg.counter("bad.two").inc()
            reg.counter("bad.three").inc()
        """
    )
    r = lint_source(src, "pkg/s.py", _ctx(), rules=[UnknownMetricName()])
    assert r.suppressed == 2
    assert _keys(r, "unknown-metric-name") == ["bad.three"]


def test_own_line_suppression_skips_justification_comments():
    # The documented workflow: directive, then a why-comment, then the
    # statement — the suppression must reach the statement.
    src = textwrap.dedent(
        """
        def f(reg):
            # fluxlint: disable=unknown-metric-name
            # legacy dashboard pins this name, keep until Q4
            reg.counter("bad.metric").inc()
        """
    )
    r = lint_source(src, "pkg/s.py", _ctx(), rules=[UnknownMetricName()])
    assert r.suppressed == 1 and r.findings == []


def test_directive_inside_string_literal_does_not_suppress():
    src = (
        "def f(reg):\n"
        '    msg = "# fluxlint: disable=unknown-metric-name"\n'
        '    reg.counter("bad.metric").inc(); x = msg\n'
        "    return x\n"
    )
    r = lint_source(src, "pkg/s.py", _ctx(), rules=[UnknownMetricName()])
    assert r.suppressed == 0
    assert _keys(r, "unknown-metric-name") == ["bad.metric"]


def test_baseline_round_trip_and_hygiene(tmp_path):
    src = 'def f(reg):\n    reg.counter("bad.metric").inc()\n'
    rule = [UnknownMetricName()]
    ctx = _ctx()

    # Justified entry: finding moves to `baselined`, lint goes clean.
    good = Baseline(
        [{"rule": "unknown-metric-name", "path": "pkg/b.py",
          "key": "bad.metric", "justification": "legacy dashboard name"}]
    )
    r = lint_source(src, "pkg/b.py", ctx, rules=rule, baseline=good)
    assert r.findings == [] and len(r.baselined) == 1
    assert r.exit_code == 0

    # Unjustified entry: the baseline itself is the finding.
    bare = Baseline(
        [{"rule": "unknown-metric-name", "path": "pkg/b.py",
          "key": "bad.metric", "justification": ""}]
    )
    r = lint_source(src, "pkg/b.py", ctx, rules=rule, baseline=bare)
    assert [f.rule for f in r.findings] == ["fluxlint-baseline"]
    assert "justification" in r.findings[0].message

    # Stale entry (matches nothing): flagged so the baseline cannot rot.
    stale = Baseline(
        [{"rule": "unknown-metric-name", "path": "pkg/b.py",
          "key": "gone.metric", "justification": "was real once"}]
    )
    r = lint_source("x = 1\n", "pkg/b.py", ctx, rules=rule, baseline=stale)
    assert [f.key for f in r.findings] == [
        "stale:unknown-metric-name:gone.metric"
    ]

    # File round trip through Baseline.load.
    payload = {"entries": good.entries}
    p = tmp_path / "base.json"
    p.write_text(json.dumps(payload))
    loaded = Baseline.load(str(p))
    r = lint_source(src, "pkg/b.py", ctx, rules=rule, baseline=loaded)
    assert r.findings == [] and len(r.baselined) == 1


# ---------------------------------------------------------------------------
# CLI: JSON schema, exit codes, no-jax loading
# ---------------------------------------------------------------------------


def _run_cli(*args, cwd=_REPO):
    return subprocess.run(
        [sys.executable, _CLI, *args],
        capture_output=True, text=True, cwd=cwd,
    )


def test_cli_repo_clean_and_json_schema():
    proc = _run_cli("--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert data["schema"] == "fluxmpi_tpu.fluxlint/v1"
    assert data["findings"] == [] and data["exit_code"] == 0
    assert data["files"] > 50
    for key in ("baselined", "suppressed", "unreadable"):
        assert key in data


def test_cli_exit_codes_findings_and_unreadable(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "from . import faults as _faults\n"
        'def f():\n    _faults.check("no.such.site")\n'
    )
    proc = _run_cli(str(bad), "--json")
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert any(
        f["rule"] == "unregistered-fault-site" for f in data["findings"]
    )

    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    proc = _run_cli(str(broken))
    assert proc.returncode == 2


def test_cli_loads_without_importing_jax():
    # The lint must stay runnable in a second without booting a backend:
    # the CLI loads the analysis package by file path, never the parent
    # fluxmpi_tpu package.
    code = (
        "import sys; sys.path.insert(0, 'scripts'); import fluxlint; "
        "a = fluxlint.load_analysis(); "
        "r = a.lint_repo('.', ['fluxmpi_tpu/analysis']); "
        "assert 'jax' not in sys.modules, 'lint imported jax'; "
        "assert 'fluxmpi_tpu' not in sys.modules, 'lint imported the package'; "
        "print(r.exit_code)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "0"


# ---------------------------------------------------------------------------
# Rule 7: jax-compat-drift
# ---------------------------------------------------------------------------


def test_compat_drift_flags_axis_size_spellings():
    src = textwrap.dedent(
        """
        import jax
        from jax import lax

        def f():
            n = jax.lax.axis_size("dp")
            m = lax.axis_size("tp")
            return n, m
        """
    )
    r = lint_source(src, "fluxmpi_tpu/parallel/ring.py", _ctx(),
                    rules=[JaxCompatDrift()])
    assert _keys(r, "jax-compat-drift") == ["axis_size", "axis_size"]

    imported = "from jax.lax import axis_size\n"
    r = lint_source(imported, "fluxmpi_tpu/ops/x.py", _ctx(),
                    rules=[JaxCompatDrift()])
    assert _keys(r, "jax-compat-drift") == ["axis_size"]


def test_compat_drift_flags_compiler_params_spellings():
    src = textwrap.dedent(
        """
        from jax.experimental.pallas import tpu as pltpu

        old = pltpu.TPUCompilerParams(dimension_semantics=("parallel",))
        new = pltpu.CompilerParams(dimension_semantics=("parallel",))
        """
    )
    r = lint_source(src, "fluxmpi_tpu/ops/k.py", _ctx(),
                    rules=[JaxCompatDrift()])
    assert _keys(r, "jax-compat-drift") == [
        "compiler_params", "compiler_params",
    ]

    imported = "from jax.experimental.pallas.tpu import TPUCompilerParams\n"
    r = lint_source(imported, "scripts/k.py", _ctx(), rules=[JaxCompatDrift()])
    assert _keys(r, "jax-compat-drift") == ["compiler_params"]


def test_compat_drift_flags_shard_map_validation_kwargs():
    src = textwrap.dedent(
        """
        from jax.experimental.shard_map import shard_map

        def f(body, mesh, spec):
            a = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                          check_vma=False)
            b = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                          check_rep=False)
            return a, b
        """
    )
    r = lint_source(src, "fluxmpi_tpu/parallel/p.py", _ctx(),
                    rules=[JaxCompatDrift()])
    assert _keys(r, "jax-compat-drift") == [
        "shard_map:check_vma", "shard_map:check_rep",
    ]


def test_compat_drift_quiet_on_seam_and_wrappers():
    # The seam itself owns the probes — exempt.
    drifted = 'import jax\nn = jax.lax.axis_size("dp")\n'
    r = lint_source(drifted, "fluxmpi_tpu/parallel/_compat.py", _ctx(),
                    rules=[JaxCompatDrift()])
    assert r.findings == []

    # Consuming the wrappers is the blessed spelling.
    good = textwrap.dedent(
        """
        from fluxmpi_tpu.parallel._compat import (
            axis_size,
            pallas_tpu_compiler_params,
            shard_map_unchecked,
        )

        def f(body, mesh, spec, name):
            n = axis_size(name)
            params = pallas_tpu_compiler_params(
                dimension_semantics=("parallel",)
            )
            mapped = shard_map_unchecked(
                body, mesh, in_specs=(spec,), out_specs=spec
            )
            return n, params, mapped
        """
    )
    r = lint_source(good, "fluxmpi_tpu/parallel/ring.py", _ctx(),
                    rules=[JaxCompatDrift()])
    assert r.findings == []

    # A bare shard_map call WITHOUT the drifted kwarg is fine too (the
    # compat module re-exports it for spec-checked call sites).
    bare = textwrap.dedent(
        """
        from fluxmpi_tpu.parallel._compat import shard_map

        def f(body, mesh, spec):
            return shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec)
        """
    )
    r = lint_source(bare, "fluxmpi_tpu/comm.py", _ctx(),
                    rules=[JaxCompatDrift()])
    assert r.findings == []


def test_compat_drift_in_default_rules():
    assert any(r.id == "jax-compat-drift" for r in default_rules())


# ---------------------------------------------------------------------------
# The tier-1 contract: the repo itself lints clean (modulo the baseline)
# ---------------------------------------------------------------------------


def test_repo_is_fluxlint_clean():
    report = lint_repo(_REPO, ["fluxmpi_tpu", "scripts"])
    assert report.unreadable == []
    assert report.findings == [], "\n" + report.text()
