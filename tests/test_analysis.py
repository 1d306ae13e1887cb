"""The ``repro.analysis`` subsystem (ISSUE 7): Tier-1 AST lint — every
rule's positive/suppressed/clean fixtures, the suppression syntax, the
baseline fail-on-new split, the CLI — and the Tier-2 compiled-artifact
auditor on the repo's REAL programs (sequential/stacked backends +
BucketedScorer here; the mesh backend in ``tests/test_mesh_exec.py``
under 8 devices), plus deliberately-broken fixtures proving each
Tier-2 check can FAIL (a gate that cannot fail gates nothing).

Also pins the acceptance bar: the repo's own ``src/`` (and
``benchmarks/``, ``examples/``) lints clean against the EMPTY checked-in
baseline.
"""
import json
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import (DEFAULT_ROOTS, get_rules, lint_file, lint_paths,
                            load_baseline, write_baseline)
from repro.analysis import hlo
from repro.analysis.__main__ import main as cli_main
from repro.analysis.lint import BASELINE_PATH, Finding
from repro.configs.base import get_reduced_config
from repro.core.averaging import broadcast_member_dim
from repro.core.cnn_elm import StackedMembers
from repro.models import cnn
from repro.serve import BucketedScorer
from repro.launch.mesh import auto_mesh

ROOT = Path(__file__).resolve().parent.parent
CFG = get_reduced_config("cnn_elm_6c12c")


def _lint(tmp_path, src, rel="src/repro/mod.py"):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return lint_file(p, get_rules(), root=tmp_path)


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# Tier-1 rules: positive + suppressed + clean per rule
# ---------------------------------------------------------------------------

def test_np_in_traced_fires_and_transitively(tmp_path):
    found = _lint(tmp_path, """\
        import jax
        import numpy as np

        def helper(x):
            return np.square(x)        # traced via the caller

        @jax.jit
        def f(x):
            return helper(x) + np.abs(x)
        """)
    assert _rules_of(found) == ["np-in-traced"]
    assert len(found) == 2             # direct call AND the helper's body


def test_np_in_traced_clean_cases(tmp_path):
    found = _lint(tmp_path, """\
        import jax
        import jax.numpy as jnp
        import numpy as np

        def host_prep(x):              # never traced: np is fine here
            return np.square(x)

        @jax.jit
        def f(x):
            return jnp.square(x) * np.float32(2.0)   # dtype ctor exempt
        """)
    assert found == []


def test_np_in_traced_suppressed(tmp_path):
    found = _lint(tmp_path, """\
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            # constant-folded on purpose: shape table built at trace time
            # repro: allow(np-in-traced)
            return x + np.square(3)
        """)
    assert found == []


def test_host_concretization_fires(tmp_path):
    found = _lint(tmp_path, """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            if jnp.any(x > 0):          # Python branch on a tracer
                return float(x)         # float() cast
            while x.sum() > 1:          # .sum() reduction in a while
                x = x - 1
            return x.item()             # .item() sync
        """)
    assert _rules_of(found) == ["host-concretization"]
    assert len(found) == 4


def test_host_concretization_clean_outside_trace(tmp_path):
    found = _lint(tmp_path, """\
        import jax

        @jax.jit
        def f(x):
            return x * 2

        def report(x):
            return float(f(x))          # host side: fine
        """)
    assert found == []


def test_host_rng_or_clock_fires(tmp_path):
    found = _lint(tmp_path, """\
        import time
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            t0 = time.perf_counter()
            return x + np.random.normal()
        """)
    assert _rules_of(found) == ["host-rng-or-clock"]
    assert len(found) == 2


@pytest.mark.parametrize("src,fires", [
    ("""\
        import jax
        from jax.profiler import TraceAnnotation

        def step(x):
            with TraceAnnotation("step"):     # opens once, at trace time
                return x * 2

        @jax.jit
        def f(xs):
            return jax.vmap(step)(xs)
        """, 1),
    ("""\
        import jax

        @jax.jit
        def f(x):
            with jax.named_scope("step"):     # device scope: fine
                return x * 2

        def run(x):
            with jax.profiler.TraceAnnotation("run"):   # host: fine
                return f(x)
        """, 0),
], ids=["fires", "clean"])
def test_host_span_in_traced(tmp_path, src, fires):
    found = _lint(tmp_path, src)
    assert len(found) == fires
    assert _rules_of(found) == ["host-span-in-traced"] * min(fires, 1)


def test_sub_f32_accum_fires(tmp_path):
    found = _lint(tmp_path, """\
        import jax
        import jax.numpy as jnp

        def reduce_members(trees, acc, x):
            s = jnp.sum(trees, axis=0, dtype=jnp.bfloat16)
            acc = acc + x.astype(jnp.bfloat16)
            acc += x.astype("bfloat16")
            g = jax.lax.psum(x.astype(jnp.bfloat16), "pod")
            return s, acc, g
        """)
    assert _rules_of(found) == ["sub-f32-accum"]
    assert len(found) == 4


def test_sub_f32_accum_clean_f32_path(tmp_path):
    found = _lint(tmp_path, """\
        import jax.numpy as jnp

        def reduce_members(trees, x):
            mean = jnp.sum(trees.astype(jnp.float32), axis=0) / len(trees)
            return mean.astype(jnp.bfloat16)   # cast AFTER is the contract
        """)
    assert found == []


def test_hardcoded_member_seed_fires_and_clean(tmp_path):
    found = _lint(tmp_path, """\
        import numpy as np
        import jax

        def bad(i):
            return np.random.default_rng(1000 + i)

        def good(plan, i):
            return jax.random.PRNGKey(plan.seed + i)
        """)
    assert [(f.rule, f.line) for f in found] == [("hardcoded-member-seed", 5)]


def test_missing_donate_fires(tmp_path):
    found = _lint(tmp_path, """\
        import jax
        from jax import lax

        @jax.jit
        def epoch(carry, xs):
            return lax.scan(lambda c, x: (c + x, None), carry, xs)
        """)
    assert _rules_of(found) == ["missing-donate"]


def test_missing_donate_clean_with_donation(tmp_path):
    found = _lint(tmp_path, """\
        import functools
        import jax
        from jax import lax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def epoch(carry, xs):
            return lax.scan(lambda c, x: (c + x, None), carry, xs)
        """)
    assert found == []


def test_bare_jit_in_serve_path_gated(tmp_path):
    src = """\
        import jax

        def build(f):
            return jax.jit(f)
        """
    in_serve = _lint(tmp_path, src, rel="src/repro/serve/other.py")
    assert _rules_of(in_serve) == ["bare-jit-in-serve"]
    # the identical code outside repro/serve is NOT a finding
    assert _lint(tmp_path, src, rel="src/repro/core/other.py") == []


# ---------------------------------------------------------------------------
# Suppression syntax
# ---------------------------------------------------------------------------

def test_suppression_same_line_and_line_above(tmp_path):
    found = _lint(tmp_path, """\
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            a = np.square(x)  # repro: allow(np-in-traced)
            # trace-time constant table  # repro: allow(np-in-traced)
            b = np.square(x)
            return a + b
        """)
    assert found == []


def test_suppression_multi_rule_and_wrong_rule(tmp_path):
    found = _lint(tmp_path, """\
        import time
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            # repro: allow(np-in-traced, host-rng-or-clock)
            a = x + np.random.normal()
            b = np.square(x)    # repro: allow(host-rng-or-clock)
            return a + b
        """)
    # the wrong-rule allow on line 9 suppresses NOTHING
    assert [(f.rule, f.line) for f in found] == [("np-in-traced", 9)]


def test_suppression_counted(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("import jax\nimport numpy as np\n\n@jax.jit\n"
                 "def f(x):\n"
                 "    return np.square(x)  # repro: allow(np-in-traced)\n")
    report = lint_paths([p], root=tmp_path)
    assert report.findings == [] and report.suppressed == 1


# ---------------------------------------------------------------------------
# Baseline: fail-on-new split + drift
# ---------------------------------------------------------------------------

BAD_SRC = ("import jax\nimport numpy as np\n\n@jax.jit\n"
           "def f(x):\n    return np.square(x)\n")


def test_baseline_roundtrip_and_split(tmp_path):
    p = tmp_path / "legacy.py"
    p.write_text(BAD_SRC)
    first = lint_paths([p], root=tmp_path)
    assert len(first.findings) == 1
    bpath = tmp_path / "baseline.json"
    write_baseline(first.findings, bpath)

    # same findings against the baseline: all baselined, none new
    again = lint_paths([p], root=tmp_path, baseline=load_baseline(bpath))
    assert again.findings == [] and len(again.baselined) == 1


def test_baseline_drift_new_finding_stays_new(tmp_path):
    p = tmp_path / "legacy.py"
    p.write_text(BAD_SRC)
    baseline = load_baseline(tmp_path / "missing.json")    # empty
    assert baseline == {}
    write_baseline(lint_paths([p], root=tmp_path).findings,
                   tmp_path / "baseline.json")
    # the file grows a NEW violation on a different line
    p.write_text(BAD_SRC + "\n\n@jax.jit\ndef g(x):\n"
                 "    return np.abs(x)\n")
    drift = lint_paths([p], root=tmp_path,
                       baseline=load_baseline(tmp_path / "baseline.json"))
    assert len(drift.baselined) == 1       # the legacy one stays baselined
    assert len(drift.findings) == 1        # the drift is NEW -> gate fails
    assert drift.findings[0].line == 11


def test_baseline_unknown_version_rejected(tmp_path):
    b = tmp_path / "b.json"
    b.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(ValueError, match="unknown baseline version"):
        load_baseline(b)


def test_repo_src_lints_clean_against_checked_in_baseline(monkeypatch):
    """THE acceptance bar: ``python -m repro.analysis`` over the default
    roots reports zero new findings, and the checked-in baseline is
    EMPTY (no grandfathered debt in src/)."""
    assert load_baseline(BASELINE_PATH) == {}
    monkeypatch.chdir(ROOT)
    report = lint_paths([Path(r) for r in DEFAULT_ROOTS], root=ROOT)
    assert report.parse_errors == []
    assert report.findings == [], "\n".join(str(f) for f in report.findings)
    assert report.files_checked > 40       # it actually walked the tree


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_clean_exit_and_report(tmp_path, monkeypatch, capsys):
    d = tmp_path / "pkg"
    d.mkdir()
    (d / "ok.py").write_text("import jax.numpy as jnp\n\n"
                             "def f(x):\n    return jnp.square(x)\n")
    rep = tmp_path / "report.json"
    rc = cli_main([str(d), "--fail-on-new", "--report", str(rep)])
    assert rc == 0
    data = json.loads(rep.read_text())
    assert data["new"] == [] and data["files_checked"] == 1
    assert "clean" in capsys.readouterr().out


def test_cli_fail_on_new_and_write_baseline(tmp_path, capsys):
    d = tmp_path / "pkg"
    d.mkdir()
    (d / "bad.py").write_text(BAD_SRC)
    bpath = tmp_path / "b.json"
    assert cli_main([str(d), "--baseline", str(bpath),
                     "--fail-on-new"]) == 1
    # snapshot the debt, then the same tree gates green
    assert cli_main([str(d), "--baseline", str(bpath),
                     "--write-baseline"]) == 0
    assert cli_main([str(d), "--baseline", str(bpath),
                     "--fail-on-new"]) == 0
    out = capsys.readouterr().out
    assert "(baselined)" in out and "1 baselined" in out


def test_cli_parse_error_exit_2(tmp_path):
    d = tmp_path / "pkg"
    d.mkdir()
    (d / "broken.py").write_text("def f(:\n")
    assert cli_main([str(d)]) == 2


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in ("np-in-traced", "host-concretization", "host-rng-or-clock",
                 "host-span-in-traced", "sub-f32-accum",
                 "hardcoded-member-seed", "missing-donate",
                 "bare-jit-in-serve"):
        assert name in out


# ---------------------------------------------------------------------------
# Tier-2: the auditor on the repo's REAL programs
# ---------------------------------------------------------------------------

def test_audit_sequential_backend_green():
    for report in hlo.audit_executor(CFG, "sequential", k=3):
        assert report.ok, str(report)


def test_audit_stacked_backend_green():
    reports = hlo.audit_executor(CFG, "stacked", k=3)
    assert {r.program for r in reports} == \
        {"stacked/_sync", "stacked/_reduce", "stacked/_stacked_epoch"}
    for report in reports:
        assert report.ok, str(report)
        report.raise_if_failed()        # and the raising path is a no-op


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="mesh audit needs "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8")
def test_audit_mesh_backend_green():
    mesh = auto_mesh((8,), ("pod",))
    for report in hlo.audit_executor(CFG, "mesh", mesh=mesh, k=3):
        assert report.ok, str(report)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="mesh audit needs "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8")
def test_audit_hierarchical_mesh_expects_two_allreduces():
    """On a ('host','pod') mesh the auditor swaps the sync/reduce check
    to ``check_two_all_reduces`` — green on the real programs, and the
    check itself FAILS a one-collective program (so the two-collective
    bar can't silently pass on the flat lowering)."""
    mesh = auto_mesh((2, 4), ("host", "pod"))
    reports = hlo.audit_executor(CFG, "mesh", mesh=mesh, k=3)
    for report in reports:
        assert report.ok, str(report)
    # a single-psum program must FAIL the two-collective check
    flat = auto_mesh((8,), ("pod",))
    _, programs = hlo.lower_stacked_programs(CFG, "mesh", mesh=flat, k=3)
    assert not hlo.check_two_all_reduces(programs["_sync"]).ok


def test_audit_average_step_plain_green():
    report = hlo.audit_average_step()
    assert report.ok, str(report)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="mesh audit needs "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8")
def test_audit_average_step_mesh_green():
    mesh = auto_mesh((8,), ("pod",))
    report = hlo.audit_average_step(mesh=mesh, weights=[1.0] * 8)
    assert report.ok, str(report)


def _tiny_scorer(max_batch=4):
    params_k = broadcast_member_dim(
        cnn.init_params(CFG, jax.random.PRNGKey(0)), 2)
    beta_k = jnp.zeros((2, cnn.feature_dim(CFG), CFG.num_classes))
    return BucketedScorer(CFG, StackedMembers(params_k, beta_k),
                          max_batch=max_batch)


def test_audit_scorer_green_and_budget_violation_raises():
    scorer = _tiny_scorer()
    report = hlo.audit_scorer(scorer, warm=True)
    assert report.ok, str(report)
    assert scorer.assert_compile_budget() == len(scorer.ladder.buckets)

    # now FORCE a dispatch that escapes the pad ladder: one rogue shape
    h = CFG.image_size
    rogue = jnp.zeros((3, h, h) if CFG.image_channels == 1
                      else (3, h, h, CFG.image_channels), jnp.float32)
    scorer._fn(scorer.members.cnn_params, scorer.members.beta, rogue)
    assert not hlo.audit_scorer(scorer).ok
    with pytest.raises(hlo.ContractViolation, match="recompiled"):
        scorer.assert_compile_budget()


def test_audit_report_str_names_failed_checks():
    scorer = _tiny_scorer()
    scorer.warmup()
    text = str(hlo.audit_scorer(scorer))
    assert "serve/BucketedScorer" in text and "compile-budget" in text


# ---------------------------------------------------------------------------
# Tier-2: deliberately-broken fixtures — every check must be able to FAIL
# ---------------------------------------------------------------------------

# raw compiled-HLO shards in the exact op format XLA emits (the same
# format tests/test_extensions.py pins for collective_stats)
HLO_TWO_ALLREDUCE = """
  %ar.1 = f32[16]{0} all-reduce(f32[16]{0} %a), replica_groups={}
  %ar.2 = f32[16]{0} all-reduce(f32[16]{0} %b), replica_groups={}
"""
HLO_ONE_ALLREDUCE = """
  %ar = f32[16]{0} all-reduce(f32[16]{0} %a), replica_groups={}
"""


def test_check_one_all_reduce_fails_on_zero_and_two():
    # zero: a real compiled program with no collectives at all
    lowered = jax.jit(lambda x: x + 1.0).lower(jnp.zeros((4,)))
    assert not hlo.check_one_all_reduce(lowered).ok
    # two: the flat-psum contract collapsed into per-leaf reductions
    assert not hlo.check_one_all_reduce(HLO_TWO_ALLREDUCE).ok
    assert hlo.check_one_all_reduce(HLO_ONE_ALLREDUCE).ok


def test_check_no_collectives_fails_on_allreduce():
    check = hlo.check_no_collectives(HLO_ONE_ALLREDUCE)
    assert not check.ok and "all-reduce" in check.detail
    assert hlo.check_no_collectives(
        jax.jit(lambda x: x * 2.0).lower(jnp.zeros((4,)))).ok


def test_check_donation_fails_without_donation():
    def f(carry, x):
        return carry + x, carry * x

    no_don = jax.jit(f).lower(jnp.zeros((8, 8)), jnp.ones((8, 8)))
    assert not hlo.check_donation(no_don).ok
    donated = jax.jit(f, donate_argnums=(0,)).lower(
        jnp.zeros((8, 8)), jnp.ones((8, 8)))
    check = hlo.check_donation(donated)
    assert check.ok, check


HLO_BF16_ACCUM = """
  %add.1 = bf16[64]{0} add(bf16[64]{0} %a, bf16[64]{0} %b)
  %reduce.2 = bf16[]{} reduce(bf16[64]{0} %add.1, bf16[] %zero)
"""


def test_check_accum_dtype_fails_on_bf16_accumulation():
    bad = hlo.check_accum_dtype(HLO_BF16_ACCUM)
    assert not bad.ok and "bf16 add" in bad.detail
    # a REAL bf16 sum: XLA itself hoists the accumulation to f32 and
    # converts at the end — the auditor must see that as clean (this is
    # exactly the artifact shape average_trees compiles to)
    x = jnp.zeros((64,), jnp.bfloat16)
    good = hlo.check_accum_dtype(jax.jit(
        lambda a: jnp.sum(a, dtype=jnp.bfloat16)).lower(x))
    assert good.ok, good


def test_check_compile_budget_fails_on_escaped_dispatch():
    class FakeLadder:
        buckets = (1, 2)

    class FakeScorer:
        ladder = FakeLadder()

        def compile_count(self):
            return 5

    check = hlo.check_compile_budget(FakeScorer())
    assert not check.ok and "escaped the pad ladder" in check.detail


def test_audit_report_raise_if_failed():
    rep = hlo.AuditReport("fixture/broken")
    rep.checks.append(hlo.check_no_collectives(HLO_ONE_ALLREDUCE))
    assert not rep.ok and rep.failures
    with pytest.raises(hlo.ContractViolation, match="fixture/broken"):
        rep.raise_if_failed()


def test_contract_violation_is_assertion_error():
    # call sites that did `except AssertionError` keep working
    assert issubclass(hlo.ContractViolation, AssertionError)


def test_as_hlo_text_accepts_str_lowered_compiled():
    lowered = jax.jit(lambda x: x + 1.0).lower(jnp.zeros((2,)))
    compiled = lowered.compile()
    for program in ("%x = f32[2]{0} add(...)", lowered, compiled):
        assert "add" in hlo._as_hlo_text(program)
    with pytest.raises(TypeError, match="cannot read HLO"):
        hlo._as_hlo_text(42)


# ---------------------------------------------------------------------------
# check_bench: the persisted-artifact schema gate
# ---------------------------------------------------------------------------

def _load_check_bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_bench", ROOT / "scripts" / "check_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_bench_passes_on_checked_in_artifacts(capsys):
    cb = _load_check_bench()
    assert cb.main([]) == 0
    assert "0 invalid" in capsys.readouterr().out


def test_check_bench_rejects_contract_violations(tmp_path):
    cb = _load_check_bench()
    src = json.loads(
        (ROOT / "experiments" / "BENCH_map_phase_mesh.json").read_text())
    # type drift
    bad = dict(src, stacked_us="fast")
    p = tmp_path / "BENCH_map_phase_mesh.json"
    p.write_text(json.dumps(bad))
    assert cb.check_file(p) != []
    # invariant drift: the one-all-reduce contract broken in the artifact
    bad = dict(src, allreduce_per_sync=2)
    p.write_text(json.dumps(bad))
    errors = cb.check_file(p)
    assert any("one all-reduce per sync" in e for e in errors)
    # missing key
    bad = {k: v for k, v in src.items() if k != "sweep"}
    p.write_text(json.dumps(bad))
    assert any("missing required key" in e for e in cb.check_file(p))
    # unknown artifact name
    q = tmp_path / "BENCH_unknown.json"
    q.write_text("{}")
    assert any("no schema" in e for e in cb.check_file(q))
