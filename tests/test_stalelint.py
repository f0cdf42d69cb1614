"""stalelint + the declared cache registry (analysis/cachereg.py).

The contract under test: the shipped tree is coherence-clean (zero
findings, zero suppressions), every declared cache resolves to a real
anchor, the docs inventory cannot drift, and each of the four rule
families genuinely rejects its seeded failure shape — including the
exact q15 snapshot-escape and the dropped-invalidation shapes the rules
exist to keep out.
"""

import pathlib

import pytest

from ballista_tpu.analysis import cachereg, stalelint

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _read(rel: str) -> str:
    return (ROOT / rel).read_text()


def _rules(diags) -> set[str]:
    return {d.rule for d in diags}


# ---------------------------------------------------------------------------
# the clean tree
# ---------------------------------------------------------------------------


def test_clean_tree_has_zero_findings():
    diags = stalelint.lint_paths()
    assert diags == [], "\n".join(str(d) for d in diags)


def test_zero_suppressions_in_tree():
    assert stalelint.suppression_count() == 0


# ---------------------------------------------------------------------------
# registry closure
# ---------------------------------------------------------------------------


def test_every_declared_anchor_resolves():
    problems = cachereg.verify_anchors()
    assert problems == [], "\n".join(problems)


def test_registry_closure_over_every_entry():
    """Every CacheEntry is structurally complete: unique name, at least
    one anchor, legal scope/coherence, snapshot entries declare a seam,
    and every contract references declared caches."""
    names = [e.name for e in cachereg.CACHES]
    assert len(names) == len(set(names))
    for e in cachereg.CACHES:
        assert e.anchors, e.name
        assert e.scope in ("process", "session", "job", "task"), e.name
        assert e.coherence in (
            "versioned", "snapshot", "immutable-keyed",
            "speculative-validated",
        ), e.name
        assert e.keyed_by and e.invalidation, e.name
        if e.coherence == "snapshot":
            assert e.seam, f"{e.name}: snapshot discipline needs a seam"
    for x in cachereg.EXEMPT:
        assert x.reason, x.anchor
    for c in cachereg.CONTRACTS:
        for cache in c.caches:
            cachereg.entry(cache)  # KeyError = undeclared reference


def test_anchor_index_rejects_duplicates():
    idx = cachereg.anchor_index()
    # every cache anchor and every exempt anchor is present exactly once
    declared = sum(len(e.anchors) for e in cachereg.CACHES)
    assert len(idx) == declared + len(cachereg.EXEMPT)


def test_issue_named_caches_are_all_declared():
    """The coverage floor: the caches the engine is built around must
    each have a registry entry (removing one silently is a test diff)."""
    for name in (
        "exec-plan-cache", "trace-cache", "plan-hints",
        "aqe-strategy-store", "result-cache", "resolved-plan-bytes",
        "eager-plan-bytes", "push-registry", "flight-pool",
        "capacity-ladder", "executor-plan-cache",
        "executor-job-snapshots", "physical-plan-cache",
    ):
        cachereg.entry(name)


def test_docs_inventory_in_sync():
    assert cachereg.docs_in_sync() is None
    assert cachereg.render_inventory() in _read("docs/analysis.md")


# ---------------------------------------------------------------------------
# rule 1: undeclared-cache
# ---------------------------------------------------------------------------

_R1_SEED = """
class ProbeExec:
    def __init__(self):
        self._lut_cache = {}
"""


def test_rule1_flags_undeclared_instance_cache():
    diags = stalelint.lint_source(_R1_SEED, "ballista_tpu/exec/probe.py")
    assert _rules(diags) == {"undeclared-cache"}
    assert "ProbeExec._lut_cache" in diags[0].message


def test_rule1_flags_undeclared_module_global_and_lru():
    src = (
        "from functools import lru_cache\n"
        "_RESULT_POOL = {}\n"
        "@lru_cache(maxsize=None)\n"
        "def build_program(sig):\n"
        "    return sig\n"
    )
    diags = stalelint.lint_source(src, "ballista_tpu/ops/probe.py")
    assert len(diags) == 2
    assert _rules(diags) == {"undeclared-cache"}


def test_rule1_accepts_declared_anchor_and_plain_locals():
    # a declared anchor (the real executor plan cache) and a local temp
    # dict inside a function are both legal
    src = (
        "class Executor:\n"
        "    def __init__(self):\n"
        "        self._plan_cache = {}\n"
        "def helper():\n"
        "    scratch_cache = {}\n"
        "    return scratch_cache\n"
    )
    diags = stalelint.lint_source(
        src, "ballista_tpu/executor/executor.py"
    )
    assert diags == []


def test_rule1_suppression_honored_and_counted():
    src = _R1_SEED.replace(
        "self._lut_cache = {}",
        "self._lut_cache = {}  # stalelint: disable=undeclared-cache",
    )
    assert stalelint.lint_source(src, "ballista_tpu/exec/probe.py") == []


# ---------------------------------------------------------------------------
# rule 2: missing-invalidation
# ---------------------------------------------------------------------------


def test_rule2_real_mutators_all_satisfy_contracts():
    for rel in ("ballista_tpu/exec/context.py",
                "ballista_tpu/scheduler/server.py"):
        diags = [
            d for d in stalelint.lint_source(_read(rel), rel)
            if d.rule == "missing-invalidation"
        ]
        assert diags == [], "\n".join(str(d) for d in diags)


def test_rule2_rejects_dropped_plan_cache_clear():
    rel = "ballista_tpu/exec/context.py"
    src = _read(rel).replace("self._plan_cache.clear()", "pass")
    assert "self._plan_cache.clear()" not in src
    diags = [
        d for d in stalelint.lint_source(src, rel)
        if d.rule == "missing-invalidation"
    ]
    assert diags, "dropping the invalidation call must fail the gate"
    assert any("_plan_cache.clear" in d.message for d in diags)


def test_rule2_rejects_rewrite_keeping_stale_plan_bytes():
    # the scheduler/server.py "resolved bytes never invalidated" hazard,
    # as a machine contract: apply_certified_rewrite must pop both plan-
    # bytes caches for touched stages
    rel = "ballista_tpu/scheduler/server.py"
    src = _read(rel).replace("eager_plan_bytes.pop", "eager_plan_bytes.get")
    diags = [
        d for d in stalelint.lint_source(src, rel)
        if d.rule == "missing-invalidation"
    ]
    assert any(
        "apply_certified_rewrite" in d.message
        or "eager_plan_bytes.pop" in d.message
        for d in diags
    ), "\n".join(str(d) for d in diags)


def test_rule2_rejects_renamed_mutator():
    rel = "ballista_tpu/exec/context.py"
    src = _read(rel).replace("def append_table", "def append_rows")
    diags = [
        d for d in stalelint.lint_source(src, rel)
        if d.rule == "missing-invalidation"
    ]
    assert any("append_table" in d.message for d in diags)


# ---------------------------------------------------------------------------
# rule 3: snapshot-escape
# ---------------------------------------------------------------------------


def test_rule3_real_executor_is_clean():
    rel = "ballista_tpu/executor/executor.py"
    diags = [
        d for d in stalelint.lint_source(_read(rel), rel)
        if d.rule == "snapshot-escape"
    ]
    assert diags == [], "\n".join(str(d) for d in diags)


def test_rule3_rejects_the_q15_shape():
    # the exact pre-fix bug: handing the LIVE executor-lifetime cache to
    # a task attempt instead of the frozen job snapshot
    rel = "ballista_tpu/executor/executor.py"
    src = _read(rel).replace(
        "plan_cache=attempt_cache,", "plan_cache=self._plan_cache,"
    )
    assert "plan_cache=self._plan_cache," in src
    diags = [
        d for d in stalelint.lint_source(src, rel)
        if d.rule == "snapshot-escape"
    ]
    assert diags, "the q15 snapshot-escape shape must be rejected"
    assert "q15" in diags[0].message


def test_rule3_rejects_plain_live_read_allows_commit_write():
    src = (
        "class Executor:\n"
        "    def __init__(self):\n"
        "        self._plan_cache = {}\n"
        "    def _job_snapshot(self, job_id):\n"
        "        return dict(self._plan_cache)\n"
        "    def run_task(self, cache):\n"
        "        flag = self._plan_cache.get(('join', 'q3'))\n"  # escape
        "        self._plan_cache.update(cache)\n"  # commit: legal
        "        self._hints.mark({}, self._plan_cache)\n"
    )
    diags = [
        d for d in stalelint.lint_source(
            src, "ballista_tpu/executor/executor.py"
        )
        if d.rule == "snapshot-escape"
    ]
    assert len(diags) == 1 and diags[0].line == 7, diags


# ---------------------------------------------------------------------------
# rule 4: unvalidated-speculation
# ---------------------------------------------------------------------------

_R4_BAD = """
def learn_strategy(ctx, fp, flags):
    cache = ctx.plan_cache
    cache[fp] = flags
"""

_R4_GOOD = """
def learn_strategy(ctx, fp, flags):
    cache = ctx.plan_cache
    cache[fp] = flags
    ctx.defer_speculation(fp, lambda: flags)
"""


def test_rule4_rejects_bare_speculative_write():
    diags = stalelint.lint_source(_R4_BAD, "ballista_tpu/ops/probe.py")
    assert _rules(diags) == {"unvalidated-speculation"}


def test_rule4_accepts_validated_write():
    assert stalelint.lint_source(
        _R4_GOOD, "ballista_tpu/ops/probe.py"
    ) == []


def test_rule4_skips_the_seam_file_and_non_operator_code():
    # the seam itself (exec/base.py) and scheduler code are out of scope
    for rel in ("ballista_tpu/exec/base.py",
                "ballista_tpu/scheduler/probe.py"):
        assert stalelint.lint_source(_R4_BAD, rel) == []


def test_rule4_real_operator_tree_is_clean():
    for path in (ROOT / "ballista_tpu" / "ops").rglob("*.py"):
        rel = str(path.relative_to(ROOT))
        diags = [
            d for d in stalelint.lint_source(path.read_text(), rel)
            if d.rule == "unvalidated-speculation"
        ]
        assert diags == [], "\n".join(str(d) for d in diags)


# ---------------------------------------------------------------------------
# gate integration
# ---------------------------------------------------------------------------


def test_combined_gate_runner_green():
    from ballista_tpu.analysis.__main__ import run_stalelint

    ok, summary = run_stalelint()
    assert ok, summary
    assert "0 findings" in summary


def test_diagnostic_str_is_greppable():
    d = stalelint.StaleDiagnostic(
        "ballista_tpu/x.py", 3, "undeclared-cache", "m"
    )
    assert str(d) == "ballista_tpu/x.py:3: undeclared-cache: m"


def test_contract_outside_sweep_is_flagged(monkeypatch):
    ghost = cachereg.InvalidationContract(
        source="ghost", file="ballista_tpu/analysis/nope.py",
        mutators=("f",), must_call=("g",), caches=("result-cache",),
    )
    monkeypatch.setattr(
        cachereg, "CONTRACTS", cachereg.CONTRACTS + (ghost,)
    )
    diags = stalelint.lint_paths()
    assert any("outside the" in d.message for d in diags)


@pytest.mark.parametrize("rule", sorted(stalelint.RULES))
def test_every_rule_documented(rule):
    text = _read("docs/analysis.md")
    assert f"`{rule}`" in text
