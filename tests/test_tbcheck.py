"""tbcheck: the AST invariant linter (round 17).

Three layers of proof:

1. The repo itself is clean — the tier-1 gate.  Every rule runs over
   the whole package and must report zero unsuppressed findings, and
   every suppression must carry a reason and be used.
2. Per-rule fixtures — a known-bad snippet per rule asserted to flag
   with the right rule id (and line), plus a known-good twin asserted
   clean.  This is also the migration proof for the old tests/test_tidy
   regexes (wall clock / unseeded random / print) and the r16 envcheck
   grep: every pattern they caught is caught here, now alias-aware.
3. Structural unit tests — the import graph puts leaf tools outside
   the sim-reachable set, the wire-layout rule derives the trace and
   tenant carve-outs from vsr/wire.py itself, and the CLI speaks the
   JSON schema.
"""

import json
import os
import subprocess
import sys

import pytest

from tigerbeetle_tpu.analysis import run_lint
from tigerbeetle_tpu.analysis import layout as layout_mod
from tigerbeetle_tpu.analysis.core import SourceFile
from tigerbeetle_tpu.analysis.imports import (
    SIM_ROOTS,
    build_graph,
    module_name,
    reachable,
)
from tigerbeetle_tpu.analysis.rules import all_rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "tigerbeetle_tpu")
FIXTURES = os.path.join(os.path.dirname(__file__), "tbcheck_fixtures")


def fixture(name: str) -> str:
    return os.path.join(FIXTURES, name)


def lint_fixture(name: str):
    """Lint one fixture file with every rule, treating it as
    sim-reachable (fixtures have no import-graph position)."""
    return run_lint(files=[fixture(name)], assume_sim=True)


# ----------------------------------------------------------------------
# 1. the tier-1 gate


@pytest.fixture(scope="module")
def repo_result():
    """One full-package pass shared by the repo-wide assertions (the
    CLI schema test below still runs its own end-to-end subprocess)."""
    return run_lint()


def test_repo_is_clean(repo_result):
    """Zero unsuppressed findings across the package — the invariant
    the whole round exists to enforce.  Failures print the finding
    list verbatim (path:line: [rule] message)."""
    assert not repo_result.findings, "\n".join(
        str(f) for f in repo_result.findings
    )
    assert repo_result.checked_files > 60  # whole package, not a subset


def test_repo_suppressions_all_carry_reasons(repo_result):
    """Indirect but total: a reasonless or unused suppression is
    itself a finding, so test_repo_is_clean also proves every
    suppression in the repo carries a reason and still earns it."""
    assert repo_result.suppressed > 0  # annotated true positives exist
    assert not [
        f for f in repo_result.findings if f.rule == "suppression"
    ]


def test_single_file_run_matches_full_run():
    """Path-scoped lint keeps the full run's import-graph position:
    router.py alone must lint clean (its allow(determinism) comments
    stay used because the sim-reachable set is still computed over
    the whole package, not just the listed file)."""
    result = run_lint(files=[
        os.path.join(PKG, "runtime", "router.py")
    ])
    assert not result.findings, "\n".join(
        str(f) for f in result.findings
    )
    assert result.suppressed >= 5  # the RouterServer wall-clock sites


def test_directory_argument_expands():
    result = run_lint(files=[os.path.join(PKG, "vsr")])
    assert result.checked_files > 5
    assert not result.findings, "\n".join(
        str(f) for f in result.findings
    )


# ----------------------------------------------------------------------
# 2. per-rule fixtures: known-bad flags, known-good twin is clean

_EXPECT_BAD = {
    # fixture -> (rule id, set of expected finding lines)
    "bad_determinism.py": ("determinism", {10, 14, 18, 22}),
    "bad_envcheck.py": ("envcheck", {8, 12, 16}),
    "bad_money.py": ("money", {7, 11, 15, 19}),
    "bad_wire_layout.py": ("wire-layout", None),
    "bad_wire_layout_claim.py": ("wire-layout", None),
    "bad_broad_except.py": ("broad-except", {7, 14, 21}),
    "bad_worker_shared.py": ("worker-shared", None),
    "bad_print.py": ("no-print", {5}),
}


@pytest.mark.parametrize("name", sorted(_EXPECT_BAD))
def test_known_bad_fixture_flags(name):
    rule, lines = _EXPECT_BAD[name]
    result = lint_fixture(name)
    hits = [f for f in result.findings if f.rule == rule]
    assert hits, f"{name}: rule {rule} reported nothing"
    if lines is not None:
        assert {f.line for f in hits} == lines, hits
    # and nothing ELSE fired — bad fixtures are bad in one dimension
    others = [f for f in result.findings if f.rule != rule]
    assert not others, others


@pytest.mark.parametrize("name", [
    "good_determinism.py", "good_envcheck.py", "good_money.py",
    "good_wire_layout.py", "good_broad_except.py",
    "good_worker_shared.py", "good_print.py",
])
def test_known_good_twin_is_clean(name):
    result = lint_fixture(name)
    assert not result.findings, "\n".join(
        str(f) for f in result.findings
    )


def test_tidy_migration_patterns_still_caught():
    """The three regexes the old tests/test_tidy.py enforced —
    time.time(), random.random(), print( — must still be caught after
    the migration, now through aliases a regex can't see."""
    src = (
        "import time as t\n"
        "import random as r\n"
        "def f():\n"
        "    t0 = t.time()\n"       # old: \btime\.time\(\)
        "    x = r.random()\n"      # old: \brandom\.random\(\)
        "    print(t0, x)\n"        # old: \bprint\(
    )
    path = fixture("_tmp_tidy_migration.py")
    with open(path, "w") as fh:
        fh.write(src)
    try:
        result = run_lint(files=[path], assume_sim=True)
        rules_hit = {f.rule for f in result.findings}
        assert "determinism" in rules_hit  # time.time + random.random
        assert "no-print" in rules_hit
        det_lines = {
            f.line for f in result.findings if f.rule == "determinism"
        }
        assert det_lines == {4, 5}
    finally:
        os.remove(path)


def test_envcheck_rule_catches_hash_knob_aliases():
    """r23 knobs (TB_HASH_REUSE / TB_HASH_THREADS) swept through every
    alias form the envcheck rule resolves — a raw read of either must
    flag no matter how the import is spelled, so the only blessed
    readers stay envcheck.hash_reuse()/hash_threads()."""
    src = (
        "import os\n"
        "import os as _o\n"
        "from os import environ as E\n"
        "from os import getenv\n"
        "def a():\n"
        "    return os.environ['TB_HASH_REUSE']\n"
        "def b():\n"
        "    return E.get('TB_HASH_THREADS')\n"
        "def c():\n"
        "    return _o.getenv('TB_HASH_REUSE')\n"
        "def d():\n"
        "    return getenv('TB_HASH_THREADS', '0')\n"
    )
    path = fixture("_tmp_hash_knobs.py")
    with open(path, "w") as fh:
        fh.write(src)
    try:
        result = run_lint(files=[path], assume_sim=True)
        env_findings = [f for f in result.findings if f.rule == "envcheck"]
        flagged = {f.line for f in env_findings}
        # direct-getenv via `from os import getenv` resolves too
        assert flagged >= {6, 8, 10}, env_findings
        assert all("TB_HASH" in f.message for f in env_findings)
    finally:
        os.remove(path)


def test_suppression_requires_reason_and_use():
    result = lint_fixture("bad_suppression.py")
    sup = [f for f in result.findings if f.rule == "suppression"]
    messages = " | ".join(f.message for f in sup)
    assert "without a rule id and reason" in messages
    assert "unused suppression" in messages
    # the reasonless allow does NOT suppress: the print still flags
    assert any(f.rule == "no-print" for f in result.findings)


def test_suppression_with_reason_suppresses():
    src = (
        "def f(x):\n"
        "    # tbcheck: allow(no-print): operator-facing tool\n"
        "    print(x)\n"
    )
    path = fixture("_tmp_allow.py")
    with open(path, "w") as fh:
        fh.write(src)
    try:
        result = run_lint(files=[path], assume_sim=True)
        assert not result.findings
        assert result.suppressed == 1
    finally:
        os.remove(path)


def test_stale_half_of_multi_rule_allow_is_reported():
    """Used-ness is per rule id: an `allow-file(a, b)` where only b
    still fires must report the dead `a` half — suppressions cannot
    rot behind a live sibling."""
    src = (
        "# tbcheck: allow-file(determinism, no-print): tooling module\n"
        "def f(x):\n"
        "    print(x)\n"  # only no-print fires; determinism is stale
    )
    path = fixture("_tmp_stale_half.py")
    with open(path, "w") as fh:
        fh.write(src)
    try:
        result = run_lint(files=[path], assume_sim=True)
        sup = [f for f in result.findings if f.rule == "suppression"]
        assert len(sup) == 1 and "determinism" in sup[0].message, (
            result.findings
        )
        assert "no-print" not in sup[0].message
        assert result.suppressed == 1  # the live half still works
    finally:
        os.remove(path)


def test_envvar_typo_fails_fast_through_tpu_fallback():
    """TB_NATIVE_SANITIZE=msan must surface its named EnvVarError, not
    vanish into the TpuStateMachine optional-native fallback as a
    silent unsanitized run."""
    code = (
        "from tigerbeetle_tpu import constants as cfg;"
        "from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine;"
        "TpuStateMachine(cfg.TEST_MIN)"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, TB_NATIVE_SANITIZE="msan",
                 JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "TB_NATIVE_SANITIZE" in proc.stderr
    assert "EnvVarError" in proc.stderr


def test_stacked_standalone_allows_merge():
    """Two standalone allows for different rules above one line must
    BOTH apply (neither clobbers the other)."""
    src = (
        "import time\n"
        "def f():\n"
        "    # tbcheck: allow(no-print): operator-facing output\n"
        "    # tbcheck: allow(determinism): log stamp, not state\n"
        "    print(time.time())\n"
    )
    path = fixture("_tmp_stacked.py")
    with open(path, "w") as fh:
        fh.write(src)
    try:
        result = run_lint(files=[path], assume_sim=True)
        assert not result.findings, result.findings
        assert result.suppressed == 2
    finally:
        os.remove(path)


# ----------------------------------------------------------------------
# 3. structural units


def _package_sources():
    files = []
    for dirpath, dirs, names in os.walk(PKG):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        files += [os.path.join(dirpath, n) for n in names
                  if n.endswith(".py")]
    return [SourceFile(p, REPO) for p in sorted(files)]


def test_import_graph_sim_reachable_set():
    """The determinism scope is the import-graph closure of the sim
    roots: consensus/state-machine/storage modules are inside; leaf
    tools that IMPORT the sim (soak, fuzz CLI) and operator front-ends
    are outside — the property the old filename exemption list only
    approximated."""
    sources = _package_sources()
    graph = build_graph({s.path: s.tree for s in sources}, PKG)
    sim = reachable(graph)
    assert set(SIM_ROOTS) <= sim
    must_be_in = {
        "tigerbeetle_tpu.vsr.multi",
        "tigerbeetle_tpu.vsr.journal",
        "tigerbeetle_tpu.state_machine.kernel",
        "tigerbeetle_tpu.state_machine.device_engine",
        "tigerbeetle_tpu.testing.chaos",  # lazily imported by vopr
        "tigerbeetle_tpu.qos",
        "tigerbeetle_tpu.utils.worker",
        # r19: SimFollower drives the follower core inside the sim,
        # so the module is clock-free (FollowerServer's wall clock is
        # injected at the process edge, cli.py).
        "tigerbeetle_tpu.runtime.follower",
        "tigerbeetle_tpu.vsr.aof",
    }
    assert must_be_in <= sim, must_be_in - sim
    must_be_out = {
        "tigerbeetle_tpu.testing.soak",   # imports the sim, not vice versa
        "tigerbeetle_tpu.testing.fuzz",
        "tigerbeetle_tpu.cli",
        "tigerbeetle_tpu.repl",
        "tigerbeetle_tpu.benchmark",
        "tigerbeetle_tpu.client",
        "tigerbeetle_tpu.flags",
        "tigerbeetle_tpu.bindings",
        "tigerbeetle_tpu.analysis.core",
    }
    assert not (must_be_out & sim), must_be_out & sim


def test_relative_import_resolution():
    """Relative imports resolve against the importer's package — an
    __init__.py's dotted name already IS its package, so one level
    strips nothing from it."""
    import ast as ast_mod

    files = {
        os.path.join(PKG, "fakepkg", "__init__.py"):
            ast_mod.parse("from . import leaf\n"),
        os.path.join(PKG, "fakepkg", "leaf.py"):
            ast_mod.parse("from .. import constants\n"),
        os.path.join(PKG, "constants.py"): ast_mod.parse(""),
    }
    graph = build_graph(files, PKG)
    assert "tigerbeetle_tpu.fakepkg.leaf" in graph[
        "tigerbeetle_tpu.fakepkg"
    ]
    assert "tigerbeetle_tpu.constants" in graph[
        "tigerbeetle_tpu.fakepkg.leaf"
    ]


def test_module_name_resolution():
    assert module_name(
        os.path.join(PKG, "vsr", "wire.py"), PKG
    ) == "tigerbeetle_tpu.vsr.wire"
    assert module_name(
        os.path.join(PKG, "testing", "__init__.py"), PKG
    ) == "tigerbeetle_tpu.testing"


def test_wire_layout_derived_from_wire_py():
    """The trace/tenant carve-outs the rule checks are DERIVED from
    vsr/wire.py's dtype declaration — assert the derivation against
    the known contract: trace [156, 173), tenant [173, 177), total
    256, no overlaps or gaps."""
    sf = SourceFile(os.path.join(PKG, "vsr", "wire.py"), REPO)
    import ast

    layouts = [
        layout_mod.parse_dtype_layout(node.value)
        for node in ast.walk(sf.tree)
        if isinstance(node, ast.Assign)
        and any(isinstance(t, ast.Name) and t.id == "HEADER_DTYPE"
                for t in node.targets)
    ]
    assert len(layouts) == 1 and layouts[0] is not None
    layout = layouts[0]
    assert layout.total == 256
    assert layout.span_of("trace_id", "trace_ts", "trace_flags") == (
        156, 173
    )
    assert layout.span_of("tenant") == (173, 177)
    problems = layout_mod.check_layout(layout, sf.lines, 256)
    assert not problems, problems


def test_wire_layout_overlap_is_flagged():
    """Acceptance: a scratch overlapping carve-out of header bytes is
    flagged."""
    result = lint_fixture("bad_wire_layout.py")
    assert any(
        f.rule == "wire-layout" and "overlaps" in f.message
        for f in result.findings
    ), result.findings


def test_wire_layout_lying_annotation_is_flagged():
    result = lint_fixture("bad_wire_layout_claim.py")
    assert any(
        f.rule == "wire-layout" and "annotation claims" in f.message
        for f in result.findings
    ), result.findings


def test_cli_end_to_end_json_schema():
    """`python -m tigerbeetle_tpu lint --json` over the repo: exit 0,
    well-formed schema (the machine-readable surface CI consumes)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "lint", "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == 1 and doc["tool"] == "tbcheck"
    assert doc["findings"] == [] and doc["counts"] == {}
    assert doc["checked_files"] > 60
    assert isinstance(doc["suppressed"], int)


def test_cli_nonzero_on_findings():
    proc = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "lint", "--json",
         fixture("bad_print.py")],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    finding = doc["findings"][0]
    assert set(finding) == {"rule", "path", "line", "message"}
    assert finding["rule"] == "no-print" and finding["line"] == 5


def _lint_snippet(src: str, name: str = "_tmp_snippet.py"):
    path = fixture(name)
    with open(path, "w") as fh:
        fh.write(src)
    try:
        return run_lint(files=[path], assume_sim=True)
    finally:
        os.remove(path)


def test_determinism_catches_distribution_draws():
    """Global-state distribution draws (np.random.normal, gauss, ...)
    are as nondeterministic as random.random and must not pass."""
    result = _lint_snippet(
        "import numpy as np\n"
        "import random\n"
        "a = np.random.normal()\n"
        "b = np.random.standard_normal(4)\n"
        "c = random.gauss(0, 1)\n"
    )
    det = [f for f in result.findings if f.rule == "determinism"]
    assert {f.line for f in det} == {3, 4, 5}, result.findings


def test_money_catches_bare_float_dtype():
    """astype(float) loses u128 precision above 2^53 exactly like
    astype(np.float64) — bare `float` must flag too."""
    result = _lint_snippet(
        "def widen(amounts):\n"
        "    return amounts.astype(float)\n"
    )
    assert any(
        f.rule == "money" and "`float`" in f.message
        for f in result.findings
    ), result.findings
    # ...but a float ANNOTATION on a money-adjacent assignment is a
    # declaration, not computation: no finding.
    result = _lint_snippet(
        "def f(amount_cents: int):\n"
        "    amount_ratio: float = compute()\n"
        "    return amount_ratio\n"
    )
    assert not result.findings, result.findings


def test_worker_shared_catches_injected_worker():
    """A class that RECEIVES its SerialWorker (instead of
    constructing one) must still trip the rule."""
    result = _lint_snippet(
        "class Flusher:\n"
        "    def __init__(self, worker):\n"
        "        self._w = worker\n"
        "        self.dirty = 0\n"
        "    def _flush_job(self):\n"
        "        self.dirty = 0\n"
        "    def kick(self):\n"
        "        self._w.submit(self._flush_job)\n"
        "    def mark(self):\n"
        "        self.dirty += 1\n"
    )
    assert any(
        f.rule == "worker-shared" and "'dirty'" in f.message
        for f in result.findings
    ), result.findings


def test_unparseable_file_is_a_finding_not_a_crash():
    result = _lint_snippet("def broken(:\n", name="_tmp_broken.py")
    assert len(result.findings) == 1
    f = result.findings[0]
    assert f.rule == "parse" and "not parseable" in f.message
    missing = fixture("_tmp_does_not_exist.py")
    result = run_lint(files=[missing], assume_sim=True)
    assert [f.rule for f in result.findings] == ["parse"]


def test_cli_rejects_unknown_flags():
    proc = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu", "lint", "--jsn"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert proc.returncode == 2
    assert "unknown lint flag" in proc.stderr


def test_rule_catalog_is_stable():
    """Suppressions name rule ids — renaming one silently orphans
    every allow comment, so the catalog is pinned here."""
    assert {r.id for r in all_rules()} == {
        "determinism", "envcheck", "money", "wire-layout",
        "broad-except", "worker-shared", "no-print",
    }
    for r in all_rules():
        assert r.doc  # every rule documents its contract
