"""Engine invariant analyzer, wired tier-1 (ISSUE 6; modeled on
test_metrics_coverage / test_failpoint_coverage):

  * scripts/check_invariants.py must exit 0 on the real tree — zero
    unsuppressed violations across all passes, every suppression with
    a reason
  * each fixture snippet in tests/analysis_fixtures/ is provably
    caught by its pass (negative checks: the analyzer actually detects
    every violation class it claims to)
  * suppression comments are honored, counted, and reasonless ones are
    themselves violations
  * the migrated check_metrics / check_failpoints shims keep their
    original function surfaces (back-compat)
"""

import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "scripts", "check_invariants.py")
FIXTURES = os.path.join(ROOT, "tests", "analysis_fixtures")

sys.path.insert(0, ROOT) if ROOT not in sys.path else None

from tidb_tpu.analysis import Driver  # noqa: E402
from tidb_tpu.analysis.blocking_under_lock import (  # noqa: E402
    BlockingUnderLockPass,
)
from tidb_tpu.analysis.core import Project  # noqa: E402
from tidb_tpu.analysis.error_shape import ErrorShapePass  # noqa: E402
from tidb_tpu.analysis.host_sync import (  # noqa: E402
    HostSyncPass,
    annotated_sites,
)
from tidb_tpu.analysis.jit_hygiene import JitHygienePass  # noqa: E402
from tidb_tpu.analysis.lock_discipline import (  # noqa: E402
    LockDisciplinePass,
)
from tidb_tpu.analysis.registry import SysvarCoveragePass  # noqa: E402
from tidb_tpu.analysis.resource_lifecycle import (  # noqa: E402
    ResourceLifecyclePass,
)


def _mini_root(tmp_path, *files, sysvars=None, readme="# nothing\n"):
    """Build a synthetic repo root: (subdir, fixture_name) pairs are
    copied under tidb_tpu/<subdir>/; a mini sysvars.py and README are
    always present so the registry passes have their anchors."""
    pkg = tmp_path / "tidb_tpu"
    (pkg / "session").mkdir(parents=True)
    (pkg / "session" / "sysvars.py").write_text(
        sysvars if sysvars is not None else "SYSVARS = {}\n")
    (tmp_path / "README.md").write_text(readme)
    for subdir, name in files:
        dst_dir = pkg / subdir if subdir else pkg
        dst_dir.mkdir(parents=True, exist_ok=True)
        dst_name = "errors.py" if name == "bad_error_code.py" else name
        shutil.copy(os.path.join(FIXTURES, name), dst_dir / dst_name)
    return str(tmp_path)


def _run_pass(root, p):
    """Unsuppressed violations + suppression/hygiene report for one pass."""
    driver = Driver(root, [p])
    reports = driver.run()
    by_id = {r.pass_id: r for r in reports}
    return by_id[p.id], by_id["suppressions"]


@pytest.fixture(scope="module")
def real_tree_cli():
    """ONE subprocess run of the tier-1 gate over the real tree (with
    --syncs riding along so the annotated-sync table shares the same
    invocation) — a full analyzer run costs seconds, so every CLI
    assertion reuses this instead of re-running it."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--syncs"], capture_output=True,
        text=True, cwd=ROOT, timeout=120)
    return proc, time.monotonic() - t0


@pytest.fixture(scope="module")
def real_tree_reports():
    """ONE in-process Driver run over the real tree, shared likewise."""
    return Driver(ROOT).run()


class TestRealTree:
    def test_repo_is_clean(self, real_tree_cli):
        """The tier-1 gate: the checker itself, as CI runs it. Must
        finish fast (budget: well under the 10s target on warm FS) and
        exit 0 with zero unsuppressed violations."""
        proc, elapsed = real_tree_cli
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "invariants ok: 0 violation(s)" in proc.stdout
        # generous CI headroom; measured ~5s cold on this box
        assert elapsed < 60, f"invariant run took {elapsed:.1f}s"

    def test_suppressions_all_carry_reasons(self, real_tree_reports):
        reports = real_tree_reports
        hygiene = [r for r in reports if r.pass_id == "suppressions"][0]
        assert not hygiene.problems, [v.render() for v in hygiene.problems]
        total = sum(len(r.suppressed) for r in reports)
        assert total > 0, "expected the documented allowlist to be nonempty"
        for r in reports:
            for v, s in r.suppressed:
                assert s.reason, f"reasonless suppression at {v.path}:{v.line}"

    def test_probe_count_sync_is_annotated(self):
        """The ISSUE's flagship annotation: the join's one intentional
        per-chunk sync is documented, not invisible."""
        sites = annotated_sites(Project(ROOT))
        join_sites = [s for s in sites if s[0].endswith("join.py")]
        assert join_sites, sites
        assert any("intentional sync" in r or "sync" in r
                   for _, _, r in join_sites)

    def test_list_and_pass_filter_cli(self):
        proc = subprocess.run(
            [sys.executable, SCRIPT, "--list"], capture_output=True,
            text=True, cwd=ROOT, timeout=120)
        assert proc.returncode == 0
        for pid in ("jit-hygiene", "host-sync", "lock-discipline",
                    "resource-lifecycle", "blocking-under-lock",
                    "protocol-conformance", "cache-key-completeness",
                    "metrics-coverage", "failpoint-coverage",
                    "sysvar-coverage", "error-shape"):
            assert pid in proc.stdout
        proc = subprocess.run(
            [sys.executable, SCRIPT, "--pass", "no-such-pass"],
            capture_output=True, text=True, cwd=ROOT, timeout=120)
        assert proc.returncode == 2

    def test_syncs_table_renders(self, real_tree_cli):
        proc, _elapsed = real_tree_cli
        assert proc.returncode == 0
        assert "annotated intentional host syncs:" in proc.stdout
        assert "executor/join.py" in proc.stdout


class TestJitHygieneFixture:
    def test_closure_jit_is_flagged(self, tmp_path):
        root = _mini_root(tmp_path, ("ops", "bad_jit_closure.py"))
        rep, _ = _run_pass(root, JitHygienePass())
        lines = {v.line for v in rep.violations}
        msgs = " | ".join(v.message for v in rep.violations)
        assert len(rep.violations) == 2, msgs
        assert "scale" in msgs and "offset" in msgs  # captured names named
        assert lines == {11, 15}, lines  # both jax.jit call sites

    def test_module_level_jit_is_clean(self, tmp_path):
        pkg = tmp_path / "tidb_tpu"
        pkg.mkdir()
        (tmp_path / "README.md").write_text("x")
        (pkg / "ok.py").write_text(
            "import functools\nimport jax\n\n\n"
            "@functools.partial(jax.jit, static_argnames=('n',))\n"
            "def kernel(x, n):\n    return x * n\n")
        rep, _ = _run_pass(str(tmp_path), JitHygienePass())
        assert not rep.violations, [v.render() for v in rep.violations]


class TestHostSyncFixture:
    def test_loop_syncs_are_flagged(self, tmp_path):
        root = _mini_root(tmp_path, ("executor", "bad_host_sync.py"))
        rep, _ = _run_pass(root, HostSyncPass())
        kinds = sorted(v.message for v in rep.violations)
        assert len(rep.violations) == 3, kinds
        assert any("int(y)" in m for m in kinds)
        assert any("np.asarray" in m for m in kinds)
        assert any(".item" in m for m in kinds)

    def test_out_of_scope_dir_is_ignored(self, tmp_path):
        # same file under parser/ (host tier): not in the pass scope
        root = _mini_root(tmp_path, ("parser", "bad_host_sync.py"))
        rep, _ = _run_pass(root, HostSyncPass())
        assert not rep.violations

    def test_chunk_loop_device_get_budget(self, tmp_path):
        """ISSUE 9 satellite: a jax.device_get inside a chunk loop
        without a # host-sync: reason fails; the annotated loop fetch
        and the post-loop finalize fetch stay clean."""
        root = _mini_root(tmp_path, ("executor", "bad_chunk_sync.py"))
        rep, _ = _run_pass(root, HostSyncPass())
        msgs = [v.render() for v in rep.violations]
        # exactly the un-annotated for-loop and while-loop fetches: the
        # annotated loop fetch is allowlisted and the finalize fetch
        # after the loop is the sanctioned shape
        assert len(rep.violations) == 2, msgs
        assert all("chunk loop" in v.message
                   and "device_get" in v.message
                   for v in rep.violations), msgs

    def test_probe_window_loop_fetch_is_flagged(self, tmp_path):
        """ISSUE 10 satellite: the fused scan→probe module class — an
        un-annotated per-token device_get inside the probe window-drain
        loop fails the pass; the batched one-fetch-per-window form (the
        fused deferral contract) stays clean."""
        root = _mini_root(tmp_path, ("executor", "bad_probe_window_sync.py"))
        rep, _ = _run_pass(root, HostSyncPass())
        msgs = [v.render() for v in rep.violations]
        assert len(rep.violations) == 2, msgs
        assert all("device_get" in v.message for v in rep.violations), msgs
        # exactly the per-token (line 21) and per-window (line 29) loop
        # fetches — never the batched post-loop fetch at line 36
        assert sorted(v.line for v in rep.violations) == [21, 29], msgs

    def test_fused_probe_module_is_clean(self, real_tree_reports):
        """The real fused-probe implementation (executor/pipeline.py)
        carries zero unsuppressed host-sync violations — its one window
        fetch sits outside the launch loop, per the budget."""
        hs = [r for r in real_tree_reports if r.pass_id == "host-sync"][0]
        pipeline = [v for v in hs.violations
                    if v.path.endswith("executor/pipeline.py")]
        assert not pipeline, [v.render() for v in pipeline]

    def test_topk_drain_loop_fetch_is_flagged(self, tmp_path):
        """ISSUE 18 satellite: the fused scan→top-k module class — an
        un-annotated per-chunk device_get inside the winner-state merge
        loop fails the pass; the single finalize fetch (the bounded
        device-state contract) stays clean."""
        root = _mini_root(tmp_path, ("ops", "bad_topk_sync.py"))
        rep, _ = _run_pass(root, HostSyncPass())
        msgs = [v.render() for v in rep.violations]
        assert len(rep.violations) == 2, msgs
        assert all("device_get" in v.message for v in rep.violations), msgs
        # exactly the per-chunk winner-state (line 22) and overflow-poll
        # (line 30) loop fetches — never the batched finalize fetch
        assert sorted(v.line for v in rep.violations) == [22, 30], msgs

    def test_fused_topk_module_is_clean(self, real_tree_reports):
        """The real device top-k kernels (ops/topk.py) carry zero
        unsuppressed host-sync violations — every chunk merge stays on
        device; the one sanctioned fetch lives at the pipeline's
        finalize, outside this module."""
        hs = [r for r in real_tree_reports if r.pass_id == "host-sync"][0]
        topk = [v for v in hs.violations if v.path.endswith("ops/topk.py")]
        assert not topk, [v.render() for v in topk]


class TestLockDisciplineFixture:
    def test_cycle_is_flagged(self, tmp_path):
        root = _mini_root(tmp_path, ("parallel", "bad_lock_cycle.py"))
        p = LockDisciplinePass(modules=("tidb_tpu/parallel/bad_lock_cycle.py",))
        rep, _ = _run_pass(root, p)
        cyc = [v for v in rep.violations if "cycle" in v.message]
        assert cyc, [v.render() for v in rep.violations]
        assert "Exchange.send_lock" in cyc[0].message
        assert "Exchange.recv_lock" in cyc[0].message

    def test_unlocked_stat_is_flagged(self, tmp_path):
        root = _mini_root(tmp_path, ("parallel", "bad_unlocked_stat.py"))
        p = LockDisciplinePass(
            modules=("tidb_tpu/parallel/bad_unlocked_stat.py",))
        rep, _ = _run_pass(root, p)
        hits = [v for v in rep.violations if "self.stats" in v.message]
        # two unlocked sites: the bare subscript write AND the
        # tuple-assign rebind (the dcn close() bug class)
        assert len(hits) == 2, [v.render() for v in rep.violations]
        assert all("without a lock" in v.message for v in hits)
        assert {v.message.split(" in ")[1].split(" ")[0] for v in hits} == \
            {"Worker.serve", "Worker.reset"}


class TestColumnarScope:
    """ISSUE 8: the analyzer roots extend to tidb_tpu/columnar/ — the
    host-sync and lock-discipline passes govern the new store exactly
    like the serving/dcn tiers."""

    def test_columnar_in_default_roots(self):
        from tidb_tpu.analysis.lock_discipline import DEFAULT_MODULES

        assert "tidb_tpu/columnar/store.py" in DEFAULT_MODULES
        assert "columnar" in HostSyncPass.SCOPE

    def test_host_sync_flagged_under_columnar(self, tmp_path):
        root = _mini_root(tmp_path, ("columnar", "bad_host_sync.py"))
        rep, _ = _run_pass(root, HostSyncPass())
        assert len(rep.violations) == 3, \
            [v.render() for v in rep.violations]

    def test_spill_rebuild_lock_cycle_flagged(self, tmp_path):
        root = _mini_root(tmp_path, ("columnar", "bad_segment_lock.py"))
        p = LockDisciplinePass(
            modules=("tidb_tpu/columnar/bad_segment_lock.py",))
        rep, _ = _run_pass(root, p)
        cyc = [v for v in rep.violations if "cycle" in v.message]
        assert cyc, [v.render() for v in rep.violations]
        assert "SegStore.store_lock" in cyc[0].message
        assert "SegStore.spill_lock" in cyc[0].message
        unlocked = [v for v in rep.violations
                    if "without a lock" in v.message]
        assert unlocked, [v.render() for v in rep.violations]

    def test_gather_wait_under_foreign_lock_is_flagged(self, tmp_path):
        """ISSUE 7 serving discipline (generalized into the ISSUE 12
        blocking-under-lock pass): a cv.wait() while holding another
        lock (the batch gather window parked with the catalog lock held)
        is flagged; waiting with only the cv's own lock is not."""
        root = _mini_root(tmp_path, ("serving", "bad_gather_wait.py"))
        p = BlockingUnderLockPass(
            modules=("tidb_tpu/serving/bad_gather_wait.py",))
        rep, _ = _run_pass(root, p)
        hits = [v for v in rep.violations if "wait()" in v.message]
        # the plain nested-with site AND the one inside a match arm
        assert len(hits) == 2, [v.render() for v in rep.violations]
        assert all("self.lock" in v.message for v in hits)
        assert all("gather-window" in v.message for v in hits)

    def test_real_serving_modules_wait_lock_free(self):
        """The real serving tier must pass its own blocking discipline
        (the default modules cover scheduler.py + batcher.py)."""
        from tidb_tpu.analysis.blocking_under_lock import DEFAULT_MODULES

        assert any("batcher" in m for m in DEFAULT_MODULES)
        assert any("scheduler" in m for m in DEFAULT_MODULES)

    def test_compaction_in_both_lock_rosters(self):
        """ISSUE 17: the background compaction worker is governed by
        the same lock discipline as the store it rebuilds for."""
        from tidb_tpu.analysis.blocking_under_lock import (
            DEFAULT_MODULES as BLOCK_MODULES,
        )
        from tidb_tpu.analysis.lock_discipline import (
            DEFAULT_MODULES as LOCK_MODULES,
        )

        assert "tidb_tpu/columnar/compaction.py" in BLOCK_MODULES
        assert "tidb_tpu/columnar/compaction.py" in LOCK_MODULES

    def test_compaction_rebuild_under_lock_flagged(self, tmp_path):
        """The fixture's rebuild-I/O-under-the-store-lock sites are
        flagged; the snapshot/build-outside/cutover protocol the real
        worker follows stays clean."""
        root = _mini_root(tmp_path, ("columnar", "bad_compaction_lock.py"))
        p = BlockingUnderLockPass(
            modules=("tidb_tpu/columnar/bad_compaction_lock.py",))
        rep, _ = _run_pass(root, p)
        hits = [v for v in rep.violations
                if "store_lock" in v.message]
        assert len(hits) == 2, [v.render() for v in rep.violations]
        assert any("spill.save" in v.message for v in hits)
        assert any("np.save" in v.message for v in hits)
        # both BAD sites live in rebuild_under_lock; the sanctioned
        # snapshot/build-outside/cutover function below stays clean
        assert len(rep.violations) == 2, \
            [v.render() for v in rep.violations]

    def test_real_modules_use_the_locked_suffix_convention(self):
        """The convention the pass leans on must hold: *_locked methods
        exist in dcn.py (documentation that the heuristic is live)."""
        with open(os.path.join(ROOT, "tidb_tpu", "parallel", "dcn.py"),
                  encoding="utf-8") as f:
            text = f.read()
        assert "_locked(" in text


class TestSysvarFixture:
    SYSVARS = (
        "SYSVARS = {}\n\n\n"
        "class SysVar:\n"
        "    def __init__(self, name, default):\n"
        "        self.name = name\n\n\n"
        "def _reg(*vs):\n"
        "    for v in vs:\n"
        "        SYSVARS[v.name] = v\n\n\n"
        "_reg(\n"
        "    SysVar('tidb_dead_knob', True),\n"
        ")\n")

    def test_unregistered_dead_and_undocumented(self, tmp_path):
        root = _mini_root(tmp_path, ("session2", "bad_sysvar.py"),
                          sysvars=self.SYSVARS)
        rep, _ = _run_pass(root, SysvarCoveragePass())
        msgs = [v.message for v in rep.violations]
        assert any("tidb_ghost_knob" in m and "not registered" in m
                   for m in msgs), msgs
        assert any("dead sysvar 'tidb_dead_knob'" in m for m in msgs), msgs
        assert any("tidb_dead_knob" in m and "not documented" in m
                   for m in msgs), msgs

    def test_clean_when_registered_read_and_documented(self, tmp_path):
        root = _mini_root(
            tmp_path,
            sysvars=self.SYSVARS.replace("tidb_dead_knob", "tidb_live_knob"),
            readme="docs: tidb_live_knob controls things\n")
        pkg = os.path.join(root, "tidb_tpu")
        with open(os.path.join(pkg, "reader.py"), "w") as f:
            f.write("def f(s):\n    return s.sysvars.get('tidb_live_knob')\n")
        rep, _ = _run_pass(root, SysvarCoveragePass())
        assert not rep.violations, [v.render() for v in rep.violations]


class TestErrorShapeFixture:
    def test_bare_and_swallowing_excepts(self, tmp_path):
        root = _mini_root(tmp_path, ("server", "bad_except.py"))
        rep, _ = _run_pass(root, ErrorShapePass())
        msgs = [v.message for v in rep.violations]
        assert len(msgs) == 2, msgs
        assert any("bare" in m for m in msgs)
        assert any("swallows" in m for m in msgs)

    def test_codeless_error_class(self, tmp_path):
        root = _mini_root(tmp_path, ("", "bad_error_code.py"))
        rep, _ = _run_pass(root, ErrorShapePass())
        msgs = [v.message for v in rep.violations]
        assert any("CodelessError" in m for m in msgs), msgs
        assert not any("GoodError" in m for m in msgs), msgs

    def test_annotated_broad_catch_is_allowed(self, tmp_path):
        pkg = tmp_path / "tidb_tpu"
        pkg.mkdir()
        (tmp_path / "README.md").write_text("x")
        (pkg / "ok.py").write_text(
            "def f(h):\n"
            "    try:\n"
            "        h()\n"
            "    except Exception:  # noqa: BLE001 — best-effort hook\n"
            "        pass\n")
        rep, _ = _run_pass(str(tmp_path), ErrorShapePass())
        assert not rep.violations, [v.render() for v in rep.violations]


class TestSuppressions:
    def test_reasoned_suppressions_are_honored_and_counted(self, tmp_path):
        root = _mini_root(tmp_path, ("executor", "suppressed_ok.py"))
        for p in (JitHygienePass(), HostSyncPass()):
            rep, hygiene = _run_pass(root, p)
            assert not rep.violations, [v.render() for v in rep.violations]
            assert not hygiene.problems
        rep, _ = _run_pass(root, JitHygienePass())
        assert len(rep.suppressed) == 1
        _v, s = rep.suppressed[0]
        assert "signature key" in s.reason or "fixture" in s.reason

    def test_reasonless_suppression_is_a_violation(self, tmp_path):
        root = _mini_root(tmp_path, ("ops", "bad_suppression.py"))
        rep, hygiene = _run_pass(root, JitHygienePass())
        # the jit violation itself is suppressed...
        assert not rep.violations
        # ...but the reasonless directive fails the build
        assert any("without a reason" in v.message
                   for v in hygiene.problems), hygiene.problems

    def test_stale_line_suppression_is_flagged(self, tmp_path):
        # a line-level disable whose governed line is clean (the code it
        # covered was fixed or drifted away) must not linger silently
        pkg = tmp_path / "tidb_tpu"
        pkg.mkdir()
        (tmp_path / "README.md").write_text("x")
        (pkg / "x.py").write_text(
            "A = 1  # lint: disable=error-shape -- covered code is gone\n")
        rep, hygiene = _run_pass(str(tmp_path), ErrorShapePass())
        assert not rep.violations
        assert any("stale suppression" in v.message
                   for v in hygiene.problems), hygiene.problems

    def test_module_disable_is_not_stale(self, tmp_path):
        # module-wide disables are prophylactic: clean-today is fine
        pkg = tmp_path / "tidb_tpu"
        pkg.mkdir()
        (tmp_path / "README.md").write_text("x")
        (pkg / "x.py").write_text(
            "# lint: module-disable=error-shape -- bench-style file\n"
            "A = 1\n")
        rep, hygiene = _run_pass(str(tmp_path), ErrorShapePass())
        assert not rep.violations
        assert not hygiene.problems, hygiene.problems

    def test_other_pass_suppression_not_stale_under_pass_filter(
            self, tmp_path):
        # running `--pass error-shape` must not misreport a (used-by-
        # jit-hygiene) suppression as stale just because that pass
        # didn't run this invocation
        root = _mini_root(tmp_path, ("executor", "suppressed_ok.py"))
        rep, hygiene = _run_pass(root, ErrorShapePass())
        assert not rep.violations
        assert not hygiene.problems, hygiene.problems

    def test_unknown_pass_in_directive_is_flagged(self, tmp_path):
        pkg = tmp_path / "tidb_tpu"
        pkg.mkdir()
        (tmp_path / "README.md").write_text("x")
        (pkg / "x.py").write_text(
            "A = 1  # lint: disable=not-a-pass -- whatever\n")
        rep, hygiene = _run_pass(str(tmp_path), ErrorShapePass())
        assert any("unknown pass" in v.message for v in hygiene.problems)

    def test_stale_host_sync_annotation_is_flagged(self, tmp_path):
        # an annotation covering no sync would silently pre-allowlist a
        # future sync on that line — it must be flagged, not ignored
        pkg = tmp_path / "tidb_tpu" / "executor"
        pkg.mkdir(parents=True)
        (tmp_path / "README.md").write_text("x")
        (pkg / "x.py").write_text(
            "def f(xs):\n"
            "    # host-sync: covered sync was refactored away\n"
            "    return sum(xs)\n")
        rep, _ = _run_pass(str(tmp_path), HostSyncPass())
        assert any("stale host-sync" in v.message
                   for v in rep.violations), rep.violations

    def test_trailing_directive_covers_wrapped_statement(self, tmp_path):
        # violation anchors to the sync call's line inside a wrapped
        # statement; a directive trailing ANY line of that statement
        # (here: the closing one) must still suppress it
        pkg = tmp_path / "tidb_tpu" / "executor"
        pkg.mkdir(parents=True)
        (tmp_path / "README.md").write_text("x")
        (pkg / "x.py").write_text(
            "import jax.numpy as jnp\n\n\n"
            "def f(chunks, g):\n"
            "    total = 0\n"
            "    for ch in chunks:\n"
            "        y = jnp.sum(ch)\n"
            "        total += g(\n"
            "            int(y),\n"
            "            2)  # host-sync: one scalar per chunk\n"
            "    return total\n")
        rep, hygiene = _run_pass(str(tmp_path), HostSyncPass())
        assert not rep.violations, [v.render() for v in rep.violations]
        assert not hygiene.problems, hygiene.problems

    def test_multiline_reason_is_joined(self, tmp_path):
        root = _mini_root(tmp_path, ("executor", "suppressed_ok.py"))
        rep, _ = _run_pass(root, JitHygienePass())
        assert len(rep.suppressed) == 1
        _v, s = rep.suppressed[0]
        # the reason wraps onto a continuation comment line in the
        # fixture; the recorded reason must carry the whole sentence
        assert "signature key covering" in s.reason, s.reason


class TestResourceLifecycleFixture:
    """ISSUE 12 tentpole (a): acquire/release pairing."""

    def test_leak_shapes_are_flagged(self, tmp_path):
        root = _mini_root(tmp_path, ("executor", "bad_resource_leak.py"))
        rep, hygiene = _run_pass(root, ResourceLifecyclePass())
        msgs = [v.render() for v in rep.violations]
        # exactly: the ENOSPC counter bump, the success-path-only
        # ScanPin close, and the consume with no release anywhere —
        # never the finally form, the return handoff, or the annotated
        # handoff
        assert len(rep.violations) == 3, msgs
        assert any("seg.pins" in m and "success path" in m
                   for m in msgs), msgs
        assert any("ScanPin" in m and "success path" in m
                   for m in msgs), msgs
        assert any("consume" in m and "no matching release" in m
                   for m in msgs), msgs
        assert not hygiene.problems, hygiene.problems

    def test_stale_lifecycle_annotation_is_flagged(self, tmp_path):
        # an annotation governing no acquire would pre-allowlist a
        # FUTURE leak on that line — flag it like stale host-sync notes
        pkg = tmp_path / "tidb_tpu" / "executor"
        pkg.mkdir(parents=True)
        (tmp_path / "README.md").write_text("x")
        (pkg / "x.py").write_text(
            "def f(xs):\n"
            "    # lifecycle: covered acquire was refactored away\n"
            "    return sum(xs)\n")
        rep, _ = _run_pass(str(tmp_path), ResourceLifecyclePass())
        assert any("stale lifecycle" in v.message
                   for v in rep.violations), rep.violations

    def test_reasonless_lifecycle_annotation_is_a_violation(self, tmp_path):
        pkg = tmp_path / "tidb_tpu" / "executor"
        pkg.mkdir(parents=True)
        (tmp_path / "README.md").write_text("x")
        (pkg / "x.py").write_text(
            "def f(t, b):\n"
            "    t.consume(b)  # lifecycle:\n")
        _rep, hygiene = _run_pass(str(tmp_path), ResourceLifecyclePass())
        assert any("lifecycle annotation without a reason" in v.message
                   for v in hygiene.problems), hygiene.problems

    def test_real_tree_is_clean(self, real_tree_reports):
        rep = [r for r in real_tree_reports
               if r.pass_id == "resource-lifecycle"][0]
        assert not rep.violations, [v.render() for v in rep.violations]


class TestBlockingUnderLockFixture:
    """ISSUE 12 tentpole (b): no registered lock across a blocking call
    — the columnar leaf-lock rule, machine-checked."""

    def test_device_get_and_consume_under_lock_flagged(self, tmp_path):
        root = _mini_root(tmp_path, ("executor", "bad_blocking_lock.py"))
        p = BlockingUnderLockPass(
            modules=("tidb_tpu/executor/bad_blocking_lock.py",))
        rep, _ = _run_pass(root, p)
        msgs = [v.render() for v in rep.violations]
        # exactly the under-lock device fetch and consume — the
        # snapshot-then-block form stays clean
        assert len(rep.violations) == 2, msgs
        assert any("device fetch" in m for m in msgs), msgs
        assert any("re-enters spill" in m for m in msgs), msgs
        assert all("self._lock" in m for m in msgs), msgs

    def test_store_leaf_rule_holds_on_real_tree(self, real_tree_reports):
        """The columnar 'store lock is a LEAF' comment is now a
        machine-checked fact: store.py carries zero unsuppressed
        blocking-under-lock violations."""
        rep = [r for r in real_tree_reports
               if r.pass_id == "blocking-under-lock"][0]
        store = [v for v in rep.violations
                 if v.path.endswith("columnar/store.py")]
        assert not store, [v.render() for v in store]
        assert not rep.violations, [v.render() for v in rep.violations]

    def test_memory_account_lock_exception_is_documented(
            self, real_tree_reports):
        """utils/memory's spill-under-account-lock is the one sanctioned
        exception — present as a SUPPRESSION (with its reason), never
        silently invisible."""
        rep = [r for r in real_tree_reports
               if r.pass_id == "blocking-under-lock"][0]
        mem = [(v, s) for v, s in rep.suppressed
               if v.path.endswith("utils/memory.py")]
        assert mem, "expected the documented account-lock suppression"
        assert all(s.reason for _v, s in mem)


class TestShardingScope:
    """ISSUE 13: the analyzer roots extend to tidb_tpu/sharding/ — the
    shuffle data plane obeys the same leaf-lock, host-sync, and
    lifecycle discipline as every other governed tier."""

    def test_sharding_in_default_roots(self):
        from tidb_tpu.analysis.blocking_under_lock import (
            DEFAULT_MODULES as BLOCK_MODULES,
        )
        from tidb_tpu.analysis.lock_discipline import (
            DEFAULT_MODULES as LOCK_MODULES,
        )
        from tidb_tpu.analysis.resource_lifecycle import (
            ResourceLifecyclePass,
        )

        assert "tidb_tpu/sharding/shuffle.py" in BLOCK_MODULES
        assert "tidb_tpu/sharding/shuffle.py" in LOCK_MODULES
        assert "sharding" in HostSyncPass.SCOPE
        assert "sharding" in ResourceLifecyclePass.SCOPE

    def test_shuffle_send_under_map_lock_is_flagged(self, tmp_path):
        """A peer-socket send/recv while holding the shard-map lock is
        the violation; snapshot-then-send stays clean."""
        root = _mini_root(tmp_path, ("sharding", "bad_shuffle_lock.py"))
        p = BlockingUnderLockPass(
            modules=("tidb_tpu/sharding/bad_shuffle_lock.py",))
        rep, _ = _run_pass(root, p)
        msgs = [v.render() for v in rep.violations]
        assert len(rep.violations) == 2, msgs
        assert any("socket send" in m for m in msgs), msgs
        assert any("socket recv" in m for m in msgs), msgs
        assert all("_shard_map_lock" in m for m in msgs), msgs

    def test_real_sharding_modules_are_clean(self, real_tree_reports):
        """The real shuffle/placement modules carry zero unsuppressed
        violations in ANY pass — the inbox lock is provably a leaf."""
        for rep in real_tree_reports:
            bad = [v for v in rep.violations
                   if "tidb_tpu/sharding/" in v.path.replace("\\", "/")]
            assert not bad, [v.render() for v in bad]


class TestElasticScope:
    """ISSUE 19: the analyzer roster extends to the topology-gate
    module — parallel/membership.py obeys the same leaf-lock and
    no-blocking-under-lock discipline as the rest of the coordination
    plane, and the elastic-topology surfaces are a pinned static
    count in check_invariants --json."""

    def test_membership_in_default_rosters(self):
        from tidb_tpu.analysis.blocking_under_lock import (
            DEFAULT_MODULES as BLOCK_MODULES,
        )
        from tidb_tpu.analysis.lock_discipline import (
            DEFAULT_MODULES as LOCK_MODULES,
        )
        from tidb_tpu.analysis.resource_lifecycle import (
            ResourceLifecyclePass,
        )

        assert "tidb_tpu/parallel/membership.py" in BLOCK_MODULES
        assert "tidb_tpu/parallel/membership.py" in LOCK_MODULES
        assert "parallel" in ResourceLifecyclePass.SCOPE

    def test_gate_rpc_under_registry_lock_is_flagged(self, tmp_path):
        """A peer send/recv while holding the gate registry lock is
        the violation (it stalls every statement's gate acquire behind
        one cutover's network); snapshot-then-send stays clean."""
        root = _mini_root(tmp_path, ("parallel", "bad_membership_lock.py"))
        p = BlockingUnderLockPass(
            modules=("tidb_tpu/parallel/bad_membership_lock.py",))
        rep, _ = _run_pass(root, p)
        msgs = [v.render() for v in rep.violations]
        assert len(rep.violations) == 2, msgs
        assert any("socket send" in m for m in msgs), msgs
        assert any("socket recv" in m for m in msgs), msgs
        assert all("_gates_lock" in m for m in msgs), msgs

    def test_bare_reader_count_mutation_is_flagged(self, tmp_path):
        """The reader-count map is mutated under the registry lock in
        one method and bare in another — the race the writer's
        drain-to-zero check cannot survive."""
        root = _mini_root(tmp_path, ("parallel", "bad_membership_lock.py"))
        p = LockDisciplinePass(
            modules=("tidb_tpu/parallel/bad_membership_lock.py",))
        rep, _ = _run_pass(root, p)
        hits = [v for v in rep.violations if "self._readers" in v.message]
        assert hits, [v.render() for v in rep.violations]
        assert all("without a lock" in v.message for v in hits)

    def test_real_membership_module_is_clean(self, real_tree_reports):
        for rep in real_tree_reports:
            bad = [v for v in rep.violations
                   if v.path.replace("\\", "/").endswith(
                       "parallel/membership.py")]
            assert not bad, [v.render() for v in bad]

    def test_elastic_surface_count_pinned(self):
        from tidb_tpu.analysis.core import Project
        from tidb_tpu.analysis.registry import (_ELASTIC_SURFACES,
                                                elastic_surfaces)

        got = elastic_surfaces(Project(ROOT))
        assert len(got) == len(_ELASTIC_SURFACES) == 11, got


class TestSuppressionCountPinned:
    """ISSUE 12 satellite: the report's suppression count is a tier-1-
    asserted number so allowlist drift is visible in review. Update the
    constant DELIBERATELY when adding/removing a suppression."""

    # ISSUE 14 added two: the ping health arm (protocol-conformance)
    # and GroupTableStack's caller-supplied key (cache-key-completeness);
    # ISSUE 22 removed three with parallel/mesh.py's shard_map wrapper;
    # ISSUE 30 removed twelve with the offline segment-sum microbench
    # under ops/: its two module-disables covered nine host-sync and
    # three jit-hygiene findings, each counted
    EXPECTED_SUPPRESSIONS = 13
    # annotated-allowlist entries are the same drift class: a future
    # `# lifecycle:` on a real leak must move a pinned number
    EXPECTED_LIFECYCLE_ANNOTATIONS = 2

    def test_suppression_count_is_pinned(self, real_tree_reports):
        total = sum(len(r.suppressed) for r in real_tree_reports)
        assert total == self.EXPECTED_SUPPRESSIONS, (
            f"suppression count moved: {total} != "
            f"{self.EXPECTED_SUPPRESSIONS}. If the change is deliberate "
            "(a new documented exception, or one removed), update "
            "EXPECTED_SUPPRESSIONS in the same commit.")

    def test_lifecycle_annotation_count_is_pinned(self):
        from tidb_tpu.analysis.resource_lifecycle import lifecycle_sites

        sites = lifecycle_sites(Project(ROOT))
        assert len(sites) == self.EXPECTED_LIFECYCLE_ANNOTATIONS, sites
        for _rel, _line, reason in sites:
            assert reason, sites

    def test_no_stale_line_directives_in_tree(self, real_tree_reports):
        """The stale-suppression sweep stays done: zero line-level
        directives that no longer suppress anything."""
        hygiene = [r for r in real_tree_reports
                   if r.pass_id == "suppressions"][0]
        stale = [v for v in hygiene.problems
                 if "stale suppression" in v.message]
        assert not stale, [v.render() for v in stale]


class TestJsonAndChangedModes:
    """ISSUE 12 satellite: machine-readable report + incremental lint
    for the builder loop."""

    def test_json_schema_round_trips(self, tmp_path):
        import json

        proc = subprocess.run(
            [sys.executable, SCRIPT, "--json"], capture_output=True,
            text=True, cwd=ROOT, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        # round-trip: serialize -> parse -> identical document
        assert json.loads(json.dumps(doc)) == doc
        assert doc["schema"] == Driver.JSON_SCHEMA
        assert doc["ok"] is True and doc["violation_count"] == 0
        assert doc["suppression_count"] == \
            TestSuppressionCountPinned.EXPECTED_SUPPRESSIONS
        assert doc["lifecycle_annotation_count"] == \
            TestSuppressionCountPinned.EXPECTED_LIFECYCLE_ANNOTATIONS
        assert doc["host_sync_annotation_count"] > 0
        ids = {p["id"] for p in doc["passes"]}
        assert {"jit-hygiene", "host-sync", "lock-discipline",
                "resource-lifecycle", "blocking-under-lock",
                "protocol-conformance", "cache-key-completeness",
                "error-shape", "suppressions"} <= ids
        for p in doc["passes"]:
            assert p["seconds"] >= 0
            for v in p["violations"] + p["problems"]:
                assert set(v) == {"pass", "path", "line", "message"}
            for s in p["suppressed"]:
                assert s["reason"]

    def test_changed_mode_is_fast_and_clean(self):
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, SCRIPT, "--changed",
             "tidb_tpu/columnar/store.py", "tidb_tpu/utils/memory.py",
             "tidb_tpu/executor/pipeline.py"],
            capture_output=True, text=True, cwd=ROOT, timeout=120)
        elapsed = time.monotonic() - t0
        assert proc.returncode == 0, proc.stdout + proc.stderr
        # the ISSUE's builder-loop budget, with interpreter startup
        assert elapsed < 5, f"--changed took {elapsed:.1f}s"

    def test_changed_mode_catches_violations_in_the_diff(self, tmp_path):
        """An incremental run over a file WITH a violation still fails:
        restriction narrows scope, never strength."""
        root = _mini_root(tmp_path, ("executor", "bad_blocking_lock.py"))
        p = BlockingUnderLockPass(
            modules=("tidb_tpu/executor/bad_blocking_lock.py",))
        driver = Driver(root, [p],
                        changed=["tidb_tpu/executor/bad_blocking_lock.py"])
        reports = driver.run()
        rep = [r for r in reports if r.pass_id == p.id][0]
        assert len(rep.violations) == 2, \
            [v.render() for v in rep.violations]
        # and a restriction EXCLUDING the bad file sees nothing
        driver2 = Driver(root, [BlockingUnderLockPass(
            modules=("tidb_tpu/executor/bad_blocking_lock.py",))],
            changed=["tidb_tpu/other.py"])
        reports2 = driver2.run()
        rep2 = [r for r in reports2 if r.pass_id == p.id][0]
        assert not rep2.violations


class TestShimBackCompat:
    """The migrated scripts keep their original function surfaces."""

    def _load(self, name):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, "scripts", f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_check_metrics_surface(self):
        mod = self._load("check_metrics")
        assert callable(mod.collect) and callable(mod.check) \
            and callable(mod.main)
        problems, names = mod.check(ROOT, os.path.join(ROOT, "README.md"))
        assert problems == [] and len(names) > 20

    def test_check_failpoints_surface(self):
        mod = self._load("check_failpoints")
        sites, armed, dynamic = mod.scan(ROOT)
        assert sites and not dynamic
        assert mod.main([]) == 0

    def test_driver_pass_parity_with_shims(self, real_tree_reports):
        """The driver's registry passes and the shims must agree: a
        clean shim run implies clean passes (same code underneath)."""
        by_id = {r.pass_id: r for r in real_tree_reports}
        for pid in ("metrics-coverage", "failpoint-coverage"):
            rep = by_id[pid]
            assert not rep.violations, [v.render() for v in rep.violations]


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
