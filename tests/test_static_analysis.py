"""graftcheck: the static-analysis suite + runtime concurrency witness.

Tier-1 contract (ISSUE 7): ``python -m kubetpu.analysis kubetpu/`` exits 0
with an empty-or-justified baseline — enforced here so every future PR is
invariant-checked by construction; each checker proves it fires on a
known-bad fixture and stays silent on the known-good twin; the donation
and transfer checkers demonstrably COVER the files PR 2/6 audited by hand
(a file move can't silently drop coverage); and the lock-order witness
catches a deliberately inverted two-lock acquisition.
"""

from __future__ import annotations

import ast
import json
import os
import re
import threading
import _thread

import pytest

from kubetpu.analysis import CHECKERS, all_checkers, analyze_paths
from kubetpu.analysis.astutil import collect_jitted
from kubetpu.analysis.baseline import Baseline
from kubetpu.analysis.__main__ import main as cli_main
from kubetpu.analysis import witness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "analysis_fixtures")
_MARKER = re.compile(r"# expect: ([A-Z0-9,]+)")


import functools


@functools.lru_cache(maxsize=1)
def _fixture_result():
    return analyze_paths([FIXTURES], root=FIXTURES)


@functools.lru_cache(maxsize=1)
def _repo_result():
    return analyze_paths([os.path.join(REPO, "kubetpu")], root=REPO)


def _expected_markers() -> set:
    out = set()
    for dirpath, _dirs, files in os.walk(FIXTURES):
        for f in files:
            if not f.endswith(".py"):
                continue
            p = os.path.join(dirpath, f)
            rel = os.path.relpath(p, FIXTURES).replace(os.sep, "/")
            with open(p, encoding="utf-8") as fh:
                for i, line in enumerate(fh, 1):
                    m = _MARKER.search(line)
                    if m:
                        for code in m.group(1).split(","):
                            out.add((rel, i, code))
    return out


# ---------------------------------------------------------------------------
# the tier-1 gate: the repo itself is clean
# ---------------------------------------------------------------------------

def test_repo_has_zero_nonbaselined_violations():
    """Every invariant the suite encodes holds across kubetpu/ — the
    machine-checked correctness envelope. New violations fail THIS test;
    deliberate exceptions go in analysis_baseline.json with a reason."""
    res = _repo_result()
    assert not res.errors, res.errors
    bl = Baseline.load(os.path.join(REPO, "analysis_baseline.json"))
    assert not bl.problems(), bl.problems()
    new, _suppressed, stale = bl.split(res.violations)
    assert new == [], (
        "new analysis violations (fix them, or baseline WITH a reason):\n"
        + "\n".join(v.render() for v in new)
    )
    assert not stale, f"stale baseline entries (remove them): {stale}"


def test_every_checker_registered_and_documented():
    codes = {c.code for c in all_checkers()}
    assert codes >= {
        "LD001", "LD002", "LD003", "JP001", "DS001", "HT001", "HT002",
        "MR001", "MR002", "MR003", "MR004", "TS001", "TS002", "CL001",
        "WP001", "WL001", "TR003", "PS001", "EC001", "AL001", "RP001",
        "LS001", "TP001",
    }
    for ck in all_checkers():
        assert ck.title and len(ck.rationale) > 80, (
            f"{ck.code} needs a real rationale (--explain contract)"
        )


# ---------------------------------------------------------------------------
# per-checker fixtures: exact codes/lines on bad, silence on good
# ---------------------------------------------------------------------------

def test_fixture_violations_match_markers_exactly():
    """Known-bad fixture lines (marked ``# expect: CODE``) fire exactly
    those codes at exactly those lines; known-good files are silent —
    one assertion covering every checker's both directions."""
    res = _fixture_result()
    assert not res.errors, res.errors
    got = {(v.path, v.line, v.code) for v in res.violations}
    expected = _expected_markers()
    assert expected, "fixture markers vanished — fixtures broken"
    missing = expected - got
    unexpected = got - expected
    assert not missing, f"checkers went blind on known-bad: {sorted(missing)}"
    assert not unexpected, (
        f"false positives on fixtures: {sorted(unexpected)}"
    )


@pytest.mark.parametrize("good", [
    "lock_good.py", "ops/jit_good.py", "sched/donate_good.py",
    "state/transfer_good.py", "metrics_good.py", "metrics_declared_good.py",
    "spans_good.py", "cross/owner.py", "clock_good.py", "wire_good.py",
    "wal_good.py", "trace_good.py", "proc_good.py", "epoch_good.py",
    "alert_good.py", "rep_good.py", "list_good.py", "state/topo_good.py",
])
def test_known_good_fixtures_are_silent(good):
    res = _fixture_result()
    noisy = [v for v in res.violations if v.path == good]
    assert noisy == [], "\n".join(v.render() for v in noisy)


# ---------------------------------------------------------------------------
# coverage self-check: the PR-2/6 hand-audited files stay in scope
# ---------------------------------------------------------------------------

AUDITED_FILES = (
    "kubetpu/assign/batched.py",
    "kubetpu/parallel/mesh.py",
    "kubetpu/framework/runtime.py",
)


def test_donation_and_transfer_checkers_cover_audited_files():
    """Satellite 6: the perf smoke gates' hand-audited files are inside
    the donation-safety and hot-path-transfer checkers' scope — asserted
    against the ACTUAL walk, so a file move that drops one out of scope
    fails here instead of silently shrinking the envelope."""
    res = _repo_result()
    for f in AUDITED_FILES:
        assert f in res.files, f"{f} missing from the analysis walk"
        for code in ("DS001", "HT001", "JP001"):
            assert f in res.coverage[code], (
                f"{code} no longer covers {f}"
            )


def test_topology_transfer_checker_covers_the_coordinate_stack():
    """PR 20: every layer that touches the slice/rack coordinate tensors
    stays inside TP001's scope — asserted against the ACTUAL walk so a
    file move cannot silently shrink the envelope around the one place
    (the batched encode placement) allowed to ship them."""
    res = _repo_result()
    for f in (
        "kubetpu/state/topology.py",
        "kubetpu/ops/topology.py",
        "kubetpu/ops/preemption.py",
        "kubetpu/sched/podgroup.py",
        "kubetpu/framework/runtime.py",
        "kubetpu/parallel/mesh.py",
    ):
        assert f in res.files, f"{f} missing from the analysis walk"
        assert f in res.coverage["TP001"], f"TP001 no longer covers {f}"


def test_replication_seam_checker_covers_store_and_replicator():
    """PR 17: the replicated read plane's correctness files stay inside
    RP001's scope — a rename/move of the store or replicator must fail
    here instead of silently un-checking the apply seam."""
    res = _repo_result()
    covered = set(res.coverage.get("RP001", ()))
    for f in ("kubetpu/store/memstore.py", "kubetpu/store/replication.py"):
        assert f in res.files, f"{f} missing from the analysis walk"
        assert f in covered, f"{f} dropped out of RP001 scope"


def test_list_seam_checker_covers_store_and_apiserver():
    """PR 18: the paginated read plane's materialization files stay
    inside LS001's scope — a rename/move of the store or apiserver
    modules must fail here instead of silently un-checking the page
    seam — and the guarded seam is really there: _list_page_locked
    still exists in memstore.py and still walks the core's paged
    primitive (a refactor away from it would leave LS001 guarding
    air while unbounded walks crept back)."""
    res = _repo_result()
    covered = set(res.coverage.get("LS001", ()))
    for f in (
        "kubetpu/store/memstore.py",
        "kubetpu/apiserver/server.py",
        "kubetpu/apiserver/remote.py",
    ):
        assert f in res.files, f"{f} missing from the analysis walk"
        assert f in covered, f"{f} dropped out of LS001 scope"
    src = open(
        os.path.join(REPO, "kubetpu", "store", "memstore.py"),
        encoding="utf-8",
    ).read()
    tree = ast.parse(src)
    seam = [
        n for n in ast.walk(tree)
        if isinstance(n, ast.FunctionDef)
        and n.name == "_list_page_locked"
    ]
    assert seam, "memstore.py lost _list_page_locked — LS001 guards air"
    pagers = [
        n for n in ast.walk(seam[0])
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
        and n.func.attr == "list_page"
    ]
    assert pagers, "_list_page_locked no longer pages the core"


def test_clock_checker_covers_lease_backoff_files():
    """CL001 (injectable-clock discipline) actually walks every
    lease/backoff file federation's stepped-clock tests depend on — a
    rename that drops one out of scope fails here, not silently."""
    res = _repo_result()
    covered = set(res.coverage.get("CL001", ()))
    for f in (
        "kubetpu/sched/leaderelection.py",
        "kubetpu/sched/federation.py",
        "kubetpu/sched/podgroup.py",
        "kubetpu/queue/priority_queue.py",
    ):
        assert f in covered, f"CL001 no longer covers {f}"


def test_wire_checker_covers_hot_path_modules_not_exempt_surfaces():
    """WP001 (wire-codec seam discipline) walks every module that touches
    request/reply/watch bodies — and does NOT walk the seam itself or the
    human-facing diagnostics/CLI surfaces, whose json use is legitimate.
    Pinned against the ACTUAL walk so a file move fails here, not
    silently."""
    res = _repo_result()
    covered = set(res.coverage.get("WP001", ()))
    for f in (
        "kubetpu/apiserver/server.py",
        "kubetpu/apiserver/remote.py",
        "kubetpu/store/memstore.py",
        "kubetpu/client/informers.py",
        "kubetpu/client/reflector.py",
        "kubetpu/sched/api_dispatcher.py",
    ):
        assert f in covered, f"WP001 no longer covers {f}"
    for f in (
        "kubetpu/api/codec.py",         # the seam encodes by design
        "kubetpu/cli.py",               # human-facing CLI output
        "kubetpu/sched/diagnostics.py",  # debug endpoints
    ):
        assert f not in covered, f"WP001 wrongly covers exempt {f}"


def test_wal_checker_covers_the_store_wrapper_not_the_replay_side():
    """WL001 (WAL append-seam discipline) walks the store wrapper — the
    one module holding a core reference the seam invariant governs — and
    does NOT walk kubetpu.store.wal (recovery's replay IS the path that
    reconstructs a core from the log). Pinned against the ACTUAL walk,
    and against the seam still existing: a rename of _commit_locked
    without updating the checker would silence it on the real store."""
    res = _repo_result()
    covered = set(res.coverage.get("WL001", ()))
    assert "kubetpu/store/memstore.py" in covered, (
        "WL001 no longer covers the store wrapper"
    )
    assert "kubetpu/store/wal.py" not in covered, (
        "WL001 wrongly covers the recovery/replay module"
    )
    # the guarded construct is really there: the seam exists AND core
    # mutations inside memstore.py all live in it (the zero-violation
    # repo gate above proves the rest)
    src = open(
        os.path.join(REPO, "kubetpu", "store", "memstore.py"),
        encoding="utf-8",
    ).read()
    tree = ast.parse(src)
    seam = [
        n for n in ast.walk(tree)
        if isinstance(n, ast.FunctionDef) and n.name == "_commit_locked"
    ]
    assert seam, "memstore.py lost _commit_locked — WL001 guards air"
    mutations = [
        n for n in ast.walk(seam[0])
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
        and n.func.attr in ("create", "update", "delete")
    ]
    assert mutations, "_commit_locked no longer mutates the core"


def test_proc_checker_covers_kubetpu_but_not_the_launch_seam():
    """PS001 (process-spawn seam discipline) walks all of kubetpu/ — the
    CLI that spawns ``kubetpu up``'s topology and the watch fan-out its
    ``watch-driver`` children carry included — and does NOT walk the seam
    itself. Pinned against the ACTUAL walk, and against the seam still
    SPAWNING: a supervisor refactored away from Popen would leave PS001
    guarding air while nothing in the repo could start a child."""
    res = _repo_result()
    covered = set(res.coverage.get("PS001", ()))
    for f in (
        "kubetpu/launch/fanout.py",     # the watch-driver's load
        "kubetpu/cli.py",
        "kubetpu/launch/cluster.py",    # topology builds specs, never spawns
        "kubetpu/native/__init__.py",   # run() probes stay in scope (and ok)
    ):
        assert f in covered, f"PS001 no longer covers {f}"
    assert "kubetpu/launch/supervisor.py" not in covered, (
        "PS001 wrongly covers the spawn seam itself"
    )
    # the seam still spawns: supervisor.py really calls subprocess.Popen
    src = open(
        os.path.join(REPO, "kubetpu", "launch", "supervisor.py"),
        encoding="utf-8",
    ).read()
    tree = ast.parse(src)
    popens = [
        n for n in ast.walk(tree)
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
        and n.func.attr == "Popen"
    ]
    assert popens, "supervisor.py lost its Popen — PS001 guards air"


def test_epoch_checker_covers_kubetpu_but_not_the_cache_itself():
    """EC001 (encode-cache invalidation scope) walks all of kubetpu/ —
    the scheduler's event handlers included — and does NOT walk the cache
    (the one module allowed to version itself). Pinned against the ACTUAL
    walk, and against the seam still being SCOPED: on_node_add must call
    invalidate_nodes with the added= keyword (a refactor back to the bare
    flush-per-add would leave the checker guarding air while the 100k
    add-wave path silently regressed to a re-encode storm)."""
    res = _repo_result()
    covered = set(res.coverage.get("EC001", ()))
    for f in (
        "kubetpu/sched/scheduler.py",
        "kubetpu/client/informers.py",
        "kubetpu/perf/runner.py",
    ):
        assert f in covered, f"EC001 no longer covers {f}"
    assert "kubetpu/state/encode_cache.py" not in covered, (
        "EC001 wrongly covers the cache's own versioning"
    )
    # the blessed seam still scopes: on_node_add carries a scoped call
    # (added=...) AND only the known handlers carry bare flushes
    src = open(
        os.path.join(REPO, "kubetpu", "sched", "scheduler.py"),
        encoding="utf-8",
    ).read()
    tree = ast.parse(src)
    scoped, bare_fns = 0, set()
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        for n in ast.walk(fn):
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "invalidate_nodes"
            ):
                if any(kw.arg == "added" for kw in n.keywords):
                    scoped += 1
                elif not n.args and not n.keywords:
                    bare_fns.add(fn.name)
    assert scoped >= 1, "on_node_add lost its scoped invalidate_nodes(added=)"
    assert bare_fns <= {"on_node_add", "on_node_update", "on_node_delete"}, (
        f"bare full-epoch flushes outside the blessed handlers: {bare_fns}"
    )


def test_alert_checker_covers_the_sentinel_not_the_rules_table():
    """AL001 (alert-threshold discipline) walks the sentinel's evaluation
    module and does NOT walk the rule table — rules.py is the literals'
    one legitimate home. Pinned against the ACTUAL walk, and against the
    seam still being REAL: the evaluators must still read thresholds off
    the rule (a refactor that inlined them as locals would leave AL001
    guarding air while the table stopped describing the live policy)."""
    res = _repo_result()
    covered = set(res.coverage.get("AL001", ()))
    assert "kubetpu/telemetry/sentinel.py" in covered, (
        "AL001 no longer covers the sentinel's evaluators"
    )
    assert "kubetpu/telemetry/rules.py" not in covered, (
        "AL001 wrongly covers the rule table itself"
    )
    assert "kubetpu/perf/workloads.py" not in covered, (
        "AL001 wrongly covers trace-profile budgets (declared data)"
    )
    src = open(
        os.path.join(REPO, "kubetpu", "telemetry", "sentinel.py"),
        encoding="utf-8",
    ).read()
    tree = ast.parse(src)
    eval_fns = [
        n for n in ast.walk(tree)
        if isinstance(n, ast.FunctionDef)
        and (n.name.startswith("_eval") or n.name.startswith("evaluate"))
    ]
    assert len(eval_fns) >= 4, "sentinel.py lost its evaluator functions"
    threshold_reads = [
        n for fn in eval_fns for n in ast.walk(fn)
        if isinstance(n, ast.Attribute)
        and n.attr in ("burn_threshold", "threshold", "mad_k",
                       "min_events", "objective")
    ]
    assert threshold_reads, (
        "evaluators no longer read rule thresholds — AL001 guards air"
    )


def test_trace_checker_covers_handlers_and_dispatcher():
    """TR003 (telemetry span coverage) walks the apiserver's HTTP front
    and the scheduler's API dispatcher — the two halves of every
    cross-process hop — and the guarded seams really exist: the handler
    still defines _track_span and every do_* verb runs it; the
    dispatcher still defines _record_call_span. Pinned against the
    ACTUAL walk so a move/rename fails here, not silently."""
    res = _repo_result()
    covered = set(res.coverage.get("TR003", ()))
    for f in (
        "kubetpu/apiserver/server.py",
        "kubetpu/sched/api_dispatcher.py",
    ):
        assert f in covered, f"TR003 no longer covers {f}"
    src = open(
        os.path.join(REPO, "kubetpu", "apiserver", "server.py"),
        encoding="utf-8",
    ).read()
    tree = ast.parse(src)
    fns = {
        n.name for n in ast.walk(tree)
        if isinstance(n, ast.FunctionDef)
    }
    assert "_track_span" in fns, "server.py lost _track_span — TR003 " \
        "guards air"
    handlers = {n for n in fns if n.startswith("do_")}
    assert {"do_GET", "do_POST", "do_PUT", "do_DELETE"} <= handlers
    src = open(
        os.path.join(REPO, "kubetpu", "sched", "api_dispatcher.py"),
        encoding="utf-8",
    ).read()
    assert "_record_call_span" in src, (
        "api_dispatcher.py lost _record_call_span — TR003 guards air"
    )


def test_audited_files_still_contain_what_the_checkers_guard():
    """The coverage claim is only meaningful if the guarded constructs
    are really there: runtime.py must still carry donated jits, and
    runtime.py + mesh.py must still carry device_put seams."""
    runtime = os.path.join(REPO, "kubetpu", "framework", "runtime.py")
    tree = ast.parse(open(runtime, encoding="utf-8").read())
    donated = [j for j in collect_jitted(tree) if j.donate]
    assert donated, "runtime.py lost its donated jits — DS001 guards air"

    from kubetpu.analysis.transfer import BLESSED_SEAMS

    for rel in ("kubetpu/framework/runtime.py", "kubetpu/parallel/mesh.py"):
        src = open(os.path.join(REPO, rel), encoding="utf-8").read()
        t = ast.parse(src)
        sites = [
            n.lineno for n in ast.walk(t)
            if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "device_put"
        ]
        assert sites, f"{rel} lost its device_put seams — HT001 guards air"
        suffix = next(s for s in BLESSED_SEAMS if rel.endswith(s))
        assert BLESSED_SEAMS[suffix], f"blessed seam set for {rel} is empty"


# ---------------------------------------------------------------------------
# CLI: formats, explain, exit codes, baseline plumbing
# ---------------------------------------------------------------------------

def test_cli_repo_run_exits_zero(monkeypatch, capsys):
    monkeypatch.chdir(REPO)
    rc = cli_main(["kubetpu"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 violations" in out


def test_cli_json_format_on_fixtures(capsys):
    rc = cli_main([FIXTURES, "--format", "json", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    doc = json.loads(out)
    codes = {v["code"] for v in doc["violations"]}
    assert {"LD001", "JP001", "DS001", "HT001", "MR001", "TS001"} <= codes
    assert doc["files"] > 0 and not doc["baseline_problems"]


def test_cli_empty_path_set_is_an_error(tmp_path, capsys):
    """A typo'd path (or wrong CWD) must not greenlight the CI gate with
    '0 files, 0 violations'."""
    rc = cli_main([str(tmp_path / "no_such_dir"), "--no-baseline"])
    err = capsys.readouterr().err
    assert rc == 2 and "no Python files matched" in err


def test_cli_explain_prints_rationale(capsys):
    rc = cli_main(["--explain", "LD001"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "PR-5" in out or "lock" in out.lower()
    rc = cli_main(["--explain", "NOPE"])
    assert rc == 2


def test_cli_select_and_list(capsys):
    rc = cli_main(["--list-checkers"])
    out = capsys.readouterr().out
    assert rc == 0 and "LD001" in out and "TS002" in out
    rc = cli_main([FIXTURES, "--select", "TS001,TS002", "--no-baseline"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "TS001" in out and "LD001" not in out


def test_baseline_suppresses_with_reason_and_rejects_without(
    tmp_path, capsys, monkeypatch,
):
    monkeypatch.chdir(REPO)
    entry = {
        "code": "TS001", "path": "tests/analysis_fixtures/spans_bad.py",
        "symbol": "tracer.span", "reason": "fixture demo",
    }
    good = tmp_path / "bl.json"
    good.write_text(json.dumps({"version": 1, "entries": [entry]}))
    rc = cli_main([
        FIXTURES, "--select", "TS001", "--baseline", str(good),
    ])
    out = capsys.readouterr().out
    assert rc == 0 and "baselined:" in out

    bad = tmp_path / "bl_bad.json"
    entry_noreason = dict(entry, reason="")
    bad.write_text(json.dumps({"version": 1, "entries": [entry_noreason]}))
    rc = cli_main([
        FIXTURES, "--select", "TS001", "--baseline", str(bad),
    ])
    assert rc == 1      # unjustified entry: the allowlist is not a mute

    # stale entries are reported (informational, not failing by default)
    stale = tmp_path / "bl_stale.json"
    stale.write_text(json.dumps({"version": 1, "entries": [
        dict(entry, path="gone/file.py"), entry,
    ]}))
    rc = cli_main([
        FIXTURES, "--select", "TS001", "--baseline", str(stale),
    ])
    out = capsys.readouterr().out
    assert "stale baseline entry" in out


# ---------------------------------------------------------------------------
# runtime lock-order witness
# ---------------------------------------------------------------------------

def _raw_lock():
    # bypass the (possibly patched) threading.Lock: witness tests manage
    # their own state explicitly
    return _thread.allocate_lock()


def test_witness_catches_seeded_two_lock_inversion():
    """Acceptance: a deliberately inverted two-lock acquisition is caught
    — as a graph cycle, even though the deadlock interleaving itself
    never fires in this run."""
    state = witness.WitnessState()
    a = witness.wrap(_raw_lock(), "memstore", state)
    b = witness.wrap(_raw_lock(), "informer", state)

    def thread_one():
        with a:
            with b:
                pass

    t = threading.Thread(target=thread_one)
    t.start()
    t.join()

    with pytest.raises(witness.LockOrderError) as ei:
        with b:
            with a:        # B -> A closes the cycle
                pass
    assert "memstore" in str(ei.value) and "informer" in str(ei.value)
    assert state.violations


def test_witness_consistent_order_is_silent():
    state = witness.WitnessState()
    a = witness.wrap(_raw_lock(), "A", state)
    b = witness.wrap(_raw_lock(), "B", state)
    for _ in range(3):
        with a:
            with b:
                pass
    assert state.violations == []
    assert ("A", "B") in state.edge_list()


def test_witness_reentrant_lock_no_self_cycle():
    state = witness.WitnessState()
    r = witness.wrap(threading.RLock(), "R", state)
    assert r.reentrant     # sniffed from the primitive's type
    with r:
        with r:        # re-entrant: no self-edge, no violation
            pass
    assert state.violations == []


def test_witness_plain_lock_self_deadlock_raises():
    """Re-acquiring a plain Lock the thread already holds would block
    forever — the witness fails immediately instead of wedging."""
    state = witness.WitnessState()
    a = witness.wrap(_raw_lock(), "plain", state)
    with pytest.raises(witness.LockOrderError, match="self-deadlock"):
        with a:
            with a:
                pass
    assert state.violations


def test_witness_condition_wait_preserves_rlock_depth():
    """Condition.wait under an RLock held at depth 2 must restore BOTH
    stack entries — otherwise the first post-wait release makes the
    witness believe the lock is free while the thread still holds it,
    and wait-heavy paths (MemStore.wait_for) lose edge recording."""
    state = witness.WitnessState()
    r = witness.wrap(threading.RLock(), "R", state)
    cond = threading.Condition(r)
    other = witness.wrap(_raw_lock(), "other", state)

    def waker():
        with cond:
            cond.notify_all()

    with r:
        with r:                       # depth 2
            with cond:                # depth 3 via the condition
                threading.Timer(0.05, waker).start()
                cond.wait(timeout=5)
            # back at depth 2: the witness must still see R held...
            with other:
                pass                  # ...so this records the R->other edge
    assert ("R", "other") in state.edge_list()
    assert state.violations == []


def test_collect_failure_drops_file_not_whole_checker():
    """One file whose collect() raises must cost that FILE's facts, not
    the checker's entire project-wide report (the tuple-unpacking
    report()s would otherwise crash on a dummy [])."""
    from kubetpu.analysis.core import analyze_paths as ap

    boom = CHECKERS["MR001"]
    orig = boom.collect

    def exploding(mod):
        if mod.relpath.endswith("metrics_good.py"):
            raise RuntimeError("synthetic collect failure")
        return orig(mod)

    boom.collect = exploding
    try:
        res = ap([FIXTURES], root=FIXTURES)
    finally:
        boom.collect = orig
    assert any("synthetic collect failure" in e for e in res.errors)
    # the other files' MR001 findings survive
    assert any(v.code == "MR001" for v in res.violations)


def test_witness_retired_state_is_passthrough():
    """Locks that outlive their installed() scope (module-level locks
    first imported during a witnessed test) degrade to pass-throughs:
    no edges into the dead graph, no LockOrderError in later tests."""
    state = witness.WitnessState()
    a = witness.wrap(_raw_lock(), "A", state)
    b = witness.wrap(_raw_lock(), "B", state)
    with a:
        with b:
            pass
    state.active = False              # what installed().__exit__ does
    with b:
        with a:                       # would close the cycle if live
            pass
    assert state.violations == []
    assert ("B", "A") not in state.edge_list()


def test_cli_runs_from_foreign_cwd(tmp_path, capsys, monkeypatch):
    """Invoked from outside the repo, the CLI still finds the repo's
    baseline by parent-walk and keys findings repo-relative — a CI job
    with a different working directory can't silently skip the
    allowlist."""
    monkeypatch.chdir(tmp_path)
    rc = cli_main([os.path.join(REPO, "kubetpu")])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 violations" in out


def test_witness_three_lock_cycle():
    state = witness.WitnessState()
    locks = [witness.wrap(_raw_lock(), n, state) for n in "ABC"]
    a, b, c = locks
    with a:
        with b:
            pass
    with b:
        with c:
            pass
    with pytest.raises(witness.LockOrderError):
        with c:
            with a:
                pass


def test_witness_installed_wraps_kubetpu_locks(_lock_order_witness):
    """The conftest autouse fixture (this module is in its witnessed set)
    really wraps locks created by kubetpu code: a MemStore built here
    gets a witnessed Condition, and normal store traffic stays clean."""
    state = _lock_order_witness
    assert state is not None, "conftest witness fixture not active"
    before = state.locks_created
    from kubetpu.store.memstore import MemStore

    store = MemStore(native=False)
    assert state.locks_created > before, (
        "MemStore's Condition was not witnessed"
    )
    store.create("pods", "default/p1", {"name": "p1"})
    store.update("pods", "default/p1", {"name": "p1", "v": 2})
    w = store.watch("pods", 0)
    assert len(w.poll()) == 2
    assert state.violations == []


def test_witness_dispatcher_and_informer_locks_stay_acyclic(
    _lock_order_witness,
):
    """Dispatcher workers + informer deliveries + store writes under the
    witness: the production lock order is cycle-free end to end."""
    from kubetpu.client.reflector import FuncHandler, Reflector, SharedInformer
    from kubetpu.sched.api_dispatcher import APIDispatcher, BindCall
    from kubetpu.store.memstore import MemStore
    from kubetpu.api import types as t

    state = _lock_order_witness
    store = MemStore(native=False)
    informer = SharedInformer("pods")
    seen: list = []
    informer.add_handler(FuncHandler(on_add=lambda o: seen.append(o)))
    reflector = Reflector(store, informer)
    reflector.sync()

    class _Client:
        def bind(self, pod, node_name):
            key = f"{pod.namespace}/{pod.name}"
            cur, rv = store.get("pods", key)
            store.update("pods", key, cur.with_node(node_name), expect_rv=rv)

    disp = APIDispatcher(_Client(), workers=2)
    pods = [
        t.Pod(name=f"w{i}", namespace="default", uid=f"uid{i}")
        for i in range(8)
    ]
    for p in pods:
        store.create("pods", f"default/{p.name}", p)
    reflector.step()
    for p in pods:
        disp.add(BindCall(pod=p, node_name="n1"))
    disp.sync()
    reflector.step()
    disp.close()
    assert disp.stats()["executed"] == len(pods)
    assert state.violations == [], state.violations
    assert state.locks_created >= 3


def test_thread_excepthook_capture_plumbing():
    """Satellite: worker-thread death handling. During a test phase
    pytest's threadexception plugin owns threading.excepthook and
    pytest.ini escalates its warning to a test FAILURE; outside test
    phases the conftest capture hook records the death for the next
    test's autouse fixture. Both halves asserted here: the escalation
    config, and the capture hook's mechanics (including the SystemExit
    clean-exit exemption)."""
    import configparser
    import types

    import tests.conftest as cf

    # the escalation contract is configuration — assert it holds
    ini = configparser.ConfigParser()
    ini.read(os.path.join(REPO, "pytest.ini"))
    assert "PytestUnhandledThreadExceptionWarning" in ini.get(
        "pytest", "filterwarnings"
    )

    mark = len(cf._thread_errors)
    quiet = object()
    orig = cf._orig_threading_hook
    cf._orig_threading_hook = lambda args: quiet
    try:
        cf._capture_thread_exception(types.SimpleNamespace(
            exc_type=RuntimeError,
            exc_value=RuntimeError("pump thread croaked"),
            exc_traceback=None,
            thread=threading.current_thread(),
        ))
        cf._capture_thread_exception(types.SimpleNamespace(
            exc_type=SystemExit, exc_value=SystemExit(0),
            exc_traceback=None, thread=threading.current_thread(),
        ))
    finally:
        cf._orig_threading_hook = orig
    fresh = cf._thread_errors[mark:]
    assert len(fresh) == 1 and "pump thread croaked" in fresh[0]
    # consume the deliberate entry so the autouse fixture stays green
    del cf._thread_errors[mark:]


def test_thread_death_fails_owning_test_end_to_end(tmp_path):
    """A freshly spawned pytest run proves the contract end to end: a
    test whose worker thread raises FAILS even though its assertions all
    pass — no vacuous green."""
    import subprocess
    import sys

    victim = tmp_path / "test_thread_death_victim.py"
    victim.write_text(
        "import threading\n"
        "def test_worker_dies_silently():\n"
        "    th = threading.Thread(\n"
        "        target=lambda: (_ for _ in ()).throw(\n"
        "            RuntimeError('worker croaked')),\n"
        "        name='doomed-worker')\n"
        "    th.start(); th.join()\n"
        "    assert True\n"
    )
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(victim), "-q",
         "-p", "no:cacheprovider", "-c", os.path.join(REPO, "pytest.ini")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert "worker croaked" in proc.stdout + proc.stderr
