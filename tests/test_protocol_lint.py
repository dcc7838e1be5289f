"""roc-lint level eight (analysis/protocol_lint + modelcheck +
protocol_specs): every protocol rule fires on a synthetic violation
tree, each model's seeded bug makes the bounded checker bite with a
counterexample schedule, the REAL tree audits clean with an empty
findings baseline, the static-vs-declared spec tables agree, the CLI
gate (and its `--select protocol` alias) bites, and the replica's
unknown-wire-kind rejection (the true positive this level fixed on
landing) holds as a drill-style regression."""

import io
import json
import os
import subprocess
import sys
import time

from roc_tpu.analysis import protocol_specs as specs
from roc_tpu.analysis.concurrency_lint import (TreeModel,
                                               run_concurrency_lint)
from roc_tpu.analysis.modelcheck import (
    MODELS, SEEDS, STATE_BUDGET, ModelReport, check_all,
    model_invariants, run_model)
from roc_tpu.analysis.protocol_lint import (
    PROTOCOL_RULES, protocol_surface, run_protocol_lint)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ROUTER = "roc_tpu/serve/router.py"
_REPLICA = "roc_tpu/serve/replica.py"


def _plant(root, relpath, text):
    p = root / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)


def _keys(findings):
    return sorted(f.key for f in findings)


# -------------------------------------------- wire-vocabulary fixtures

def test_wire_vocabulary_sent_unhandled_fires(tmp_path):
    """A kind put on the wire with no receiver branch fires once per
    kind (not per send site); a fully-handled kind stays quiet."""
    _plant(tmp_path, _ROUTER,
           "def run(wire, sub):\n"
           "    wire.send({'kind': 'req', 'id': 1, 'ids': [],\n"
           "               'deadline_ms': None, 'rid': None})\n"
           "    wire.send({'kind': 'bogus', 'x': 1})\n"
           "    wire.send({'kind': 'bogus', 'x': 2})\n")
    _plant(tmp_path, _REPLICA,
           "def read_loop(msg):\n"
           "    kind = msg.get('kind')\n"
           "    if kind == 'close':\n"
           "        return\n"
           "    if kind != 'req':\n"
           "        raise ValueError(kind)\n"
           "    go(msg)\n")
    got = run_protocol_lint(str(tmp_path), select=["wire-vocabulary"])
    assert _keys(got) == ["sent-unhandled|router->replica|bogus"], \
        [(f.key, f.msg) for f in got]
    assert "no branch for it" in got[0].msg


def test_wire_vocabulary_handled_unsent_and_spec_sanction(tmp_path):
    """A receiver branch for a kind the sender never puts on the wire
    is dead vocabulary — except when the spec sanctions it with
    ``sent: False`` (close: stdin EOF is the close signal)."""
    _plant(tmp_path, _ROUTER,
           "def run(wire):\n"
           "    wire.send({'kind': 'req', 'id': 1, 'ids': [],\n"
           "               'deadline_ms': None, 'rid': None})\n")
    _plant(tmp_path, _REPLICA,
           "def read_loop(msg):\n"
           "    kind = msg.get('kind')\n"
           "    if kind == 'close':\n"       # sanctioned: sent False
           "        return\n"
           "    if kind == 'zombie':\n"      # dead vocabulary
           "        return\n"
           "    if kind != 'req':\n"
           "        raise ValueError(kind)\n"
           "    go(msg)\n")
    got = run_protocol_lint(str(tmp_path), select=["wire-vocabulary"])
    assert _keys(got) == ["handled-unsent|router->replica|zombie"], \
        [(f.key, f.msg) for f in got]
    assert "dead vocabulary" in got[0].msg


def test_wire_vocabulary_missing_unknown_kind_rejection(tmp_path):
    """A kind-dispatching receiver with neither a != guard nor a
    final else is the replica:146 bug class — a typo'd kind silently
    falls through; adding the guard clears it."""
    _plant(tmp_path, _ROUTER,
           "def run(wire):\n"
           "    wire.send({'kind': 'req', 'id': 1, 'ids': [],\n"
           "               'deadline_ms': None, 'rid': None})\n")
    _plant(tmp_path, _REPLICA,
           "def read_loop(msg):\n"
           "    kind = msg.get('kind')\n"
           "    if kind == 'close':\n"
           "        return\n"
           "    if kind == 'req':\n"
           "        go(msg)\n")
    got = run_protocol_lint(str(tmp_path), select=["wire-vocabulary"])
    assert _keys(got) == \
        ["no-unknown-rejection|router->replica|read_loop"], \
        [(f.key, f.msg) for f in got]

    # the ==-chain-with-final-else shape is an accepted rejection too
    _plant(tmp_path, _REPLICA,
           "def read_loop(msg):\n"
           "    kind = msg.get('kind')\n"
           "    if kind == 'close':\n"
           "        return\n"
           "    elif kind == 'req':\n"
           "        go(msg)\n"
           "    else:\n"
           "        reject(kind)\n")
    assert not run_protocol_lint(str(tmp_path),
                                 select=["wire-vocabulary"])


# ----------------------------------------- wire-field-contract fixtures

def test_wire_field_contract_missing_and_undeclared(tmp_path):
    """A send site that omits a required field or carries an
    undeclared one fires; the exact declared shape stays quiet; a
    helper-built payload (the _error_payload idiom) resolves one
    level deep."""
    _plant(tmp_path, _ROUTER,
           "def _payload(i):\n"
           "    return {'kind': 'req', 'id': i, 'ids': [],\n"
           "            'deadline_ms': None, 'rid': None}\n"
           "def run(wire):\n"
           "    wire.send(_payload(1))\n"       # helper: exact shape
           "    wire.send({'kind': 'req', 'id': 2, 'ids': [],\n"
           "               'deadline_ms': None})\n"      # missing rid
           "    wire.send({'kind': 'req', 'id': 3, 'ids': [],\n"
           "               'deadline_ms': None, 'rid': None,\n"
           "               'hedge': True})\n")           # undeclared
    got = run_protocol_lint(str(tmp_path),
                            select=["wire-field-contract"])
    assert _keys(got) == [
        "missing|router->replica|req|rid",
        "undeclared|router->replica|req|hedge",
    ], [(f.key, f.msg) for f in got]
    assert all(f.rule == "wire-field-contract" for f in got)


# ------------------------------------------ protocol-spec-drift fixtures

def test_spec_drift_flags_stale_rows_and_missing_sites(tmp_path):
    """A skeleton tree that no longer sends/handles the declared
    vocabulary and lost its declared transition sites drifts in
    every direction the rule covers."""
    _plant(tmp_path, _ROUTER, "def run(wire):\n    pass\n")
    _plant(tmp_path, _REPLICA, "def read_loop(msg):\n    pass\n")
    got = run_protocol_lint(str(tmp_path),
                            select=["protocol-spec-drift"])
    keys = set(_keys(got))
    assert "unsent|router->replica|req" in keys
    assert "unhandled|router->replica|close" in keys
    assert f"missing-site|{_ROUTER}|Router.submit" in keys
    assert f"missing-site|{_REPLICA}|serve_loop" in keys
    # 'close' is declared sent: False — its absence from the send
    # sites is NOT drift
    assert "unsent|router->replica|close" not in keys


def test_spec_drift_flags_undeclared_and_despite_spec_kinds(tmp_path):
    """An observed kind the spec lacks (both directions) and a send
    of a declared never-sent kind are drift — the spec must be
    edited FIRST."""
    _plant(tmp_path, _ROUTER,
           "def run(wire):\n"
           "    wire.send({'kind': 'promote', 'id': 1})\n"
           "    wire.send({'kind': 'close'})\n")
    _plant(tmp_path, _REPLICA,
           "def read_loop(msg):\n"
           "    kind = msg.get('kind')\n"
           "    if kind == 'promote':\n"
           "        go(msg)\n")
    got = run_protocol_lint(str(tmp_path),
                            select=["protocol-spec-drift"])
    keys = set(_keys(got))
    assert "undeclared-kind|router->replica|promote" in keys
    assert "sent-despite-spec|router->replica|close" in keys
    despite = [f for f in got
               if f.key == "sent-despite-spec|router->replica|close"]
    assert "stdin EOF" in despite[0].msg    # the spec note travels


def test_spec_drift_catches_invariant_table_drift(tmp_path):
    """The checker's implemented invariant set is cross-checked
    against the declared MODEL_INVARIANTS — drift in either
    direction (doctored reports here) is a finding."""
    doctored = [ModelReport(name="router-lifecycle",
                            invariants=("terminal-exactly-once",))]
    got = run_protocol_lint(str(tmp_path),
                            select=["protocol-spec-drift"],
                            model_reports=doctored)
    keys = set(_keys(got))
    assert "invariant-drift|router-lifecycle" in keys
    # the other two models are declared but absent from the reports
    assert "invariant-drift|ckpt-commit" in keys
    assert "invariant-drift|table-swap" in keys


def test_static_invariants_match_declared_spec():
    """The spec-equality pin: modelcheck's implemented invariant
    names equal protocol_specs.MODEL_INVARIANTS exactly, per model —
    the drift rule's clean verdict on the real tree is this equality,
    not a vacuous pass."""
    assert model_invariants() == {
        m: tuple(v) for m, v in specs.MODEL_INVARIANTS.items()}
    assert set(MODELS) == set(specs.MODEL_INVARIANTS)


# ------------------------------------------------- the model checker

def test_models_explore_exhaustively_and_fast():
    """All three shipped models explore to completion well inside the
    state budget, find zero violations, and the whole pass stays in
    the millisecond preflight class (asserted wall-time bound)."""
    t0 = time.monotonic()
    reports = check_all()
    wall = time.monotonic() - t0
    assert wall < 2.0, f"model check took {wall:.2f}s"
    assert [r.name for r in reports] == list(MODELS)
    for r in reports:
        assert r.complete, r.name
        assert r.violations == [], (r.name, r.violations)
        assert 0 < r.states < STATE_BUDGET, (r.name, r.states)
        assert r.transitions >= r.states - 1


def test_seeded_double_requeue_bites():
    """Dropping the per-corpse requeue guard (the seeded router bug)
    violates failover-requeue-at-most-once with a concrete
    crash/mark-dead schedule."""
    rep = run_model("router-lifecycle",
                    seed=SEEDS["router-lifecycle"])
    bad = {v["invariant"] for v in rep.violations}
    assert "failover-requeue-at-most-once" in bad, rep.violations
    v = next(x for x in rep.violations
             if x["invariant"] == "failover-requeue-at-most-once")
    assert v["trace"], "counterexample schedule must be non-empty"
    assert any("markdead" in step for step in v["trace"])


def test_seeded_manifest_first_bites():
    """Publishing the manifest before the shard rename (the seeded
    commit bug) violates publish-last AND the restore-side torn-state
    invariant — the two views of the same window."""
    rep = run_model("ckpt-commit", seed=SEEDS["ckpt-commit"])
    bad = {v["invariant"] for v in rep.violations}
    assert "manifest-published-last" in bad, rep.violations
    assert "restore-never-torn" in bad, rep.violations


def test_seeded_swap_mid_query_bites():
    """Reading the live published version per row instead of the
    microbatch capture (the seeded swap bug) violates
    single-version-batch."""
    rep = run_model("table-swap", seed=SEEDS["table-swap"])
    bad = {v["invariant"] for v in rep.violations}
    assert bad == {"single-version-batch"}, rep.violations


def test_seeded_live_qmode_bites():
    """Selecting the dequant program by the LIVE published version's
    quant spec instead of the captured one (the PR-19 seeded bug —
    the mid-rollout fp32→int8 window) violates quant-spec-pinned and
    ONLY that: the captured rows themselves are still consistent, so
    single-version-batch must stay green."""
    rep = run_model("table-swap", seed="live-qmode")
    bad = {v["invariant"] for v in rep.violations}
    assert bad == {"quant-spec-pinned"}, rep.violations
    # the original swap bug is unchanged by the quant extension
    rep2 = run_model("table-swap", seed=SEEDS["table-swap"])
    assert {v["invariant"] for v in rep2.violations} == \
        {"single-version-batch"}, rep2.violations


def test_seeded_shard_gather_bites():
    """Merging gathered foreign rows from whatever version the owner
    publishes at answer time instead of refusing the mismatched pin
    (the PR-20 seeded bug — the owner republished between capture and
    gather) violates gather-version-pinned and ONLY that: the locally
    owned rows still come from the captured version, so
    single-version-batch and quant-spec-pinned stay green."""
    rep = run_model("table-swap", seed="shard-gather")
    bad = {v["invariant"] for v in rep.violations}
    assert bad == {"gather-version-pinned"}, rep.violations
    # the sibling seeds are unchanged by the gather extension
    rep2 = run_model("table-swap", seed="live-qmode")
    assert {v["invariant"] for v in rep2.violations} == \
        {"quant-spec-pinned"}, rep2.violations


def test_modelcheck_findings_carry_schedule_and_budget(tmp_path):
    """A violation report becomes a modelcheck-invariant finding
    carrying the counterexample schedule; an exhausted budget is
    itself a finding (an unexplorable model proves nothing)."""
    seeded = run_model("table-swap", seed=SEEDS["table-swap"])
    got = run_protocol_lint(str(tmp_path),
                            select=["modelcheck-invariant"],
                            model_reports=[seeded])
    assert _keys(got) == ["table-swap|single-version-batch"]
    assert "[schedule: " in got[0].msg
    assert got[0].detail["trace"]
    assert got[0].unit == "model:table-swap"

    tiny = run_model("router-lifecycle", budget=10)
    assert not tiny.complete
    got = run_protocol_lint(str(tmp_path),
                            select=["modelcheck-invariant"],
                            model_reports=[tiny])
    assert _keys(got) == ["router-lifecycle|budget"]
    assert "state budget" in got[0].msg


def test_unknown_model_and_seed_raise():
    import pytest
    with pytest.raises(ValueError):
        run_model("nope")
    with pytest.raises(ValueError):
        run_model("table-swap", seed="double-requeue")


# ------------------------------- ckpt-commit-order (migrated, PR 15→18)

def test_commit_order_fires_on_manifest_before_shard_rename(tmp_path):
    """Checkpoint-v3 two-phase-commit ORDER, now owned by the
    protocol level: a writer publishing the manifest BEFORE a shard
    rename re-creates the torn-read window — the lint bites under its
    own rule name; the correct order and a pragma'd site pass."""
    _plant(tmp_path, "roc_tpu/ck.py",
           "import os\n"
           "from roc_tpu.utils.checkpoint import commit_manifest\n"
           "def bad_writer(d, snap, shards, tmp, shard):\n"
           "    commit_manifest(d, snap, shards)\n"           # line 4
           "    os.replace(tmp, shard)\n"
           "def good_writer(d, snap, shards, tmp, shard):\n"
           "    os.replace(tmp, shard)\n"
           "    commit_manifest(d, snap, shards)\n"
           "def waived_writer(d, snap, shards, tmp, shard):\n"
           "    commit_manifest(d, snap, shards)  "
           "# re-commit of a landed shard: roc-lint: "
           "ok=ckpt-commit-order\n"
           "    os.replace(tmp, shard)\n")
    got = run_protocol_lint(str(tmp_path),
                            select=["ckpt-commit-order"])
    assert [(f.rule, f.line) for f in got] == \
        [("ckpt-commit-order", 4)], [(f.line, f.msg) for f in got]
    assert "BEFORE a shard rename" in got[0].msg
    assert got[0].key == "commit-order|bad_writer"
    # the migration left NO duplicate behind: the concurrency level
    # no longer reports commit order (one source of truth)
    conc = run_concurrency_lint(str(tmp_path),
                                select=["artifact-lock-ownership"])
    assert conc == [], [(f.rule, f.msg) for f in conc]


# ------------------------------------------------- registration + tree

def test_rules_registered_and_not_trace():
    from roc_tpu.analysis.driver import all_rule_names, is_trace_rule
    from roc_tpu.obs.events import CATEGORIES
    names = all_rule_names()
    for r in PROTOCOL_RULES:
        assert r in names
        # pure AST + pure-Python BFS: a `--select protocol` preflight
        # must never force the jax trace rig
        assert not is_trace_rule(r)
    assert "protocol" in CATEGORIES


def test_tree_is_clean_and_baseline_empty():
    """The REAL tree audits clean (the replica's unknown-kind true
    positive was FIXED, not baselined): the findings baseline stays
    empty."""
    got = run_protocol_lint(_REPO)
    assert got == [], "\n".join(f.render() for f in got)
    data = json.load(open(
        os.path.join(_REPO, "scripts", "lint_baseline.json")))
    assert data["findings"] == []


def test_surface_documents_the_real_wire_protocol():
    """The extracted surface IS the protocol documentation: both
    channels, every kind status ok, every dispatcher rejecting
    unknown kinds, every declared transition site present, the
    helper-resolved res send sites included, and the checkpoint
    artifact inventory riding along (the PR-15 migration)."""
    reports = check_all()
    surface = protocol_surface(TreeModel(_REPO), reports)
    chans = {c["name"]: c for c in surface["channels"]}
    assert set(chans) == {"router->replica", "replica->router"}
    for c in chans.values():
        for kind, k in c["kinds"].items():
            assert k["status"] == "ok", (c["name"], kind, k)
        assert c["dispatchers"], c["name"]
        for d in c["dispatchers"]:
            assert d["rejects_unknown"], (c["name"], d)
    # close is declared never-sent with the stdin-EOF note
    close = chans["router->replica"]["kinds"]["close"]
    assert close["sent"] is False and close["sent_at"] == []
    assert "EOF" in close["note"]
    # res is sent from three sites: the ok callback, the error path
    # via the _error_payload helper, and the read_loop rejection
    res = chans["replica->router"]["kinds"]["res"]
    assert len(res["sent_at"]) == 3, res
    assert all(s["present"] for s in surface["sites"])
    arts = {a["module"]: a["artifacts"]
            for a in surface["artifacts"]}
    assert any(x["kind"] == "ckpt-manifest"
               for x in arts["roc_tpu/utils/checkpoint.py"])
    assert any(x["kind"] == "ckpt-shard"
               for x in arts["roc_tpu/resilience/async_save.py"])
    t = surface["totals"]
    assert t["channels"] == 2 and t["models"] == 3
    assert t["violations"] == 0 and t["states"] > 0
    assert t["sites"] == sum(len(v) for v in
                             list(specs.LIFECYCLE_SITES.values())
                             + list(specs.COMMIT_SITES.values()))
    assert surface["state_budget"] == STATE_BUDGET


def test_report_renders_protocol_tables():
    """roc_tpu.report renders the wire-vocabulary / model tables from
    the --json payload (``--protocol``) AND from the protocol_surface
    event an audited run leaves in its event stream."""
    from roc_tpu import report
    surface = protocol_surface(TreeModel(_REPO), check_all())
    out = io.StringIO()
    report.summarize([], protocol=surface, out=out)
    text = out.getvalue()
    assert "wire vocabulary: router->replica" in text
    assert "(by design)" in text            # close: sent False
    assert "unknown-kind rejection" in text
    assert "NO REJECTION" not in text
    assert "router-lifecycle" in text and "BUDGET EXHAUSTED" not in text
    assert "protocol transition sites" in text
    # event-stream path: same tables, no payload file needed
    ev = {"cat": "protocol", "kind": "protocol_surface",
          "channels": surface["channels"],
          "models": surface["models"], "totals": surface["totals"]}
    out2 = io.StringIO()
    report.summarize([ev], out=out2)
    text2 = out2.getvalue()
    assert "wire vocabulary: router->replica" in text2
    assert "router-lifecycle" in text2


# ------------------------------- the replica fix (drill-style regression)

def test_replica_rejects_unknown_wire_kind(monkeypatch):
    """The true positive this level fixed: an unknown wire kind used
    to fall through read_loop's close-check and dispatch AS A REQUEST.
    Now it comes back as a typed non-retryable error res (when it
    carries an id) and dispatches nothing — while a well-formed req on
    the same stdin still serves."""
    from roc_tpu.serve import replica as rep

    class _Fut:
        def add_done_callback(self, cb):
            pass

    class FakeServer:
        def __init__(self):
            self.submitted = []

        def submit(self, ids, deadline_ms=None, rid=None):
            self.submitted.append(list(ids))
            return _Fut()

        def drain(self, timeout=None):
            return True

    sent = []

    class FakeWire:
        def send(self, obj):
            sent.append(obj)

    stdin = io.StringIO(
        json.dumps({"kind": "promote", "id": 7}) + "\n"
        + json.dumps({"kind": "request", "ids": [9]}) + "\n"  # no id
        + json.dumps({"kind": "req", "id": 8, "ids": [1, 2]}) + "\n"
        + json.dumps({"kind": "close"}) + "\n")
    monkeypatch.setattr(rep.sys, "stdin", stdin)
    srv = FakeServer()
    clean = rep.serve_loop(srv, FakeWire(), replica=0,
                           drain_timeout_s=2.0)
    assert clean
    errs = [m for m in sent
            if m.get("kind") == "res" and m.get("ok") is False]
    assert [e["id"] for e in errs] == [7], sent
    assert errs[0]["error"] == "ServeError"
    assert "unknown wire kind 'promote'" in errs[0]["msg"]
    assert errs[0]["retryable"] is False
    # neither unknown kind dispatched anything; the real req did
    assert srv.submitted == [[1, 2]]
    assert sent[-1]["kind"] == "drained"


# --------------------------------------------------------- CLI wiring

def _run_cli(args, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "roc_tpu.analysis"] + args,
        cwd=cwd or _REPO, capture_output=True, text=True, timeout=120,
        env=env)


def test_cli_select_protocol_alias_green_on_tree():
    """`--select protocol` (the scripts/test.sh preflight
    line) expands to all five rules, runs jax-free fast, exits 0 on
    the tree, and the --json payload carries the surface with all
    three models explored to completion."""
    r = _run_cli(["--select", "protocol", "--json"])
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["summary"]["new"] == 0
    surface = payload["protocol_surface"]
    assert surface["totals"]["models"] == 3
    assert surface["totals"]["violations"] == 0
    for m in surface["models"]:
        assert m["complete"], m
        assert m["states"] > 0


def test_cli_ratchet_bites_on_planted_violation(tmp_path):
    """A seeded manifest-before-rename writer in a scratch tree fails
    the CLI through the alias (the ratchet bites from zero)."""
    _plant(tmp_path, "roc_tpu/ck.py",
           "import os\n"
           "def commit_manifest(d, snap, shards):\n"
           "    pass\n"
           "def bad_writer(d, snap, shards, tmp, shard):\n"
           "    commit_manifest(d, snap, shards)\n"
           "    os.replace(tmp, shard)\n")
    r = _run_cli(["--root", str(tmp_path), "--select", "protocol"])
    assert r.returncode == 1
    assert "ckpt-commit-order" in r.stdout
    assert "ck.py" in r.stdout


def test_cli_never_absorbs_protocol_findings(tmp_path):
    """--update-baseline must not absorb a live protocol finding
    (shrink-only contract, same as every level)."""
    _plant(tmp_path, "roc_tpu/ck.py",
           "import os\n"
           "def commit_manifest(d, snap, shards):\n"
           "    pass\n"
           "def bad_writer(d, snap, shards, tmp, shard):\n"
           "    commit_manifest(d, snap, shards)\n"
           "    os.replace(tmp, shard)\n")
    bp = tmp_path / "scripts" / "lint_baseline.json"
    bp.parent.mkdir()
    bp.write_text(json.dumps({"version": 1, "findings": []}))
    r = _run_cli(["--root", str(tmp_path), "--select", "protocol",
                  "--update-baseline"])
    assert r.returncode == 1
    assert json.loads(bp.read_text())["findings"] == []
