"""roc-lint level six (analysis/concurrency_lint): every rule fires
on a synthetic violation tree, pragma suppression works, the REAL
tree audits clean with an empty findings baseline, the CLI gate (and
its `--select concurrency` alias) bites, and the discovered
concurrency surface documents the runtime's actual thread model."""

import json
import os
import subprocess
import sys

from roc_tpu.analysis.concurrency_lint import (
    CONCURRENCY_RULES, TreeModel, concurrency_surface,
    run_concurrency_lint)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plant(root, relpath, text):
    p = root / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------- synthetic fixtures

def test_signal_unsafe_handler_fires(tmp_path):
    """A registered handler that emits/locks/imports/prints fires per
    violation; flag-only handlers and SIG_DFL stay quiet; the one-level
    call-graph walk catches a helper that emits."""
    _plant(tmp_path, "roc_tpu/sig.py",
           "import signal\n"
           "import threading\n"
           "from roc_tpu.obs.events import emit\n"
           "_LOCK = threading.Lock()\n"
           "FLAG = [False]\n"
           "def _helper():\n"
           "    emit('run', 'noooo')\n"                       # line 7
           "def bad_handler(signum, frame):\n"
           "    import os\n"                                  # line 9
           "    with _LOCK:\n"                                # line 10
           "        FLAG[0] = True\n"
           "    print('caught')\n"                            # line 12
           "    _helper()\n"
           "def good_handler(signum, frame):\n"
           "    FLAG[0] = True\n"
           "def install():\n"
           "    signal.signal(signal.SIGTERM, bad_handler)\n"
           "    signal.signal(signal.SIGINT, good_handler)\n"
           "    signal.signal(signal.SIGUSR1, signal.SIG_DFL)\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["signal-unsafe-handler"])
    lines = sorted(f.line for f in got)
    assert lines == [7, 9, 10, 12], \
        [(f.line, f.msg) for f in got]
    assert all(f.rule == "signal-unsafe-handler" for f in got)
    # the helper finding names both the handler and the via-path
    via = [f for f in got if f.line == 7]
    assert "via _helper" in via[0].msg


def test_lock_order_cycle_fires_and_pragma(tmp_path):
    """A seeded A->B / B->A nesting is a cycle; consistent nesting is
    not; a pragma on a participating acquisition suppresses it."""
    _plant(tmp_path, "roc_tpu/locks.py",
           "import threading\n"
           "A = threading.Lock()\n"
           "B = threading.Lock()\n"
           "def t1():\n"
           "    with A:\n"
           "        with B:\n"
           "            pass\n"
           "def t2():\n"
           "    with B:\n"
           "        with A:\n"
           "            pass\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["lock-order-cycle"])
    assert len(got) == 1
    assert got[0].rule == "lock-order-cycle"
    assert "A" in got[0].msg and "B" in got[0].msg
    # fingerprint is the sorted lock set — stable across line drift
    assert got[0].key.startswith("cycle=")

    # consistent ordering: no finding
    _plant(tmp_path, "roc_tpu/locks.py",
           "import threading\n"
           "A = threading.Lock()\n"
           "B = threading.Lock()\n"
           "def t1():\n"
           "    with A:\n"
           "        with B:\n"
           "            pass\n"
           "def t2():\n"
           "    with A:\n"
           "        with B:\n"
           "            pass\n")
    assert not run_concurrency_lint(str(tmp_path),
                                    select=["lock-order-cycle"])

    # pragma on one edge suppresses the cycle
    _plant(tmp_path, "roc_tpu/locks.py",
           "import threading\n"
           "A = threading.Lock()\n"
           "B = threading.Lock()\n"
           "def t1():\n"
           "    with A:\n"
           "        # B never contended: roc-lint: ok=lock-order-cycle\n"
           "        with B:\n"
           "            pass\n"
           "def t2():\n"
           "    with B:\n"
           "        with A:\n"
           "            pass\n")
    assert not run_concurrency_lint(str(tmp_path),
                                    select=["lock-order-cycle"])


def test_lock_order_cycle_through_call_chain(tmp_path):
    """The acquired-while-holding edge walks resolvable calls: a
    with-block calling a function that takes the other lock still
    closes the cycle."""
    _plant(tmp_path, "roc_tpu/locks2.py",
           "import threading\n"
           "A = threading.Lock()\n"
           "B = threading.Lock()\n"
           "def takes_b():\n"
           "    with B:\n"
           "        pass\n"
           "def t1():\n"
           "    with A:\n"
           "        takes_b()\n"
           "def t2():\n"
           "    with B:\n"
           "        with A:\n"
           "            pass\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["lock-order-cycle"])
    assert len(got) == 1


def test_condvar_wait_no_predicate_fires(tmp_path):
    """The seeded predicate-less Condition.wait() (the PR-11 race
    class) fires; while-loop waits and Event.wait stay quiet."""
    _plant(tmp_path, "roc_tpu/cv.py",
           "import threading\n"
           "class Q:\n"
           "    def __init__(self):\n"
           "        self._cv = threading.Condition()\n"
           "        self._stop = threading.Event()\n"
           "        self.items = []\n"
           "    def bad_take(self):\n"
           "        with self._cv:\n"
           "            if not self.items:\n"
           "                self._cv.wait()\n"               # line 10
           "            return self.items.pop()\n"
           "    def good_take(self):\n"
           "        with self._cv:\n"
           "            while not self.items:\n"
           "                self._cv.wait()\n"
           "            return self.items.pop()\n"
           "    def idle(self):\n"
           "        self._stop.wait(1.0)\n")    # Event: level-triggered
    got = run_concurrency_lint(str(tmp_path),
                               select=["condvar-wait-no-predicate"])
    assert [(f.rule, f.line) for f in got] == \
        [("condvar-wait-no-predicate", 10)]
    assert "Q.bad_take" in got[0].msg


def test_unguarded_shared_state_fires(tmp_path):
    """Attributes the thread body mutates (appends, augmented
    assigns) read from public methods without the lock fire; locked
    accesses, private methods, and constant flag publishes don't."""
    _plant(tmp_path, "roc_tpu/shared.py",
           "import threading\n"
           "class W:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.vals = []\n"
           "        self.n = 0\n"
           "        self.done = False\n"
           "        self._t = threading.Thread(target=self._run)\n"
           "        self._t.start()\n"
           "    def _run(self):\n"
           "        while True:\n"
           "            with self._lock:\n"
           "                self.vals.append(1)\n"
           "            self.n += 1\n"
           "            self.done = True\n"      # flag publish: exempt
           "    def peek(self):\n"
           "        return list(self.vals), self.n\n"   # lines 17-18
           "    def peek_locked(self):\n"
           "        with self._lock:\n"
           "            return list(self.vals), self.n\n"
           "    def is_done(self):\n"
           "        return self.done\n"          # exempt flag
           "    def _private_peek(self):\n"
           "        return self.vals\n"
           "    def stop(self):\n"
           "        self._t.join()\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["unguarded-shared-state"])
    assert sorted(f.key for f in got) == ["W.peek:n", "W.peek:vals"]
    assert all("W.peek" in f.msg for f in got)


def test_blocking_under_lock_fires(tmp_path):
    """device_put / sleeps / file I/O / Future.result reachable while
    a lock is held fire (directly and one resolvable call deep);
    the same calls outside the lock, and pragma'd holds, stay quiet."""
    _plant(tmp_path, "roc_tpu/blk.py",
           "import threading\n"
           "import time\n"
           "import jax\n"
           "L = threading.Lock()\n"
           "def slow():\n"
           "    time.sleep(1.0)\n"
           "def f(x, fut):\n"
           "    with L:\n"
           "        y = jax.device_put(x)\n"                 # line 9
           "        time.sleep(0.1)\n"                       # line 10
           "        r = fut.result()\n"                      # line 11
           "        slow()\n"                                # line 12
           "    z = jax.device_put(x)\n"       # outside: fine
           "    time.sleep(0.1)\n"             # outside: fine
           "    return y, r, z\n"
           "def g(x):\n"
           "    with L:\n"
           "        # bounded: roc-lint: ok=blocking-under-lock\n"
           "        return jax.device_put(x)\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["blocking-under-lock"])
    assert sorted(f.line for f in got) == [9, 10, 11, 12]
    via = [f for f in got if f.line == 12]
    assert "via slow" in via[0].msg


def test_thread_no_shutdown_path_fires(tmp_path):
    """A thread nobody joins and whose body polls no stop Event fires
    (daemon= alone doesn't count); a joined thread and a
    stop-Event-polling thread are both fine."""
    _plant(tmp_path, "roc_tpu/thr.py",
           "import threading\n"
           "def _work():\n"
           "    while True:\n"
           "        pass\n"
           "def leak():\n"
           "    t = threading.Thread(target=_work, daemon=True)\n"
           "    t.start()\n"                                 # no join
           "def joined():\n"
           "    t = threading.Thread(target=_work)\n"
           "    t.start()\n"
           "    t.join()\n"
           "def evented():\n"
           "    stop = threading.Event()\n"
           "    def _poll():\n"
           "        while not stop.is_set():\n"
           "            pass\n"
           "    t = threading.Thread(target=_poll)\n"
           "    t.start()\n"
           "    stop.set()\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["thread-no-shutdown-path"])
    assert len(got) == 1
    assert got[0].line == 6
    assert "_work" in got[0].msg and "daemon" in got[0].msg


def test_lock_order_cycle_survives_mutual_recursion(tmp_path):
    """Regression (review): mutually recursive acquirers must not
    memo-poison the lock summary — the cycle cut returns a truncated
    set that, if cached as final, silently dropped the C->A edge and
    the genuine C->A->C deadlock with it."""
    _plant(tmp_path, "roc_tpu/rec.py",
           "import threading\n"
           "LA = threading.Lock()\n"
           "LB = threading.Lock()\n"
           "LC = threading.Lock()\n"
           "def a():\n"
           "    with LA:\n"
           "        pass\n"
           "    b()\n"
           "def b():\n"
           "    with LB:\n"
           "        pass\n"
           "    a()\n"                 # mutual recursion: cycle cut
           "def holder():\n"
           "    with LC:\n"
           "        b()\n"             # edges LC->LB AND LC->LA
           "def closer():\n"
           "    with LA:\n"
           "        with LC:\n"        # closes the LC->LA->LC cycle
           "            pass\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["lock-order-cycle"])
    assert len(got) == 1, [f.msg for f in got]
    assert "LC" in got[0].msg and "LA" in got[0].msg


def test_blocking_under_lock_thread_names_are_function_local(tmp_path):
    """Regression (review): a Thread stored to `t` in one function
    must not make an unrelated function's `t.join()` (a str/list
    join) a blocking finding."""
    _plant(tmp_path, "roc_tpu/blk2.py",
           "import threading\n"
           "L = threading.Lock()\n"
           "def spawns():\n"
           "    t = threading.Thread(target=print)\n"
           "    t.start()\n"
           "    t.join()\n"
           "def unrelated(parts):\n"
           "    t = ','\n"
           "    with L:\n"
           "        return t.join(parts)\n"    # str.join: not a thread
           "def real(pool):\n"
           "    t = threading.Thread(target=print)\n"
           "    t.start()\n"
           "    with L:\n"
           "        t.join()\n"                # line 15: genuine
           "    return t\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["blocking-under-lock"])
    assert [(f.line, f.rule) for f in got] == \
        [(15, "blocking-under-lock")], [(f.line, f.msg) for f in got]


def test_thread_shutdown_attr_joins_are_class_scoped(tmp_path):
    """Regression (review): ClassB joining its own `self._t` must not
    vouch for ClassA's never-joined, never-polling `self._t`."""
    _plant(tmp_path, "roc_tpu/thr2.py",
           "import threading\n"
           "class A:\n"
           "    def __init__(self):\n"
           "        self._t = threading.Thread(target=self._run)\n"
           "        self._t.start()\n"         # line 5: never joined
           "    def _run(self):\n"
           "        pass\n"
           "class B:\n"
           "    def __init__(self):\n"
           "        self._t = threading.Thread(target=self._run)\n"
           "        self._t.start()\n"
           "    def _run(self):\n"
           "        pass\n"
           "    def close(self):\n"
           "        self._t.join()\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["thread-no-shutdown-path"])
    assert len(got) == 1, [(f.line, f.msg) for f in got]
    assert got[0].line == 4


# ---------------------------------- explicit acquire()/release() pairs

def test_acquire_release_regions_model_held_locks(tmp_path):
    """ISSUE-13 satellite: explicit ``.acquire()``/``.release()``
    pairs model held regions exactly like with-blocks — the ordering
    graph closes cycles through them, blocking calls inside the span
    flag (including the ``acquire(); try: ... finally: release()``
    idiom), and statements AFTER the release are free."""
    _plant(tmp_path, "roc_tpu/acq.py",
           "import threading\n"
           "import time\n"
           "A = threading.Lock()\n"
           "B = threading.Lock()\n"
           "def t1():\n"
           "    A.acquire()\n"
           "    try:\n"
           "        time.sleep(1.0)\n"                         # line 8
           "        with B:\n"
           "            pass\n"
           "    finally:\n"
           "        A.release()\n"
           "def t2():\n"
           "    B.acquire()\n"
           "    with A:\n"                                     # line 15
           "        pass\n"
           "    B.release()\n"
           "def t3():\n"
           "    A.acquire()\n"
           "    time.sleep(0.5)\n"                             # line 20
           "    A.release()\n"
           "    time.sleep(0.5)\n")                            # line 22
    got = run_concurrency_lint(str(tmp_path))
    # A->B through t1's try/finally region, B->A through t2's span
    cyc = [f for f in got if f.rule == "lock-order-cycle"]
    assert len(cyc) == 1, [f.msg for f in got]
    assert "A" in cyc[0].msg and "B" in cyc[0].msg
    bl_lines = sorted(f.line for f in got
                      if f.rule == "blocking-under-lock")
    assert 8 in bl_lines       # sleep inside the try/finally region
    assert 20 in bl_lines      # sleep inside the plain span
    assert 22 not in bl_lines  # sleep AFTER the release is free


def test_acquire_without_release_holds_to_end(tmp_path):
    """A missing release is modeled as held-to-end-of-list — exactly
    what the leaked lock does at runtime."""
    _plant(tmp_path, "roc_tpu/leak.py",
           "import threading\n"
           "import time\n"
           "A = threading.Lock()\n"
           "def leaky():\n"
           "    A.acquire()\n"
           "    time.sleep(0.5)\n")                            # line 6
    got = run_concurrency_lint(str(tmp_path),
                               select=["blocking-under-lock"])
    assert [f.line for f in got] == [6], [f.msg for f in got]


def test_acquire_release_covers_unguarded_shared_state(tmp_path):
    """A public method reading thread-written state between
    ``acquire()`` and ``release()`` counts as guarded; the same read
    outside the span still fires — the Router/Server locking styles
    are both fully covered."""
    _plant(tmp_path, "roc_tpu/ug2.py",
           "import threading\n"
           "class W:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.items = []\n"
           "        self._t = threading.Thread(target=self._run)\n"
           "        self._t.start()\n"
           "    def _run(self):\n"
           "        with self._lock:\n"
           "            self.items.append(1)\n"
           "    def good(self):\n"
           "        self._lock.acquire()\n"
           "        try:\n"
           "            return len(self.items)\n"
           "        finally:\n"
           "            self._lock.release()\n"
           "    def bad(self):\n"
           "        return len(self.items)\n"                  # line 18
           "    def close(self):\n"
           "        self._t.join()\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["unguarded-shared-state"])
    assert [f.line for f in got] == [18], \
        [(f.line, f.msg) for f in got]


# ----------------------------------- artifact lock ownership (ISSUE 14)

def test_artifact_lock_ownership_fires_on_ungated_writers(tmp_path):
    """Two writers to one rotation prefix without the shared-rotation
    handshake = one finding per write site; a process_index-gated
    writer and a per-process prefix are the sanctioned protocols."""
    _plant(tmp_path, "roc_tpu/ck.py",
           "from roc_tpu.resilience.recovery import "
           "CheckpointRotation\n"
           "def writer_a(tr):\n"
           "    rot = CheckpointRotation('shared/ck')\n"
           "    rot.save(tr)\n"                               # line 4
           "def writer_b(tr):\n"
           "    rot = CheckpointRotation('shared/ck')\n"
           "    rot.save(tr)\n"                               # line 7
           "def gated_writer(tr):\n"
           "    import jax\n"
           "    rot = CheckpointRotation('shared/ck')\n"
           "    if jax.process_index() == 0:\n"
           "        rot.save(tr)\n"
           "def per_proc_writer(tr):\n"
           "    import os\n"
           "    rot = CheckpointRotation(f'ck.{os.getpid()}')\n"
           "    rot.save(tr)\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["artifact-lock-ownership"])
    assert sorted(f.line for f in got) == [4, 7], \
        [(f.line, f.msg) for f in got]
    assert all(f.rule == "artifact-lock-ownership" for f in got)
    assert "shared-rotation handshake" in got[0].msg


def test_artifact_lock_ownership_bindings_are_function_scoped(
        tmp_path):
    """One function's per-process prefix must not vouch for another
    function's shared prefix just because both bind the name
    ``rot``."""
    _plant(tmp_path, "roc_tpu/ck.py",
           "from roc_tpu.resilience.recovery import "
           "CheckpointRotation\n"
           "import os\n"
           "def per_proc(tr):\n"
           "    rot = CheckpointRotation(f'ck.{os.getpid()}')\n"
           "    rot.save(tr)\n"
           "def shared(tr):\n"
           "    rot = CheckpointRotation('shared/ck')\n"
           "    rot.save(tr)\n")                              # line 8
    got = run_concurrency_lint(str(tmp_path),
                               select=["artifact-lock-ownership"])
    assert [f.line for f in got] == [8], \
        [(f.line, f.msg) for f in got]


def test_artifact_lock_ownership_local_binding_no_module_shadow(
        tmp_path):
    """A function-local per-process binding must not shadow the
    MODULE-level shared binding another function writes through."""
    _plant(tmp_path, "roc_tpu/ck.py",
           "import os\n"
           "from roc_tpu.resilience.recovery import "
           "CheckpointRotation\n"
           "rot = CheckpointRotation('shared/ck')\n"
           "def module_writer(tr):\n"
           "    rot.save(tr)\n"                               # line 5
           "def per_proc(tr):\n"
           "    rot = CheckpointRotation(f'ck.{os.getpid()}')\n"
           "    rot.save(tr)\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["artifact-lock-ownership"])
    assert [f.line for f in got] == [5], \
        [(f.line, f.msg) for f in got]
    assert "module_writer" in got[0].msg


def test_artifact_lock_ownership_attr_bindings_are_class_scoped(
        tmp_path):
    """Two classes reusing one attribute name: class A's per-process
    prefix must not exempt class B's shared-prefix writer."""
    _plant(tmp_path, "roc_tpu/ck.py",
           "import os\n"
           "from roc_tpu.resilience.recovery import "
           "CheckpointRotation\n"
           "class A:\n"
           "    def __init__(self):\n"
           "        self.rot = CheckpointRotation("
           "f'ck.{os.getpid()}')\n"
           "    def write(self, tr):\n"
           "        self.rot.save(tr)\n"
           "class B:\n"
           "    def __init__(self):\n"
           "        self.rot = CheckpointRotation('shared/ck')\n"
           "    def write(self, tr):\n"
           "        self.rot.save(tr)\n")                     # line 12
    got = run_concurrency_lint(str(tmp_path),
                               select=["artifact-lock-ownership"])
    assert [f.line for f in got] == [12], \
        [(f.line, f.msg) for f in got]
    assert "B.write" in got[0].msg


def test_artifact_lock_ownership_gate_via_callee_chain(tmp_path):
    """The real tree's shape: the write funnels through a helper that
    carries the gate (checkpoint_trainer's process_index() != 0
    return) — evidence travels the resolvable call chain, including
    through a tree-local CheckpointRotation.save."""
    _plant(tmp_path, "roc_tpu/ck.py",
           "import jax\n"
           "class CheckpointRotation:\n"
           "    def __init__(self, prefix):\n"
           "        self.prefix = prefix\n"
           "    def save(self, tr):\n"
           "        helper(tr, self.prefix)\n"
           "def helper(tr, p):\n"
           "    if jax.process_count() > 1 "
           "and jax.process_index() != 0:\n"
           "        return\n"
           "    open(p, 'w').close()\n"
           "def writer(tr):\n"
           "    rot = CheckpointRotation('shared/ck')\n"
           "    rot.save(tr)\n"
           "def direct(tr):\n"
           "    helper(tr, 'shared/ck')\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["artifact-lock-ownership"])
    assert got == [], [(f.line, f.msg) for f in got]


def test_artifact_lock_ownership_pragma_and_writer_fns(tmp_path):
    """Direct checkpoint_trainer()/save_checkpoint() call sites are
    writers too, and the standard pragma documents a known-single-
    writer site."""
    _plant(tmp_path, "roc_tpu/ck.py",
           "def checkpoint_trainer(tr, p):\n"
           "    pass\n"
           "def bad(tr):\n"
           "    checkpoint_trainer(tr, 'ck')\n"               # line 4
           "def vouched(tr):\n"
           "    # one writer process per prefix: "
           "roc-lint: ok=artifact-lock-ownership\n"
           "    checkpoint_trainer(tr, 'ck')\n")
    got = run_concurrency_lint(str(tmp_path),
                               select=["artifact-lock-ownership"])
    assert [f.line for f in got] == [4], \
        [(f.line, f.msg) for f in got]


def test_artifact_surface_inventories_real_tree():
    """The surface documents which process-shared artifacts each
    module touches and their ownership protocol: the tree's rotation
    writers inherit the proc0 gate, the warm state publishes via
    atomic replace, the compile cache is multi-writer-safe."""
    surface = concurrency_surface(TreeModel(_REPO))
    arts = {m["module"]: m["artifacts"]
            for m in surface["artifacts"]}
    assert any(a["kind"] == "rotation"
               and a["owner"] == "proc0-gate"
               for a in arts.get("roc_tpu/train/cli.py", [])), arts
    assert any(a["kind"] == "warm-state"
               and a["owner"] == "atomic-replace"
               for a in arts.get("roc_tpu/prewarm.py", []))
    assert any(a["kind"] == "compile-cache"
               for a in arts.get("roc_tpu/train/cli.py", []))
    # checkpoint-v3 writers (ISSUE 15): the per-shard writers (the
    # async saver thread's included) and the proc-0 manifest commit
    # are inventoried with their ownership protocol
    assert any(a["kind"] == "ckpt-manifest"
               and a["owner"] == "proc0-commit-after-shards"
               for a in arts.get("roc_tpu/utils/checkpoint.py", []))
    assert any(a["kind"] == "ckpt-shard"
               and a["owner"] == "per-process-file"
               for a in arts.get("roc_tpu/resilience/async_save.py",
                                 []))
    assert surface["totals"]["artifacts"] >= 5


# ------------------------------------------------- registration + tree

def test_rules_registered_and_not_trace():
    from roc_tpu.analysis.driver import all_rule_names, is_trace_rule
    names = all_rule_names()
    for r in CONCURRENCY_RULES:
        assert r in names
        # pure AST: a `--select concurrency` preflight must never
        # force the jax trace rig
        assert not is_trace_rule(r)


def test_tree_is_clean_and_baseline_empty():
    """The REAL tree audits clean (true positives were FIXED, not
    baselined): the findings baseline stays empty."""
    got = run_concurrency_lint(_REPO)
    assert got == [], "\n".join(f.render() for f in got)
    data = json.load(open(
        os.path.join(_REPO, "scripts", "lint_baseline.json")))
    assert data["findings"] == []


def test_surface_documents_the_runtime_thread_model():
    """The discovered surface names the threads/locks/handlers the
    runtime actually has — the audit doubling as documentation."""
    surface = concurrency_surface(TreeModel(_REPO))
    by_mod = {m["module"]: m for m in surface["modules"]}
    # the five known thread spawns
    assert "roc_tpu/core/streaming.py" in by_mod       # StagingPool
    assert "roc_tpu/serve/server.py" in by_mod         # Server._loop
    assert "roc_tpu/obs/heartbeat.py" in by_mod        # watchdog
    assert "roc_tpu/serve/router.py" in by_mod         # replica readers
    # the checkpoint saver thread (ISSUE 15) — the tree-clean pin
    # above already proves all six rules model it
    asv = by_mod["roc_tpu/resilience/async_save.py"]
    assert any(t["target"] == "self._loop" for t in asv["threads"])
    assert any(lk["kind"] == "condition" for lk in asv["locks"])
    srv = by_mod["roc_tpu/serve/server.py"]
    assert any(t["target"] == "self._loop" for t in srv["threads"])
    assert any(lk["kind"] == "condition" for lk in srv["locks"])
    # the preemption guard's SIGTERM/SIGINT handler (SIG_DFL resets
    # are not handlers)
    pre = by_mod["roc_tpu/resilience/preempt.py"]
    assert any(h["handler"] == "_handle" for h in pre["handlers"])
    assert surface["totals"]["threads"] >= 4
    assert surface["totals"]["handlers"] >= 1


def test_report_renders_concurrency_surface_table():
    """roc_tpu.report renders the thread-model table from the
    --json payload (``--concurrency``) AND from the surface event an
    audited run leaves in its event stream."""
    import io

    from roc_tpu import report
    surface = concurrency_surface(TreeModel(_REPO))
    out = io.StringIO()
    report.summarize([], concurrency=surface, out=out)
    text = out.getvalue()
    assert "concurrency surface" in text
    assert "roc_tpu/serve/server.py" in text
    assert "Server._lock[condition]" in text
    # event-stream path: same table, no payload file needed
    ev = {"cat": "analysis", "kind": "concurrency_surface",
          "modules": surface["modules"], "totals": surface["totals"]}
    out2 = io.StringIO()
    report.summarize([ev], out=out2)
    assert "Server._lock[condition]" in out2.getvalue()


def test_known_pragmas_suppress_with_reasons():
    """The two sanctioned suppressions carry their why at the site:
    the preemption guard's async-signal-safe os.write and the event
    bus's serialized sink write."""
    src = open(os.path.join(
        _REPO, "roc_tpu", "resilience", "preempt.py")).read()
    assert "roc-lint: ok=signal-unsafe-handler" in src
    src = open(os.path.join(
        _REPO, "roc_tpu", "obs", "events.py")).read()
    assert "roc-lint: ok=blocking-under-lock" in src


# --------------------------------------------------------- CLI wiring

def _run_cli(args, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "roc_tpu.analysis"] + args,
        cwd=cwd or _REPO, capture_output=True, text=True, timeout=60,
        env=env)


def test_cli_select_concurrency_alias_green_on_tree():
    """`--select concurrency` (the scripts/test.sh preflight
    line) expands to all six rules, runs jax-free fast, and exits 0
    on the tree with the surface in the --json payload."""
    r = _run_cli(["--select", "concurrency", "--json"])
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["summary"]["new"] == 0
    surface = payload["concurrency_surface"]
    assert surface["totals"]["threads"] >= 4
    assert any(m["module"] == "roc_tpu/serve/server.py"
               for m in surface["modules"])


def test_cli_ratchet_bites_on_planted_violation(tmp_path):
    """A seeded predicate-less Condition.wait in a scratch tree fails
    the CLI through the alias (the ratchet bites from zero)."""
    _plant(tmp_path, "roc_tpu/srv.py",
           "import threading\n"
           "class S:\n"
           "    def __init__(self):\n"
           "        self._cv = threading.Condition()\n"
           "    def take(self):\n"
           "        with self._cv:\n"
           "            self._cv.wait()\n")
    r = _run_cli(["--root", str(tmp_path), "--select", "concurrency"])
    assert r.returncode == 1
    assert "condvar-wait-no-predicate" in r.stdout
    assert "srv.py" in r.stdout


def test_cli_never_absorbs_concurrency_findings(tmp_path):
    """--update-baseline must not absorb a live concurrency finding
    (shrink-only contract, same as every level)."""
    _plant(tmp_path, "roc_tpu/srv.py",
           "import threading\n"
           "class S:\n"
           "    def __init__(self):\n"
           "        self._cv = threading.Condition()\n"
           "    def take(self):\n"
           "        with self._cv:\n"
           "            self._cv.wait()\n")
    bp = tmp_path / "scripts" / "lint_baseline.json"
    bp.parent.mkdir()
    bp.write_text(json.dumps({"version": 1, "findings": []}))
    r = _run_cli(["--root", str(tmp_path), "--select", "concurrency",
                  "--update-baseline"])
    assert r.returncode == 1
    assert json.loads(bp.read_text())["findings"] == []
