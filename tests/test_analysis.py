"""roc-lint static analyzer (roc_tpu/analysis): every rule fires on a
synthetic violation, the tree itself is clean modulo the baseline, and
the CLI gate is wired into the tier (the lint_prints.sh successor)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from roc_tpu.analysis.ast_lint import run_ast_lint
from roc_tpu.analysis.findings import (Finding, dedupe, load_baseline,
                                       save_baseline, shrink_baseline,
                                       split_findings)
from roc_tpu.analysis.hlo_lint import check_bytes_model, check_large_copy
from roc_tpu.analysis.jaxpr_lint import JaxprUnit, run_jaxpr_lint

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plant(root, relpath, text):
    p = root / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------- AST fixtures

def test_stdout_print_fires_and_allows(tmp_path):
    _plant(tmp_path, "roc_tpu/mod.py",
           "import sys\n"
           "print('leak')\n"
           "print('err', file=sys.stderr)\n"
           "print(format_metrics(1, {}))\n")
    got = run_ast_lint(str(tmp_path), select=["stdout-print"])
    assert [(f.rule, f.line) for f in got] == [("stdout-print", 2)]


def test_host_sync_hot_path_fires(tmp_path):
    _plant(tmp_path, "roc_tpu/ops/hot.py",
           "import jax\n"
           "def f(x, rate):\n"
           "    a = jax.device_get(x)\n"
           "    b = x.sum().item()\n"
           "    c = float(x.sum())\n"
           "    d = float(rate)\n"          # plain name: allowed
           "    # host-side numpy: roc-lint: ok=host-sync-hot-path\n"
           "    e = jax.device_get(x)\n"    # pragma'd: allowed
           "    return a, b, c, d, e\n")
    # the same code OUTSIDE a hot-path module is not flagged
    _plant(tmp_path, "roc_tpu/cold.py",
           "import jax\n"
           "def f(x):\n"
           "    return float(x.sum())\n")
    got = run_ast_lint(str(tmp_path), select=["host-sync-hot-path"])
    assert [f.line for f in got] == [3, 4, 5]
    assert all(f.unit == "roc_tpu/ops/hot.py" for f in got)


def test_sync_h2d_in_loop_fires(tmp_path):
    _plant(tmp_path, "roc_tpu/core/streaming.py",
           "import jax\n"
           "import numpy as np\n"
           "def stage_once(feats, lo, hi):\n"
           "    # outside any loop: the sanctioned pool call site\n"
           "    return jax.device_put(np.ascontiguousarray("
           "feats[lo:hi]))\n"
           "def bad(blocks):\n"
           "    out = []\n"
           "    for b in blocks:\n"
           "        x = np.ascontiguousarray(b)\n"
           "        out.append(jax.device_put(x))\n"
           "    i = 0\n"
           "    while i < 3:\n"
           "        # cold loop: roc-lint: ok=sync-h2d-in-loop\n"
           "        jax.device_put(blocks[i])\n"
           "        i += 1\n"
           "    comp = [jax.device_put(b) for b in blocks]\n"
           "    return out, comp\n")
    # the same calls OUTSIDE the hot modules are not this rule's
    # business
    _plant(tmp_path, "roc_tpu/train/cold.py",
           "import jax\n"
           "def f(bs):\n"
           "    return [jax.device_put(b) for b in bs]\n")
    got = run_ast_lint(str(tmp_path), select=["sync-h2d-in-loop"])
    # the for-body copy + put, and the comprehension rewrite (the
    # obvious ratchet dodge) — the pragma'd while body stays quiet
    assert [(f.rule, f.line) for f in got] == \
        [("sync-h2d-in-loop", 9), ("sync-h2d-in-loop", 10),
         ("sync-h2d-in-loop", 16)]
    assert all(f.unit == "roc_tpu/core/streaming.py" for f in got)


def test_dequant_hot_path_fires(tmp_path):
    """PR-19 rule: a float32 materialization of a tableish value
    inside roc_tpu/serve/ (astype, asarray(dtype=), or a float32()
    cast) is a finding — the dequantize must stay fused in-register
    — while the pragma'd sanctioned site and non-table values stay
    quiet, and serve-external code is not this rule's business."""
    _plant(tmp_path, "roc_tpu/serve/hot.py",
           "import jax.numpy as jnp\n"
           "import numpy as np\n"
           "def f(q_table, stage0, ids, x):\n"
           "    a = q_table.astype(jnp.float32)\n"
           "    b = np.asarray(stage0, dtype=np.float32)\n"
           "    c = jnp.float32(q_table)\n"
           "    d = x.astype(jnp.float32)\n"          # not tableish
           "    # export-time: roc-lint: ok=dequant-hot-path\n"
           "    e = q_table.astype(jnp.float32)\n"
           "    return a, b, c, d, e\n")
    _plant(tmp_path, "roc_tpu/core/cold.py",
           "import numpy as np\n"
           "def f(table):\n"
           "    return np.asarray(table, dtype=np.float32)\n")
    got = run_ast_lint(str(tmp_path), select=["dequant-hot-path"])
    assert [(f.rule, f.line) for f in got] == \
        [("dequant-hot-path", 4), ("dequant-hot-path", 5),
         ("dequant-hot-path", 6)]
    assert all(f.unit == "roc_tpu/serve/hot.py" for f in got)


def test_bare_jit_fires_and_observed_form_allowed(tmp_path):
    _plant(tmp_path, "roc_tpu/train/steps.py",
           "import jax\n"
           "from roc_tpu.obs.compile_watch import ObservedJit\n"
           "def build(fn):\n"
           "    bad = jax.jit(fn)\n"
           "    good = ObservedJit(jitfn=jax.jit(fn), name='s')\n"
           "    return bad, good\n")
    got = run_ast_lint(str(tmp_path), select=["bare-jit"])
    assert [(f.rule, f.line) for f in got] == [("bare-jit", 4)]


def test_swallowed_exception_fires_and_allows(tmp_path):
    """Recovery/streaming/checkpoint paths: bare except (any body)
    and except-with-pass-only body both fire; a handler that handles
    (or re-raises) is clean, the pragma suppresses, and the same code
    OUTSIDE the scoped paths is not flagged."""
    code = ("import os\n"
            "def f(p):\n"
            "    try:\n"
            "        os.remove(p)\n"
            "    except:\n"                               # line 5
            "        print('x', file=None)\n"
            "    try:\n"
            "        os.remove(p)\n"
            "    except OSError:\n"                       # line 9
            "        pass\n"
            "    try:\n"
            "        os.remove(p)\n"
            "    except OSError as e:\n"
            "        raise RuntimeError('ctx') from e\n"  # handled: ok
            "    try:\n"
            "        os.remove(p)\n"
            "    # why: roc-lint: ok=swallowed-exception\n"
            "    except OSError:\n"                       # pragma'd
            "        pass\n")
    _plant(tmp_path, "roc_tpu/resilience/rec.py", code)
    _plant(tmp_path, "roc_tpu/ops/cold.py", code)  # out of scope
    got = run_ast_lint(str(tmp_path), select=["swallowed-exception"])
    assert [(f.rule, f.unit, f.line) for f in got] == [
        ("swallowed-exception", "roc_tpu/resilience/rec.py", 5),
        ("swallowed-exception", "roc_tpu/resilience/rec.py", 9)]


def test_event_clock_fires_and_allows(tmp_path):
    """event-clock: hand-passed reserved clock kwargs on emit() and
    hand-rolled event dicts (cat+msg literals) both fire; normal emit
    calls, non-event dicts, the bus module itself, and the pragma are
    all clean."""
    code = ("from roc_tpu.obs.events import emit\n"
            "def f(bus):\n"
            "    emit('epoch', 'ok', epoch=1)\n"            # clean
            "    emit('epoch', 'bad', t=123.0)\n"           # line 4
            "    bus.emit('run', 'bad2', proc=3, host='h')\n"  # line 5
            "    rec = {'cat': 'epoch', 'msg': 'handrolled'}\n"  # 6
            "    ok = {'cat': 'span'}\n"                    # clean
            "    ok2 = {'msg': 'x', 'name': 'y'}\n"         # clean
            "    emit('epoch', 'sup', t=1.0)  "
            "# why: roc-lint: ok=event-clock\n"
            "    return rec, ok, ok2\n")
    _plant(tmp_path, "roc_tpu/train/mod.py", code)
    # the bus module itself legitimately builds the stamped record
    _plant(tmp_path, "roc_tpu/obs/events.py",
           "def emit(cat, msg, **f):\n"
           "    return {'t': 0.0, 'cat': cat, 'msg': msg, **f}\n")
    got = run_ast_lint(str(tmp_path), select=["event-clock"])
    assert [(f.rule, f.unit, f.line) for f in got] == [
        ("event-clock", "roc_tpu/train/mod.py", 4),
        ("event-clock", "roc_tpu/train/mod.py", 5),
        ("event-clock", "roc_tpu/train/mod.py", 6)]


def test_event_clock_registered_and_tree_clean():
    from roc_tpu.analysis.driver import all_rule_names, is_trace_rule
    assert "event-clock" in all_rule_names()
    assert not is_trace_rule("event-clock")
    # ratchet bites from zero on the real tree: no unbaselined finding
    got = run_ast_lint(_REPO, select=["event-clock"])
    assert got == [], [(f.unit, f.line, f.msg) for f in got]


def test_metric_adhoc_fires_and_allows(tmp_path):
    """metric-adhoc (PR 17): serve/train hot paths must record
    through the metrics registry — an ad-hoc ``self._n_* +=``
    counter and a ``*_ms``/``*_lat`` ``.append`` both fire; registry
    calls, non-metric attributes, the pragma, and the same code
    OUTSIDE the scoped paths are all clean."""
    code = ("class S:\n"
            "    def hot(self, ms):\n"
            "        self._n_shed += 1\n"                  # line 3
            "        self.lat_ms.append(ms)\n"            # line 4
            "        self._h_batch.record(ms)\n"          # registry: ok
            "        self._c_shed.inc()\n"                # registry: ok
            "        self.rows.append(ms)\n"              # not *_ms: ok
            "        # span buffer: roc-lint: ok=metric-adhoc\n"
            "        self.laps_ms.append(ms)\n")          # pragma'd
    _plant(tmp_path, "roc_tpu/serve/mod.py", code)
    _plant(tmp_path, "roc_tpu/train/trainer.py", code)
    _plant(tmp_path, "roc_tpu/ops/cold.py", code)  # out of scope
    got = run_ast_lint(str(tmp_path), select=["metric-adhoc"])
    assert [(f.rule, f.unit, f.line) for f in got] == [
        ("metric-adhoc", "roc_tpu/serve/mod.py", 3),
        ("metric-adhoc", "roc_tpu/serve/mod.py", 4),
        ("metric-adhoc", "roc_tpu/train/trainer.py", 3),
        ("metric-adhoc", "roc_tpu/train/trainer.py", 4)]


def test_metric_adhoc_registered_and_tree_clean():
    """The rule rides the shrink-only baseline ratchet from zero: the
    real serve/ + trainer hot paths carry no unpragma'd ad-hoc
    metric sites (the sanctioned timer-lap buffers carry the
    documented pragma)."""
    from roc_tpu.analysis.driver import all_rule_names, is_trace_rule
    assert "metric-adhoc" in all_rule_names()
    assert not is_trace_rule("metric-adhoc")
    got = run_ast_lint(_REPO, select=["metric-adhoc"])
    assert got == [], [(f.unit, f.line, f.msg) for f in got]


# ----------------------------------------------------- jaxpr fixtures

def _unit(fn, *args, name="fix", **ctx):
    ctx.setdefault("num_nodes", 64)
    ctx.setdefault("vf_elems", 64 * 16)
    return JaxprUnit(name, jax.make_jaxpr(fn)(*args), **ctx)


def test_jaxpr_f32_upcast_fires_only_in_bf16_path():
    x = jnp.ones((64, 16), jnp.bfloat16)
    u = _unit(lambda a: a.astype(jnp.float32) * 2.0, x,
              compute_dtype="bfloat16")
    got = run_jaxpr_lint([u], select=["jaxpr-f32-upcast"])
    assert _rules(got) == ["jaxpr-f32-upcast"]
    # class-width tensors ([V, C], C << F) stay sanctioned
    small = jnp.ones((64, 4), jnp.bfloat16)
    u2 = _unit(lambda a: a.astype(jnp.float32) * 2.0, small,
               compute_dtype="bfloat16")
    assert not run_jaxpr_lint([u2], select=["jaxpr-f32-upcast"])
    # and an fp32-configured path never arms the rule
    u3 = _unit(lambda a: a.astype(jnp.float32) * 2.0, x,
               compute_dtype="float32")
    assert not run_jaxpr_lint([u3], select=["jaxpr-f32-upcast"])


def test_jaxpr_host_callback_fires():
    def f(x):
        jax.debug.print("x sum {}", x.sum())
        return x * 2
    u = _unit(f, jnp.ones(8))
    got = run_jaxpr_lint([u], select=["jaxpr-host-callback"])
    assert _rules(got) == ["jaxpr-host-callback"]
    assert "debug_print" in got[0].msg


def test_jaxpr_non_donated_fires_on_update_shaped_arg():
    big = jnp.ones((256, 64))
    other = jnp.ones((128, 32))

    def f(a, b):
        return a + 1.0, b.sum()

    u = _unit(jax.jit(f), big, other, donate_min_bytes=1024)
    got = run_jaxpr_lint([u], select=["jaxpr-non-donated"])
    # a's aval matches output 0 and is undonated; b's matches nothing
    # (the matching is aval-level, so distinct shapes isolate it)
    assert len(got) == 1 and "arg 0" in got[0].msg
    # donated: clean
    u2 = _unit(jax.jit(f, donate_argnums=(0,)), big, other,
               donate_min_bytes=1024)
    assert not run_jaxpr_lint([u2], select=["jaxpr-non-donated"])


def test_jaxpr_non_donated_value_and_grad_recognized():
    """The rule's one known false positive, fixed at the rule (the
    retired tail_grad baseline entry): a (scalar value, grads...)
    jaxpr's grad-shaped output is a COTANGENT of its primal argument,
    not an update of it — the caller still needs the primal for the
    optimizer apply, so donation is not the fix."""
    w = jnp.ones((64, 32))

    def value_and_grad_step(params, x):
        return jax.value_and_grad(
            lambda p: (x @ p).sum())(params)

    u = _unit(jax.jit(value_and_grad_step), w, jnp.ones((16, 64)),
              donate_min_bytes=1024)
    assert not run_jaxpr_lint([u], select=["jaxpr-non-donated"])

    # an update-style step (no leading scalar) is judged as before
    def update_step(params, x):
        g = jax.grad(lambda p: (x @ p).sum())(params)
        return params - 0.1 * g

    u2 = _unit(jax.jit(update_step), w, jnp.ones((16, 64)),
               donate_min_bytes=1024)
    got = run_jaxpr_lint([u2], select=["jaxpr-non-donated"])
    assert len(got) == 1 and "arg 0" in got[0].msg

    # value-and-grad whose PRIMAL arg also matches the scalar-first
    # output list via a LATER output is still exempt, but one that
    # echoes an arg as output 0's aval is not value-and-grad shaped
    def echo_first(params, x):
        return params * 2.0, (x @ params).sum()

    u3 = _unit(jax.jit(echo_first), w, jnp.ones((16, 64)),
               donate_min_bytes=1024)
    assert run_jaxpr_lint([u3], select=["jaxpr-non-donated"])


def test_jaxpr_non_donated_scalar_first_param_update_still_fires():
    """A scalar PARAM that flattens first (learned-eps style) must not
    disarm the rule for an update step: the echoed output prefix
    (scalar head + first weight) mirrors the input prefix in order,
    which value_and_grad's (loss, cotangents...) never does unless the
    primal's first TWO leaves are scalar."""
    params = {"eps": jnp.ones(()), "w": jnp.ones((64, 32))}

    def update_step(params, x):
        g = jax.grad(
            lambda p: ((x @ p["w"]).sum() * p["eps"]))(params)
        return jax.tree_util.tree_map(lambda pp, gg: pp - 0.1 * gg,
                                      params, g)

    u = _unit(jax.jit(update_step), params, jnp.ones((16, 64)),
              donate_min_bytes=1024)
    got = run_jaxpr_lint([u], select=["jaxpr-non-donated"])
    assert len(got) == 1 and "[64, 32]" in got[0].msg

    # ...while value_and_grad over the SAME scalar-first params keeps
    # its exemption (output 1 is the scalar's cotangent, which does
    # not track input leaf 1)
    def vag_step(params, x):
        return jax.value_and_grad(
            lambda p: ((x @ p["w"]).sum() * p["eps"]))(params)

    u2 = _unit(jax.jit(vag_step), params, jnp.ones((16, 64)),
               donate_min_bytes=1024)
    assert not run_jaxpr_lint([u2], select=["jaxpr-non-donated"])


def test_baseline_is_empty():
    """The tree lints clean with an EMPTY findings baseline — the last
    entry (the tail_grad value-and-grad false positive) is retired at
    the rule, not absorbed."""
    data = json.load(open(
        os.path.join(_REPO, "scripts", "lint_baseline.json")))
    assert data["findings"] == []


def test_jaxpr_collective_materialize_fires():
    from jax.sharding import Mesh, PartitionSpec as P
    from roc_tpu.parallel.distributed import _shard_map
    mesh = Mesh(np.asarray(jax.devices()), ("parts",))
    x = jnp.ones((64, 16))

    def body(xb):
        full = jax.lax.all_gather(xb, "parts", axis=0, tiled=True)
        return jax.lax.psum(full, "parts")

    sm = _shard_map(body, mesh, P("parts"), P())
    parts = len(jax.devices())
    # shard_map body avals are block-local: vf_elems is PER-DEVICE
    per_dev = (64 * 16) // parts
    u = _unit(jax.jit(sm), x, halo="gather", vf_elems=per_dev,
              mesh_parts=parts)
    got = run_jaxpr_lint([u], select=["jaxpr-collective-materialize"])
    # the psum of the FULL gathered [V, F] fires; the whole-region
    # gather itself is the designed halo and stays sanctioned
    assert len(got) == 1 and "psum" in got[0].msg
    # under halo='ring' the [V, F] gather itself is also a violation
    u2 = _unit(jax.jit(sm), x, halo="ring", vf_elems=per_dev,
               mesh_parts=parts)
    got2 = run_jaxpr_lint([u2],
                          select=["jaxpr-collective-materialize"])
    assert len(got2) == 2
    assert any("ring" in f.msg for f in got2)


def test_jaxpr_int32_overflow_fires():
    def f():
        idx = jax.lax.iota(jnp.int32, 1 << 16)
        return idx * jnp.int32(1 << 16)      # bound ~2^32 in int32

    got = run_jaxpr_lint([_unit(f)], select=["jaxpr-int32-overflow"])
    assert _rules(got) == ["jaxpr-int32-overflow"]
    assert "mul" in got[0].msg

    def ok():
        idx = jax.lax.iota(jnp.int32, 1 << 16)
        return idx * jnp.int32(4)

    assert not run_jaxpr_lint([_unit(ok)],
                              select=["jaxpr-int32-overflow"])


# ------------------------------------------------------- HLO fixtures

_HLO = """\
ENTRY %main.1 (p0: f32[512,128]) -> f32[512,128] {
  %big = f32[512,128]{0,1} transpose(f32[512,128]{1,0} %p0)
  %tiny = f32[8,4]{0,1} transpose(f32[4,8]{1,0} %q)
  %bits = u32[512,128]{1,0} copy(u32[512,128]{1,0} %rng_state)
  ROOT %r = f32[512,128]{1,0} copy(f32[512,128]{0,1} %big)
}
%fused_computation.2 (param_0: f32[512,128]) -> f32[512,128] {
  %infused = f32[512,128]{1,0} copy(f32[512,128]{0,1} %param_0)
}
"""


def test_hlo_large_copy_fires_outside_fusions():
    got = check_large_copy("hlo:fix", _HLO, copy_min_elems=512 * 128)
    ops = sorted(f.key.split("|")[0] for f in got)
    # the entry transpose + copy; the fused-body copy, the tiny
    # transpose and the integer (RNG state) copy stay silent
    assert ops == ["copy", "transpose"]


def test_hlo_bytes_model_fires_past_factor():
    got = check_bytes_model("hlo:fix", 1e9, 1000, factor=32.0)
    assert _rules(got) == ["hlo-bytes-model"]
    assert not check_bytes_model("hlo:fix", 3.1e4, 1000, factor=32.0)
    # missing introspection is not a finding
    assert not check_bytes_model("hlo:fix", None, 1000)
    assert not check_bytes_model("hlo:fix", 1e9, None)


# -------------------------------------------- built-trainer fixtures

def test_partition_imbalance_rule():
    """[partition-imbalance] fires past max/mean 1.5 on >1 device,
    stays silent on balanced splits and single devices, and carries a
    ratchetable fingerprint."""
    from roc_tpu.analysis.driver import check_partition_imbalance
    got = check_partition_imbalance("partition:fix",
                                    [100, 10, 10, 10])
    assert len(got) == 1
    assert got[0].rule == "partition-imbalance"
    assert "3.08" in got[0].msg
    assert got[0].fingerprint == \
        "partition-imbalance|partition:fix|parts=4"
    # balanced: quiet
    assert not check_partition_imbalance("partition:fix",
                                         [10, 11, 10, 10])
    # single device: the straggler IS the device — not a finding
    assert not check_partition_imbalance("partition:fix", [100])
    # empty / zero-edge degenerate inputs never divide by zero
    assert not check_partition_imbalance("partition:fix", [])
    assert not check_partition_imbalance("partition:fix", [0, 0])


def test_partition_imbalance_registered():
    from roc_tpu.analysis.driver import all_rule_names, is_trace_rule
    assert "partition-imbalance" in all_rule_names()
    assert is_trace_rule("partition-imbalance")
    assert is_trace_rule("jaxpr-f32-upcast")
    assert not is_trace_rule("stdout-print")


# ------------------------------------------------- baseline mechanics

def test_baseline_split_and_shrink_only(tmp_path):
    bp = str(tmp_path / "baseline.json")
    save_baseline(bp, ["r|u|a", "r|u|gone"])
    findings = [Finding("r", "u", "m", key="a"),
                Finding("r", "u", "m", key="new")]
    new, old, stale = split_findings(findings, load_baseline(bp))
    assert [f.key for f in new] == ["new"]
    assert [f.key for f in old] == ["a"]
    assert stale == {"r|u|gone"}
    # the ratchet can only shrink: the stale entry is dropped, the new
    # finding is NOT absorbed
    kept = shrink_baseline(bp, findings)
    assert kept == {"r|u|a"}
    assert load_baseline(bp) == {"r|u|a"}


def test_dedupe_keeps_first():
    fs = [Finding("r", "u", "m", key="k"), Finding("r", "u", "m2",
                                                   key="k")]
    assert len(dedupe(fs)) == 1


# ----------------------------------------------- tree + tier wiring

def test_tree_has_zero_unbaselined_findings():
    """Both trainers' step jaxprs (single + 8-virtual-device mesh),
    the model graph, the compiled HLO, and the whole source tree:
    clean modulo scripts/lint_baseline.json."""
    from roc_tpu.analysis.driver import analyze
    findings = analyze(_REPO)
    baseline = load_baseline(
        os.path.join(_REPO, "scripts", "lint_baseline.json"))
    new, _, _ = split_findings(findings, baseline)
    assert not new, "\n".join(f.render() for f in new)


def test_cli_strict_gate():
    """The tier gate: `python -m roc_tpu.analysis --strict` exits 0
    on the tree inside the <90 s CPU budget with all six levels
    (AST/concurrency/jaxpr/HLO/programspace/collective) enabled
    (lint_prints.sh's
    successor — tests/test_obs.py keeps the wrapper covered), and the
    pre-flight budget lines scripts/test.sh surfaces are printed."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.analysis", "--strict"],
        cwd=_REPO, capture_output=True, text=True, timeout=90,
        env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new" in r.stdout
    assert "program budget gin_flat8:" in r.stdout
    assert "program budget sgc_stream:" in r.stdout


def test_cli_ratchet_bites(tmp_path):
    """A planted violation in a scratch tree fails the CLI."""
    _plant(tmp_path, "roc_tpu/leaky.py", "print('oops stdout')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.analysis",
         "--root", str(tmp_path), "--select", "stdout-print"],
        capture_output=True, text=True, timeout=60, env=env)
    assert r.returncode == 1
    assert "leaky.py:1" in r.stdout


def test_cli_update_baseline_shrinks_never_absorbs(tmp_path):
    _plant(tmp_path, "roc_tpu/leaky.py", "print('oops stdout')\n")
    bp = tmp_path / "scripts" / "lint_baseline.json"
    bp.parent.mkdir()
    bp.write_text(json.dumps(
        {"version": 1,
         "findings": ["jaxpr-non-donated|jaxpr:t|y",
                      "stdout-print|gone|x"]}))
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.analysis",
         "--root", str(tmp_path), "--select", "stdout-print",
         "--update-baseline"],
        capture_output=True, text=True, timeout=60, env=env)
    # the stale entry of the rule that RAN is dropped; the trace-rule
    # entry is untouched (its rule never ran in this --select pass);
    # the live violation is NOT absorbed -> still fails
    assert r.returncode == 1
    assert json.loads(bp.read_text())["findings"] == \
        ["jaxpr-non-donated|jaxpr:t|y"]


def test_cli_selective_run_reports_no_phantom_stale(tmp_path):
    """An AST-only --select run must not call trace-rule baseline
    entries stale (the lint_prints.sh wrapper would otherwise nag on
    every invocation)."""
    _plant(tmp_path, "roc_tpu/clean.py", "x = 1\n")
    bp = tmp_path / "scripts" / "lint_baseline.json"
    bp.parent.mkdir()
    bp.write_text(json.dumps(
        {"version": 1, "findings": ["jaxpr-non-donated|jaxpr:t|y"]}))
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "roc_tpu.analysis",
         "--root", str(tmp_path), "--select", "stdout-print",
         "--strict"],
        capture_output=True, text=True, timeout=60, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 stale" in r.stdout
    assert "no longer fire" not in r.stdout
