"""chip_smoke.py's own contract, as far as a sandbox without a chip
can hold it: the rehearsal flag runs the script's whole control flow
on a tiny graph and can never print the pass line; without the flag
and without a TPU the script refuses, names the platform it found and
prints no result; alone in a directory it cannot run at all."""

import json
import os
import shutil
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(args, tmp_path, script=_SMOKE, cwd=_REPO):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env["ROC_TPU_FLIGHT_DIR"] = str(tmp_path)
    # the script finds the repository next to itself, never through
    # the caller's environment
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, script] + args, env=env,
                          cwd=cwd, capture_output=True, text=True,
                          timeout=600)


def test_rehearsal_runs_every_phase_and_never_prints_the_pass_line(
        tmp_path):
    out = tmp_path / "out"
    r = _run(["--rehearsal", "--out", str(out)], tmp_path)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    assert "ok" not in line
    assert line["device"]["platform"] == "cpu"
    assert line["layers"] == "602-256-41"
    assert line["native_loaded"] is True
    assert line["last_loss"] < line["first_loss"]
    assert line["cache"]["dir"] == str(tmp_path / "cache")
    assert line["cache"]["new_entries"] > 0
    assert "kernels" not in line
    assert {"step_block_ms", "step_fetch_ms"} <= set(line["barrier"])
    # the artifacts it read back are where it was told to put them
    assert (out / "train_p1.events.jsonl").exists()
    assert (out / "train_p1.metrics.jsonl").exists()


def test_without_a_tpu_it_refuses_and_names_the_platform(tmp_path):
    r = _run(["--out", str(tmp_path / "out")], tmp_path)
    assert r.returncode not in (0, None)
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout.strip() == ""           # no result of any kind
    assert not (tmp_path / "out").exists()  # and nothing was started


def test_alone_in_a_directory_it_fails(tmp_path):
    """chip_smoke.py drives the repository's program; the script by
    itself has nothing to drive and must not pretend otherwise."""
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(_SMOKE, lone / "chip_smoke.py")
    for args in ([], ["--rehearsal"]):
        r = _run(args, tmp_path, script=str(lone / "chip_smoke.py"),
                 cwd=str(lone))
        assert r.returncode != 0, args
        assert "{" not in r.stdout, r.stdout


def test_pass_line_has_exactly_the_contract_keys():
    """The last stdout line of a passing run is what the driver parses:
    ``ok`` and ``device`` only, the device as JAX reports it.  The run's
    findings go on the ``report`` line before it, never into this one."""
    import importlib.util
    from types import SimpleNamespace
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert json.loads(smoke.pass_line([dev])) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    with open(_SMOKE) as f:
        body = f.read()
    # one place prints it, after the report, as main()'s last act
    assert body.count("print(pass_line(") == 1
    assert body.index('print(json.dumps({"report"') \
        < body.index("print(pass_line(")
