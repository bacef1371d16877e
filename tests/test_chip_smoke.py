"""``chip_smoke.py`` off the chip: it must refuse to run without a TPU, and
its runner must turn a failed phase into a non-zero exit.  What the phases
themselves do is only provable on the chip (the script is that proof)."""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolves annotations there
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu():
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith(("TFS_", "XLA_FLAGS"))
    }
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, SCRIPT],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout == ""  # no phase line, no verdict


def test_runner_exit_status_follows_the_phases(capsys):
    smoke = _load()
    ran = []

    def good(ctx, sz):
        ran.append("good")
        return {"answer": 42}

    def bad(ctx, sz):
        ran.append("bad")
        raise RuntimeError("boom")

    ctx = {"platform": "cpu", "full_width": False}
    assert smoke.run([("a", good), ("b", good)], smoke.Sizes(), ctx) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l.get("phase") for l in lines] == ["a", "b", "summary", None]
    assert lines[0]["ok"] and lines[0]["answer"] == 42
    assert lines[-2]["failed"] == []
    # the verdict is read strictly: exactly these keys, nothing beside them
    assert set(lines[-1]) == {"ok", "device"} and lines[-1]["ok"] is True
    assert set(lines[-1]["device"]) == {"platform", "kind", "count"}
    assert isinstance(lines[-1]["device"]["count"], int)

    ran.clear()
    rc = smoke.run([("a", good), ("b", bad), ("c", good)], smoke.Sizes(), ctx)
    out = capsys.readouterr()
    lines = [json.loads(l) for l in out.out.splitlines()]
    assert rc == 1
    assert ran == ["good", "bad", "good"]  # later phases still report
    assert [l.get("ok") for l in lines] == [True, False, True, None, False]
    assert "boom" in lines[1]["error"] and lines[-2]["failed"] == ["b"]
    assert set(lines[-1]) == {"ok", "device"}
    assert "RuntimeError: boom" in out.err  # the traceback is kept

    # a subset of the phases never counts as a pass
    assert smoke.run([("a", good)], smoke.Sizes(), ctx, complete=False) == 1
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert lines[-2]["partial"] == ["a"]
    assert lines[-1]["ok"] is False and set(lines[-1]) == {"ok", "device"}
