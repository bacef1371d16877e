"""Where the persistent compile cache lives (``compile_cache.py``): jax's
own ``JAX_COMPILATION_CACHE_DIR`` places it from outside and nothing in the
package overrides that; entry points fall back to one fixed directory under
the checkout.  jax reads its variable at import, so each case is a fresh
interpreter; the three run side by side."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, sys
import jax
from tensorframes_tpu import compile_cache
explicit = sys.argv[1] if len(sys.argv) > 1 else None
if explicit:
    active = compile_cache.configure(explicit)
    where = compile_cache.cache_dir()
else:
    where = compile_cache.configure_entry_point()
    active = True
print(json.dumps({
    "active": active,
    "cache_dir": where,
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "min_compile_s": jax.config.jax_persistent_cache_min_compile_time_secs,
}))
"""


def _spawn(extra_env, *argv):
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "TFS_COMPILE_CACHE")
    }
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra_env)
    return subprocess.Popen(
        [sys.executable, "-c", _PROBE, *argv],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd="/",
    )


def _result(proc):
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 0, err[-2000:]
    return json.loads(out.splitlines()[-1]), err


def test_cache_placement(tmp_path):
    placed = str(tmp_path / "placed")
    yielding = _spawn(
        {
            "JAX_COMPILATION_CACHE_DIR": placed,
            "TFS_COMPILE_CACHE": str(tmp_path / "knob"),
        },
        str(tmp_path / "explicit"),
    )
    defaults = [_spawn({}), _spawn({})]

    # the env var wins over the knob AND an explicit path; the floors are
    # still lowered, and the loser says so once
    got, err = _result(yielding)
    assert got["active"] and got["jax_dir"] == placed
    assert got["cache_dir"] == placed and got["min_compile_s"] == 0.0
    assert err.count("yields to it") == 1
    assert not (tmp_path / "knob").exists()
    assert not (tmp_path / "explicit").exists()

    # nothing set: one fixed directory under the checkout, every time
    first, _ = _result(defaults[0])
    second, _ = _result(defaults[1])
    want = os.path.join(REPO, ".cache", "jax")
    assert first["jax_dir"] == second["jax_dir"] == want
    assert first["cache_dir"] == want
