"""``enable_compile_cache``: where the persistent cache goes, and source
paths made independent of where the checkout sits.

Each case runs in a child process on the CPU: turning the persistent cache
on inside the test process would make later compiles warn, and the suite
treats those warnings as errors.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

_CHILD = """
import json, re, jax
from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
got = enable_compile_cache()
rx = jax.config.jax_hlo_source_file_canonicalization_regex
print(json.dumps({"got": got, "checkout": str(CHECKOUT),
                  "dir": jax.config.jax_compilation_cache_dir,
                  "stripped": re.sub(rx, "", str(CHECKOUT / "src" / "a.py")),
                  "other": re.sub(rx, "", "/elsewhere/src/a.py")}))
"""


@pytest.mark.parametrize("env_dir", [None, "given-cache"])
def test_cache_dir_and_source_paths(env_dir, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["checkout"] == str(ROOT)
    want = (str(tmp_path / env_dir) if env_dir is not None
            else str(ROOT / ".jax_cache"))
    assert out["got"] == want and out["dir"] == want
    assert out["stripped"] == os.path.join("src", "a.py")
    assert out["other"] == "/elsewhere/src/a.py"
