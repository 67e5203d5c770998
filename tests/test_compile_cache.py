"""Where the persistent compile cache lives (utils/compile_cache.py).

Checked in child processes pinned to the CPU, so this process's own jax
config (cache off, per conftest.py) is untouched.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import json, jax, spark_rapids_jni_tpu\n"
    "print(json.dumps([jax.config.jax_compilation_cache_dir,"
    " jax.config.jax_persistent_cache_min_compile_time_secs,"
    " jax.config.jax_enable_compilation_cache]))\n"
)


def _probe(extra_env):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])  # [dir, min_secs, enabled]


def test_unset_env_uses_fixed_checkout_dir_in_every_process():
    first = _probe({})
    second = _probe({})
    assert first[0] == os.path.join(REPO, ".jax_cache")
    assert second[0] == first[0]  # no pid, time or tempfile in the path
    assert first[1] == 0.0  # short kernel compiles are kept too


def test_env_dir_is_left_to_jax(tmp_path):
    placed = str(tmp_path / "cache")
    cache_dir, min_secs, _ = _probe({"JAX_COMPILATION_CACHE_DIR": placed})
    assert cache_dir == placed  # jax read it; the code set no other
    assert min_secs == 0.0


@pytest.mark.parametrize("flag,expect", [("false", False), ("true", True)])
def test_jax_enable_switch_is_inherited_by_children(flag, expect):
    """conftest.py exports JAX_ENABLE_COMPILATION_CACHE=false; a child
    (a sidecar worker, say) must come up with the cache off."""
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    assert _probe({"JAX_ENABLE_COMPILATION_CACHE": flag})[2] is expect


def test_configure_sets_no_dir_when_env_places_it(monkeypatch):
    import jax

    from spark_rapids_jni_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.configure() is None
    assert jax.config.jax_compilation_cache_dir == before
