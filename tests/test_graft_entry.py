"""Driver-contract tests for ``__graft_entry__``.

The CPU-mesh forcing once lived only under ``__main__`` while a driver
*imports* the module and calls ``dryrun_multichip(8)`` directly.
These tests pin the fixed contract: the module imports light (no jax,
so no backend is initialized on import), and ``dryrun_multichip`` runs
green from a process whose backend cannot host the virtual mesh.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=900,
    )


def test_import_initializes_no_backend():
    # jax itself is preloaded at interpreter startup in this image, so
    # test the functional invariant: importing __graft_entry__ must not
    # *initialize* the backend — the platform must still be switchable
    # afterwards (an initialized backend makes the switch a no-op).
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = _run(
        "import __graft_entry__; "
        "import jax; jax.config.update('jax_platforms', 'cpu'); "
        "assert jax.devices()[0].platform == 'cpu', jax.devices(); "
        "print('LIGHT-IMPORT-OK')",
        env,
    )
    assert proc.returncode == 0, proc.stderr
    assert "LIGHT-IMPORT-OK" in proc.stdout


def test_dryrun_multichip_from_unforced_process():
    # Driver-like process: jax available but NOT an 8-device CPU mesh
    # (here: a single-device CPU backend, standing in for a process
    # that holds the chip, so the test stays hermetic). dryrun_multichip must
    # detect this and re-exec itself with the forced virtual mesh.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = _run(
        "import jax; assert len(jax.devices()) == 1; "
        "import __graft_entry__ as g; g.dryrun_multichip(8); "
        "print('DRIVER-PATH-OK')",
        env,
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "DRIVER-PATH-OK" in proc.stdout
