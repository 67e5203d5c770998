"""A four-chip cell rehearses on four virtual CPU devices, and the CPU
backend takes their number once, before JAX starts. ``test_correct.py``
drives every cell of ``BENCHMARK.json`` in ONE process, so the flag cannot
wait for the q95 module alone: it is set here, after collection and before
the first test runs (nothing under ``bench/`` imports JAX before a
session's set-up), and only where a test of a four-chip cell is among
those selected. ``pytest bench/tests -k q1`` runs on the one device it
always had; the one-chip cells compute on device 0 either way.
"""

import os
import sys

import pytest


def _wants_four_devices(item) -> bool:
    return "q95_mesh" in item.nodeid or "-x4" in item.nodeid


@pytest.hookimpl(trylast=True)  # after -k and -m have deselected
def pytest_collection_modifyitems(config, items):
    if "jax" in sys.modules or not any(_wants_four_devices(i) for i in items):
        return
    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()
