"""The four-chip cell's comparison has been shown to fail: two faults
under the timed path of ``tpcds-sf10-web.q95-x4`` come out as not correct
at the rehearsal size (the float32 control and an altered answer are
``test_correct.py``'s, which runs over every cell), and the rehearsal walks
on four virtual CPU devices.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import control  # noqa: E402
from benchlib import compare, loader  # noqa: E402

CELL = "tpcds-sf10-web.q95-x4"


def verdict(numbers: dict) -> bool:
    return all(c["ok"] for c in compare.judge(numbers, loader.cell(CELL)["config"]["limits"]))


def _recompile(session, plan_of=None, tables=None):
    from spark_rapids_jni_tpu import plan as P

    session.compiled = []
    for name, q in session.queries:
        plan = (plan_of or (lambda q: q.plan(P)))(q)
        plan = P.insert_exchanges(plan, session.mesh.world, sharded=session.config["sharded"])
        session.compiled.append(P.compile_ir(plan, {t: (tables or session.tables)[t] for t in q.TABLES},
                                             name=name, mesh=session.mesh))


def _a_shard_left_out(session):
    """One shard's rows never enter the exchange: the slots of the last
    chip's part of web_sales are marked absent before the plan reads them."""
    import jax.numpy as jnp

    st = session.tables["web_sales"]
    per = st.num_rows // st.n_parts
    keep = jnp.arange(st.num_rows) < per * (st.n_parts - 1)
    _recompile(session, tables=dict(session.tables, web_sales=st.replace(present=st.present & keep)))


def _returns_membership_skipped(session):
    """The plan without its last IN: orders that were never returned count."""
    def plan_of(q):
        from spark_rapids_jni_tpu import plan as P

        total = q.plan(P)
        per_order = total.input
        j2 = per_order.input  # Join(j1, returned, semi)
        return P.Aggregate(P.Aggregate(j2.left, keys=per_order.keys, aggs=per_order.aggs),
                           keys=total.keys, aggs=total.aggs)

    _recompile(session, plan_of=plan_of)


@pytest.mark.parametrize("fault", [_a_shard_left_out, _returns_membership_skipped])
def test_a_fault_under_the_timed_path_is_not_correct(fault):
    r = control.readings(CELL, seed=2_147_483_777, seconds=0.2, rehearse=True, control=False, prepare=fault)
    assert not verdict(r["program"]), r["program"]


def test_the_sound_program_is_correct_on_another_seed():
    r = control.readings(CELL, seed=2_300_104_740, seconds=0.2, rehearse=True, control=True)
    assert verdict(r["program"]), r["program"]
    assert not verdict(r["control"]), r["control"]
    assert r["program"]["exchange.unretried_overflows"] == 0 and r["program"]["mesh.devices_short"] == 0


def test_the_rehearsal_walks_on_four_virtual_devices():
    """A new process with no XLA_FLAGS: the driver asks the CPU backend
    for the cell's four devices itself."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELL, "--seed", "7",
                        "--seconds", "1", "--trace", "1", "--rehearse"],
                       env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["checks_pass"] and last["failed"] == 0
    assert {"exchange_ms", "exec_run_ms", "plan_stage_self_ms", "serve_overhead_ms"} <= set(last["per_layer_read"])
    assert "'count': 4" in p.stderr
