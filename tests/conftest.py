"""Test harness: hermetic, no-TPU-required tier the reference lacks (SURVEY §4.4).

All tests run on a virtual 8-device CPU mesh so multi-chip sharding paths
(parallel/shuffle) are exercised without hardware. Set SRJT_TEST_TPU=1 to run
the same suite against real devices.
"""

import os

if os.environ.get("SRJT_TEST_TPU", "0") != "1":  # srjt-lint: allow-environ(bootstrap: JAX_PLATFORMS must be set BEFORE any package import, and importing utils/knobs imports the package which imports jax)
    # The environment is what every child process inherits; the live
    # config is mirrored in case a pytest plug-in imported jax first.
    os.environ["JAX_PLATFORMS"] = "cpu"
    # No persistent compile cache under test: six xdist workers and the
    # sidecar workers they spawn must not write <checkout>/.jax_cache.
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)

import fcntl  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


# ---------------------------------------------------------------------------
# the native library: built before any test file is imported
# ---------------------------------------------------------------------------
# runtime.native_lib() keeps its first answer for the life of the process
# and every xdist worker imports every test file at collection, so "is
# libsrjt.so there" has to be settled before the workers start: the
# controller builds it in pytest_configure and the `native` fixture below
# is the one gate the tests go through.

_NATIVE_ABSENT = "native library absent"  # the gate's skip reason starts with this
_native_status = "not looked for"


def _build_native():
    """Bring native/build/libsrjt.so up to date and return the header's
    line. No toolchain is a supported platform (the native tests skip); a
    toolchain whose build fails, or whose library does not load, is a
    usage error."""
    build = os.path.join(REPO, "native", "build")
    so = os.path.join(build, "libsrjt.so")
    missing = " or ".join(t for t in ("cmake", "ninja") if shutil.which(t) is None)
    if missing and not os.path.exists(so):
        return f"{_NATIVE_ABSENT}: no {missing} and no prebuilt {so}"
    if missing:
        how = f"prebuilt, no {missing} to refresh it"
    else:
        os.makedirs(build, exist_ok=True)
        with open(os.path.join(build, ".pytest-build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)  # two pytest runs in one tree do not build at once
            was = os.path.getmtime(so) if os.path.exists(so) else None
            cmds = [["ninja", "-C", build]]  # every run: a no-op when fresh, and no stale .so survives
            if not os.path.exists(os.path.join(build, "build.ninja")):
                cmds.insert(0, ["cmake", "-S", os.path.join(REPO, "native"), "-B", build, "-G", "Ninja"])
            for cmd in cmds:
                done = subprocess.run(cmd, capture_output=True, text=True)
                if done.returncode != 0:
                    out = done.stdout + done.stderr
                    at = max(out.find("FAILED:"), 0)  # ninja's first failure, not the jobs that ended after it
                    raise pytest.UsageError(
                        f"building the native library failed ({' '.join(cmd)}):\n{out[at:at + 4000]}"
                    )
        how = "found up to date" if was == os.path.getmtime(so) else "built now"
    from spark_rapids_jni_tpu import runtime

    if runtime.native_available():
        return f"native library {how}: {so}"
    if missing:
        return f"{_NATIVE_ABSENT}: the prebuilt {so} does not load and there is no {missing}"
    raise pytest.UsageError(f"{so} was built but does not load (runtime.native_lib())")


def pytest_configure(config):
    global _native_status
    if not hasattr(config, "workerinput"):  # xdist starts its workers after this
        _native_status = _build_native()


def pytest_report_header(config):
    return _native_status


def pytest_terminal_summary(terminalreporter):
    if _native_status.startswith(_NATIVE_ABSENT):
        cost = sum(
            _NATIVE_ABSENT in str(rep.longrepr)
            for rep in terminalreporter.stats.get("skipped", [])
        )
        terminalreporter.write_line(f"{_native_status}: {cost} tests skipped for it")


@pytest.fixture(scope="session")
def native():
    """The one gate of the tests that need libsrjt.so: the bound runtime
    module, or a skip where the platform has no toolchain."""
    from spark_rapids_jni_tpu import runtime

    if not runtime.native_available():
        pytest.skip(f"{_NATIVE_ABSENT} (the run's header says why)")
    return runtime


# ---------------------------------------------------------------------------
# process-global state: what "pristine" is, written once
# ---------------------------------------------------------------------------


def _scrub_worker_namespace(metrics):
    """The in-process worker (tests/_inproc.py) runs ``_handle_conn`` in
    THIS process, so its always-on request COUNTERS share the registry
    with the ``sidecar.worker.*`` GAUGES other files fold remote
    snapshots into — a type clash the two-process deployment can never
    hit. Dropping the namespace serves both orders."""
    reg = metrics.registry()
    with reg._lock:
        for name in [n for n in reg._metrics if n.startswith("sidecar.worker.")]:
            del reg._metrics[name]


def reset_process_state():
    """Put every process-global switch of the package back where a fresh
    process has it (the chaos switches off, whatever the environment
    armed: the tiers of ci/premerge.sh that arm them read the knobs
    themselves) and return what was found out of place, one phrase each.
    Singletons that are caches (memgov's catalog, the plan and subresult
    caches, the sketches, the journal) are dropped and not reported,
    except an out-of-core partition entry nobody released. Lazy
    sys.modules look-ups: a file that never touched a subsystem does not
    import it here."""

    def mod(name):
        return sys.modules.get("spark_rapids_jni_tpu." + name)

    dirty = []

    def put_back(what, now, pristine, restore):
        if now != pristine:
            dirty.append(f"{what} {now!r} (a fresh process has {pristine!r})")
        restore()

    knobs = mod("utils.knobs")
    if (m := mod("utils.faultinj")) is not None:
        put_back("fault injection enabled", m.is_enabled(), False, m.disable)
    if (m := mod("utils.retry")) is not None:
        put_back("retry enabled", m.is_enabled(), False, m.disable)
        m.reset_stats()
    if (m := mod("utils.deadline")) is not None:
        put_back("default deadline budget", m.default_budget(), None,
                 lambda: m.set_default_budget(None))
    if (m := mod("sidecar")) is not None and m._BREAKER is not None:
        br, keys = m._BREAKER, ("state", "threshold", "cooldown_s")
        # a fresh one reads the knobs, as breaker() did
        fresh = mod("utils.deadline").CircuitBreaker(br.name).snapshot()
        now = br.snapshot()
        put_back("sidecar breaker", {k: now[k] for k in keys}, {k: fresh[k] for k in keys},
                 lambda: br.configure(threshold=fresh["threshold"], cooldown_s=fresh["cooldown_s"]))
    if (m := mod("parallel.shuffle")) is not None:
        br = m.exchange_breaker()
        opened = sorted(a for a, snap in br.snapshot().items() if snap["state"] != "closed")
        put_back("exchange breakers not closed:", opened, [], br.reset)
    if (m := mod("utils.tracing")) is not None:
        env = knobs.get_bool("SRJT_TRACE_ENABLED")
        put_back("tracing enabled", m.is_enabled(), env, lambda: m.set_enabled(env))
    if (m := mod("utils.metrics")) is not None:
        env = knobs.get_bool("SRJT_METRICS_ENABLED")
        put_back("metrics enabled", m.is_enabled(), env, m.enable if env else m.disable)
        _scrub_worker_namespace(m)
    if (m := mod("memgov")) is not None:
        # every path of an out-of-core plan releases its partition entries
        # (OutOfCorePlan._release): a survivor is leaked spill bytes plus a
        # stale checkpoint a later run could wrongly resume from
        left = m._catalog.kind_stats("partition") if m._catalog is not None else (0, 0)
        put_back("out-of-core partition catalog (entries, bytes)", left, (0, 0), m.reset)
        m._enabled = m._env_enabled()
    for name in ("cache", "plan.stats", "serve.journal"):
        if (m := mod(name)) is not None:
            m.reset()
    return dirty


@pytest.fixture(autouse=True, scope="module")
def _file_leaves_process_state_pristine(request):
    """What makes a worker's next file independent of its last one,
    whatever --dist dealt: pristine state before a file's first test, and
    after its last one a tripwire in the style of the session ones below
    — what the file left out of place is put back and then fails the
    file's teardown by name. Mend a finding in the test that made it
    (the ``clean_state`` fixture, or try/finally)."""
    reset_process_state()
    yield
    dirty = reset_process_state()
    assert not dirty, f"{request.module.__name__} left process-global state behind: " + "; ".join(dirty)


@pytest.fixture
def clean_state():
    """Pristine process state around ONE test; the files whose tests arm
    fault injection, retry, budgets or breakers opt in with
    ``pytestmark = pytest.mark.usefixtures("clean_state")``."""
    reset_process_state()
    yield
    reset_process_state()


@pytest.fixture
def own_span_log(clean_state, tmp_path):
    """Tracing off (the premerge trace tier arms it process-wide; a test
    scopes it with ``tracing.enabled()``), a fresh flight recorder and a
    span log of the test's own under tmp_path. The env-configured base
    (that tier's artifacts path) is put back afterwards, so the real-pool
    acceptance — which uses the env path on purpose — still archives its
    spans; ``clean_state`` puts the arming back."""
    from spark_rapids_jni_tpu.utils import trace_sink, tracing

    prev_base = trace_sink.log_path()
    tracing.set_enabled(False)
    trace_sink.reset_for_tests()
    trace_sink.set_log_path(str(tmp_path / "spans.jsonl"))
    yield
    trace_sink.reset_for_tests()
    trace_sink.set_log_path(prev_base)


@pytest.fixture(autouse=True, scope="session")
def _assert_no_arena_slab_leak():
    """ISSUE 6 leak tripwire: every slab-arena memfd opened during the
    session must be closed (SidecarPool.shutdown / set_arena / explicit
    ArenaSlab.close) by session end — an open slab is leaked pinned
    host pages plus a leaked fd. Lazy sys.modules lookup: runs only
    when the suite actually touched the pool."""
    yield
    import sys as _sys

    pool_mod = _sys.modules.get("spark_rapids_jni_tpu.sidecar_pool")
    if pool_mod is not None:
        leaked = pool_mod.open_slab_count()
        assert leaked == 0, (
            f"{leaked} arena slab(s) leaked past session teardown: "
            + "; ".join(pool_mod.arena_leak_report())
        )


@pytest.fixture(autouse=True, scope="session")
def _assert_no_scheduler_thread_leak():
    """ISSUE 8 leak tripwire (mirrors the slab-leak check): every serve
    Scheduler started during the session must have joined all its
    dispatch-slot threads (Scheduler.shutdown) by session end — a live
    scheduler is leaked daemon threads still able to dispatch queries
    into torn-down fixtures. Lazy sys.modules lookup: runs only when
    the suite actually touched the serving layer."""
    yield
    import sys as _sys
    import threading as _threading

    serve_mod = _sys.modules.get("spark_rapids_jni_tpu.serve")
    if serve_mod is not None:
        serve_mod.shutdown_scheduler(drain=False, timeout_s=10.0)
        leaked = serve_mod.live_scheduler_count()
        assert leaked == 0, (
            f"{leaked} serve scheduler(s) leaked past session teardown: "
            + "; ".join(serve_mod.leak_report())
        )
        stragglers = [
            t.name for t in _threading.enumerate()
            if t.name.startswith("srjt-serve-") and t.is_alive()
        ]
        assert not stragglers, (
            f"serve dispatch threads leaked past session teardown: "
            f"{stragglers}"
        )


@pytest.fixture(autouse=True, scope="session")
def _assert_no_spill_file_leak():
    """ISSUE 20 leak tripwire (the spill-file lifecycle satellite):
    the test session must leave the spill dir empty — every disk-spilled
    frame (and its durable manifest sidecar) written during the session
    is unlinked by catalog close/unregister/re-materialization by
    session end. A surviving .frm is leaked disk bytes no process will
    reclaim until the next manifest-armed startup sweep. Lazy
    sys.modules lookup: runs only when the suite touched memgov."""
    yield
    import glob as _glob
    import sys as _sys
    import tempfile as _tempfile

    memgov_mod = _sys.modules.get("spark_rapids_jni_tpu.memgov")
    if memgov_mod is None:
        return
    # close any surviving catalog first: its own teardown is the
    # mechanism under test, not the tripwire's job to replicate
    memgov_mod.reset()
    dirs = {os.path.join(_tempfile.gettempdir(), f"srjt-spill-{os.getpid()}")}
    spill_dir = os.environ.get("SRJT_SPILL_DIR")  # srjt-lint: allow-environ(session-teardown tripwire: knobs may already be monkeypatch-reverted; the raw env var is exactly what the CI tier armed)
    if spill_dir:
        dirs.add(spill_dir)
    leaked = []
    for d in dirs:
        leaked += _glob.glob(os.path.join(d, "*.frm"))
        leaked += _glob.glob(os.path.join(d, "*.mf"))
    assert not leaked, (
        f"{len(leaked)} spill file(s) leaked past session teardown: "
        f"{sorted(leaked)[:10]}"
    )


# ---------------------------------------------------------------------------
# premerge fast tier (VERDICT r3 item 9)
# ---------------------------------------------------------------------------
# The full hermetic suite takes ~25 min on this 1-core box; ci/premerge.sh
# runs `-m "not slow"` (<~8 min) and ci/nightly.sh runs everything. The
# set below is the measured top of the duration report (>=10 s each;
# calibrated round 4, re-calibrated round 8 when the accumulated tail
# pushed the fast tier past the 870 s harness ceiling — ~345 s moved
# out); a renamed test silently drops back into the fast tier, which
# is the safe failure mode.
_SLOW_TESTS = {
    # round-8 re-calibration: the >=10 s tail accumulated since round 4
    # (tpcds distributed/oracle pairs, decimal128 long multiplies, the
    # chaos parity storm, ragged encode parity, the two-process
    # exchange chaos acceptance — the last two still run premerge in
    # their dedicated env-armed tiers, everything runs nightly)
    "test_tpcds_queries.py::TestQ94::test_distributed_identical",
    "test_tpcds_queries.py::TestQ94::test_matches_exact_oracle",
    "test_tpcds_queries.py::TestQ7::test_distributed_bit_identical",
    "test_tpcds_queries.py::TestQ7::test_matches_exact_oracle",
    "test_tpcds_queries.py::TestQ19::test_distributed_bit_identical",
    "test_tpcds_queries.py::TestQ98WindowRatio::test_matches_oracle",
    "test_tpcds_queries.py::TestReportingShapes::"
    "test_q52_distributed_bit_identical",
    "test_models.py::TestQ55::test_q55_distributed_matches_single_chip",
    "test_decimal_utils.py::test_overflow_mult",
    "test_decimal_utils.py::test_simple_neg_multiply",
    "test_decimal_utils.py::test_null_propagation",
    "test_chaos.py::test_chaos_parity_retryable_storm",
    "test_ragged_bytes.py::test_pallas_kernels_interpret_parity",
    "test_ragged_bytes.py::test_padded_vs_scatter_encode_parity",
    "test_data_plane.py::TestTcpExchangeTwoProcess::"
    "test_two_process_groupby_bit_identical_under_chaos",
    # srjt-cluster (ISSUE 16): the 4-process chaos acceptance, the
    # world-4 topology bit-identity pair, and the in-process failover
    # rendezvous all burn heartbeat/retry wall-clock by design;
    # ci/premerge.sh runs the whole file env-armed in the dedicated
    # cluster tier (no slow filter there), nightly runs them too
    "test_cluster.py::TestClusterChaosFourRank::"
    "test_four_rank_groupby_survives_rank_kill",
    "test_cluster.py::TestTopology::test_tree_equals_all_to_all_world4",
    "test_cluster.py::TestDistributedPlanQuery::"
    "test_q55x4_bit_identical_with_dead_rank",
    "test_cluster.py::TestRecovery::"
    "test_exchange_failover_bit_identical_in_process",
    "test_table_ops.py::test_distributed_groupby_table_int_keys",
    # the hang-storm acceptance burns ~6 budget expiries of wall-clock
    # by design; ci/premerge.sh runs it env-armed in the dedicated
    # deadline tier (no slow filter there), nightly runs it too
    "test_deadline.py::TestChaosHangStorm::"
    "test_every_query_completes_or_raises_deadline_exceeded_in_budget",
    "test_cast_decimal.py::test_edges",
    "test_cast_decimal.py::test_type_dispatch_by_precision",
    "test_concurrency.py::test_concurrent_executor_threads_isolated",
    "test_decimal_utils.py::test_large_pos_multiply_ten_by_ten",
    "test_decimal_utils.py::test_simple_neg_multiply_one_by_one",
    "test_decimal_utils.py::test_simple_pos_multiply_one_by_one",
    "test_decimal_utils.py::test_simple_pos_multiply_one_by_zero",
    "test_decimal_utils.py::test_simple_pos_multiply_zero_by_neg_one",
    "test_decimal_utils.py::test_spark_compat_multiply",
    "test_f64acc.py::TestDD::test_exact_f32_values_roundtrip_exactly",
    "test_f64acc.py::TestDD::test_mod",
    "test_f64acc.py::TestDD::test_roundtrip_bits",
    "test_f64acc.py::TestExactMean::test_correctly_rounded_mean",
    "test_f64acc.py::TestExactSum::test_bit_identical_small_span",
    "test_f64acc.py::TestExactSum::test_wide_span_relative_bound",
    "test_graft_entry.py::test_dryrun_multichip_from_unforced_process",
    # the memgov squeeze/escalation tier compiles several per-capacity
    # exchange programs and spawns a sidecar worker; ci/premerge.sh runs
    # the whole file env-armed in the dedicated low-budget tier (no slow
    # filter there), nightly runs it too
    "test_memgov.py::TestShuffleEscalation::"
    "test_escalation_that_cannot_fit_raises_retryable",
    "test_memgov.py::TestShuffleEscalation::"
    "test_escalation_admitted_under_ample_budget",
    "test_memgov.py::TestSqueeze::"
    "test_groupby_squeeze_spills_and_splits_interleave",
    "test_memgov.py::TestSqueeze::test_q1_bit_identical_under_squeeze",
    "test_memgov.py::test_sidecar_arena_registers_with_catalog",
    "test_models.py::TestFusedPipelines::test_q1_fused_matches_op_tier",
    "test_models.py::TestFusedPipelines::test_q6_fused_matches_op_tier",
    "test_models.py::TestTpcds::test_q95_matches_pandas",
    "test_models.py::TestTpch::test_q1_exact_f64_adversarial_magnitudes",
    "test_models.py::TestTpch::test_q1_matches_pandas",
    "test_native_columnar.py::test_cast_to_decimal_matches_python_op",
    "test_native_columnar.py::test_decimal128_native_matches_python[mul--1]",
    "test_native_columnar.py::test_decimal128_native_matches_python[mul--20]",
    "test_native_columnar.py::test_decimal128_native_matches_python[mul--6]",
    "test_operators.py::test_full_join_string_keys_matches_pandas",
    "test_parquet_reader.py::test_deep_nesting_row_groups",
    "test_parquet_reader.py::test_multiple_row_groups",
    "test_ragged_bytes.py::TestRaggedCompact::test_aligned_and_unaligned_mix",
    "test_regex.py::test_replace_re[\\d+-#]",
    "test_row_conversion.py::test_grouped_decode_matches_per_column",
    "test_row_conversion.py::test_roundtrip_wide",
    "test_sidecar.py::test_convert_to_rows_dispatches_device_and_matches_host",
    # the real-subprocess pool tier spawns 2-3 jax workers each;
    # ci/premerge.sh runs the whole file env-armed in the dedicated
    # crash-storm tier (no slow filter there), nightly runs them too
    # the chaos-under-load serving acceptance runs 40 concurrent TPC
    # queries under a retryable+reject storm (and the pipeline
    # submission test pays a q6 compile); ci/premerge.sh runs the
    # whole file env-armed in the dedicated serve tier (no slow filter
    # there), nightly runs it too
    "test_serve.py::TestChaosUnderLoad::"
    "test_storm_while_serving_yields_bit_identical_results",
    "test_serve.py::TestSubmit::test_compiled_pipeline_is_submittable",
    "test_sidecar_pool.py::TestRealWorkerPool::"
    "test_q1_bit_identical_through_kill9_failover",
    "test_sidecar_pool.py::TestRealWorkerPool::"
    "test_crash_and_corrupt_storm_survives",
    "test_table_ops.py::test_distributed_join_semi_anti[left_anti]",
    "test_table_ops.py::test_distributed_join_semi_anti[left_semi]",
    "test_table_ops.py::test_distributed_join_string_key",
    "test_table_ops.py::test_memory_budget_split_retry",
    "test_table_ops.py::test_q95_distributed_matches_single_chip",
    # the plan-compiler oracle tier's heavy tail (each test pays one
    # or more fused-pipeline XLA compiles; the 5-8 s trio rides along
    # because round 14 measured the fast tier at 842 s of the 870 s
    # harness ceiling — margin beats calibration purity there);
    # ci/premerge.sh runs the whole file env-armed in the dedicated
    # compiler tier (no slow filter there), nightly runs it too
    "test_plan_queries.py::TestRollupHaving::test_q27_rollup_matches_oracle",
    "test_plan_queries.py::TestSetOpsExists::test_q38_intersect_chain",
    "test_plan_queries.py::TestDecorrelation::test_q1_matches_oracle",
    "test_plan_queries.py::TestFusedStars::test_q43_case_pivot_matches_oracle",
    "test_plan_queries.py::TestFusedStars::test_q26_matches_exact_oracle",
    "test_plan_queries.py::TestSetOpsExists::test_q69_exists_chain_matches_oracle",
    "test_plan_queries.py::TestWindowRatio::test_q20_matches_oracle",
    # srjt-cbo (ISSUE 19): the mass-green campaign's oracle tier (each
    # test pays a fused-pipeline compile; measured 104 s total) and the
    # OOC model-chosen-K acceptance (pays two q1-shape executions);
    # ci/premerge.sh runs both files env-armed in their dedicated
    # compiler/ooc tiers (no slow filter there), nightly runs them too
    "test_plan_queries.py::TestCboCampaign::test_q8_zip_intersect_matches_oracle",
    "test_plan_queries.py::TestCboCampaign::test_q9_bucketed_case_matches_oracle",
    "test_plan_queries.py::TestCboCampaign::test_q10_or_exists_matches_oracle",
    "test_plan_queries.py::TestCboCampaign::test_q15_zip_band_star_matches_oracle",
    "test_plan_queries.py::TestCboCampaign::test_q28_band_aggregates_match_oracle",
    "test_plan_queries.py::TestCboCampaign::test_q30_state_decorrelation_matches_oracle",
    "test_plan_queries.py::TestCboCampaign::test_q32_catalog_excess_discount_matches_oracle",
    "test_plan_queries.py::TestCboCampaign::test_q34_having_band_matches_oracle",
    "test_plan_queries.py::TestCboCampaign::test_q35_state_demo_stats_match_oracle",
    "test_plan_queries.py::TestCboCampaign::test_q39_std_over_mean_matches_oracle",
    "test_ooc.py::TestCostModelPartitions::test_model_chosen_k_overhead_bounded",
    # srjt-durable (ISSUE 20): the kill -9 acceptance spawns a child
    # coordinator (jax import + two plan compiles) and SIGKILLs it;
    # ci/premerge.sh covers the restart posture in the dedicated
    # restart tier (bench_restart-driven), nightly runs this too
    "test_durable.py::TestKillNineAcceptance::"
    "test_restart_answers_journaled_queries_bit_identical",
}


# parametrized ids with regex metacharacters escape unpredictably in
# nodeids — match those families by prefix instead of exact id
_SLOW_PREFIXES = (
    "test_regex.py::test_replace_re[",
    # round-8: the java-semantics split family runs 9-16 s per pattern
    "test_regex.py::test_split_re_vs_java_semantics[",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        nodeid = item.nodeid.replace("tests/", "")
        if nodeid in _SLOW_TESTS or nodeid.startswith(_SLOW_PREFIXES):
            item.add_marker(pytest.mark.slow)
