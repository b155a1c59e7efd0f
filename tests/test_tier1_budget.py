"""Tier-1 wall-clock budget gate.

The driver runs the fast tier under a hard `timeout -k 10 1470`, six
workers (`/root/TESTS_LAST_RUN.json`, `commands`) — when the suite
outgrows that, the symptom is an opaque SIGTERM mid-run and a count cut
short, not a named failure. This gate turns the limit into a first-class
assertion: conftest.py records the session's wall clock (start to
`pytest_sessionfinish` on the controller) and every test's
setup+call+teardown duration to a JSON ledger at session end, and the
NEXT full run fails here (naming the slowest offenders) if the previous
run's wall clock exceeded the limit. The sum of the durations is
recorded beside it: what one worker would take (ROADMAP D14).

Knobs:
  RAY_TPU_T1_BUDGET_S         limit in seconds (default 1470, matching
                              the driver's timeout)
  RAY_TPU_T1_DURATIONS_FILE   ledger path (default /tmp/_t1_durations.json)

The gate self-skips when the ledger is missing (first run on a box),
was written before the wall clock was recorded, or came from a partial
run (a dev running one file must not trip a whole-suite budget).
"""

import json
import os

import pytest

# A full `-m "not slow"` tier-1 run collects several hundred tests;
# anything far below that is a partial/dev invocation.
MIN_TESTS_FOR_FULL_RUN = 200


def _budget_s() -> float:
    return float(os.environ.get("RAY_TPU_T1_BUDGET_S", "1470"))


def _ledger_path() -> str:
    return os.environ.get("RAY_TPU_T1_DURATIONS_FILE",
                          "/tmp/_t1_durations.json")


def test_tier1_duration_budget():
    path = _ledger_path()
    if not os.path.exists(path):
        pytest.skip("no durations ledger yet (first run on this box)")
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        pytest.skip("durations ledger unreadable")
    count = int(data.get("count", 0))
    if count < MIN_TESTS_FOR_FULL_RUN:
        pytest.skip(f"ledger covers {count} tests — partial run, "
                    f"not a tier-1 session")
    if "wall_s" not in data:
        pytest.skip("ledger predates the recorded wall clock")
    wall, total = float(data["wall_s"]), float(data.get("total_s", 0.0))
    budget = _budget_s()
    slowest = sorted((data.get("tests") or {}).items(),
                     key=lambda kv: -kv[1])[:10]
    lines = "\n".join(f"  {dur:8.2f}s  {nodeid}"
                      for nodeid, dur in slowest)
    assert wall <= budget, (
        f"tier-1 recorded wall clock {wall:.1f}s ({total:.1f}s of tests "
        f"summed over the workers) exceeds the {budget:.0f}s budget "
        f"(RAY_TPU_T1_BUDGET_S) — trim or mark slow the offenders "
        f"before the verify timeout does it for you.\n"
        f"slowest tests last run:\n{lines}")


def test_ledger_shape_roundtrip(tmp_path, monkeypatch):
    """The gate reads exactly what conftest's sessionfinish writes."""
    ledger = tmp_path / "durations.json"
    tests = {f"tests/test_x.py::t{i}": 0.5 for i in range(300)}
    ledger.write_text(json.dumps(
        {"wall_s": 30.0, "total_s": sum(tests.values()),
         "count": len(tests), "tests": tests}))
    monkeypatch.setenv("RAY_TPU_T1_DURATIONS_FILE", str(ledger))
    monkeypatch.setenv("RAY_TPU_T1_BUDGET_S", "100")
    test_tier1_duration_budget()  # 150s of tests in 30s of 100s: passes

    monkeypatch.setenv("RAY_TPU_T1_BUDGET_S", "25")
    with pytest.raises(AssertionError) as ei:
        test_tier1_duration_budget()
    assert "exceeds" in str(ei.value)
    assert "tests/test_x.py::t0" in str(ei.value)


def test_ledger_partial_run_skips(tmp_path, monkeypatch):
    ledger = tmp_path / "durations.json"
    ledger.write_text(json.dumps(
        {"wall_s": 1e9, "total_s": 1e9, "count": 3,
         "tests": {"a": 1.0, "b": 2.0, "c": 3.0}}))
    monkeypatch.setenv("RAY_TPU_T1_DURATIONS_FILE", str(ledger))
    with pytest.raises(pytest.skip.Exception):
        test_tier1_duration_budget()
