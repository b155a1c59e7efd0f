"""chip_smoke.py without the chip: its phase runner's failure contract,
and a rehearsal of its phase functions at a tiny width on the CPU mesh.

The rehearsal switch lives here, not in the program: chip_smoke.py has no
CPU mode (it fails at phase 0 without a TPU), so these tests call its
phase functions with their own `Sizes` and fake the scheduler's chip
count through TPU_VISIBLE_CHIPS. What only the chip can show — the
compiled kernels, bf16 tolerances, device memory — is what
`python chip_smoke.py` through the chip tool is for.
"""

import dataclasses
import importlib.util
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod   # its train loop pickles by name
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def _lines(buf):
    return [json.loads(x) for x in buf.getvalue().splitlines()]


# -- the runner's failure contract -----------------------------------------

def test_no_accelerator_fails_at_phase_0(chip_smoke, monkeypatch,
                                         tmp_path):
    """JAX_PLATFORMS=cpu: non-zero exit at phase 0 with a clear message
    and a traceback line; no result line."""
    monkeypatch.setattr(chip_smoke, "SCRATCH", str(tmp_path / "s"))
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(tmp_path / "t"))
    buf = io.StringIO()
    assert chip_smoke.main([], out=buf) == 1
    (line,) = _lines(buf)
    assert line["phase"] == "0 device" and line["ok"] is False
    assert "needs a TPU" in line["traceback"]
    assert "Traceback (most recent call last)" in line["traceback"]


@pytest.mark.parametrize("how", ["raises", "check_out_of_tolerance"])
def test_failed_phase_stops_the_run_before_ok(chip_smoke, monkeypatch,
                                              tmp_path, how):
    """A later phase that raises, or measures past its tolerance, prints
    its traceback line, exits non-zero, and nothing follows it: no phase
    after a failure, and never the `{"ok": true, "device": ...}` line."""
    monkeypatch.setattr(chip_smoke, "SCRATCH", str(tmp_path / "s"))
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(tmp_path / "t"))
    ran = []

    def device(rep, chips):
        ran.append("device")
        return {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def kernels(rep, sz):
        ran.append("kernels")
        if how == "raises":
            raise FloatingPointError("boom in the kernel phase")
        rep.check("fwd_rel_err", 0.5, 1e-2)
        rep.check("bwd_dq_rel_err", 1e-3, 2e-2)

    def later(rep, sz):
        ran.append("later")

    monkeypatch.setattr(chip_smoke, "phase_device", device)
    monkeypatch.setattr(chip_smoke, "phase_kernels", kernels)
    monkeypatch.setattr(chip_smoke, "phase_trainer", later)
    monkeypatch.setattr(chip_smoke, "phase_server", later)
    buf = io.StringIO()
    assert chip_smoke.main([], out=buf) == 1
    assert ran == ["device", "kernels"]
    first, last = _lines(buf)
    assert first["phase"] == "0 device" and first["ok"] is True
    assert last["phase"] == "1 kernels" and last["ok"] is False
    if how == "raises":
        assert "FloatingPointError: boom" in last["traceback"]
    else:
        # every measured error is in the line, beside its tolerance
        assert [(c["measured"], c["tolerance"], c["ok"])
                for c in last["checks"]] == [(0.5, 1e-2, False),
                                             (1e-3, 2e-2, True)]
        assert "fwd_rel_err" in last["traceback"]
    assert not any("device" in x and "phase" not in x
                   for x in _lines(buf))


def test_all_phases_pass_prints_exactly_the_result_line(
        chip_smoke, monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "SCRATCH", str(tmp_path / "s"))
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(tmp_path / "t"))
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_device",
                        lambda rep, chips: device)
    for name in ("phase_kernels", "phase_trainer", "phase_server"):
        monkeypatch.setattr(chip_smoke, name, lambda rep, sz: None)
    buf = io.StringIO()
    assert chip_smoke.main([], out=buf) == 0
    assert buf.getvalue().splitlines()[-1] == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}')
    assert [x.get("phase") for x in _lines(buf)] == [
        "0 device", "1 kernels", "2 trainer", "3 server", None]


# -- the rehearsal ---------------------------------------------------------

@pytest.fixture
def rehearsal(chip_smoke, monkeypatch, tmp_path):
    """Tiny sizes, four chips for the scheduler to hand out (the driver
    counts TPU_VISIBLE_CHIPS without touching jax), scratch in tmp."""
    import ray_tpu
    from ray_tpu.models import configs

    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")
    monkeypatch.setattr(chip_smoke, "SCRATCH", str(tmp_path))
    ray_tpu.shutdown()
    # tp=4 must divide the kv heads.
    cfg = dataclasses.replace(configs.tiny_test(), n_kv_heads=4)
    sz = chip_smoke.Sizes(
        train_cfg=cfg, serve_cfg=cfg, batch=8, seq=32, steps=5,
        slots=4, max_seq_len=128, prompt_len=16,
        new_tokens=(6, 3, 6, 3), http_new_tokens=6,
        flash=(1, 128, 4, 4, 16))
    buf = io.StringIO()
    yield chip_smoke.Runner(buf), sz, buf
    ray_tpu.shutdown()


def test_rehearse_trainer_phases(rehearsal, chip_smoke):
    """Phases 4a and 4b (4b is phase 2 plus the comparison): arguments,
    TpuTrainer.fit under ray_tpu.init, the TPU claim, the loop in the
    driver's process, falling losses, state released between phases."""
    run, sz, buf = rehearsal
    sharded = run.phase("4a", chip_smoke.phase_trainer_sharded, sz)
    run.phase("4b", chip_smoke.phase_trainer_reference, sz, sharded)
    a, b = _lines(buf)
    assert a["ok"] and b["ok"]
    assert a["four_chips"]["mesh"] == {"fsdp": 4}
    assert a["four_chips"]["tpu_resources"] == 4.0
    assert b["one_device"]["mesh"] == {}
    assert len(sharded) == sz.steps and sharded[-1] < sharded[0]
    names = {c["name"] for c in a["checks"] + b["checks"]}
    assert {"four_chips_scheduler_tpu_claimed",
            "four_chips_loop_in_driver_process",
            "one_device_loss_fell", "live_bytes_after_release",
            "sharded_vs_one_device_loss_abs_err"} <= names
    # XLA reference attention at this length, and the program agrees.
    assert b["one_device"]["train_step_tpu_custom_calls"] == 0
    assert set(b["one_device"]["attention_dispatch"]) == {
        "reference_no_tpu"}


def test_rehearse_server_phases(rehearsal, chip_smoke):
    """Phase 3 (concurrent submits, the HTTP request through serve.run,
    logits against a plain forward, greedy_generate) and 4c (tp=4
    against one chip) on the virtual CPU mesh."""
    run, sz, buf = rehearsal
    run.phase("3", chip_smoke.phase_server, sz)
    run.phase("4c", chip_smoke.phase_server_sharded, sz)
    three, four = _lines(buf)
    assert three["ok"] and four["ok"]
    assert three["http"]["status"] == 200
    assert three["http"]["pid"] == os.getpid()
    assert three["engine"]["new_tokens"] == list(sz.new_tokens)
    assert three["tokens_vs_forward"]["mismatches"] == 0
    assert three["tokens_vs_forward"]["positions_checked"] > 0
    # Rows of 128 do not fill the kernel's lanes at a head of 16: the XLA
    # code ran, and the phase says so; the engine counted the rows held.
    assert three["engine"]["decode_attn_kernels_traced"] == 0
    assert 0 < three["engine"]["cache_rows_held"] \
        < three["engine"]["cache_rows"]
    names = {c["name"] for c in three["checks"] + four["checks"]}
    assert {"first_token_logits_abs_err", "live_bytes_after_release",
            "decode_blocks_read_the_cache_through_the_kernel",
            "tp4_vs_one_chip_first_token_logits_abs_err",
            "tp4_tokens_vs_forward_match_reference_argmax"} <= names


# -- the compile cache helper ----------------------------------------------

def test_compile_cache_is_placeable_from_outside(monkeypatch):
    """Off on a CPU backend; on an accelerator, at the fixed in-checkout
    path unless JAX_COMPILATION_CACHE_DIR places it, which is then left
    in force untouched; small programs are kept either way."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu._private import compile_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.enable() is None
    assert {k: getattr(jax.config, k) for k in keys} == was
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert compile_cache.enable() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        # What jax itself does at import where the variable is set:
        jax.config.update("jax_compilation_cache_dir", "/placed/outside")
        monkeypatch.setenv(compile_cache.ENV, "/placed/outside")
        assert compile_cache.enable() == "/placed/outside"
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert not os.path.exists("/placed/outside")
