"""`chip_scan_table.py --tiny`: the chip script's rows and exit code,
rehearsed in the Pallas interpreter (its times mean nothing here)."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _script():
    spec = importlib.util.spec_from_file_location(
        "chip_scan_table", os.path.join(ROOT, "chip_scan_table.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_rehearsal_writes_a_row_a_case_and_a_verdict(tmp_path, capsys):
    out = tmp_path / "scan_table.jsonl"
    assert _script().main(["--tiny", "--out", str(out)]) == 0
    rows = [json.loads(x) for x in out.read_text().splitlines()]
    assert [json.loads(x) for x in capsys.readouterr().out.splitlines()] \
        == rows
    (case, verdict) = rows
    assert case["case"] == "tiny" and case["finite"]
    assert case["o_max_abs_diff"] < 1e-5 > case["state_max_abs_diff"]
    assert {"xla_ms", "kernel_ms"} <= set(case)
    assert verdict["ok"] is True


def test_without_a_tpu_the_table_refuses_to_run(capsys):
    assert _script().main(["--out", os.devnull]) == 1
    assert json.loads(capsys.readouterr().out) == {"ok": False,
                                                   "error": "no TPU"}


def test_every_case_is_a_shape_the_kernel_takes(monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta_rule
    fa = importlib.import_module("ray_tpu.ops.flash_attention")

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    for name, rows, n, heads, real, dtype in _script().CASES:
        x = jax.ShapeDtypeStruct((rows, n, heads, 128), jnp.dtype(dtype))
        assert delta_rule.scan_usable(x, x, x), name
        assert real is None or real < n
