"""chip_flash_table.py without the chip: `--tiny` walks every kind of
reading (block pairs, the run grid, K and V expanded, the mask on every
live block, the parent's kernel, under the crossover; then the same of dq
and dkv) through the Pallas interpreter, so the script still runs when the
chip's minutes are spent on it. Its times mean nothing here."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tiny_rehearsal_writes_every_kind_of_row(tmp_path, capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_flash_table", os.path.join(ROOT, "chip_flash_table.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = tmp_path / "table.jsonl"
    # "The parent" is this tree: a checkout whose kernel takes K and V
    # expanded and knows no `static_offs` is timed the same way.
    assert mod.main(["--tiny", "--out", str(out), "--parent", ROOT]) == 0
    rows = [json.loads(x) for x in out.read_text().splitlines()]
    assert capsys.readouterr().out.count("\n") == len(rows)
    assert {r["what"] for r in rows} == {
        "device", "table_grid", "run_grid", "expanded_before_the_call",
        "mask_on_every_live_block", "parent_kernel", "under_the_crossover",
        "bwd_table_grid", "bwd_run_grid", "bwd_mask_on_every_live_block",
        "bwd_expanded_before_the_call", "bwd_parent_kernel"}
    assert rows[0]["platform"] == "cpu" and rows[0]["tiny"]
    grids = [r for r in rows if r["what"] == "table_grid"]
    assert all(r["steps"] == r["live_steps"] >= r["masked_steps"] > 0
               and r["out_max_err"] < 0.05 and r["lse_max_err"] < 0.05
               for r in grids)
    assert all(isinstance(r[ms], float) for r in rows[1:]
               for ms in ("ms", "dq_ms", "dkv_ms") if ms in r)
    # dq and dkv apart at every block pair, each against `_reference`'s
    # vjp on a kv head's whole group; the chosen pair is among them.
    bwd = [r for r in rows if r["what"] == "bwd_table_grid"]
    assert len(bwd) == 2 and sum(r["chosen"] for r in bwd) == 1
    assert all(r["dq"] == r["dkv"] and r["dq"]["steps"]
               == r["dq"]["live_steps"] > r["dq"]["masked_steps"] > 0
               and max(r["dq_err"], r["dk_err"], r["dv_err"]) < 0.02
               for r in bwd)
    # Restored after the readings that patch them.
    assert mod.fa._live_pairs.__module__ == mod.fa.__name__
    assert mod.fa._block_kind.__name__ == "_block_kind"
