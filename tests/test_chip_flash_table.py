"""chip_flash_table.py without the chip: `--tiny` walks every kind of
reading (block pairs, the run grid, K and V expanded, the mask on every
live block, the parent's kernel, under the crossover) through the Pallas
interpreter, so the script still runs when the chip's minutes are spent
on it. Its times mean nothing here."""

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
        "mask_on_every_live_block", "parent_kernel", "under_the_crossover"}
    assert rows[0]["platform"] == "cpu" and rows[0]["tiny"]
    grids = [r for r in rows if r["what"] == "table_grid"]
    assert all(r["steps"] == r["live_steps"] >= r["masked_steps"] > 0
               and r["out_max_err"] < 0.05 and r["lse_max_err"] < 0.05
               for r in grids)
    assert all(isinstance(r["ms"], float) for r in rows[1:]
               if r["what"] != "under_the_crossover")
    # Restored after the reading that patches it.
    assert mod.fa._live_pairs.__module__ == mod.fa.__name__
