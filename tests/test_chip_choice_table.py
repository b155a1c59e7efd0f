"""chip_choice_table.py without the chip: `--tiny` walks every block of a
prompt in two small buckets, the float32 bias and the tie path, then a
test-size stack's `chosen_rows` and `prefill` with both biases computed on
the same scores inside `_attend_chunk`, all through the Pallas interpreter
against the definition and "the parent", so the script still runs when
the chip's minutes are spent on it. Its times mean nothing here."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _script():
    spec = importlib.util.spec_from_file_location(
        "chip_choice_table", os.path.join(ROOT, "chip_choice_table.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_without_a_chip_and_without_tiny_it_gives_no_verdict(tmp_path,
                                                             capsys):
    out = tmp_path / "table.jsonl"
    assert _script().main(["--out", str(out)]) == 1
    assert not out.exists() and not capsys.readouterr().out


def test_tiny_rehearsal_holds_every_block_to_both(tmp_path, capsys):
    mod = _script()
    out = tmp_path / "table.jsonl"
    # "The parent" is this tree: its `topk_bias` called with no offset
    # counts every column, as a checkout that knows none does.
    assert mod.main(["--tiny", "--out", str(out), "--parent", ROOT]) == 0
    rows = [json.loads(x) for x in out.read_text().splitlines()]
    assert capsys.readouterr().out.count("\n") == len(rows)
    assert rows[-1] == {"ok": True, "rows": len(rows) - 1,
                        "device": {"platform": "cpu", "kind": "cpu"}}
    assert all(r["kernel"] and r["equal_definition"] and r["equal_parent"]
               for r in rows[:-1])
    blocks = [r for r in rows[:-1] if "bucket" in r]
    # 3,000 tokens in blocks of 512: six of the 4,096 bucket, all four of
    # the 2,048 bucket; then each bucket's float32 bias and its tie path.
    assert [(r["bucket"], r["first"]) for r in blocks
            if r["scores"] == "index_scores_tile"
            and r["dtype"] == "bfloat16"] == [
        (4096, f) for f in range(0, 3072, 512)] + [
        (2048, f) for f in range(0, 2048, 512)]
    assert [r["columns_counted"] for r in blocks[:6]] == \
        [1024] * 2 + [2048] * 2 + [3072] * 2
    assert {(r["scores"], r["dtype"]) for r in blocks} == {
        ("index_scores_tile", "bfloat16"), ("index_scores_tile", "float32"),
        ("rounded to quarters", "bfloat16")}
    assert all(r["chosen_a_row"][1] == 24 for r in blocks)
    # Inside the programs: two layers, a call a block of 1,024 queries;
    # `chosen_rows` of 2,048 tokens is one chunk, 3,000 tokens run both
    # chunks of the 4,096 bucket.
    programs = [r for r in rows[:-1] if "program" in r]
    assert [(r["program"], r["tokens"], r["offsets"], r["last_offset"])
            for r in programs] == [("chosen_rows", 2048, 2, 1024),
                                   ("prefill", 3000, 4, 3072)]
    assert all(r["topk_bias_calls"] >= r["offsets"]
               and 0 < r["calls_on_the_tie_path"] <= r["topk_bias_calls"]
               for r in programs)
    # The wrapper is gone with the run.
    assert mod.sa.topk_bias.__module__ == mod.sa.__name__
