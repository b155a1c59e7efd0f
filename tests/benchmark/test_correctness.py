"""What decides `correct` for a serving cell's window: the tokens the
engine streamed against the reference's logits for the same positions.
Here the "engine" is a stand-in whose answers are made by hand, so that
a wrong token can be put in."""

import types

import numpy as np
import pytest

from lib import modelcfg, reference, serving
from lib.spec import Spec


def _greedy(arch, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = np.asarray(reference.forward_logits(arch, params, seq))
        seq.append(int(np.argmax(logits[-1])))
    return seq[len(prompt):]


@pytest.fixture(scope="module")
def answered(tiny_root):
    spec = Spec(tiny_root, "tiny-lone")
    cfg = modelcfg.transformer_config(spec.config, spec.sizes)
    params = modelcfg.make_params(cfg, 2**31 + 5)
    rng = np.random.default_rng(7)
    rows = []
    for i, n in enumerate((9, 14, 11, 30)):
        prompt = rng.integers(0, cfg.vocab_size, size=n).tolist()
        answer = _greedy(spec.config, params, prompt, 5)
        req = types.SimpleNamespace(prompt=prompt, tokens=answer)
        row = serving.Row(i, 0.0, 0.0, 5, n, req)
        row.tokens, row.done = 5, True
        rows.append(row)
    ctx = types.SimpleNamespace(spec=spec)
    return ctx, {"params": params}, rows, cfg.vocab_size


def _check(ctx, built, rows):
    client = types.SimpleNamespace(rows=rows)
    return serving.check_window_tokens(ctx, built, client, rows)


def test_greedy_answers_pass_and_the_shortest_are_taken(answered):
    ctx, built, rows, _ = answered
    got = _check(ctx, built, rows)
    assert got["ok"] and got["request_indices"] == [0, 2, 1]
    assert got["positions"] == got["argmax_agree"] == 15
    assert got["token_deficit_max"] == 0.0 < got["token_deficit_tol"]


@pytest.mark.parametrize("position", [0, 2, 4])
def test_one_wrong_token_fails(answered, position):
    """A token the reference ranks far below its best, as a wrong slot,
    cache row or block would stream: the least likely one."""
    ctx, built, rows, vocab = answered
    row = rows[0]
    good = list(row.req.tokens)
    seq = row.req.prompt + good[:position]
    logits = np.asarray(reference.forward_logits(
        ctx.spec.config, built["params"], seq))[-1]
    row.req.tokens = good[:position] + [int(np.argmin(logits))] \
        + good[position + 1:]
    try:
        got = _check(ctx, built, rows)
    finally:
        row.req.tokens = good
    assert not got["ok"]
    assert got["token_deficit_max"] > got["token_deficit_tol"]
    assert got["argmax_agree"] < got["positions"]


def test_a_request_that_comes_round_again_is_checked_once(answered):
    ctx, built, rows, _ = answered
    got = _check(ctx, built, [rows[0], rows[0], rows[3]])
    assert got["request_indices"] == [0, 3]


def test_nothing_answered_in_full_is_not_correct(answered):
    ctx, built, rows, _ = answered
    rows[1].done = False
    try:
        assert not _check(ctx, built, [rows[1]])["ok"]
    finally:
        rows[1].done = True


@pytest.mark.parametrize("change,ok", [
    ({}, True),
    ({"n_layers": 1}, False),             # a layer fewer
    ({"norm_eps": 0.5}, False),           # another normalisation
])
def test_program_logits_against_the_reference(answered, change, ok):
    """The set-up check: the program's prefill and decode through the
    cache against a reference that is told another architecture."""
    ctx, built, _, _ = answered
    spec = ctx.spec
    cfg = modelcfg.transformer_config(spec.config, spec.sizes)
    other = types.SimpleNamespace(
        seed=11, spec=types.SimpleNamespace(
            sizes=spec.sizes, config=dict(spec.config, **change)))
    got = serving.check_against_reference(other, cfg, built["params"], 4, 128)
    assert got["ok"] is ok
    assert (got["logit_rel_rms_err"] <= got["tolerance_rel"]) is ok
