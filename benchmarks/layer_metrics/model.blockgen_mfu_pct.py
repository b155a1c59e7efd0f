"""Model: the block programs' share of the chip's peak, the whole of a
pass: the operations a pass of the stretch asks for (the configuration's
reference counts them, `pass_flops`: the parameters each position of an
owned slot's block uses, the attention of the block's queries over the
rows the slot holds, the head for the positions of the slots a pass
denoises) over the device time a pass of `jit_decode_k<k>` at the peak
bf16 FLOP/s. Positions and head rows from the stretch's
`engine.process_block` spans (`denoise_passes`, `commit_passes`,
`block_length` over `k`), held rows from `engine.dispatch_block`
(`cache_rows_held` over `k`). Slots nobody owns and a block's positions
that are already final are work of the program's, not of the model's, so
they lower this share. It bounds what any kernel's gain can give the
cell's `serve_out_tok_s`. Also notes, for PERF.md, a pass's device time
by scope (`decode_scope_ms_pass` in the run's log line)."""

from lib import peaks, progspans, scopetime

SCOPES = ("attn_global", "moe_router", "moe_experts", "block_head",
          "block_sample")


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    ref = m["ctx"].spec.reference
    done = ps.attribute_sums("engine.process_block") if ps else {}
    sent = ps.attribute_sums("engine.dispatch_block") if ps else {}
    ms_pass = ps.decode_ms_step() if ps else None
    Bd = int(m["arch"].get("block_length") or 0)
    if not ms_pass or not Bd or not done.get("k") or not sent.get("k") \
            or "denoise_passes" not in done \
            or not hasattr(ref, "pass_flops"):
        return None
    denoise = done["denoise_passes"] / done["k"]
    commit = done.get("commit_passes", 0) / done["k"]
    asked = ref.pass_flops(
        m["arch"], (denoise + commit) * Bd,
        sent.get("cache_rows_held", 0) / sent["k"], denoise * Bd)
    by_scope = scopetime.decode_scope_seconds(m) or {}
    steps = ps.decode_steps()
    m["ctx"].notes["decode_scope_ms_pass"] = dict(
        {s: by_scope.get(s, 0.0) * 1e3 / steps for s in SCOPES},
        all=ms_pass)
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    return 100.0 * asked / (ms_pass / 1e3) / peak["bf16_flops"]
