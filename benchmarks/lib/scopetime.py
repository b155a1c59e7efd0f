"""Device time by the program's own scopes (`jax.named_scope`), for the
readers of one layer of a model: the operations of device 0 in the traced
stretch that start while a decode program runs (inside a `jit_decode*`
module event) and whose scope path (`lib/progspans.scope_paths`) holds the
scope as one of its elements. An operation the compiler fused across two
scopes counts under its root's. A pallas kernel keeps its scope path
(the grouped products run megablox's, `.../moe_experts/jit(gmm)/...`). A
kernel the compiler puts in for an operation loses it (XLA names the
grouped product of `lax.ragged_dot`, which the program takes where the
pallas kernel does not tile the shape, `ragged-dot-none.<n>`, scope
`ragged-dot-none`: my chip run, PR 28), so such a kernel is told by its
name: `KERNELS`.
Where the trace has no such scope or kernel (an older commit, another
architecture) a reader finds nothing and returns None."""

from __future__ import annotations

import bisect
import os
import re
from typing import Any, Dict, Optional

from . import progspans, xplane

# Operation name -> the scope it is counted under.
KERNELS = ((re.compile(r"^ragged-dot"), "moe_experts"),)


def decode_scope_seconds(m: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """{scope path element: seconds of device 0 inside the decode
    programs}, read once a run and kept in `m`."""
    if "decode_scope_s" in m:
        return m["decode_scope_s"]
    ctx = m["ctx"]
    path = xplane.find_xplane(os.path.join(ctx.out_dir, "trace")) \
        if ctx.trace else None
    out: Optional[Dict[str, float]] = None
    if path:
        raw = progspans.read_profile(path)
        devs = raw.get("devices", {})
        first = min(devs, key=lambda p: int(
            xplane.DEVICE_PLANE.match(p).group(1)), default=None)
        if first is not None:
            t0, t1 = raw.get("window") or (float("-inf"), float("inf"))
            decode = sorted((s, s + d) for name, s, d in devs[first]["modules"]
                            if progspans.DECODE_BLOCK.match(name))
            starts = [s for s, _ in decode]
            out = {}
            for name, s, d in devs[first]["ops"]:
                part = min(s + d, t1) - max(s, t0)
                i = bisect.bisect_right(starts, s) - 1
                op = xplane.op_name(name).split(" ")[0]
                if part <= 0 or i < 0 or s >= decode[i][1] \
                        or xplane.CONTAINER.match(op):
                    continue
                elements = set((raw["scopes"].get(name) or "").split("/"))
                elements.update(scope for pattern, scope in KERNELS
                                if pattern.match(op))
                for element in elements - {""}:
                    out[element] = out.get(element, 0.0) + part / 1e9
    m["decode_scope_s"] = out
    return out


def decode_ms_step(m: Dict[str, Any], scopes) -> Optional[float]:
    """Device time under any of `scopes` inside the decode programs, per
    decode step the device ran in the stretch."""
    ps = progspans.for_run(m)
    by_scope = decode_scope_seconds(m) if ps else None
    steps = ps.decode_steps() if ps else 0.0
    if not by_scope or not steps or not any(s in by_scope for s in scopes):
        return None
    return sum(by_scope.get(s, 0.0) for s in scopes) * 1e3 / steps
