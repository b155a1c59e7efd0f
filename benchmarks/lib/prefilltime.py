"""Device time and counters of the prefill programs, for the readers of
one layer of a model inside an admission tile: what `lib/scopetime.py` is
to the decode programs. The operations of device 0 in the traced stretch
that start while a prefill program runs (inside a `jit_prefill*` or
`jit_first_token*` module event), by the elements of their scope path
(`jax.named_scope`: `moe_router`, `moe_experts`, `attn_window`,
`attn_global`); a grouped product the compiler names itself is told by
its name (`scopetime.KERNELS`). Per request: over the requests whose
prefill ran in the stretch, counted as `ProgramSpans.prefill_ms_req`
counts them. Where the trace has no such scope (an older commit, another
architecture) a reader finds nothing and returns None."""

from __future__ import annotations

import bisect
import os
from typing import Any, Dict, Optional

from . import progspans, scopetime, xplane

TILE, DELIVERY = "engine.prefill_tile", "engine.deliver_first"


def scope_seconds(m: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """{scope path element: seconds of device 0 inside the prefill
    programs}, read once a run and kept in `m`."""
    if "prefill_scope_s" in m:
        return m["prefill_scope_s"]
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
            tiles = sorted((s, s + d) for name, s, d in devs[first]["modules"]
                           if progspans.PREFILL.match(name))
            starts = [s for s, _ in tiles]
            out = {}
            for name, s, d in devs[first]["ops"]:
                part = min(s + d, t1) - max(s, t0)
                i = bisect.bisect_right(starts, s) - 1
                op = xplane.op_name(name).split(" ")[0]
                if part <= 0 or i < 0 or s >= tiles[i][1] \
                        or xplane.CONTAINER.match(op):
                    continue
                elements = set((raw["scopes"].get(name) or "").split("/"))
                elements.update(scope for pattern, scope in scopetime.KERNELS
                                if pattern.match(op))
                for element in elements - {""}:
                    out[element] = out.get(element, 0.0) + part / 1e9
    m["prefill_scope_s"] = out
    return out


def launches(ps: progspans.ProgramSpans) -> float:
    """Launches of the prefill programs in the stretch, an edge launch by
    its part."""
    return sum(n for name, n in ps.launches.items()
               if progspans.PREFILL.match(name))


def requests(ps: progspans.ProgramSpans) -> float:
    """Requests whose prefill ran in the stretch: the launches times the
    requests a tile held (`ProgramSpans.prefill_ms_req`)."""
    tiles = ps.named(TILE)
    reqs = {i for t in tiles for i in str(t.stats.get("req_ids", "")).split()}
    if not tiles or not reqs:
        return 0.0
    return launches(ps) * len(reqs) / len(tiles)


def scope_ms_req(m: Dict[str, Any], scopes) -> Optional[float]:
    """Device time under any of `scopes` inside the prefill programs, per
    request prefilled in the stretch."""
    ps = progspans.for_run(m)
    by_scope = scope_seconds(m) if ps else None
    n = requests(ps) if ps else 0.0
    if not by_scope or not n or not any(s in by_scope for s in scopes):
        return None
    return sum(by_scope.get(s, 0.0) for s in scopes) * 1e3 / n


def routed_per_tile(ps: progspans.ProgramSpans) -> Optional[Dict[str, float]]:
    """The admission tiles' routing counters, a tile: the sums the
    `engine.deliver_first` spans of the stretch carry
    (`prefill_moe_experts_hit`, `prefill_moe_rows`,
    `prefill_moe_rows_max`) over their `moe_tiles`."""
    sums = ps.attribute_sums(DELIVERY)
    if not sums.get("moe_tiles") or not sums.get("prefill_moe_rows"):
        return None
    return {k: sums.get("prefill_moe_" + k, 0) / sums["moe_tiles"]
            for k in ("experts_hit", "rows", "rows_max")}
