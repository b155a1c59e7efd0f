"""Trainer: share of the devices' busy time spent recomputing in the
backward pass what `jax.checkpoint` did not keep of the forward: the
operations of every device in the traced stretch whose scope path holds
the element `rematted_computation` (jax names the recomputation so where
it transposes a checkpoint: `jax/_src/ad_checkpoint.py`) over busy time
(`lib/progspans`). Containers (`while`, `call`) are left out, as the
phases' reader leaves them. The split by what was checkpointed, the
layers of `fwd` or the chunks of `loss_head` (`chunked_cross_entropy`'s
own checkpoint), goes to an earlier output line. A program with no such
scope (nothing checkpointed, or nothing differentiated) reads nothing."""

import os
import re

from lib import progspans, xplane

ELEMENT = "rematted_computation"
# `transpose(jvp(fwd))`, `transpose(jvp(loss_head))`: whose checkpoint.
OWNER = re.compile(r"^transpose\(.*\b(fwd|loss_head)\b")


def remat_seconds(raw):
    """{"fwd" | "loss_head" | "other": device-seconds under `ELEMENT`},
    all devices, cut to the profile's window; {} where there are none."""
    t0, t1 = raw.get("window") or (float("-inf"), float("inf"))
    scopes = raw.get("scopes", {})
    out = {}
    for dev in raw.get("devices", {}).values():
        for name, s, d in dev["ops"]:
            part = min(s + d, t1) - max(s, t0)
            parts = (scopes.get(name) or "").split("/")
            if part <= 0 or ELEMENT not in parts or xplane.CONTAINER.match(
                    xplane.op_name(name).split(" ")[0]):
                continue
            owner = next((m.group(1) for m in map(OWNER.match, parts) if m),
                         "other")
            out[owner] = out.get(owner, 0.0) + part / 1e9
    return out


def read(metric, m):
    ps = progspans.for_run(m)
    if not ps or not ps.busy_total_s:
        return None
    if "raw_profile" not in m:      # kept for whoever reads it next
        m["raw_profile"] = progspans.read_profile(xplane.find_xplane(
            os.path.join(m["ctx"].out_dir, "trace")))
    by_owner = remat_seconds(m["raw_profile"])
    if not by_owner:
        return None
    m["ctx"].log(phase="remat", remat_s=by_owner,
                 busy_total_s=ps.busy_total_s)
    return 100.0 * sum(by_owner.values()) / ps.busy_total_s
