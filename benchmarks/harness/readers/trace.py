"""Quantities of the traced slice.  `ctx["trace"]` holds what
`trace_reduce.py` found on the primary's chip (`busy_s`), the slice's
length (`window_s`), and the requests and events the primary committed
in the slice (deltas of its scrape around it).  A slice in which no
operation ran on a device gives nothing, never 0."""

from .. import roofline


def read(spec: dict, ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("window_s"):
        return None
    what = spec["quantity"]
    if what == "idle_pct":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if what == "busy_ms_per_request":
        return 1e3 * tr["busy_s"] / tr["requests"] if tr["requests"] else None
    if what == "roofline_pct":
        if not tr["events"]:
            return None
        least, _bound = roofline.least_seconds(tr["events"], tr["device_kind"])
        return 100.0 * least / tr["busy_s"]
    raise ValueError(f"unknown trace quantity {what!r}")
