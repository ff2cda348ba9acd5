"""The sum of the gauges `keys` over the sum of the gauges `over`, on
the primary as the window closes, times `scale`.  Gauges are levels,
not counts: no delta is taken."""


def read(spec: dict, ctx: dict) -> float | None:
    snap = ctx["at_close"][0]
    if not all(k in snap for k in spec["keys"] + spec["over"]):
        return None
    bottom = sum(snap[k] for k in spec["over"])
    if not bottom:
        return None
    return spec.get("scale", 1.0) * sum(snap[k] for k in spec["keys"]) / bottom
