"""Mean of a histogram over the window: the delta of `.sum` over the
delta of `.count` on the primary.  (The scrape's own percentiles are
lifetime numbers and include start-up and the warm requests.)"""


def read(spec: dict, ctx: dict) -> float | None:
    key = spec["keys"][0]
    before, after = ctx["before"][0], ctx["after"][0]
    if key + ".count" not in after:
        return None
    n = after[key + ".count"] - before.get(key + ".count", 0)
    if n <= 0:
        return None
    total = after[key + ".sum"] - before.get(key + ".sum", 0)
    return spec.get("scale", 1.0) * total / n
