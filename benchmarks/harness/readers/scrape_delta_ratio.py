"""Window delta of the sum of `keys` on the primary, over the window
delta of the sum of `over` (scrape keys, or the word `requests`: the
create_transfers requests committed in the window), times `scale`."""


def _delta(keys: list, ctx: dict) -> float | None:
    before, after = ctx["before"][0], ctx["after"][0]
    if not all(k in after for k in keys):
        return None
    return sum(after[k] - before.get(k, 0) for k in keys)


def read(spec: dict, ctx: dict) -> float | None:
    top = _delta(spec["keys"], ctx)
    if spec["over"] == "requests":
        bottom = ctx["requests"]
    else:
        bottom = _delta(spec["over"], ctx)
    if top is None or not bottom:
        return None
    return spec.get("scale", 1.0) * top / bottom
