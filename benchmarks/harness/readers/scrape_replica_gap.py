"""The primary's value of a key less the slowest backup's, scraped as
the window closes (in flight requests still running).  Nothing to
read where the configuration has one replica."""


def read(spec: dict, ctx: dict) -> float | None:
    key = spec["keys"][0]
    at_close = ctx["at_close"]
    if len(at_close) < 2 or not all(key in s for s in at_close):
        return None
    return float(at_close[0][key] - min(s[key] for s in at_close[1:]))
