"""`scrape_hist_mean` on every replica but the primary (the first of
each scrape list), and of those means the largest: the slower backup's.
Nothing to read at one replica, or where no backup has the histogram."""

from . import scrape_hist_mean


def read(spec: dict, ctx: dict) -> float | None:
    means = [
        scrape_hist_mean.read(spec, {"before": [before], "after": [after]})
        for before, after in zip(ctx["before"][1:], ctx["after"][1:])
    ]
    return max((m for m in means if m is not None), default=None)
