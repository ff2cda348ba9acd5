"""Observability spine: typed metrics registry + tracer knobs.

One `Registry` per component (replica, state machine, device engine),
composed into a single tree by the owning process (ReplicaServer) and
rendered two ways from the SAME counters:

- `TB_STATS` log lines (runtime/server.py _print_stats),
- the `stats` wire operation (scrapeable over the TCP bus, obs.scrape;
  what `benchmarks/` reads).

Knobs (validated in envcheck.py):

- ``TB_METRICS=0|1`` — 1 (default) records latency histograms; 0 skips
  the clock reads (counters stay live: logic depends on them).
- ``TB_TRACE=none|json`` — promotes the utils/tracer.py span tracer to
  a first-class backend choice; ``json`` writes a Chrome-trace file per
  process (``TB_TRACE_PATH`` or ``tb_trace_r<i>.json``), mergeable into
  one cross-replica Perfetto timeline by testing/cluster.merge_traces.
"""

from tigerbeetle_tpu.obs.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    percentile_of_counts,
    stat_property,
)
