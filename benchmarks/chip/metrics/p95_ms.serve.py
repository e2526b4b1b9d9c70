"""The 95th percentile of request latency, from each request's due time to
its answer, over every request due in the traced window, in the cells
where it moves ``p50_ms``.  Under the profiler a batch runs about a tenth
longer than untraced, so this reads above an untraced run's tail."""


def read(run):
    return (run.get("values") or {}).get("p95_ms")
