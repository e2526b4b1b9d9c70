"""What the per-layer readers of the served cells compute, from the
scheduler's own window counters (``run["loop"]``: ``batches``,
``requests``, ``live_rows``, ``padded_rows``) and the traced window.
Each returns ``None`` where the run holds nothing to read.  A quantity is
one reader file per end-to-end metric it moves (``<name>.serve`` moves
``p50_ms``, ``<name>.overload`` ``served_rows_per_s``), each calling one
of these."""


def _batches(run):
    c = run.get("loop")
    return c["batches"] if c else 0


def pad_share(run):
    """1 − live rows ÷ dispatched rows (batches × ``r_block``), in %."""
    if not _batches(run):
        return None
    c = run["loop"]
    return 100.0 * (1.0 - c["live_rows"] / c["padded_rows"])


def requests_per_batch(run):
    """Requests completed ÷ batches completed."""
    if not _batches(run):
        return None
    return run["loop"]["requests"] / run["loop"]["batches"]


def dispatch_wait_ms(run):
    """Host seconds of the ``knn.batch`` spans less those of the
    ``knn.store.dispatch`` spans inside them, per batch: how long a formed
    batch waits for the single dispatch worker, with its assembly and its
    results' delivery."""
    spans = run["trace"]["host_spans"]
    if not _batches(run) or "knn.batch" not in spans:
        return None
    wait = spans["knn.batch"] - spans.get("knn.store.dispatch", 0.0)
    return wait / run["loop"]["batches"] * 1e3


def device_ms_per_batch(run):
    """Device busy time of the window per batch."""
    if not _batches(run):
        return None
    return run["trace"]["busy_s"] / run["loop"]["batches"] * 1e3


def scatter_ms_per_batch(run):
    """Device time of the ``knn.scatter`` scope (the S densify, paid once
    per store call) per batch; nothing where the trace carries no
    ``knn.*`` scope."""
    scopes = run["trace"]["scopes"]
    if not _batches(run) or not any(s.startswith("knn.") for s in scopes):
        return None
    return scopes.get("knn.scatter", 0.0) / run["loop"]["batches"] * 1e3
