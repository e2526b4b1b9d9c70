"""Device time of the scan's ``knn.matmul`` name scope in the traced window
per 4,096 window rows (``timeline.py``)."""
import timeline


def read(run):
    return timeline.named_ms_per_block(run, "scopes", ["knn.matmul"])
