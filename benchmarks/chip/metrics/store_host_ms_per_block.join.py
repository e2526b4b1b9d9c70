"""Host time of the store's own work per R block in the traced window,
the ``knn.store.prep``, ``.launch`` and ``.pull`` spans (its wait for the
device left out), per 4,096 window rows (``timeline.py``)."""
import timeline

SPANS = ["knn.store.prep", "knn.store.launch", "knn.store.pull"]


def read(run):
    return timeline.named_ms_per_block(run, "host_spans", SPANS)
