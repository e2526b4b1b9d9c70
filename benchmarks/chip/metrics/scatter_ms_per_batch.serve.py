"""``servereads.scatter_ms_per_batch`` in the cells where it moves
``p50_ms``."""
from servereads import scatter_ms_per_batch as read  # noqa: F401
