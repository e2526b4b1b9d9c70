"""``servereads.requests_per_batch`` in the cells where it moves
``p50_ms``."""
from servereads import requests_per_batch as read  # noqa: F401
