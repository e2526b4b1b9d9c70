"""``servereads.requests_per_batch`` in the cells where it moves
``served_rows_per_s``."""
from servereads import requests_per_batch as read  # noqa: F401
