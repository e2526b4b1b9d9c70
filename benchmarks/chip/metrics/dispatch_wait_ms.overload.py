"""``servereads.dispatch_wait_ms`` in the cells where it moves
``served_rows_per_s``."""
from servereads import dispatch_wait_ms as read  # noqa: F401
