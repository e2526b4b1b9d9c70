"""``servereads.dispatch_wait_ms`` in the cells where it moves ``p50_ms``."""
from servereads import dispatch_wait_ms as read  # noqa: F401
