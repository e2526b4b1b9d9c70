"""``servereads.pad_share`` in the cells where it moves ``p50_ms``."""
from servereads import pad_share as read  # noqa: F401
