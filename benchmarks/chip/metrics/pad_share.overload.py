"""``servereads.pad_share`` in the cells where it moves
``served_rows_per_s``."""
from servereads import pad_share as read  # noqa: F401
