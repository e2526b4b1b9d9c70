"""Least time of the window's scan work (``roofline.py``) over the device
busy time of the traced window."""
import roofline


def read(run):
    scan, busy = run["scan"], run["trace"]["busy_s"]
    if scan is None or busy <= 0:
        return None
    return 100.0 * roofline.least_time_s(scan["ops"], scan["bytes"], scan["peak"]) / busy
