"""The harness's modules import each other by plain name, as ``run.py``
runs them; the program lives under ``src``."""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
for p in (HERE.parents[2] / "src", HERE.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
