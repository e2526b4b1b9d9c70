"""Finds a cell's files by the names in ``BENCHMARK.json``.

- A cell (``workloads`` entry) names a configuration, a traffic mix and
  its chips.
- A configuration is the file its ``configs`` entry names: sizes, the
  check's limits, and ``data``, whose ``generator`` names a data
  generator and whose other keys are that generator's parameters.
- A data generator is ``generators/<name>.py`` with ``generate(cfg, n,
  structure, seed)``, returning ``n`` rows of the configuration's kind as
  padded-CSR host arrays (``datagen.py``).
- A traffic mix is ``traffic/<mix>.json``: parameters only, one of them
  ``loop``, the loop that drives them.
- A loop is ``loops/<name>.py`` with ``run(store, pool, dim, traffic,
  seconds, log_dir, counter, on_ready)``.  It returns a record whose
  ``metrics`` holds the end-to-end values it measures (``run.py`` adds
  ``setup_s`` and ``index_bytes_per_nnz``) and whose ``counters``, if
  any, traced runs hand to the per-layer readers as ``run["loop"]``.
- A per-layer metric is ``metrics/<name>.py`` with ``read(run)``,
  returning ``None`` where the run holds nothing to read.

Adding a cell, a configuration, a generator, a mix, a loop or a metric is
adding files and entries: nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, here: pathlib.Path = HERE) -> dict:
    return json.loads((here / "traffic" / f"{name}.json").read_text())


def metrics_of(bench: dict, kind: str, cell_name: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def module(kind: str, name: str, here: pathlib.Path = HERE):
    """The module ``<kind>/<name>.py``."""
    path = here / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {name!r} under {here}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, here: pathlib.Path = HERE):
    """``read`` of ``metrics/<name>.py``."""
    return module("metrics", name, here).read


def loop(name: str, here: pathlib.Path = HERE):
    """``run`` of ``loops/<name>.py``."""
    return module("loops", name, here).run


def generator(name: str, here: pathlib.Path = HERE):
    """``generate`` of ``generators/<name>.py``."""
    return module("generators", name, here).generate
