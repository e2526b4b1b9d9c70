"""The program's own names on the profiler's timeline: ``knn.*`` host
spans and ``knn.*`` device name scopes, added to ``tracereduce``'s
reduction without changing what it computes.

The program enters a ``jax.profiler.TraceAnnotation`` named
``knn.<span>`` for every tracer span (``knn.store.r_block`` over
``knn.store.prep``, ``.launch``, ``.wait``, ``.pull``), and sets the
name scopes ``knn.matmul``, ``knn.scatter``, ``knn.bound`` and
``knn.topk`` where the scan's work is written.  A scope lands in each
compiled op's ``op_name`` metadata.  The v5e trace carries it as the
``tf_op`` stat of the op's event metadata on the device plane (the
metadata the ``XLA Ops`` events point to, e.g. ``jit(local)/.../
knn.scatter/scatter-add:``).  ``jax.profiler.ProfileData`` shows only an
event's own stats, so ``extract`` reads the device planes' event metadata
from the ``.xplane.pb`` itself (``XSpace`` protobuf, wire format) and
gives each op the scope of its name.  A name whose metadata entries
disagree on the scope gets none.  Nothing is read from the program.

- ``extract``: ``tracereduce.extract``'s structure, with each device op
  as ``[start_ns, dur_ns, name, scope]`` (``scope`` the innermost
  ``knn.*`` component of the op's name scope, or ``None``) and the host
  events named ``bench.*`` or ``knn.*``.
- ``reduce``: ``tracereduce.reduce`` of the same trace, so ``busy_s``,
  ``window_s`` and ``device_ops`` are computed as there, and each idle
  gap is named by the innermost ``bench.*`` or ``knn.*`` annotation over
  its middle; plus ``scopes``, device seconds per scope inside the window
  by ``device_ops``' leaf-op rule, averaged over the devices as busy time
  is, with unscoped ops under ``other``; ``leaf_s``, their sum; and
  ``host_spans``, host seconds per ``knn.*`` span name, clipped to the
  window.
- ``ms_per_block``: seconds of the window per 4,096 window rows, the
  fixed yardstick of ``device_ms_per_block.join``.
- ``named_ms_per_block``: that of a sum of ``scopes`` or ``host_spans``
  entries, as the per-layer metrics that read the program's names take it.
"""
from __future__ import annotations

import glob
import os
import re

import tracereduce

HOST_PREFIXES = (tracereduce.PREFIX, "knn.")
OTHER = "other"
ROWS_PER_BLOCK = 4096
_SCOPE = re.compile(r"(?:^|/)(knn\.[A-Za-z_]+)(?=/|$)")


def scope_of(op_name) -> str | None:
    """The innermost ``knn.*`` component of a name-scope path."""
    found = _SCOPE.findall(op_name) if isinstance(op_name, str) else []
    return found[-1] if found else None


def _varint(buf, i):
    v = shift = 0
    while True:
        c = buf[i]
        i += 1
        v |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return v, i


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields, None for fixed-width ones."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_values(plane, number):
    """The values of the map field ``number`` of one message."""
    for f, entry in _fields(plane):
        if f == number:
            for g, v in _fields(entry):
                if g == 2:
                    yield v


def _device_op_scopes(raw) -> dict:
    """``{device plane: {op event name: scope}}`` from the ``tf_op`` stat
    of each device plane's event metadata.  XSpace: planes = 1; XPlane:
    name = 2, event_metadata = 4, stat_metadata = 5; XEventMetadata:
    name = 2, stats = 5; XStatMetadata: id = 1, name = 2; XStat:
    metadata_id = 1, str_value = 5, ref_value = 7."""
    out: dict = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name = next((bytes(v).decode() for g, v in _fields(plane) if g == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for sm in _map_values(plane, 5):
            d = dict(_fields(sm))
            stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        scopes: dict = {}
        for em in _map_values(plane, 4):
            op, scope = None, None
            for g, v in _fields(em):
                if g == 2:
                    op = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        text = (bytes(stat[5]).decode() if 5 in stat
                                else stat_names.get(stat.get(7), ""))
                        scope = scope_of(text.rsplit(":", 1)[0])
            if op is not None:
                scopes[op] = scope if scopes.get(op, scope) == scope else None
        out[name] = scopes
    return out


def extract(log_dir: str) -> dict:
    """Device ops with their scopes, and the ``bench.*`` and ``knn.*``
    host events, of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    path = max(paths, key=os.path.getmtime)
    data = ProfileData.from_file(path)
    with open(path, "rb") as f:
        scopes = _device_op_scopes(f.read())
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == tracereduce.OP_LINE:
                    of = scopes.get(plane.name, {})
                    devices.setdefault(plane.name, []).extend(
                        [e.start_ns, e.duration_ns, e.name, of.get(e.name)]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.start_ns, e.duration_ns, e.name]
                            for e in line.events if e.name.startswith(HOST_PREFIXES))
    return {"devices": devices, "host": host}


def _scope_seconds(ex: dict, lo: float, hi: float) -> dict:
    per: dict = {}
    for events in ex["devices"].values():
        inside = sorted((e[0], -e[1], e[2], (e[3] if len(e) > 3 else None) or OTHER)
                        for e in events if e[0] < hi and e[0] + e[1] > lo)
        for j, (s, neg_d, _, scope) in enumerate(inside):
            if j + 1 < len(inside) and inside[j + 1][0] < s - neg_d:
                continue  # holds the next op: not a leaf
            per[scope] = per.get(scope, 0) - neg_d
    n = len(ex["devices"])
    return {scope: ns / n / 1e9 for scope, ns in sorted(per.items())}


def _host_seconds(host: list, lo: float, hi: float) -> dict:
    per: dict = {}
    for s, d, name in host:
        if name.startswith("knn."):
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                per[name] = per.get(name, 0) + clipped
    return {name: ns / 1e9 for name, ns in sorted(per.items())}


def reduce(ex: dict) -> dict:
    """``tracereduce.reduce`` plus ``scopes``, ``leaf_s`` and
    ``host_spans`` (see the module docstring)."""
    plain = {"devices": {p: [e[:3] for e in evs] for p, evs in ex["devices"].items()},
             "host": ex["host"]}
    out = tracereduce.reduce(plain)
    lo, hi = tracereduce._window(ex["host"])
    out["scopes"] = _scope_seconds(ex, lo, hi)
    out["leaf_s"] = sum(out["scopes"].values())
    out["host_spans"] = _host_seconds(ex["host"], lo, hi)
    return out


def ms_per_block(seconds: float, rows: int) -> float | None:
    """``seconds`` of the window per 4,096 window rows, in ms."""
    if not rows:
        return None
    return seconds / (rows / ROWS_PER_BLOCK) * 1e3


def named_ms_per_block(run: dict, kind: str, names) -> float | None:
    """The seconds of the ``kind`` (``"scopes"`` or ``"host_spans"``)
    entries ``names`` of ``run["trace"]``, per 4,096 of ``run["rows"]``, in
    ms.  A name the trace lacks counts 0 while the trace carries any
    ``knn.*`` name; a trace that carries none reads ``None``."""
    t = run["trace"]
    if not t["host_spans"] and not any(s.startswith("knn.") for s in t["scopes"]):
        return None
    return ms_per_block(sum(t[kind].get(n, 0.0) for n in names), run["rows"])
