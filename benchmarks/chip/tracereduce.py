"""From a profiler trace to device busy time, idle gaps and top device ops.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
small plain structure; ``reduce`` works on that structure only, so it can
be checked on a recorded trace without a chip.

- Device ops are the events of the ``XLA Ops`` line of every ``/device:``
  plane.  They are not matched by program name, so renaming a program does
  not hide it.
- Busy time is the union of a device's op intervals inside the window,
  averaged over the devices; idle is the rest of the window.
- The top ops are leaf ops (a loop's event spans its body's ops and is
  left out), named by their HLO instruction and summed by name.
- The window is the benchmark's own ``bench.window`` annotation.  Each idle
  gap is named by the innermost ``bench.*`` annotation that covers its
  middle, which says what the host was doing meanwhile.
"""
from __future__ import annotations

import glob
import os

OP_LINE = "XLA Ops"
PREFIX = "bench."
TOP = 10


def extract(log_dir: str) -> dict:
    """Device ops and benchmark annotations of the newest trace under
    ``log_dir``, as ``{"devices": {plane: [[start_ns, dur_ns, name]]},
    "host": [[start_ns, dur_ns, name]]}``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices.setdefault(plane.name, []).extend(
                        [e.start_ns, e.duration_ns, e.name] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.start_ns, e.duration_ns, e.name]
                            for e in line.events if e.name.startswith(PREFIX))
    return {"devices": devices, "host": host}


def _union(intervals, lo, hi):
    """Merged, clipped, sorted [start, end) intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _window(host):
    spans = [(s, s + d) for s, d, name in host if name == PREFIX + "window"]
    if not spans:
        raise ValueError("trace holds no bench.window annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _gap_name(host, t):
    inner = None
    for s, d, name in host:
        if s <= t < s + d and name != PREFIX + "window":
            if inner is None or s > inner[0]:
                inner = (s, name)
    return inner[1] if inner else "host.between_annotations"


def reduce(ex: dict) -> dict:
    """Busy and window seconds, the top device ops and the longest idle
    gaps of one extracted trace (see the module docstring)."""
    lo, hi = _window(ex["host"])
    if not ex["devices"]:
        raise ValueError("trace holds no device ops")
    busy = []
    per_op: dict = {}
    gaps = []
    for i, plane in enumerate(sorted(ex["devices"])):
        events = ex["devices"][plane]
        merged = _union(((s, s + d) for s, d, _ in events), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        inside = sorted((s, -d, name) for s, d, name in events if s < hi and s + d > lo)
        for j, (s, neg_d, name) in enumerate(inside):
            if j + 1 < len(inside) and inside[j + 1][0] < s - neg_d:
                continue  # holds the next op: not a leaf
            short = name.split(" = ", 1)[0]
            per_op[short] = per_op.get(short, 0) - neg_d
        if i == 0:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            gaps = sorted(((e - s, s) for s, e in zip(edges[::2], edges[1::2])
                           if e > s), reverse=True)[:TOP]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[_gap_name(ex["host"], s + ns / 2), ns / 1e9] for ns, s in gaps],
    }
