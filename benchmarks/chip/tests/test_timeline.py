import json
import pathlib

import pytest

import timeline
import tracereduce

DATA = pathlib.Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "trace_synth50k_join.json"
SCOPED = DATA / "trace_synth50k_join_scoped.json"


def _hand_made():
    return {
        "devices": {"/device:TPU:0": [
            [100, 100, "while.1", None],            # holds the next two: not a leaf
            [100, 50, "fusion.1", "knn.matmul"],
            [150, 50, "fusion.2", "knn.scatter"],
            [300, 60, "copy.3", "knn.scatter"],
            [400, 30, "sort.4", "knn.topk"],
            [500, 20, "pad.5", None],
        ]},
        "host": [
            [50, 500, "bench.window"],
            [60, 480, "bench.query"],
            [70, 460, "knn.store.r_block"],
            [210, 80, "knn.store.prep"],
            [290, 5, "knn.store.launch"],
            [360, 40, "knn.store.pull"],
            [430, 70, "knn.store.wait"],
            [40, 30, "knn.store.prep"],            # clipped to the window
        ],
    }


def test_scopes_sum_to_the_leaf_ops_with_unscoped_ops_as_other():
    r = timeline.reduce(_hand_made())
    assert r["scopes"] == {
        "knn.matmul": pytest.approx(50e-9), "knn.scatter": pytest.approx(110e-9),
        "knn.topk": pytest.approx(30e-9), "other": pytest.approx(20e-9)}
    assert r["leaf_s"] == pytest.approx(210e-9)
    plain = {"devices": {p: [e[:3] for e in evs] for p, evs in _hand_made()["devices"].items()},
             "host": _hand_made()["host"]}
    leaf = sum(s for name, s in tracereduce.reduce(plain)["device_ops"])
    assert r["leaf_s"] == pytest.approx(leaf)


def test_host_spans_and_gaps_are_named_by_the_program():
    r = timeline.reduce(_hand_made())
    assert r["host_spans"]["knn.store.prep"] == pytest.approx(100e-9)  # 80 + 20 clipped
    assert r["host_spans"]["knn.store.wait"] == pytest.approx(70e-9)
    assert [g[0] for g in r["idle_gaps"]] == [
        "knn.store.prep",       # [200, 300): host prep, the device idle
        "knn.store.wait",       # [430, 500)
        "knn.store.r_block",    # [50, 100)
        "knn.store.pull",       # [360, 400)
        "bench.query",          # [520, 550): after the block's span ends
    ]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [100e-9, 70e-9, 50e-9, 40e-9, 30e-9])


def test_recorded_scoped_chip_trace(monkeypatch):
    """A 140 ms cut of a traced synth50k.join window on one v5e, around the
    idle gap between the two R blocks of one store call: every reading is
    there, the scopes add up to all leaf ops, and the gap is host prep."""
    ex = json.loads(SCOPED.read_text())
    r = timeline.reduce(ex)
    sc, hs = r["scopes"], r["host_spans"]
    host = sum(hs[f"knn.store.{p}"] for p in ("prep", "launch", "pull"))
    for seconds in (sc["knn.scatter"], sc["knn.matmul"], sc["knn.topk"], host):
        assert timeline.ms_per_block(seconds, 4096) > 0
    assert sc["other"] > 0
    monkeypatch.setattr(tracereduce, "TOP", 10**6)
    every_leaf = sum(s for _, s in tracereduce.reduce(
        {"devices": {p: [e[:3] for e in evs] for p, evs in ex["devices"].items()},
         "host": ex["host"]})["device_ops"])
    assert sum(sc.values()) == pytest.approx(every_leaf)
    assert r["idle_gaps"][0][0] == "knn.store.prep"
    assert r["idle_gaps"][0][1] > 0.01


def test_scope_of_takes_the_innermost_knn_component():
    assert timeline.scope_of(
        "jit(local)/while/body/knn.scatter/jit(_where)/select_n") == "knn.scatter"
    assert timeline.scope_of("jit(f)/knn.topk/knn.bound/gather") == "knn.bound"
    assert timeline.scope_of("jit(knn_topk)/dot_general") is None
    assert timeline.scope_of(None) is None


def test_a_trace_without_names_reduces_as_before():
    """The older recorded chip trace (no scopes, no knn.* events): every
    number tracereduce gives is unchanged, and all device time is other."""
    ex = json.loads(FIXTURE.read_text())
    old = tracereduce.reduce(ex)
    new = timeline.reduce(ex)
    assert {k: new[k] for k in old} == old
    assert set(new["scopes"]) == {"other"}
    assert new["host_spans"] == {}


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _len(number, payload):
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _int(number, value):
    return _varint(number << 3) + _varint(value)


def _entry(number, key, message):
    return _len(number, _int(1, key) + _len(2, message))


def test_op_scopes_are_read_from_the_device_planes_event_metadata():
    """A hand-encoded XSpace: the tf_op stat as a string and as a
    reference, an op without it, a name whose entries disagree, and a host
    plane that is skipped."""
    def stat(value):
        return _len(5, _int(1, 7) + _len(5, value.encode()))

    ref_stat = _len(5, _int(1, 7) + _int(7, 9))
    device = b"".join([
        _len(2, b"/device:TPU:0"),
        _entry(5, 7, _int(1, 7) + _len(2, b"tf_op")),
        _entry(5, 9, _int(1, 9) + _len(2, b"jit(local)/while/body/knn.topk/top_k:")),
        _entry(4, 1, _len(2, b"%fusion.51 = f32[8]")
               + stat("jit(local)/while/body/knn.scatter/scatter-add:")),
        _entry(4, 2, _len(2, b"%sort.10 = f32[8]") + ref_stat),
        _entry(4, 3, _len(2, b"%while.46 = f32[8]")),
        _entry(4, 4, _len(2, b"%copy.1 = f32[8]") + stat("jit(f)/knn.matmul/copy:")),
        _entry(4, 5, _len(2, b"%copy.1 = f32[8]") + stat("jit(g)/knn.bound/copy:")),
    ])
    host = _len(2, b"/host:CPU") + _entry(4, 1, _len(2, b"%fusion.51 = f32[8]")
                                          + stat("knn.matmul/x:"))
    space = _len(1, device) + _len(1, host)
    assert timeline._device_op_scopes(space) == {"/device:TPU:0": {
        "%fusion.51 = f32[8]": "knn.scatter",
        "%sort.10 = f32[8]": "knn.topk",
        "%while.46 = f32[8]": None,
        "%copy.1 = f32[8]": None,
    }}
