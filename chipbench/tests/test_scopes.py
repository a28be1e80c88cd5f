"""reduce/scopes.py: the wire-format reader on a synthetic XSpace (encoded
here, field numbers of xplane.proto) and the grouping by named scope on a
trace recorded on the chip (reduce/recorded_scope_trace.json, PR 27).

    python3 -m pytest chipbench/tests/test_scopes.py
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench.reduce import scopes  # noqa: E402


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num, val):
    """One field: an int as a varint, bytes / str length-delimited."""
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    val = val.encode() if isinstance(val, str) else val
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def _space():
    stat_meta = [_f(5, _f(1, 7) + _f(2, _f(1, 7) + _f(2, "tf_op"))),
                 _f(5, _f(1, 9) + _f(2, _f(1, 9) + _f(2, "flops")))]

    def op(mid, name, tf_op):
        stats = _f(5, _f(1, 9) + _f(3, 1234)) + _f(5, _f(1, 7) + _f(5, tf_op))
        return _f(4, _f(1, mid) + _f(2, _f(1, mid) + _f(2, name) + stats))

    metas = [op(1, "%while.1 = (f32[]) while(...)",
                "jit(_step)/jit(main)/kda/kda.core/while"),
             op(2, "%fusion.2 = f32[8] fusion(...)",
                "jit(_step)/jit(main)/transpose(jvp(kda))/kda.core/dot_general:"),
             op(3, "%mla.3 = bf16[1] custom-call(...)",
                "jit(_step)/jit(main)/checkpoint/mla/pallas_call:"),
             op(4, "%fusion.4 = f32[8] fusion(...)", "jit(_step)/jit(main)/add")]
    ev = lambda mid, off_ps, dur_ps: _f(4, _f(1, mid) + _f(2, off_ps)
                                       + _f(3, dur_ps))
    line = _f(3, _f(2, "XLA Ops") + _f(3, 1000)
              + ev(1, 0, 10_000_000)        # while, 10 us, spans the next
              + ev(2, 1_000_000, 4_000_000)  # 4 us inside it
              + ev(3, 12_000_000, 6_000_000)
              + ev(4, 20_000_000, 2_000_000))
    other = _f(3, _f(2, "XLA Modules") + _f(3, 1000) + ev(1, 0, 5_000_000))
    dev = _f(1, _f(2, "/device:TPU:0") + b"".join(stat_meta + metas)
             + line + other)
    host = _f(1, _f(2, "/host:CPU") + line)
    return dev + host


def test_wire_reader_and_grouping(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_space())
    ev = scopes.load(str(path))
    assert [e[1] for e in ev] == ["%while.1", "%fusion.2", "%mla.3",
                                  "%fusion.4"]
    assert ev[1][2:4] == (1000 + 1000, 4000)  # ns: line start + offset
    assert ev[2][4].endswith("mla/pallas_call:")
    red = scopes.by_scope(ev, scopes.SCOPES)
    want = {"kda.core": 10e-6, "mla": 6e-6, "other": 2e-6}  # while 6 + 4 inside
    assert {k: round(v, 12) for k, v in red["scope_s"].items()} == want
    assert abs(red["busy_s"] - 18e-6) < 1e-12 and red["devices"] == 1


def test_scope_of_prefers_the_inner_scope_listed_first():
    f = lambda s: scopes.scope_of(s, scopes.SCOPES)
    assert f("jit(f)/checkpoint/kda/kda.core/neg:") == "kda.core"
    assert f("jit(f)/transpose(jvp(kda))/mul:") == "kda"
    assert f("jit(f)/rematted_computation/moe.experts/ragged_dot:"
             ) == "moe.experts"
    assert f("jit(f)/moe.route/sort:") == "moe.route"
    assert f("jit(f)/kdax/add:") == "other" and f("") == "other"


def test_recorded_chip_trace():
    path = os.path.join(os.path.dirname(HERE), "reduce",
                        "recorded_scope_trace.json")
    with open(path) as f:
        rec = json.load(f)
    red = scopes.by_scope(scopes.load_json(path), scopes.SCOPES)
    assert red["devices"] == rec["expect"]["devices"] == 1
    assert abs(red["busy_s"] - rec["expect"]["busy_s"]) < 1e-12
    for k, v in rec["expect"]["scope_s"].items():
        assert abs(red["scope_s"][k] - v) < 1e-12, k
    # Self times partition the busy time (one line, ops do not overlap
    # except parent and child).
    assert abs(sum(red["scope_s"].values()) - red["busy_s"]) < 1e-9
    assert red["scope_s"]["kda.core"] > red["scope_s"]["mla"] > 0
