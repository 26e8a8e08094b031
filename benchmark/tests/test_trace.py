"""The trace reduction, on a hand-made trace and on a recorded one."""

import json
import os

import pytest

from benchmark.harness import trace as tracing

DATA = os.path.join(os.path.dirname(__file__), "data")


def recorded():
    with open(os.path.join(DATA, "ttfc_trace.json")) as f:
        raw = json.load(f)
    return {
        "devices": {int(k): v for k, v in raw["devices"].items()},
        "host": [tuple(h) for h in raw["host"]],
    }


def test_hand_made_trace():
    # Window 0..100 ns; two chips. Chip 0: a while 10..50 holding fusions
    # 12..20 and 30..40, then an all-to-all 60..70. Chip 1: busy 0..30.
    trace = {
        "devices": {
            0: {
                "ops": [
                    ("while.1", 10, 50), ("fusion.2", 12, 20), ("fusion.3", 30, 40),
                    ("all_to_all.4", 60, 70),
                ],
                "collectives": [(60, 70)],
                "modules": [("jit_loop", 5, 75)],
            },
            1: {"ops": [("fusion.9", 0, 30)], "modules": [("jit_loop", 0, 30)]},
        },
        "host": [
            ("bench:window", 0, 100), ("bench:spawn", 0, 10), ("bench:join", 10, 80),
            ("bench:path", 80, 100),
        ],
    }
    r = tracing.reduce(trace, [0, 1])
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((50 + 30) / 2 * 1e-9)
    assert r["collective_s"] == pytest.approx(10 / 2 * 1e-9)
    ops = dict(r["device_ops"])
    assert ops["jit_loop/while.1"] == pytest.approx(22e-9)
    assert ops["jit_loop/fusion.2"] == pytest.approx(8e-9)
    assert ops["jit_loop/all_to_all.4"] == pytest.approx(10e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx(
        {"bench:spawn": 10e-9, "bench:join": 10e-9 + 10e-9, "bench:path": 20e-9}
    )


def test_recorded_trace_adds_up():
    trace = recorded()
    r = tracing.reduce(trace, [0])
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(v for _k, v in r["idle_gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-9)
    assert r["collective_s"] == 0
    # Every op lies in a program run, and nested ops give self time that
    # adds up to no more than the window.
    assert all(not name.startswith("?/") for name, _s in r["device_ops"])
    assert sum(s for _n, s in r["device_ops"]) <= r["window_s"]
    assert [s for _n, s in r["device_ops"]] == sorted(
        (s for _n, s in r["device_ops"]), reverse=True
    )


def test_merge_clips_and_joins():
    assert tracing.merge([(5, 15), (0, 3), (2, 4), (14, 30)], 1, 20) == [[1, 4], [5, 20]]


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce({"devices": {}, "host": [("bench:join", 0, 1)]}, [0])


def test_collectives_are_found_by_opcode():
    text = (
        "%all_to_all.678 = u32[4,1344,1]{1,2,0:T(1,128)S(1)} all-to-all(%all_to_all.677),"
        " channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}"
    )
    assert tracing._COLLECTIVE.search(text)
    assert tracing._COLLECTIVE.search("%psum.3 = u32[8]{0} all-reduce-start(u32[8]{0} %x)")
    assert not tracing._COLLECTIVE.search("%fusion.9 = u32[8]{0} fusion(%all_to_all.678)")


def test_short_names():
    assert tracing.short_op("%fusion.12 = u32[8]{0} fusion(u32[8]{0} %x)") == "fusion.12"
    assert tracing.short_module("jit_loop(1326472792497226314)") == "jit_loop"
