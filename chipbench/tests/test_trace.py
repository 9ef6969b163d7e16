"""The trace reduction on hand-made intervals, and on a small trace recorded
on a TPU v5e by ``record_trace.py`` (``data/tiny_trace.xplane.pb``)."""

from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data" / "tiny_trace.xplane.pb"


def test_reduce_by_hand():
    # window 0..100 ns; two rounds 10..40 and 50..90; a pause 92..98
    spans = [
        ("chipbench.window", 0, 100),
        ("chipbench.round", 10, 40),
        ("chipbench.round", 50, 90),
        ("chipbench.pause", 92, 98),
    ]
    ops = {
        0: [("a", -5, 15), ("b", 12, 30), ("a", 60, 80), ("c", 95, 130)],
        1: [("a", 20, 40)],
    }
    got = trace.reduce(spans, ops, [0, 1])
    # device 0 busy: [0, 30] + [60, 80] + [95, 100] = 55; device 1: 20
    assert got["busy_s"] == pytest.approx((55 + 20) / 2 * 1e-9)
    assert got["window_s"] == pytest.approx(100e-9)
    # a: (15 + 20 + 20) / 2, b: 18 / 2, c: 5 / 2
    assert got["device_ops"] == [["a", pytest.approx(27.5e-9)], ["b", pytest.approx(9e-9)], ["c", pytest.approx(2.5e-9)]]
    # device 0's gaps: 30..60 (middle 45: between the rounds, in the
    # window) and 80..95 (middle 87.5: in the second round)
    assert got["idle_gaps"] == [("chipbench.window", pytest.approx(30e-9)), ("chipbench.round", pytest.approx(15e-9))]
    assert got["idle_by_span"] == {"chipbench.window": pytest.approx(30e-9), "chipbench.round": pytest.approx(15e-9)}


def test_reduce_recorded_trace():
    # recorded on one TPU v5e: three rounds of a 2048 x 2048 bf16 matrix
    # product and a 5 ms sleep, then a 10 ms pause. The device's clock runs
    # about 1 ms ahead of the host spans, so the first round's operations
    # fall before the window and the others inside the round before theirs.
    spans, ops = trace.collect(str(DATA))
    assert sorted(n for n, _, _ in spans) == ["chipbench.pause"] + ["chipbench.round"] * 3 + ["chipbench.window"]
    got = trace.reduce(spans, ops, [0])
    # window 45,852,649 .. 76,419,338 ns; inside it two groups of
    # copy-start (13, 14 ns), copy-done (3, 3 ns) and the product's fusion
    # (90,868, 90,890 ns), each operation 1 ns after the one before
    assert got["window_s"] == pytest.approx(30_566_689e-9)
    assert got["busy_s"] == pytest.approx(181_791e-9)
    names = [n.split(" = ")[0] for n, _ in got["device_ops"]]
    assert names == ["%fusion", "%copy-start", "%copy-done"]
    assert [t for _, t in got["device_ops"]] == pytest.approx([181_758e-9, 27e-9, 6e-9])
    # gaps: after the last product to the window's end (in the pause), and
    # from the window's start and between the products (in the rounds)
    assert got["idle_gaps"][:3] == [
        ("chipbench.pause", pytest.approx(18_371_137e-9)),
        ("chipbench.round", pytest.approx(6_569_251e-9)),
        ("chipbench.round", pytest.approx(5_444_506e-9)),
    ]
    assert got["idle_by_span"] == {
        "chipbench.pause": pytest.approx(18_371_137e-9),
        "chipbench.round": pytest.approx(12_013_761e-9),
    }


def test_reduce_refuses_a_trace_without_the_device():
    with pytest.raises(ValueError):
        trace.reduce([("chipbench.window", 0, 10)], {}, [0])
