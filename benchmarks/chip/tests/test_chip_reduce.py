"""Trace reduction of the chip benchmark, on a hand-made trace with
known answers and on a small recorded TPU trace."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip import reduce  # noqa: E402

from chip_trace_cases import DEV, handmade  # noqa: E402


def test_handmade_busy_idle_and_kernel_time():
    ev = handmade()
    lo, hi = reduce.window(ev)
    assert (lo, hi) == (0, 100)
    assert reduce.devices(ev) == [DEV]
    d = reduce.Device(ev, DEV, lo, hi)
    assert d.busy == [[10, 40], [50, 70]]
    assert d.busy_ns == 50
    assert d.idle_share == pytest.approx(0.5)
    assert sum(e[4] for e in d.pallas()) == 15
    assert sum(e[4] for e in d.pallas("full_solve")) == 10
    assert sum(e[4] for e in d.pallas("resolve")) == 5
    assert d.module_count("resolve") == 1
    assert d.gaps() == [[0, 10], [40, 50], [70, 100]]


def test_handmade_breakdown():
    ev = handmade()
    d = reduce.Device(ev, DEV, *reduce.window(ev))
    ops = dict(reduce.top_ops(d))
    assert "while" not in ops
    assert ops["pallas:closed_call"] == pytest.approx(15e-9)
    assert ops["copy"] == pytest.approx(15e-9)
    gaps = reduce.idle_gaps(ev, d)
    assert gaps[0] == ["bench.day: build_inputs", pytest.approx(30e-9)]
    # [40, 50): the dispatch covers 6 of it, the runtime's Execute 4;
    # [0, 10): no host event but the benchmark's span
    assert sorted(g[0] for g in gaps[1:]) == [
        "bench.day: PjitFunction(resolve)", "bench.day: nothing traced"]


def test_window_is_required():
    with pytest.raises(RuntimeError):
        reduce.window([e for e in handmade() if e[2] != "bench.window"])


def test_union_and_clip():
    assert reduce.union([[5, 8], [0, 2], [1, 3], [8, 9]]) == [[0, 3], [5, 9]]
    assert reduce.clip([[0, 3], [5, 9]], 2, 6) == [[2, 3], [5, 6]]
    assert reduce.short("%closed_call.14 = f32[44] custom-call(...)") \
        == "closed_call"


def recorded():
    path = Path(__file__).with_name("data") / "trace_controller.json"
    return json.loads(path.read_text())["events"]


def test_recorded_trace_reduction():
    """One 256-cluster day-ahead solve and two re-solves on a v5e."""
    ev = recorded()
    lo, hi = reduce.window(ev)
    d = reduce.Device(ev, reduce.devices(ev)[0], lo, hi)
    assert hi - lo == 24491979
    assert d.busy_ns == 16754238
    assert 0.0 < d.idle_share < 1.0
    assert len(d.pallas()) == 24
    assert len(d.pallas("full_solve")) == 20            # 20 outer epochs
    assert sum(e[4] for e in d.pallas("full_solve")) == 16258696
    assert d.module_count("full_solve") == 1
    assert d.module_count("resolve") == 2
    assert sum(e[4] for e in d.pallas()) <= d.busy_ns
    top = reduce.top_ops(d, 3)
    assert top[0][0] == "pallas:closed_call"
    gaps = reduce.idle_gaps(ev, d, 3)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    assert gaps[0][0].startswith("bench.resolve: ")
