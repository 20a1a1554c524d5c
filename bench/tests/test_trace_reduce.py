"""The trace reduction on a small profile recorded on one TPU v5 lite
(`data/small.xplane.pb`: three rounds of a jitted add-and-reduce under a
`bench.step` span, a 2 ms sleep under `bench.batch`, and a jitted sort)."""

from pathlib import Path

import pytest
import smoke  # noqa: F401

import trace_reduce as tr

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(str(TRACE), 1)


def test_busy_and_window(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # the ops of three tiny steps take well under a millisecond, the host
    # sleeps and dispatches in between
    assert reduced["busy_s"] < 1e-3
    assert 0.05 < reduced["window_s"] < 0.2


def test_busy_is_the_union_of_op_intervals():
    c = tr.collect(tr.load(str(TRACE)))
    ops = c["devices"]["/device:TPU:0"]
    assert len(ops) == 24
    total = sum(e - s for s, e, _ in ops)
    busy = tr.union_length([(s, e) for s, e, _ in ops])
    assert busy <= total + 1e-12
    assert tr.reduce_collected(c, 1)["busy_s"] == pytest.approx(busy)


def test_op_classes_and_top_ops(reduced):
    classes = reduced["op_classes"]
    assert max(classes, key=classes.get) == "sort"
    assert sum(classes.values()) == pytest.approx(
        sum(v["s"] for v in reduced["ops"].values()))
    names = [n for n, _ in reduced["device_ops"]]
    assert names[0].startswith("%sort")
    assert len(names) <= tr.TOP
    assert all(len(n) <= 160 for n in names)


def test_idle_gaps_are_named_by_host_spans(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) == tr.TOP
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert {label for label, _ in gaps} <= {"bench.step", "bench.batch",
                                             "host idle"}
    assert "bench.batch" in {label for label, _ in gaps}
    assert reduced["host_spans"]["bench.batch"] > 0.006  # three 2 ms sleeps


def test_op_kind():
    assert tr.op_kind("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %a), "
                      "kind=kCustom, calls=%f") == "fusion kCustom"
    assert tr.op_kind("%copy.644 = s32[4]{0} copy(s32[4]{0} %b)") == "copy"
    assert tr.op_kind("fusion.12") == "fusion"
