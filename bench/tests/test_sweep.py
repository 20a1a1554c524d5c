"""The sweep's picks of the load a serving cell's traffic file states."""

import pytest
import smoke  # noqa: F401

import sweep


def _line(rate, waiting):
    return {"rate": rate, "waiting": waiting}


def test_knee_is_the_last_rate_before_the_queue_grows():
    lines = [_line(0.9, [1, 3, 6, 9]), _line(0.3, [0, 0, 0, 0]),
             _line(0.6, [0, 1, 0, 1]), _line(1.2, [0, 0, 0, 0])]
    # 1.2 reads flat by chance, but 0.9 grew before it: the knee is 0.6
    assert sweep.knee(lines) == 0.6


def test_no_knee_when_the_lowest_rate_grows():
    assert sweep.knee([_line(0.3, [2, 4, 8, 16])]) is None


@pytest.mark.parametrize("target, want", [(17.0, 34), (4.0, 8)])
def test_count_scales_from_the_nearest_replay(target, want):
    lines = [{"count": 16, "wall_s": 8.0}, {"count": 32, "wall_s": 16.0}]
    assert sweep.count_for(lines, target) == want
