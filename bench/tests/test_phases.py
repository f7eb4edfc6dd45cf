"""The device-idle time under the program's ``devdag.*`` phases, on hand-built spans."""

import pytest

from bench import phases


def test_innermost_segments_of_nested_spans():
    spans = [("devdag.job", 10, 90), ("devdag.launch", 20, 50),
             ("devdag.readback", 60, 80), ("bench.job", 0, 100)]
    got = phases.innermost_segments([s for s in spans if s[0].startswith("devdag.")], 0, 100)
    assert got == [(0, 10, phases.OUTSIDE), (10, 20, "devdag.job"),
                   (20, 50, "devdag.launch"), (50, 60, "devdag.job"),
                   (60, 80, "devdag.readback"), (80, 90, "devdag.job"),
                   (90, 100, phases.OUTSIDE)]
    # spans are clipped to the window
    assert phases.innermost_segments([("devdag.job", -5, 30)], 0, 20) == [(0, 20, "devdag.job")]


def test_idle_spans_innermost_wins():
    host = [("bench.window", 0, 100), ("bench.job", 0, 100),
            ("devdag.job", 10, 90), ("devdag.launch", 20, 50),
            ("devdag.readback", 60, 80)]
    # the device runs [40, 70): idle [0, 40) and [70, 100)
    devices = {"/device:TPU:0": [("walker", 40, 70)],
               "/device:TPU:1": []}  # no operation: not a device used
    idle = phases.idle_spans(devices, host, (0, 100))
    assert idle == {
        "devdag.launch": pytest.approx(20e-9),   # [20, 40)
        phases.OUTSIDE: pytest.approx(20e-9),    # [0, 10) and [90, 100)
        "devdag.job": pytest.approx(20e-9),      # [10, 20) and [80, 90)
        "devdag.readback": pytest.approx(10e-9),  # [70, 80)
    }
    assert sum(idle.values()) == pytest.approx(70e-9)  # every idle ns once


def test_idle_spans_without_device_work_raises():
    with pytest.raises(ValueError):
        phases.idle_spans({"/device:TPU:0": [("k", 200, 300)]}, [], (0, 100))
