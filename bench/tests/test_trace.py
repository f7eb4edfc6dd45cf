"""The reduction from a profiler trace to busy time, kernel time and idle gaps."""

from pathlib import Path

import pytest

from bench import peaks, trace
from bench.metrics import device_idle_share, job_mfu, walker_ms_per_job, walker_roofline
from bench.pipelines import linreg
from bench.run import Measured

RECORDED = Path(__file__).parent / "data" / "higgs-linreg.solo.xplane.pb.gz"
HIGGS = {"cols": 29, "rows": 10_993_664}


def test_hand_built_case():
    devices = {
        "/device:TPU:0": [("k", 10, 20), ("k", 15, 30), ("copy", 40, 45), ("k", 90, 120)],
        "/device:TPU:1": [("x", 200, 300)],  # nothing in the window: not a device used
    }
    host = [("bench.window", 0, 100), ("compile", 45, 85), ("answer", 28, 33)]
    r = trace.reduce(devices, host, (0, 100))
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(35e-9)  # [10, 30] + [40, 45] + [90, 100]
    assert r.idle_share == pytest.approx(0.65)
    assert r.matching("^k$") == (pytest.approx(35e-9), 3)  # clipped, overlap counted
    assert r.ops == [["k", pytest.approx(35e-9)], ["copy", pytest.approx(5e-9)]]
    # [45, 90] is mostly compile; [0, 10] and [30, 40] only the window covers
    assert r.idle_gaps == [["compile", pytest.approx(45e-9)],
                           ["bench.window", pytest.approx(20e-9)]]


def test_gap_without_host_event_and_empty_window():
    r = trace.reduce({"/device:TPU:0": [("k", 0, 4)]}, [], (0, 10))
    assert r.idle_gaps == [[trace.NO_HOST_EVENT, pytest.approx(6e-9)]]
    with pytest.raises(ValueError):
        trace.reduce({"/device:TPU:0": [("k", 20, 30)]}, [], (0, 10))


@pytest.fixture(scope="module")
def recorded():
    devices, host = trace.load(RECORDED)
    return devices, host, trace.reduce(devices, host, trace.window_of(host))


def test_recorded_chip_trace(recorded):
    devices, host, r = recorded
    jobs = sum(1 for name, _, _ in host if name == "bench.job")
    assert list(devices) == ["/device:TPU:0"]
    assert 0 < r.busy_s < r.window_s
    assert sum(r.op_seconds.values()) >= r.busy_s
    seconds, count = r.matching(walker_ms_per_job.KERNEL)
    assert count == jobs > 0  # one walker launch per job
    assert 0 < seconds <= r.busy_s
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    assert len(r.ops) <= 10 and len(r.idle_gaps) <= 10
    assert r.idle_gaps[0][0] == "TpuCompiler::Compile"  # the walker's compile fills the gaps


def test_shares_stay_under_100_percent(recorded):
    devices, host, r = recorded
    jobs = sum(1 for name, _, _ in host if name == "bench.job")
    work = linreg.work(HIGGS, HIGGS["rows"], {"tenants": 1})
    least, bound = peaks.least_seconds(work["flops"], work["bytes"], "TPU v5 lite")
    assert bound == "memory"
    m = Measured(setup_s=0.0, window_s=r.window_s, jobs=jobs, latencies_s=[],
                 compiles=jobs, least_s=least, trace=r, trace_jobs=jobs)
    for metric in (walker_roofline, job_mfu, device_idle_share):
        assert 0 < metric.read(m) <= 100
    assert walker_roofline.read(m) > job_mfu.read(m)
    assert walker_ms_per_job.read(m) > least * 1e3


def test_span_time_per_host_event():
    devices = {"/device:TPU:0": [("k", 0, 100)]}
    host = [("bench.window", 10, 90), ("devdag.readback", 0, 20), ("devdag.readback", 40, 50),
            ("devdag.launch", 30, 35), ("devdag.readback", 95, 99)]  # after the window
    r = trace.reduce(devices, host, (10, 90))
    assert r.span_counts == {"bench.window": 1, "devdag.readback": 2, "devdag.launch": 1}
    assert r.span_seconds["devdag.readback"] == pytest.approx(20e-9)  # [10, 20) + [40, 50)
    assert r.span_seconds["devdag.launch"] == pytest.approx(5e-9)


def test_recorded_span_time(recorded):
    devices, host, r = recorded
    jobs = sum(1 for name, _, _ in host if name == "bench.job")
    assert r.span_counts["bench.job"] == r.span_counts["bench.run_device_dag"] == jobs
    assert 0 < r.span_seconds["bench.run_device_dag"] <= r.span_seconds["bench.job"] <= r.window_s


def test_recorded_program_spans():
    """A chip trace with the program's phases: one ``devdag.readback`` per job, of positive time."""
    devices, host = trace.load(RECORDED.with_name("higgs-linreg.solo.spans.xplane.pb.gz"))
    r = trace.reduce(devices, host, trace.window_of(host))
    jobs = sum(1 for name, _, _ in host if name == "bench.job")
    assert r.span_counts["devdag.readback"] == jobs > 0
    assert 0 < r.span_seconds["devdag.readback"] < r.span_seconds["devdag.job"] <= r.window_s
