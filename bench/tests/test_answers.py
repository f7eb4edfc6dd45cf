"""The closed loop's bookkeeping of answers: latencies, what a call owes, whose answer it is."""

from pathlib import Path

import pytest

from bench import peaks
from bench import run as harness
from bench.metrics import job_mfu

OWN_JOB = harness.load_module(Path(__file__).parent / "matmul_requests.py")


def calls_of(*outs):
    """A job returning ``outs`` one call after another (calling any callable), then raising."""
    it = iter(outs)

    def job():
        out = next(it, None)
        if out is None:
            raise RuntimeError("no more calls")
        return out() if callable(out) else out

    return job


def at(*answers):
    """A call whose answers' times are offsets in seconds from the call's start."""
    import time

    def call():
        t0 = time.perf_counter()
        time.sleep(max(a[2] for a in answers))  # no answer before its time
        return [(key, answer, t0 + t, *(t0 + s for s in arrived))
                for key, answer, t, *arrived in answers]

    return call


def test_latency_runs_from_arrival_where_given():
    job = calls_of(at(((0, 0), "a", 0.030, 0.005), ((0, 1), "b", 0.030, 0.020)))
    win = harness.run_window(job, 0.01, [0], [(0, 0), (0, 1)])
    assert win["answers"][:2] == [((0, 0), "a"), ((0, 1), "b")]
    assert win["latencies_s"][:2] == [pytest.approx(0.025), pytest.approx(0.010)]
    assert win["unanswered"] == 0


def test_latency_runs_from_the_call_without_arrival():
    win = harness.run_window(calls_of(at(((0, 0), "a", 0.030))), 0.01, [0], [(0, 0)])
    assert 0.030 <= win["latencies_s"][0] < 0.5


@pytest.mark.parametrize("arrived, answered", [
    (-0.010, 0.020),   # arrived before the call started
    (0.030, 0.020),    # arrived after its answer
])
def test_an_answer_whose_arrival_lies_outside_its_call_fails(arrived, answered):
    job = calls_of(at(((0, 0), "a", answered, arrived), ((0, 1), "b", answered, 0.0)))
    win = harness.run_window(job, 0.01, [0], [(0, 0), (0, 1)])
    assert win["answers"][0] == ((0, 1), "b")  # the other answer of the call stands
    assert win["errors"][0].startswith("answer (0, 0): arrived")
    assert win["unanswered"] == 1 + 2 * (win["started"] - 1)  # and each later call fails both


def test_an_answer_timed_after_its_call_returned_fails():
    import time

    job = calls_of(lambda: [((0, 0), "a", time.perf_counter() + 1.0)])
    win = harness.run_window(job, 0.01, [0], [(0, 0)])
    assert win["answers"] == [] and win["errors"][0].startswith("answer (0, 0)")


def test_short_surplus_and_raising_calls():
    owed = [(0, 0), (1, 0)]
    job = calls_of(at(((0, 0), "a", 0.0)),                                    # one of two owed
                   at(((0, 0), "a", 0.0), ((1, 0), "b", 0.0), ((1, 1), "c", 0.0)))  # a key not owed
    win = harness.run_window(job, 0.05, [0], owed)
    assert win["answers"] == [((0, 0), "a")]  # a call with a key it does not owe keeps none
    assert win["errors"][0].startswith("a call returned keys [(0, 0), (1, 0), (1, 1)]")
    assert all(e == "RuntimeError: no more calls" for e in win["errors"][1:])
    assert win["started"] == len(win["errors"]) + 1
    assert win["unanswered"] == 1 + 2 * len(win["errors"])


def test_a_key_returned_twice_fails_all_the_call_owes():
    owed = [(0, r) for r in range(4)]
    job = calls_of(at(*[((0, 0), "a", 0.0)] * 4))
    win = harness.run_window(job, 0.01, [0], owed)
    assert win["answers"] == [] and win["unanswered"] == 4 * win["started"]


def test_owed_keys_that_repeat_or_name_no_tenant_are_refused():
    def pipeline(keys):
        return type("P", (), {"owed": staticmethod(lambda cfg, traffic: keys)})

    assert harness.owed_of(pipeline([[0, 1], [1, 0]]), {}, {"tenants": 2}) == [(0, 1), (1, 0)]
    for keys in ([(0, 0), (0, 0)], [(2, 0)], []):
        with pytest.raises(SystemExit):
            harness.owed_of(pipeline(keys), {}, {"tenants": 2})


class Exact:
    """Checker that reads an answer as the tenant's seed plus the request it answers."""

    def __init__(self, cfg, rows, seed):
        self.seed = seed

    def __call__(self, got, request):
        return {"off": float(abs(got - self.seed - request))}


@pytest.mark.parametrize("answers, failed", [
    ([((0, 0), 10), ((1, 0), 11), ((1, 1), 12), ((0, 2), 12)], 0),
    ([((0, 0), 10), ((1, 0), 11), ((1, 1), 11), ((0, 0), 10)], 1),  # request 0's answer for 1
])
def test_every_answer_is_judged_by_its_tenant_and_request(answers, failed):
    pipeline = type("P", (), {"Checker": Exact})
    got, worst, control = harness.check_answers(pipeline, {}, None, [10, 11], answers,
                                                {"off": 0.5})
    assert got == failed and control == {}
    assert worst["off"] == float(failed)


@pytest.mark.parametrize("requests", [1, 4, 8])
def test_job_mfu_of_an_own_job_does_not_scale_with_the_answers_a_call_owes(requests):
    """A call of ``requests`` answers that takes twice its least time reads 50%, however
    many answers it owes: ``work`` counts one answer, and ``jobs`` counts answers."""
    cfg, traffic = {"width": 4096}, {"requests": requests, "arrival_gap_ms": 0.0}
    owed = len(OWN_JOB.owed(cfg, traffic))
    width = cfg["width"]
    call_least, _ = peaks.least_seconds(2.0 * requests * width * width,
                                        4.0 * width * width + 8.0 * requests * width,
                                        "TPU v5 lite")
    work = OWN_JOB.work(cfg, None, traffic)
    least_s, _ = peaks.least_seconds(work["flops"], work["bytes"], "TPU v5 lite")
    assert least_s * owed == pytest.approx(call_least)
    calls = 100
    m = harness.Measured(setup_s=0.0, window_s=calls * 2 * call_least, jobs=calls * owed,
                         latencies_s=[], compiles=0, least_s=least_s)
    assert job_mfu.read(m) == pytest.approx(50.0)
