"""Differential tests: slot-array queues vs the deque reference (§16).

The slot-array implementation the executor drains must be
indistinguishable from the original lock-guarded deques behind the public
queue API: identical pop/steal chunk sequences under identical op
sequences, identical counters and exactly-once task delivery. The
steal-amount memoization rests
on ``first_chunk`` / ``first_chunk_fn`` reproducing a fresh partitioner's
first chunk, so that equivalence is property-tested against the real
partitioners here too.
"""

import random

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (PARTITIONERS, CentralizedQueue, DistributedQueues,
                        RangeTask, SlotCentralizedQueue, SlotDistributedQueues,
                        first_chunk, first_chunk_fn, make_partitioner)

TECHS = sorted(PARTITIONERS)
LAYOUTS = ["PERCORE", "PERGROUP"]


def _tasks(n):
    return [RangeTask(i, i, 1, lambda s, z: None, 1.0) for i in range(n)]


def _ids(chunk):
    return [t.task_id for t in chunk]


# ---------------------------------------------------------------------------
# the steal-amount closure: first_chunk(_fn) == a fresh partitioner's chunk
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(
    tech=st.sampled_from(TECHS),
    r=st.integers(1, 100_000),
    p=st.integers(1, 64),
    seed=st.integers(0, 5),
)
def test_first_chunk_matches_fresh_partitioner(tech, r, p, seed):
    """The closed form and its specialized closure both reproduce the first
    chunk a fresh partitioner would hand out — the identity the slot
    queues' memoized steal amounts rest on."""
    want = make_partitioner(tech, r, p, seed=seed).next_chunk()
    assert first_chunk(tech, r, p, seed=seed) == want
    assert first_chunk_fn(tech, p, seed=seed)(r) == want


# ---------------------------------------------------------------------------
# centralized: identical pop sequences and counters
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(
    tech=st.sampled_from(TECHS),
    n=st.integers(0, 500),
    p=st.integers(1, 16),
    seed=st.integers(0, 3),
)
def test_centralized_differential(tech, n, p, seed):
    tasks = _tasks(n)
    dq = CentralizedQueue(tasks, make_partitioner(tech, max(1, n), p,
                                                  seed=seed))
    sq = SlotCentralizedQueue(tasks, tech, p, seed=seed)
    seen = []
    w = 0
    while True:
        a, b = dq.pop(w), sq.pop(w)
        assert _ids(a) == _ids(b)
        if not a:
            break
        seen.extend(_ids(a))
        w = (w + 1) % p
    assert dq.pops == sq.pops
    assert sorted(seen) == list(range(n))  # exactly once


# ---------------------------------------------------------------------------
# distributed: identical pop/steal sequences under a random op schedule
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(
    tech=st.sampled_from(TECHS),
    layout=st.sampled_from(LAYOUTS),
    n=st.integers(0, 400),
    p=st.integers(1, 8),
    seed=st.integers(0, 3),
    opseed=st.integers(0, 10_000),
)
def test_distributed_differential(tech, layout, n, p, seed, opseed):
    """Drive both implementations through the same interleaved pop/steal/
    push sequence: every chunk handed out, every steal amount, and every
    counter must match, and each task must surface exactly once."""
    tasks = _tasks(n)
    dq = DistributedQueues(tasks, tech, p, layout=layout, seed=seed)
    sq = SlotDistributedQueues(tasks, tech, p, layout=layout, seed=seed)
    assert dq.n_queues == sq.n_queues
    assert dq.queue_sizes() == sq.queue_sizes()

    rng = random.Random(opseed)
    popped = []
    for _ in range(3 * n + 10):
        w = rng.randrange(p)
        if rng.random() < 0.6:
            a, b = dq.pop_local(w), sq.pop_local(w)
            assert _ids(a) == _ids(b)
            popped.extend(_ids(a))
        else:
            v = rng.randrange(dq.n_queues)
            a, b = dq.steal(w, v), sq.steal(w, v)
            assert _ids(a) == _ids(b)
            if a:  # loot goes home as one chunk in both impls
                dq.push_local(w, a)
                sq.push_local(w, b)
        if len(dq) == 0:
            break

    # final drain: local pops first, then steal leftovers to worker 0
    while len(dq) or len(sq):
        moved = False
        for w in range(p):
            while True:
                a, b = dq.pop_local(w), sq.pop_local(w)
                assert _ids(a) == _ids(b)
                if not a:
                    break
                popped.extend(_ids(a))
                moved = True
        for v in range(dq.n_queues):
            a, b = dq.steal(0, v), sq.steal(0, v)
            assert _ids(a) == _ids(b)
            if a:
                popped.extend(_ids(a))
                moved = True
        assert moved or (len(dq) == 0 and len(sq) == 0)

    assert sorted(popped) == list(range(n))  # exactly once, nothing lost
    assert dq.local_pops == sq.local_pops
    assert dq.steals == sq.steals
    assert dq.failed_steals == sq.failed_steals
    assert len(dq) == len(sq) == 0


@settings(max_examples=15, deadline=None)
@given(
    tech=st.sampled_from(TECHS),
    n=st.integers(1, 300),
    p=st.integers(2, 8),
    seed=st.integers(0, 3),
)
def test_steal_to_home_matches_steal_plus_push(tech, n, p, seed):
    """The fused index-space theft lands the same tasks as the two-step
    surface, as one pop-able chunk in the thief's home queue."""
    tasks = _tasks(n)
    a = SlotDistributedQueues(tasks, tech, p, layout="PERCORE", seed=seed)
    b = SlotDistributedQueues(tasks, tech, p, layout="PERCORE", seed=seed)
    moved = a.steal_to_home(0, 1)
    loot = b.steal(0, 1)
    b.push_local(0, loot)
    assert moved == len(loot)
    if moved:
        # the loot drains behind worker 0's own pre-filled chunks in both
        while True:
            ca, cb = a.pop_local(0), b.pop_local(0)
            assert _ids(ca) == _ids(cb)
            if not ca:
                break
    assert a.steals == b.steals
    assert a.queue_sizes() == b.queue_sizes()


# ---------------------------------------------------------------------------
# slot internals the executor hot path depends on
# ---------------------------------------------------------------------------

def test_pop_view_survives_push_growth():
    """pop_local_idx hands out VIEWS of the index buffer; later pushes must
    never rewrite a popped head region (growth reallocates, not compacts)."""
    tasks = _tasks(64)
    q = SlotDistributedQueues(tasks, "STATIC", 2, layout="PERCORE")
    got = q.pop_local_idx(0)
    snapshot = got.copy()
    # push enough to force repeated buffer growth on worker 0's home queue
    for k in range(6):
        q.push_local(0, _tasks(64))
    assert np.array_equal(got, snapshot)


def test_stolen_loot_is_a_copy():
    """Steal returns a copy: the victim's tail region may be rewritten by
    later pushes, so loot must not alias the victim buffer."""
    tasks = _tasks(32)
    q = SlotDistributedQueues(tasks, "STATIC", 2, layout="PERCORE")
    loot = q._steal_indices(0, 1)
    assert loot is not None
    snapshot = loot.copy()
    q.push_local(1, _tasks(64))  # rewrites the victim's freed tail region
    assert np.array_equal(loot, snapshot)


def test_empty_queue_surfaces():
    q = SlotDistributedQueues([], "GSS", 2, layout="PERCORE")
    assert len(q) == 0
    assert q.pop_local(0) == []
    assert len(q.pop_local_idx(0)) == 0
    assert q.steal(0, 1) == []
    assert q.steal_to_home(0, 1) == 0
    assert q.failed_steals == 2
    c = SlotCentralizedQueue([], "GSS", 2)
    assert c.pop() == [] and len(c) == 0
