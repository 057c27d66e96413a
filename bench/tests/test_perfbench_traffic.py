"""The traffic generator, the window's driver, the answer sample and the
verdict, on fakes: no JAX work, no chip."""

from __future__ import annotations

import contextlib
import json

import pytest

from bench import traffic
from bench.harness import Driver, Req, Run, Sample, judge
from perfbench_tiny import BENCH

SAT = json.loads((BENCH / "traffic/sat-int8.json").read_text())


def _run(mix, seconds=10.0):
    return Run(cell="c", cfg={}, mix=mix, seconds=seconds, seed=1, trace=False,
               peaks={}, flops_per_request=1.0)


def test_large_seeds_are_hashed_not_truncated():
    a, b = traffic.seed_words(2 ** 40 + 5), traffic.seed_words(5)
    assert a != b and all(0 <= w < 2 ** 31 for w in a + b)
    assert traffic.seed_words(2 ** 40 + 5) == a


def test_warm_sizes_cover_every_batch_the_engine_can_form():
    assert traffic.warm_batch_sizes(SAT) == [8]  # clients fill whole batches
    assert traffic.warm_batch_sizes(dict(SAT, clients=12)) == list(range(1, 9))


@pytest.mark.parametrize("change", [{"loop": "open"}, {"clients": 0},
                                    {"check": {"sample": 0}}, {"prompt_len": 0}])
def test_a_mix_outside_the_generator_is_refused(change):
    with pytest.raises(ValueError):
        traffic.validate(dict(SAT, **change))


class Answer:
    def __init__(self, idx):
        self.request = FakeRequest(idx)
        self.request.result = [float(idx)]
        self.idx, self.answer = idx, None


def _sampled(seed, n=300, k=8):
    s = Sample(k, traffic.rng(seed, "check"))
    answers = [Answer(i) for i in range(n)]
    for a in answers:
        s.offer(a)
    return s, answers


def test_the_sample_is_seeded_and_keeps_only_its_answers():
    s, answers = _sampled(2 ** 40 + 3)
    kept = {a.idx for a in s.kept}
    assert len(kept) == 8 and s.seen == 300
    assert kept == {a.idx for a in _sampled(2 ** 40 + 3)[0].kept}
    assert kept != {a.idx for a in _sampled(5)[0].kept}
    # kept answers are copied to the host; every device answer is let go
    assert all(a.request.result is None for a in answers)
    assert all((a.answer is not None) == (a.idx in kept) for a in answers)


def test_the_sample_is_drawn_over_the_whole_run():
    """Every answer is as likely to be kept: over many seeds each third of
    the run holds about a third of the kept answers."""
    thirds = [0, 0, 0]
    for seed in range(200):
        for a in _sampled(seed, n=90, k=6)[0].kept:
            thirds[a.idx // 30] += 1
    assert all(abs(t - 400) < 80 for t in thirds), thirds
    s, _ = _sampled(9, n=5, k=8)  # fewer answers than the sample: all are kept
    assert sorted(a.idx for a in s.kept) == [0, 1, 2, 3, 4]


@pytest.mark.parametrize("errs,failed,limit,correct", [
    ([0.05, 0.06], 0, 0.25, True),
    ([0.05, 0.3], 0, 0.25, False),  # one answer over the limit
    ([0.05], 1, 0.25, False),  # a request due in the window failed
    ([], 0, 0.25, False),  # nothing compared
    ([0.05], 0, None, False),  # no limit stated
])
def test_the_verdict(errs, failed, limit, correct):
    ok, checks = judge(errs, failed, limit)
    assert ok is correct
    assert list(checks) == ["rel_err_max", "compared", "failed"]
    assert all(set(v) == {"value", "limit"} for v in checks.values())


class FakeResult:
    def __init__(self, ready_after):
        self.ready_after = ready_after

    def is_ready(self):
        self.ready_after -= 1
        return self.ready_after <= 0


class FakeRequest:
    def __init__(self, req_id):
        self.req_id, self.attempts, self.result = req_id, 0, None


class FakeLoop:
    def __init__(self):
        self.queue, self.failed = [], []

    @property
    def backlog(self):
        return len(self.queue)


class FakeDeployment:
    """Completes one queued request per step; its answer is ready after
    two polls.  Records the most requests ever outstanding."""

    def __init__(self):
        self.loop, self.pending, self.next_id = FakeLoop(), 0, 0
        self.outstanding, self.peak = 0, 0

    def submit(self, x):
        r = FakeRequest(self.next_id)
        self.next_id += 1
        self.loop.queue.append(r)
        self.outstanding += 1
        self.peak = max(self.peak, self.outstanding)
        return r

    def step(self):
        r = self.loop.queue.pop(0)
        r.result = FakeResult(2)
        self.outstanding -= 1
        return [r]


def test_closed_loop_keeps_exactly_its_clients_busy():
    d = FakeDeployment()
    run = _run(SAT, seconds=0.05)
    drv = Driver(d, run, rows=list(range(8)), sample=Sample(2, traffic.rng(3, "check")),
                 annotate=lambda name: contextlib.nullcontext())
    drv.drive(0.05)
    assert d.peak == SAT["clients"]
    assert len({r.client for r in run.reqs}) == SAT["clients"]
    assert len(run.reqs) > SAT["clients"]  # clients came back for more
    assert all(r.due_s < 0.05 for r in run.reqs)
    # the window closed on the first answers at or after its 0.05 s
    assert run.window_s >= 0.05
    assert run.window_s in {r.done_s for r in run.reqs}
    assert not any(0.05 <= (r.done_s or 0) < run.window_s for r in run.reqs)


def test_a_batch_is_ready_when_all_its_answers_are():
    run = _run(SAT, seconds=10.0)
    drv = Driver(None, run, rows=[0], sample=Sample(1, traffic.rng(3, "check")),
                 annotate=lambda name: contextlib.nullcontext())
    batch = [Req(i, i, due_s=0.0, prompt=0, request=FakeRequest(i)) for i in range(3)]
    for r, after in zip(batch, (1, 1, 3)):  # the last slice lags two polls
        r.request.result = FakeResult(after)
    drv.awaiting = [batch]
    assert drv.poll() == [] and drv.poll() == []
    assert drv.poll() == batch  # all three come back together
    assert len({r.done_s for r in batch}) == 1 and drv.awaiting == []
    # one answer is kept for the check, on the host; no device answer is held
    assert all(r.request.result is None for r in batch)
    assert sum(r.answer is not None for r in batch) == 1
