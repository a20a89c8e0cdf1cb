"""The closed loop: the generator's one sequence and its pool of clients on a
fake system, then the toy cell on the CPU -- saturated by construction,
whatever the engine's speed."""
import collections
import json
import threading
import time

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import loadgen
from benchmarks.tests import toy

MIX = {"kind": "closed", "clients": 6, "sequence_len": 40, "order_seed": 23,
       "prompt_lens": [32, 64, 128, 256, 512],
       "prompt_weights": [0.20, 0.30, 0.25, 0.15, 0.10],
       "output_lens": [16, 32, 64, 128, 256],
       "output_weights": [0.30, 0.30, 0.20, 0.15, 0.05]}


def _take(mix, seed, n):
    requests = loadgen.sequence(mix, seed, 50257)
    return [next(requests) for _ in range(n)]


def test_sequence_is_the_mixs_own_and_the_seed_draws_the_ids():
    a, b = _take(MIX, 1, 100), _take(MIX, 2 ** 31 + 7, 100)
    assert [(len(x.prompt), x.max_new) for x in a] \
        == [(len(x.prompt), x.max_new) for x in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.index for x in a] == list(range(100))
    assert all(1 <= x.prompt.min() and x.prompt.max() <= 50257 for x in a)
    # every stretch of sequence_len holds the menus' weights exactly, and
    # the stretches differ in order
    for lo in (0, 40):
        stretch = a[lo:lo + 40]
        assert collections.Counter(len(x.prompt) for x in stretch) \
            == {32: 8, 64: 12, 128: 10, 256: 6, 512: 4}
        assert collections.Counter(x.max_new for x in stretch) \
            == {16: 12, 32: 12, 64: 8, 128: 6, 256: 2}
    assert [x.max_new for x in a[:40]] != [x.max_new for x in a[40:80]]
    # without order_seed the run's seed draws the order too
    free = {k: v for k, v in MIX.items() if k != "order_seed"}
    assert [x.max_new for x in _take(free, 1, 40)] \
        != [x.max_new for x in _take(free, 2, 40)]


class _Answer:
    """A fake system's answer: a token every 2 ms, ``n`` of them."""

    def __init__(self, fired, n):
        self.fired, self.n, self.stamps, self.cancelled = fired, n, [], False
        self.t0 = time.perf_counter()

    def poll(self, now):
        if self.fired.handle is None or self.cancelled:
            return True
        arrived = min(self.n, int((now - self.t0) / 0.002))
        self.stamps += [now] * (arrived - len(self.stamps))
        return arrived == self.n

    def cancel(self):
        self.cancelled = True


def test_clients_wait_for_their_answer_and_send_the_sequence_in_order():
    """A fake system that answers in 6-14 ms: never more than ``clients``
    requests outstanding, the requests sent in the sequence's order from one
    thread, a refusal recorded, and what is in flight cancelled at the stop."""
    sent, answers, threads = [], [], set()
    stop = threading.Event()

    def submit(a):
        sent.append(a.index)
        threads.add(threading.current_thread().name)
        assert sum(not x.poll(time.perf_counter()) for x in answers) < 6
        if a.index == 9:
            raise RuntimeError("refused")
        return a.index

    def on_fired(f):
        answers.append(_Answer(f, 3 + 2 * (f.arrival.index % 3)))
        return answers[-1]

    mix = dict(MIX, poll_s=0.0005)
    t_open = time.perf_counter() + 0.1
    loop = threading.Thread(target=loadgen.closed_loop, name="the-loop", args=(
        mix, loadgen.sequence(mix, 5, 1000), submit, t_open, on_fired, stop))
    loop.start()
    time.sleep(0.4)
    stop.set()
    loop.join(timeout=5)
    assert not loop.is_alive()
    assert sent == list(range(len(sent))) and len(sent) > 100
    assert threads == {"the-loop"}
    refused = answers[9].fired
    assert refused.handle is None and "refused" in refused.error
    # every answer but those cancelled at the stop (at most 6) came whole,
    # stamped as it arrived: 2 ms a token, to within a poll or two
    whole = [x for x in answers if len(x.stamps) == x.n]
    assert len(answers) - len(whole) - 1 <= 6
    assert sum(x.cancelled for x in answers) <= 6
    gaps = [b - a for x in whole for a, b in zip(x.stamps, x.stamps[1:])]
    assert 0.0015 < sorted(gaps)[len(gaps) // 2] < 0.004
    # due when sent: from the pre-roll (negative) on, never late
    assert answers[0].fired.arrival.due_s < 0 < answers[-1].fired.arrival.due_s
    assert all(x.fired.due_at <= x.fired.fired_at for x in answers)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_root(str(tmp_path_factory.mktemp("bench_closed")))


def _traced_line(root, monkeypatch, seed, round_s):
    """The toy backlog cell with ``--trace 1`` on an engine whose decode
    round takes ``round_s`` longer; also what entered the engine."""
    from bigdl_tpu.serving import lm_engine
    toy.without_profiler(monkeypatch)
    compiled = lm_engine.LMServingEngine._decode_compiled

    def slowed(self):
        step = compiled(self)

        def call(*args):
            time.sleep(round_s)
            return step(*args)
        return call

    monkeypatch.setattr(lm_engine.LMServingEngine, "_decode_compiled", slowed)
    submit, entered = lm_engine.LMServingEngine.submit, []
    threads, outstanding, streams = set(), [0], []

    def recorded(self, prompt, **kw):
        if threading.current_thread() is not threading.main_thread():
            # the load's thread, not the warm-up's
            entered.append((len(prompt), kw["max_new_tokens"], int(prompt[0])))
            threads.add(threading.current_thread().name)
            outstanding[0] = max(outstanding[0],
                                 1 + sum(not x.done() for x in streams))
            streams.append(submit(self, prompt, **kw))
            return streams[-1]
        return submit(self, prompt, **kw)

    monkeypatch.setattr(lm_engine.LMServingEngine, "submit", recorded)
    line = run.run_cell(root, "toy.backlog", seed, 2.0, True,
                        require_accelerator=False)
    json.dumps(line)
    return line, entered, threads, outstanding[0]


def test_the_cell_stays_saturated_when_the_round_gets_four_times_shorter(
        root, monkeypatch):
    """12 clients on 4 slots: every slot decodes at a 12-ms round and at a
    3-ms round, and the faster engine is sent more of the same sequence."""
    sequences = {}
    for seed, round_s in ((2 ** 31 + 28, 0.012), (29, 0.003)):
        line, entered, threads, queued = _traced_line(root, monkeypatch, seed,
                                                      round_s)
        monkeypatch.undo()
        assert line["correct"] and line["failed"] == 0
        assert line["metrics"]["slot_occupancy"]["value"] >= 95.0
        # one thread sends for all 12 clients, each waiting for its answer
        assert len(threads) == 1
        assert queued == toy.TOY_BACKLOG["clients"]
        assert line["attempted"] == len(entered)
        sequences[round_s] = entered
    slow, fast = sequences[0.012], sequences[0.003]
    assert len(fast) > 1.5 * len(slow)
    # two seeds, two speeds: the same lengths in the same order, other ids
    assert [e[:2] for e in fast[:len(slow)]] == [e[:2] for e in slow]
    assert [e[2] for e in fast[:len(slow)]] != [e[2] for e in slow]
