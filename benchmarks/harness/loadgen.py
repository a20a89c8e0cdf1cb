"""Load from a mix file: an open loop (a seeded arrival schedule and the thread
that fires it) or a closed loop (a pool of clients that each wait for their
answer before sending the mix's next request).

Extends the idea of ``bigdl_tpu/traffic/loadgen.py`` (seeded, open loop,
poisson / bursty) with what a benchmark needs: weighted length menus, the
lateness of every firing, and a schedule whose WORK does not change with the
seed.  Every seed gets the same multiset of inter-arrival gaps (the
exponential distribution's quantiles) and the same multiset of prompt and
output lengths (the menus' weights, apportioned over the arrivals) in another
order, and other token ids; so two seeds differ as two shuffles of one trace
do, not as two draws of a small sample do.

A mix is a JSON object::

    {"kind": "poisson" | "bursty", "rate_rps": 6.0,
     "burst_factor": 3, "burst_period_s": 8, "burst_duty": 0.3,   (bursty)
     "prompt_lens": [32, 64], "prompt_weights": [0.5, 0.5],
     "output_lens": [16, 32], "output_weights": [0.5, 0.5],
     "order_seed": 7}                                             (optional)

With ``order_seed`` the ORDER of gaps and lengths is the mix's own and the
run's seed draws only the token ids: for a cell judged on tokens a second
behind a full queue, where the order in which long and short answers take the
slots decides how many prefills fall inside the window.

A closed loop has no rate: callers that wait for a reply send as fast as the
system answers, so it is loaded to the same depth at any speed::

    {"kind": "closed", "clients": 48, "poll_s": 0.001, "sequence_len": 4096,
     "prompt_lens": ..., "output_lens": ..., (the menus, as above)
     "order_seed": 23}                                            (optional)

All clients draw from ONE sequence of requests (``sequence``), in the order
the system is sent them, whichever client sends which; their answers are
watched from one thread, every ``poll_s`` seconds.
"""
import math
import threading
import time
from typing import Callable, Iterator, List, NamedTuple

import numpy as np


class Arrival(NamedTuple):
    index: int
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # (t,) int32, 1-based ids
    max_new: int


def apportion(weights, n: int) -> List[int]:
    """``n`` split by ``weights``, largest remainders first: the counts a
    weighted menu gives without sampling noise."""
    total = float(sum(weights))
    exact = [w / total * n for w in weights]
    counts = [int(math.floor(e)) for e in exact]
    order = sorted(range(len(exact)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def _menu(lens, weights, n: int, rng: np.random.RandomState) -> np.ndarray:
    out = np.repeat(np.asarray(lens, np.int64), apportion(weights, n))
    rng.shuffle(out)
    return out


def _cumulative_rate(mix: dict):
    """(Lambda, inverse Lambda) of the arrival intensity: expected arrivals
    up to t, piecewise linear."""
    rate = float(mix["rate_rps"])
    if mix["kind"] == "poisson":
        return (lambda t: rate * t), (lambda a: a / rate)
    if mix["kind"] != "bursty":
        raise ValueError(f"unknown arrival kind {mix['kind']!r}")
    factor, period, duty = (float(mix["burst_factor"]),
                            float(mix["burst_period_s"]),
                            float(mix["burst_duty"]))
    if not 0.0 < duty < 1.0 or factor * duty > 1.0:
        raise ValueError("bursty needs 0 < duty < 1 and factor * duty <= 1")
    on = rate * factor                                  # inside a burst
    off = rate * (1.0 - factor * duty) / (1.0 - duty)   # mean stays `rate`
    per_on, per_period = on * duty * period, rate * period

    def cum(t):
        k, r = divmod(t, period)
        return k * per_period + (on * r if r < duty * period else
                                 per_on + off * (r - duty * period))

    def inv(a):
        k, r = divmod(a, per_period)
        return k * period + (r / on if r < per_on else
                             duty * period + (r - per_on) / off)
    return cum, inv


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> List[Arrival]:
    """The arrivals due inside ``[0, seconds)``."""
    cum, inv = _cumulative_rate(mix)
    n = int(math.floor(cum(seconds)))
    if n < 1:
        raise ValueError(f"no arrival in {seconds} s at this rate")
    rng = np.random.RandomState(seed % (2 ** 32))
    order = (np.random.RandomState(int(mix["order_seed"]))
             if "order_seed" in mix else rng)
    # unit-rate gaps: the exponential's quantiles at the n mid-points, scaled
    # to mean exactly 1, in seeded order
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    gaps *= n / gaps.sum()
    order.shuffle(gaps)
    unit_times = np.cumsum(gaps) - gaps[0] * 0.5   # first arrival early
    prompts = _menu(mix["prompt_lens"], mix["prompt_weights"], n, order)
    outputs = _menu(mix["output_lens"], mix["output_weights"], n, order)
    arrivals = []
    for i in range(n):
        ids = rng.randint(1, vocab + 1, size=int(prompts[i])).astype(np.int32)
        arrivals.append(Arrival(i, float(inv(float(unit_times[i]))), ids,
                                int(outputs[i])))
    return arrivals


def sequence(mix: dict, seed: int, vocab: int) -> Iterator[Arrival]:
    """The endless sequence of requests a closed loop's clients draw from, in
    stretches of ``sequence_len``: each stretch holds the menus' weights
    apportioned without sampling noise, shuffled.  ``due_s`` is 0: a closed
    loop's request is due when a client is free to send it."""
    n = int(mix["sequence_len"])
    rng = np.random.RandomState(seed % (2 ** 32))
    order = (np.random.RandomState(int(mix["order_seed"]))
             if "order_seed" in mix else rng)
    index = 0
    while True:
        prompts = _menu(mix["prompt_lens"], mix["prompt_weights"], n, order)
        outputs = _menu(mix["output_lens"], mix["output_weights"], n, order)
        for t, max_new in zip(prompts, outputs):
            ids = rng.randint(1, vocab + 1, size=int(t)).astype(np.int32)
            yield Arrival(index, 0.0, ids, int(max_new))
            index += 1


class Fired(NamedTuple):
    arrival: Arrival
    due_at: float           # perf_counter clock
    fired_at: float
    handle: object          # what submit returned, or None
    error: str              # "" when submit returned


def fire(arrivals: List[Arrival], submit: Callable[[Arrival], object],
         t_open: float, on_fired: Callable[[Fired], None],
         stop: threading.Event) -> None:
    """Fire each arrival at ``t_open + due_s`` whether or not earlier ones
    completed (open loop).  ``submit`` must not block.  Runs in the caller's
    thread until the schedule ends or ``stop`` is set."""
    for a in arrivals:
        due_at = t_open + a.due_s
        lag = due_at - time.perf_counter()
        if lag > 0 and stop.wait(lag):
            return
        if stop.is_set():
            return
        try:
            handle, error = submit(a), ""
        except Exception as e:  # noqa: BLE001 -- a refusal is a result
            handle, error = None, repr(e)
        on_fired(Fired(a, due_at, time.perf_counter(), handle, error))


def closed_loop(mix: dict, requests: Iterator[Arrival],
                submit: Callable[[Arrival], object], t_open: float,
                on_fired: Callable[[Fired], object],
                stop: threading.Event) -> None:
    """``mix["clients"]`` clients, each sending the next of ``requests`` once
    its previous answer is complete (closed loop, no think time), all driven
    from the caller's ONE thread: ``on_fired`` returns the request's record,
    whose ``poll(now)`` is called every ``mix["poll_s"]`` seconds (it stamps
    what has arrived) and returns True once the answer has ended; a client
    whose answer has ended sends at once.  The system is sent the sequence
    in its own order.  A request is due when it is sent (``due_s`` is that
    instant after ``t_open``, negative in a pre-roll).  When ``stop`` is set
    the records still live get ``cancel()`` and the loop polls on until each
    has ended.

    One polling thread and not a thread per client: a consumer thread per
    stream takes the interpreter lock from the server's worker once a token,
    16 times a round in a cell whose every slot decodes; on the chip that
    cost the engine 1.2 ms of every 35-ms round (my chip run, PR 28: 420.6
    tokens/s against 435.6 polled at 0.5 ms and 437.4 at 1 ms)."""
    poll_s, free, live = float(mix["poll_s"]), int(mix["clients"]), []
    while True:
        stopping = stop.is_set()
        while free and not stopping:
            at = time.perf_counter()
            a = next(requests)._replace(due_s=at - t_open)
            try:
                handle, error = submit(a), ""
            except Exception as e:  # noqa: BLE001 -- a refusal is a result
                handle, error = None, repr(e)
            live.append(on_fired(Fired(a, at, time.perf_counter(), handle,
                                       error)))
            free -= 1
        if stopping:
            for record in live:
                record.cancel()
        now = time.perf_counter()
        still = [record for record in live if not record.poll(now)]
        free += len(live) - len(still)
        live = still
        if stopping and not live:
            return
        time.sleep(poll_s)
