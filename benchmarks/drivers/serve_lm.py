"""Driver ``serve_lm``: a GPT-2-shaped ``TransformerLM`` behind
``LMServingEngine``, under the mix's load: an open-loop arrival schedule, or a
closed loop's pool of clients (``"kind": "closed"``).

The calls are the ones ``chip_smoke.py`` proved on the chip (build, engine,
``warmup()``, ``submit()``); the sizes come from the configuration file and the
traffic from the cell's mix.  Token stamps are the client's: one consumer
thread per stream in flight on ``LMStream.tokens()``; in a closed loop the
generator's one thread polls every stream in flight (``_Client.poll``).

Mix keys beyond the generator's: ``"follow_s"``: how long after the window
closes the requests due inside it are still followed; what runs on then is
cancelled (it was attempted, it has not failed, and its tokens up to then
count).  A latency cell follows until every first token has come and the
longest requests are done or nearly; a throughput cell follows for 0 s.
``"preroll_s"`` (default 0): the load starts that long before the window
opens, so that a throughput cell's window finds every slot busy and a queue
behind them; the pre-roll is set-up, its tokens are not counted.
"""
import gc
import threading
import time

import numpy as np

import jax.numpy as jnp

from benchmarks.harness import loadgen, reference_gpt2, stats

#: how many finished requests the check replays, besides the longest
CHECK_SAMPLE = 31


def program_params(w: dict) -> dict:
    """The benchmark's weights in ``TransformerLM``'s layout (layer-stacked,
    the assignment a checkpoint loader makes: models/transformer/io.py)."""
    return {
        "embed": w["wte"], "pos": w["wpe"],
        "ln_f": {"weight": w["ln_f_g"], "bias": w["ln_f_b"]},
        "blocks": {
            "ln1": {"weight": w["ln1_g"], "bias": w["ln1_b"]},
            "ln2": {"weight": w["ln2_g"], "bias": w["ln2_b"]},
            "attn": {k: w[k] for k in
                     ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo")},
            "w1": w["w_fc"], "b1": w["b_fc"],
            "w2": w["w_proj"], "b2": w["b_proj"]},
    }


def build_engine(config: dict, seed: int):
    """The engine as the configuration states it.  ``"quantize"`` (absent in
    every committed configuration) serves a ``quantize()`` clone: with the
    engine's ``kv_quant`` it is how the check's control, and a later
    configuration that states int8, switch the program's own int8 paths on."""
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine
    model = TransformerLM(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        n_head=config["n_head"], n_layers=config["n_layer"],
        max_len=config["n_positions"])
    model.params = program_params(reference_gpt2.make_weights(
        seed, config, config["assumed"]["serve_dtype"]))
    model.buffers = {}
    model.evaluate()
    if config.get("quantize"):
        model = model.quantize(config["quantize"])
    args = dict(config["engine"])
    args["prefill_buckets"] = tuple(args["prefill_buckets"])
    return LMServingEngine(model, **args)


class _Client:
    """One request as its client sees it."""

    def __init__(self, fired: loadgen.Fired, polled: bool = False):
        self.arrival = fired.arrival
        self.due_at = fired.due_at
        self.late_s = fired.fired_at - fired.due_at
        self.stream = fired.handle
        self.error = fired.error
        self.stamps = []
        self.generated = None       # 1-based served tokens, set by release()
        self.truncated = False
        self.thread = None
        if self.stream is not None and not polled:
            self.thread = threading.Thread(target=self._consume, daemon=True)
            self.thread.start()

    def _consume(self):
        try:
            for _ in self.stream.tokens():
                self.stamps.append(time.perf_counter())
        except Exception as e:  # noqa: BLE001 -- the engine's refusal or error
            self.error = repr(e)

    def poll(self, now: float) -> bool:
        """A closed loop's client: stamp the tokens that have arrived since
        the last poll; True once the stream has ended.  ``done()`` is read
        first, so an ended stream's last tokens are stamped; the count is the
        length of ``LMStream._tokens`` (``generated`` would copy the answer
        so far under the stream's lock, for every stream, every poll)."""
        if self.stream is None:
            return True
        ended = self.stream.done()
        self.stamps.extend([now] * (len(self.stream._tokens) - len(self.stamps)))
        if ended:
            try:
                self.stream.result(timeout=0)
            except Exception as e:  # noqa: BLE001 -- the engine's error
                self.error = repr(e)
        return ended

    def cancel(self) -> None:
        if self.stream is not None and not self.stream.done():
            self.stream.cancel()

    def release(self) -> None:
        """Keep what the client received and let go of the stream (it holds
        the engine, and the engine the weights)."""
        if self.stream is not None:
            self.generated = np.asarray(self.stream.generated, np.int32)
            self.truncated = self.stream.truncation is not None
            self.stream = None

    @property
    def complete(self) -> bool:
        return (not self.error and self.generated is not None
                and not self.truncated
                and len(self.stamps) == self.arrival.max_new)


def _warm(engine, config, rng) -> None:
    """Compile (or load) every program this cell's traffic uses, then run
    each once: one prompt per prefill bucket, a few decode rounds."""
    engine.warmup()
    streams = [engine.submit(rng.randint(1, config["vocab_size"] + 1, size=b),
                             max_new_tokens=4)
               for b in engine.prefill_buckets]
    for s in streams:
        s.result(timeout=600)


def _lm_counters(engine) -> dict:
    m = engine.metrics
    return {"lm.slot_steps": m.slot_steps,
            "lm.active_slot_steps": m.active_slot_steps,
            "lm.decode_steps": m.decode_steps, "lm.prefills": m.prefills,
            "lm.completed": m.completed, "lm.rejected": m.rejected}


def check_streams(config: dict, seed: int, clients: list, out) -> list:
    """The comparison that decides ``correct`` for a served model: a seeded
    sample of the finished requests, the longest among them, each replayed
    once through the plain reference (teacher-forced on the served tokens).
    Numbers compared, over the sample's served tokens: the widest and the
    mean gap by which a served token's reference logit lies below the
    reference's best at its position.  The share of served tokens that are
    not the reference's best is printed beside them."""
    done = [c for c in clients if c.complete]
    if not done:
        return [{"name": "finished_requests", "value": 0, "limit": 1,
                 "ok": False}]
    rng = np.random.RandomState(seed % (2 ** 32))
    longest = max(done, key=lambda c: len(c.arrival.prompt) + c.arrival.max_new)
    rest = [c for c in done if c is not longest]
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[:CHECK_SAMPLE]]
    w = reference_gpt2.make_weights(seed, config, config["assumed"]["serve_dtype"])
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    gaps = []
    for c in picks:
        gen = c.generated - 1
        ids = np.zeros((config["n_positions"],), np.int32)
        t = len(c.arrival.prompt)
        ids[:t] = c.arrival.prompt - 1
        ids[t:t + len(gen)] = gen
        positions = np.arange(t - 1, t - 1 + len(gen), dtype=np.int32)
        logits = reference_gpt2.forward(w, ids, config["n_head"])
        gaps.append(np.asarray(
            reference_gpt2.gaps_below_best(logits, positions, gen)))
    gaps = np.concatenate(gaps)
    out({"check": "served tokens against the plain f32 reference",
         "requests": len(picks), "tokens": int(gaps.size),
         "tokens_not_reference_best": int((gaps > 0).sum()),
         "not_best_share_pct": float((gaps > 0).mean() * 100),
         "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean())})
    numbers = {"served_gap_max": float(gaps.max()),
               "served_gap_mean": float(gaps.mean())}
    limits = config["check"]
    return [{"name": k, "value": v, "limit": limits[k],
             "ok": bool(v <= limits[k])} for k, v in numbers.items()]


def run(bench) -> dict:
    """``bench`` is the harness's ``Run``: cell, config, mix, seed, seconds,
    trace, and the hooks ``open_window`` / ``close_window`` / ``out``."""
    from bigdl_tpu.obs.tracer import get_tracer
    config, mix, seed = bench.config, bench.mix, bench.seed
    t0 = time.perf_counter()
    engine = build_engine(config, seed)
    t1 = time.perf_counter()
    _warm(engine, config, np.random.RandomState((seed + 1) % (2 ** 32)))
    # what imports and set-up left on the heap leaves the collector's sight: a
    # full collection over it stops every thread for 110-160 ms once a run
    # (440 ms with the tracer's events), in whatever phase the worker is in
    gc.collect()
    gc.freeze()
    bench.out({"setup_phases_s": {"weights_and_engine": t1 - t0,
                                  "compile_or_load_and_warm": time.perf_counter() - t1,
                                  "preroll": float(mix.get("preroll_s", 0.0))}})
    preroll = float(mix.get("preroll_s", 0.0))
    closed = mix["kind"] == "closed"
    tracer = get_tracer()
    clients, stop = [], threading.Event()
    if bench.trace:
        tracer.enable()
        tracer.clear()

    def submit(a):
        return engine.submit(a.prompt, max_new_tokens=a.max_new, temperature=0.0)

    t_open = time.perf_counter() + preroll
    if closed:
        def polled(fired):
            clients.append(_Client(fired, polled=True))
            return clients[-1]

        load = (loadgen.closed_loop, mix,
                loadgen.sequence(mix, seed, config["vocab_size"]))
        on_fired = polled
    else:
        arrivals = [a._replace(due_s=a.due_s - preroll)
                    for a in loadgen.schedule(mix, seed, bench.seconds + preroll,
                                              config["vocab_size"])]
        load = (loadgen.fire, arrivals)
        on_fired = lambda f: clients.append(_Client(f))     # noqa: E731
    firing = threading.Thread(target=load[0], daemon=True,
                              args=load[1:] + (submit, t_open, on_fired, stop))
    firing.start()
    bench.sleep_until(t_open)
    before = _lm_counters(engine)
    bench.open_window(at=t_open)
    bench.sleep_until(t_open + bench.seconds)
    # the window closes where --seconds says, however late this thread woke
    t_close = bench.close_window(at=t_open + bench.seconds)
    after = _lm_counters(engine)
    if closed:
        stop.set()                      # the loop cancels what is in flight
    firing.join(timeout=30)             # an open loop's late firings
    stop.set()
    bench.sleep_until(t_close + float(mix["follow_s"]), until=lambda: all(
        c.stream is None or c.stream.done() for c in clients))
    for c in clients:
        c.cancel()
    for c in clients:
        if c.thread is not None:
            c.thread.join(timeout=300)
        c.release()
    spans = []
    if bench.trace:
        tracer.disable()
        spans = [(e["name"], e["ts"] * 1e-6 + tracer._epoch_perf,
                  e.get("dur", 0.0) * 1e-6) for e in tracer.events()]
    spans += [("bench/fire_late", c.due_at, c.late_s) for c in clients]
    from bigdl_tpu.obs.ledger import get_ledger
    temps = [(row["memory"] or {}).get("temp_bytes", 0)
             for row in get_ledger().executables()
             if row["tag"].startswith(f"lm/{engine.name}/")]
    peak = bench.memory_peak_bytes(max(temps, default=0))
    engine.close()
    del engine, firing, submit
    gc.unfreeze()           # or the engine's cycles would keep its arrays
    gc.collect()
    bench.out({"device_bytes_in_use_after_close": [
        (d.memory_stats() or {}).get("bytes_in_use") for d in bench.devices]})

    # -- what the client saw ---------------------------------------------
    # tokens, failures and the check take every request fired, the pre-roll's
    # too; the latency notes take the requests that were due inside the window
    everyone = clients
    clients = [c for c in everyone if c.arrival.due_s >= 0]
    in_window = lambda t: t_open <= t < t_close     # noqa: E731
    ttft = [(c.stamps[0] - c.due_at) * 1e3 for c in clients if c.stamps]
    itl = [(b - a) * 1e3 for c in clients
           for a, b in zip(c.stamps, c.stamps[1:])]
    tokens_in_window = sum(in_window(t) for c in everyone for t in c.stamps)
    # a refusal or an error fails; so does a request a latency cell followed
    # and never saw a token of.  Where the window's close cancels the queue
    # (follow_s 0), a request still waiting was attempted and has not failed
    failed = [c for c in everyone if c.error
              or (not c.stamps and float(mix["follow_s"]) > 0)]
    fifth = bench.seconds / 5.0
    by_fifth = [[(c.stamps[0] - c.due_at) * 1e3 for c in clients if c.stamps
                 and k * fifth <= c.arrival.due_s < (k + 1) * fifth]
                for k in range(5)]
    early = [c for c in clients if c.arrival.due_s < 4 * fifth]
    bench.out({"ttft_median_ms_by_fifth_of_window":
               [stats.median(v) if v else None for v in by_fifth],
               "due_in_first_four_fifths": len(early),
               "of_those_finished_by_close": sum(
                   c.complete and c.stamps[-1] < t_close for c in early)})
    bench.out({"offered": len(everyone) if closed else len(arrivals),
               "fired": len(everyone),
               "due_in_window": len(clients),
               "finished": sum(c.complete for c in clients),
               "failed": len(failed),
               "errors": sorted({c.error for c in everyone if c.error})[:3],
               "ttft_ms": stats.describe(ttft) if ttft else None,
               "itl_ms": stats.describe(itl) if itl else None,
               "fire_late_ms": stats.describe(
                   [c.late_s * 1e3 for c in clients]) if clients else None,
               "tokens_in_window": tokens_in_window,
               "tokens_in_window_per_s": tokens_in_window / bench.seconds})
    # the rate is taken from emission to emission (stats.emission_rate), so
    # that it does not move in steps of one round's 16 tokens; the plain count
    # over --seconds is printed beside it
    stamps = [t for c in everyone for t in c.stamps]
    end_to_end = {"out_tokens_per_s": stats.emission_rate(stamps, t_open,
                                                          t_close)}
    # what the estimator's settle rests on: how long one round's tokens take
    # to reach their clients, against the gap to the next round's
    xs, ends, settle = stats.emission_groups(
        [t for t in stamps if in_window(t)])
    if len(ends) > 2:
        first = np.append(0, ends[:-1] + 1)
        bench.out({"emission_groups": {
            "n": len(ends), "settle_ms": settle * 1e3,
            "tokens_median": stats.median(ends + 1 - first),
            "width_ms": stats.describe((xs[ends] - xs[first]) * 1e3),
            "gap_to_next_ms": stats.describe(
                (xs[first[1:]] - xs[ends[:-1]]) * 1e3, q=5.0)}})
    if ttft and itl:
        end_to_end.update(ttft_p95_ms=stats.percentile(ttft, 95),
                          itl_p95_ms=stats.percentile(itl, 95))
    counters = {k: after[k] - before[k] for k in after}
    if counters["lm.slot_steps"]:
        counters["lm.slot_occupancy"] = (counters["lm.active_slot_steps"]
                                         / counters["lm.slot_steps"])
    # every decoded token read its stream's whole context: the KV positions
    # the decode rounds of the traced window had to read
    lo, hi = bench.traced_window or (t_open, t_close)
    counters["lm.decode_context_tokens"] = sum(
        len(c.arrival.prompt) + i for c in everyone
        for i, t in enumerate(c.stamps) if i > 0 and lo <= t < hi)
    checks = check_streams(config, seed, everyone, bench.out)
    return {"attempted": len(everyone), "failed": len(failed),
            "end_to_end": end_to_end, "checks": checks,
            "memory_peak_bytes": peak, "window": (t_open, t_close),
            "spans": spans, "counters": counters}
