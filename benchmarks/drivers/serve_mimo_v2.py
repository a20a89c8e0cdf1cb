"""Driver ``serve_mimo_v2``: MiMo-V2-Flash, cut to one chip's share (16 of 256
experts, an eighth of the vocabulary, layers 0-6) of a sixteen-chip
expert-parallel deployment (the configuration file says how), as a
``TransformerLM`` whose layer plan mixes SLIDING layers (a window of 128, 8 K/V
heads, a learned sink) and FULL ones (4 K/V heads) over keys of 192 and values
of 128 lanes, behind ``LMServingEngine`` with a KV pool of TWO CLASSES of
blocks (``serving.kvcache.blocks``: the windowed class lets go of what lies
behind the window), under ONE queue that two closed loops feed: a few long
streams that decode deep in 16k-32k-token contexts, and many short chat turns
that churn through the slots beside them.

The clients, the window and the notes are ``serve_ling3``'s and
``serve_glm47``'s (what could be imported is).  What differs: how the model is
built from the configuration, the reference the check replays through
(``harness/reference_mimo_v2.py``), the two loops of the mix (``long`` and
``short``, each a closed loop of ``harness/loadgen``), when the window opens
(every long stream has its ``window_opens_at_token``-th token AND the short
loop's pre-roll is over), and the counters the pool's classes add -- of which
``window_blocks_held_max``, the most blocks of the windowed class a decoding
sequence held after a round's release, is COMPARED: a run in which the
allocator let go of nothing is not correct.
"""
import gc
import threading
import time

import numpy as np

import jax.numpy as jnp

from benchmarks.drivers.serve_lm import _Client
from benchmarks.drivers.serve_solar2 import TRACE_REQUESTS
from benchmarks.harness import loadgen, reference_mimo_v2, stats

#: finished short requests the check replays, the longest first
CHECK_SHORT = 6
#: replayed requests are padded to a multiple of this (one compilation a
#: length and layer kind); a short one to SHORT_PAD
CHECK_PAD = 4096
SHORT_PAD = 2048
#: how long the long streams may take to reach the window's first token
OPEN_TIMEOUT_S = 1500.0


# -- the configuration as a TransformerLM -----------------------------------------
def layer_plan(c: dict):
    """The program's layer plan from the published lists: the leading layers
    that stand alone (layer 0: full attention, dense) one group each, then
    whole periods of the pattern stacked."""
    from bigdl_tpu.models.transformer import LayerSpec, RopeSpec
    R = reference_mimo_v2
    rot = R.rotary_dim(c)
    specs = [LayerSpec(
        n_head=c["num_attention_heads"],
        window=c["sliding_window"] if sliding else None,
        rope=RopeSpec(theta=float(c["swa_rope_theta" if sliding
                                    else "rope_theta"]), rotary_dim=rot),
        mlp="moe" if routed else "dense", n_kv_head=R.kv_heads(c, sliding),
        sink=R.has_sink(c, sliding))
        for sliding, routed in R.layer_kinds(c)]
    lead = next((i for i, (_, routed) in enumerate(R.layer_kinds(c)) if routed),
                len(specs))
    rest = specs[lead:]
    plan = [(1, (s,)) for s in specs[:lead]]
    if rest:
        period = next(p for p in range(1, len(rest) + 1)
                      if len(rest) % p == 0
                      and rest == rest[:p] * (len(rest) // p))
        plan.append((len(rest) // period, tuple(rest[:period])))
    return plan


def build_model(c: dict):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.parallel.expert import MoESpec
    first, count = reference_mimo_v2.held_experts(c)
    moe = MoESpec(n_experts=c["experts_published"], top_k=c["num_experts_per_tok"],
                  width=c["moe_intermediate_size"], shared_width=0,
                  routed_scale=1.0, norm_topk=c["norm_topk_prob"],
                  held=(first, count), score=c["scoring_func"])
    return TransformerLM(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        n_head=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        ffn_size=c["intermediate_size"], max_len=c["max_position_embeddings"],
        tie_embeddings=False, pos_encoding="none", head_dim=c["head_dim"],
        n_kv_head=c["num_key_value_heads"], norm="rmsnorm",
        norm_eps=c["layernorm_epsilon"], mlp_act="swiglu", bias=False, moe=moe,
        layer_plan=layer_plan(c), v_head_dim=c["v_head_dim"],
        value_scale=c["attention_value_scale"],
        attention_impl=c["assumed"].get("attention_impl", "xla"))


def program_layer(w: dict) -> dict:
    """One reference layer in ``TransformerLM``'s layout (heads flattened)."""
    h = w["ln1"].shape[0]
    flat = lambda a: a.reshape(a.shape[0], -1)          # noqa: E731
    p = {"ln1": {"weight": w["ln1"]}, "ln2": {"weight": w["ln2"]},
         "attn": {"wq": flat(w["wq"]), "wk": flat(w["wk"]), "wv": flat(w["wv"]),
                  "wo": w["wo"].reshape(-1, h)}}
    if "sink" in w:
        p["attn"]["sink"] = w["sink"]
    if "w_gate" in w:
        p["mlp"] = {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]}
    else:
        p["moe"] = {"router": w["router"], "select_bias": w["router_bias"],
                    "w_gate": w["e_gate"], "w_up": w["e_up"], "w_down": w["e_down"]}
    return p


def program_params(model, seed: int, c: dict, dtype) -> dict:
    """The benchmark's weights in ``TransformerLM``'s layout, stacked by the
    plan (the assignment a checkpoint loader makes).  A layer at a time, its
    buffers donated to the stacking."""
    import jax
    stack = jax.jit(lambda *a: jnp.stack(a), donate_argnums=0)
    ends = reference_mimo_v2.make_ends(seed, c, dtype)
    groups, base = [], 0
    for repeat, period in model.plan:
        n = len(period)
        groups.append([
            jax.tree_util.tree_map(stack, *[
                program_layer(reference_mimo_v2.make_layer(
                    seed, c, base + r * n + i, dtype)) for r in range(repeat)])
            for i in range(n)])
        base += repeat * n
    return {"embed": ends["embed"], "head": ends["head"],
            "ln_f": {"weight": ends["norm_f"]}, "groups": groups}


def build_engine(config: dict, seed: int, **overrides):
    from bigdl_tpu.serving import LMServingEngine
    model = build_model(config)
    model.params = program_params(model, seed, config,
                                  config["assumed"]["serve_dtype"])
    model.buffers = {}
    model.evaluate()
    args = dict(config["engine"], **overrides)
    args["prefill_buckets"] = tuple(args["prefill_buckets"])
    return LMServingEngine(model, **args)


def _warm(engine, config, mix, rng) -> None:
    """Compile (or load) every program this cell's traffic uses, then run each
    once: one prompt of the mix's longest length (its first chunk through the
    whole-prompt prefill, every later one through the suffix prefill over both
    classes of blocks), one of every short bucket, a few decode rounds."""
    engine.warmup()
    engine.warmup_prefix(suffix_lens=[engine.prefill_buckets[-1]],
                         prefix_blocks=[engine.table_width])
    vocab = config["vocab_size"]
    lens = [max(mix["long"]["prompt_lens"])] + sorted(mix["short"]["prompt_lens"])
    for t in lens:
        engine.submit(rng.randint(1, vocab + 1, size=t),
                      max_new_tokens=4).result(timeout=1200)


def _lm_counters(engine) -> dict:
    m = engine.metrics
    return {"lm.slot_steps": m.slot_steps,
            "lm.active_slot_steps": m.active_slot_steps,
            "lm.decode_steps": m.decode_steps, "lm.prefills": m.prefills,
            "lm.tokens": m.tokens,
            "lm.completed": m.completed, "lm.rejected": m.rejected,
            "lm.prompt_tokens": m.prompt_tokens,
            "lm.prefix_matched_tokens": m.prefix_matched_tokens,
            "lm.moe_assignments": m.moe_assignments,
            "lm.moe_experts_hit": m.moe_experts_hit,
            "lm.moe_expert_layer_rounds": m.moe_expert_layer_rounds,
            "lm.decode_context_tokens": m.decode_context_tokens,
            "lm.decode_window_tokens": m.decode_window_tokens,
            "lm.window_blocks_released": m.window_blocks_released,
            "lm.window_blocks_allotted": m.window_blocks_allotted,
            "lm.window_blocks_held": m.window_blocks_held,
            "lm.window_blocks_spanned": m.window_blocks_spanned}


# -- the comparison that decides ``correct`` ---------------------------------------
def pick_requests(long_clients: list, short_clients: list) -> list:
    """What the check replays: of the long streams, for each prompt length
    the one with the most tokens out; of the short requests that FINISHED,
    the ``CHECK_SHORT`` longest (prompt + answer), first fired first among
    equals."""
    by_len = {}
    for c in long_clients:
        if not c.error and c.stamps:
            t = len(c.arrival.prompt)
            if t not in by_len or len(c.stamps) > len(by_len[t].stamps):
                by_len[t] = c
    done = [c for c in short_clients if c.complete]
    done.sort(key=lambda c: (-(len(c.arrival.prompt) + c.arrival.max_new),
                             c.arrival.index))
    return [by_len[t] for t in sorted(by_len, reverse=True)] + done[:CHECK_SHORT]


def check_requests(config: dict, seed: int, picks: list, held_max: int,
                   released: int, out) -> list:
    """Each of ``picks`` replayed once through the plain reference,
    teacher-forced on prompt + served ids (a long stream: what was served up
    to the close).  Compared: over the sample's served tokens, the widest and
    the mean gap by which a served token's reference logit lies below the
    reference's best at its position; and ``held_max``, the most blocks of the
    windowed class that a decoding sequence held after a round's release,
    against the allocator's promise ``ceil(window / block_len) + 1`` (with
    nothing ``released`` it reads as the blocks of a whole context)."""
    B = config["engine"]["block_len"]
    promise = -(-config["sliding_window"] // B) + 1
    picks = [c for c in picks if c.generated is not None and len(c.generated)]
    if not picks:
        return [{"name": "served_streams", "value": 0, "limit": 1, "ok": False}]
    requests, rows, gens = [], [], []
    for c in picks:
        gen = c.generated - 1
        t = len(c.arrival.prompt)
        need = t + len(gen)
        unit = CHECK_PAD if need > SHORT_PAD else SHORT_PAD
        pad = min(-(-need // unit) * unit, config["max_position_embeddings"])
        ids = np.zeros((pad,), np.int32)    # causal: the padding is never seen
        ids[:t] = c.arrival.prompt - 1
        ids[t:need] = gen
        requests.append(ids)
        rows.append(np.arange(t - 1, need - 1, dtype=np.int32))
        gens.append(gen)
    t0 = time.perf_counter()
    logits, moved, pairs = reference_mimo_v2.forward_requests(
        seed, config, config["assumed"]["serve_dtype"], requests, rows)
    gaps = [np.asarray(reference_mimo_v2.gaps_below_best(
        lg, np.arange(len(gen)), jnp.asarray(gen)))
        for lg, gen in zip(logits, gens)]
    every = np.concatenate(gaps)
    out({"check": "served tokens against the plain f32 reference",
         "requests": len(picks),
         "prompt_lens": [len(c.arrival.prompt) for c in picks],
         "tokens_by_request": [int(g.size) for g in gaps],
         "gap_mean_by_request": [float(g.mean()) for g in gaps],
         "tokens": int(every.size),
         "tokens_not_reference_best": int((every > 0).sum()),
         "not_best_share_pct": float((every > 0).mean() * 100),
         "gap_max": float(every.max()), "gap_mean": float(every.mean()),
         "window_blocks_held_max": int(held_max),
         "window_blocks_released": int(released),
         "router_picks_moved_by_bf16_rounding_pct": 100.0 * moved / max(pairs, 1),
         "reference_s": time.perf_counter() - t0})
    limits = config["check"]
    checks = [{"name": k, "value": v, "limit": limits[k],
               "ok": bool(v <= limits[k])}
              for k, v in (("served_gap_max", float(every.max())),
                           ("served_gap_mean", float(every.mean())))]
    checks.append({"name": "window_blocks_held_max", "value": int(held_max),
                   "limit": promise,
                   "ok": bool(0 < held_max <= promise and released > 0)})
    return checks


# -- one run ---------------------------------------------------------------------
def run(bench) -> dict:
    """``bench`` is the harness's ``Run``; see ``serve_lm.run``."""
    from bigdl_tpu.obs.tracer import get_tracer
    config, mix, seed = bench.config, bench.mix, bench.seed
    if mix["kind"] != "closed" or "long" not in mix or "short" not in mix:
        raise SystemExit("serve_mimo_v2: the cell is one queue fed by two "
                         "closed loops (long, short)")
    t0 = time.perf_counter()
    engine = build_engine(config, seed)
    t1 = time.perf_counter()
    _warm(engine, config, mix, np.random.RandomState((seed + 1) % (2 ** 32)))
    # as serve_lm: what set-up left on the heap leaves the collector's sight
    gc.collect()
    gc.freeze()
    t2 = time.perf_counter()
    stats0 = engine.stats()
    tracer = get_tracer()
    longs, shorts, stop = [], [], threading.Event()
    sample_rate = tracer.sample_rate
    if bench.trace:
        tracer.set_sample_rate(TRACE_REQUESTS)
        tracer.enable()
        tracer.clear()

    def submit(a):
        return engine.submit(a.prompt, max_new_tokens=a.max_new, temperature=0.0)

    def polled(into):
        def record(fired):
            into.append(_Client(fired, polled=True))
            return into[-1]
        return record

    def loop(part, into, seed, t_ref):
        th = threading.Thread(
            target=loadgen.closed_loop, daemon=True,
            args=(mix[part], loadgen.sequence(mix[part], seed,
                                              config["vocab_size"]),
                  submit, t_ref, polled(into), stop))
        th.start()
        return th

    # the long clients submit first: their prefills lie in the set-up
    t_load = time.perf_counter()
    n_long, n_open = int(mix["long"]["clients"]), int(mix["window_opens_at_token"])
    firing = [loop("long", longs, seed, t_load)]

    def every_long_has(n):
        # (or one has failed: the run is over, and says so below)
        return lambda: (any(c.error for c in longs) or (
            len(longs) >= n_long and all(len(c.stamps) >= n for c in longs)))

    bench.sleep_until(t_load + OPEN_TIMEOUT_S, until=every_long_has(1))
    # ... then the short loop, from preroll_s before the window at the least
    t_short = time.perf_counter()
    # (its ids from a seed of its own: not the long loop's, and not the warm
    # prompts' either, whose cached blocks a shared draw would hit)
    firing.append(loop("short", shorts, seed + 2, t_short + float(mix["preroll_s"])))
    bench.sleep_until(t_short + float(mix["preroll_s"]))
    bench.sleep_until(t_load + OPEN_TIMEOUT_S, until=every_long_has(n_open))
    if any(c.error for c in longs) or not every_long_has(n_open)():
        stop.set()
        engine.close()
        raise SystemExit(
            f"serve_mimo_v2: after {OPEN_TIMEOUT_S:.0f} s not every long stream "
            f"has its token {n_open}; errors: "
            f"{sorted({c.error for c in longs if c.error})[:3]}")
    t_open = time.perf_counter()
    bench.out({"setup_phases_s": {"weights_and_engine": t1 - t0,
                                  "compile_or_load_and_warm": t2 - t1,
                                  "long_prefills": t_short - t_load,
                                  "preroll_and_first_tokens": t_open - t_short},
               "decode_attn": engine.decode_attn,
               "kv_arena_bytes": engine.pool.arena_bytes,
               "kv_classes": stats0["kv_classes"],
               "prefix_cache": stats0.get("prefix_cache")})
    before, rounds_before = _lm_counters(engine), engine.rounds_stats()
    bench.open_window(at=t_open)
    bench.sleep_until(t_open + bench.seconds)
    # the window closes where --seconds says, however late this thread woke
    t_close = bench.close_window(at=t_open + bench.seconds)
    after, rounds_after = _lm_counters(engine), engine.rounds_stats()
    held_max = engine.metrics.window_blocks_held_max
    bench.out({"window_rounds": {
        "count": rounds_after["count"] - rounds_before["count"],
        "median_plain_s": rounds_after["median_plain_s"],
        "phase_s": {k: v - rounds_before["phase_s"][k]
                    for k, v in rounds_after["phase_s"].items()}},
        "kv_classes_at_close": engine.stats()["kv_classes"]})
    stop.set()                          # the loops cancel what is in flight
    for th in firing:
        th.join(timeout=60)
    clients = longs + shorts
    bench.sleep_until(t_close + 30.0, until=lambda: all(
        c.stream is None or c.stream.done() for c in clients))
    for c in clients:
        c.cancel()
    for c in clients:
        c.release()
    picks = pick_requests(longs, shorts)
    spans, events = [], []
    if bench.trace:
        tracer.disable()
        tracer.set_sample_rate(sample_rate)
        bench.out({"tracer_events_dropped": tracer.dropped})
        events = [(e["name"], e["ts"] * 1e-6 + tracer._epoch_perf,
                   e.get("dur", 0.0) * 1e-6, e.get("args") or {})
                  for e in tracer.events()]
        spans = [e[:3] for e in events]
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
    in_window = lambda t: t_open <= t < t_close     # noqa: E731
    tokens_in_window = sum(in_window(t) for c in clients for t in c.stamps)
    # a refusal or an error fails; the window's close cancels what is in
    # flight (attempted, not failed)
    failed = [c for c in clients if c.error]
    bench.out({"fired": len(clients), "fired_long": len(longs),
               "finished": sum(c.complete for c in clients),
               "failed": len(failed),
               "errors": sorted({c.error for c in clients if c.error})[:3],
               "tokens_before_window": sum(
                   t < t_open for c in clients for t in c.stamps),
               "tokens_in_window": tokens_in_window,
               "long_tokens_in_window": sum(
                   in_window(t) for c in longs for t in c.stamps),
               "short_admissions_in_window": sum(
                   in_window(c.stamps[0]) for c in shorts if c.stamps),
               "tokens_in_window_per_s": tokens_in_window / bench.seconds})
    stamps = [t for c in clients for t in c.stamps]
    end_to_end = {"out_tokens_per_s": stats.emission_rate(stamps, t_open,
                                                          t_close)}
    counters = {k: after[k] - before[k] for k in after}
    if counters["lm.slot_steps"]:
        counters["lm.slot_occupancy"] = (counters["lm.active_slot_steps"]
                                         / counters["lm.slot_steps"])
    if counters["lm.moe_expert_layer_rounds"]:
        counters["lm.moe_experts_hit_share"] = (
            counters["lm.moe_experts_hit"]
            / (config["n_routed_experts"] * counters["lm.moe_expert_layer_rounds"]))
    if counters["lm.window_blocks_spanned"]:
        counters["lm.window_blocks_held_share"] = (
            counters["lm.window_blocks_held"] / counters["lm.window_blocks_spanned"])
    bench.out({"window_counters": {k: counters[k] for k in sorted(counters)}})
    # the traced sub-window: what its decode rounds had to do, from the args
    # of their lm/decode_step spans (the program's own counts, round by round)
    lo, hi = bench.traced_window or (t_open, t_close)
    steps = [a for n, s, _, a in events if n == "lm/decode_step" and lo <= s < hi]
    counters["lm.traced_decode_rounds"] = len(steps)
    for key, arg in (("lm.traced_moe_experts_hit", "moe_experts_hit"),
                     ("lm.traced_moe_assignments", "moe_assignments"),
                     ("lm.traced_ctx_tokens", "ctx_tokens"),
                     ("lm.traced_window_tokens", "window_tokens"),
                     ("lm.traced_active_slots", "active")):
        counters[key] = sum(a.get(arg, 0) for a in steps)
    # what the traced prefills computed, by TRUE lengths: a chunk's prefix and
    # own tokens, and the assignments that landed on held experts
    counters["lm.traced_prefill_chunks"] = [
        (a.get("prefix_len", 0),
         min(a.get("bucket", 0), a.get("prompt_len", 0) - a.get("prefix_len", 0)))
        for n, s, _, a in events if n == "lm/prefill" and lo <= s < hi]
    counters["lm.traced_prefill_assignments"] = sum(
        a.get("moe_assignments", 0) for n, s, _, a in events
        if n == "lm/first_token" and lo <= s < hi)
    checks = check_requests(config, seed, picks, held_max,
                            after["lm.window_blocks_released"], bench.out)
    return {"attempted": len(clients), "failed": len(failed),
            "end_to_end": end_to_end, "checks": checks,
            "memory_peak_bytes": peak, "window": (t_open, t_close),
            "spans": spans, "counters": counters}
