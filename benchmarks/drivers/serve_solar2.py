"""Driver ``serve_solar2``: Solar-Open2-250B, cut to one chip's share of an
eight-chip expert-parallel deployment (the configuration file says how), as a
``TransformerLM`` whose layer plan mixes softmax (NoPE, grouped, gated) and
KDA (gated delta-rule) layers, behind ``LMServingEngine`` with a state arena
beside the paged K/V pool, under a closed loop's pool of clients.

The window, the clients and the notes are ``serve_lm``'s closed loop (what
could be imported is; the rest is repeated here, as ``serve_laguna`` repeats
the open loop, and a later ``benchmark`` issue's to fold).  What differs: how
the model is built from the configuration, the reference the check replays
through (``harness/reference_solar2.py``, a layer at a time), and the
counters the routed layers and the recurrent state add.
"""
import gc
import threading
import time

import numpy as np

import jax.numpy as jnp

from benchmarks.drivers.serve_lm import _Client, _warm
from benchmarks.harness import loadgen, reference_solar2, stats

#: how many finished requests the check replays, besides the longest
CHECK_SAMPLE = 7
#: lengths the replayed requests are padded to (one compilation each a layer)
CHECK_PADS = (768, 1280, 2048)
#: from which of a stream's tokens on the check's note counts it as late
LATE = 256
#: the share of requests that keep their own spans in a traced run
TRACE_REQUESTS = 1.0 / 16


# -- the configuration as a TransformerLM -----------------------------------------
def layer_plan(c: dict):
    """The program's layer plan from ``gqa_layers``: whole periods of the
    published pattern (a softmax layer, then the KDA layers up to the next),
    stacked."""
    from bigdl_tpu.models.transformer import LayerSpec
    heads = reference_solar2.kda_shape(c)[0]
    specs = [LayerSpec(n_head=c["num_attention_heads"], mlp="moe")
             if kind == "softmax" else
             LayerSpec(n_head=heads, mlp="moe", mixer="kda")
             for kind in reference_solar2.layer_kinds(c)]
    period = next(p for p in range(1, len(specs) + 1)
                  if len(specs) % p == 0 and specs == specs[:p] * (len(specs) // p))
    return [(len(specs) // period, tuple(specs[:period]))]


def build_model(c: dict):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.parallel.expert import MoESpec
    first, count = reference_solar2.held_experts(c)
    moe = MoESpec(n_experts=c["experts_published"], top_k=c["num_experts_per_tok"],
                  width=c["moe_intermediate_size"],
                  shared_width=c["moe_intermediate_size"] * c["n_shared_experts"],
                  routed_scale=c["routed_scaling_factor"],
                  norm_topk=c["norm_topk_prob"], held=(first, count),
                  score="sigmoid")
    return TransformerLM(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        n_head=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        max_len=c["max_position_embeddings"],
        tie_embeddings=c["tie_word_embeddings"],
        pos_encoding="rope" if c["use_rope"] else "none",
        n_kv_head=c["num_key_value_heads"], head_dim=c["head_dim"],
        norm="rmsnorm", norm_eps=c["rms_norm_eps"], mlp_act="swiglu", bias=False,
        attn_gate="elementwise" if c["use_gqa_gate"] else False, moe=moe,
        layer_plan=layer_plan(c),
        kda_conv=c["linear_attn_config"]["short_conv_kernel_size"],
        attention_impl=c["assumed"].get("attention_impl", "flash"),
        block_size=c["assumed"].get("flash_block"))


def program_layer(w: dict) -> dict:
    """One reference layer in ``TransformerLM``'s layout (heads flattened)."""
    h = w["wq"].shape[0]
    flat = lambda a: a.reshape(a.shape[0], -1)          # noqa: E731
    p = {"ln1": {"weight": w["ln1"]}, "ln2": {"weight": w["ln2"]},
         "moe": {"router": w["router"], "select_bias": w["router_bias"],
                 "w_gate": w["e_gate"], "w_up": w["e_up"], "w_down": w["e_down"],
                 "shared": {"w_gate": w["s_gate"], "w_up": w["s_up"],
                            "w_down": w["s_down"]}}}
    if "conv" not in w:
        p["attn"] = {"wq": flat(w["wq"]), "wk": flat(w["wk"]), "wv": flat(w["wv"]),
                     "wo": w["wo"].reshape(-1, h), "wg": flat(w["wg"])}
    else:
        p["kda"] = {"wq": flat(w["wq"]), "wk": flat(w["wk"]), "wv": flat(w["wv"]),
                    "wo": w["wo"].reshape(-1, h), "conv": flat(w["conv"]),
                    "wf1": w["wf1"], "wf2": flat(w["wf2"]), "a_log": w["a_log"],
                    "dt_bias": w["dt_bias"].reshape(-1), "wb": w["wb"],
                    "wg1": w["wg1"], "wg2": flat(w["wg2"]), "norm": w["norm"]}
    return p


def program_params(model, seed: int, c: dict, dtype) -> dict:
    """The benchmark's weights in ``TransformerLM``'s layout, stacked by the
    plan (the assignment a checkpoint loader makes).  A layer at a time, its
    buffers donated to the stacking."""
    import jax
    stack = jax.jit(lambda *a: jnp.stack(a), donate_argnums=0)
    ends = reference_solar2.make_ends(seed, c, dtype)
    groups, base = [], 0
    for repeat, period in model.plan:
        n = len(period)
        groups.append([
            jax.tree_util.tree_map(stack, *[
                program_layer(reference_solar2.make_layer(
                    seed, c, base + r * n + i, dtype)) for r in range(repeat)])
            for i in range(n)])
        base += repeat * n
    return {"embed": ends["embed"], "head": ends["head"],
            "ln_f": {"weight": ends["norm_f"]}, "groups": groups}


def build_engine(config: dict, seed: int):
    from bigdl_tpu.serving import LMServingEngine
    model = build_model(config)
    model.params = program_params(model, seed, config,
                                  config["assumed"]["serve_dtype"])
    model.buffers = {}
    model.evaluate()
    args = dict(config["engine"])
    args["prefill_buckets"] = tuple(args["prefill_buckets"])
    return LMServingEngine(model, **args)


def _lm_counters(engine) -> dict:
    m = engine.metrics
    return {"lm.slot_steps": m.slot_steps,
            "lm.active_slot_steps": m.active_slot_steps,
            "lm.decode_steps": m.decode_steps, "lm.prefills": m.prefills,
            "lm.completed": m.completed, "lm.rejected": m.rejected,
            "lm.moe_assignments": m.moe_assignments,
            "lm.moe_experts_hit": m.moe_experts_hit,
            "lm.moe_expert_layer_rounds": m.moe_expert_layer_rounds,
            "lm.state_row_steps": m.state_row_steps}


# -- the comparison that decides ``correct`` ---------------------------------------
def check_streams(config: dict, seed: int, clients: list, out) -> list:
    """A seeded sample of the finished requests, the longest among them, each
    replayed once through the plain reference (teacher-forced on the served
    tokens).  Numbers compared, over the sample's served tokens: the widest and
    the mean gap by which a served token's reference logit lies below the
    reference's best at its position."""
    done = [c for c in clients if c.complete]
    if not done:
        return [{"name": "finished_requests", "value": 0, "limit": 1,
                 "ok": False}]
    rng = np.random.RandomState(seed % (2 ** 32))
    longest = max(done, key=lambda c: len(c.arrival.prompt) + c.arrival.max_new)
    rest = [c for c in done if c is not longest]
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[:CHECK_SAMPLE]]
    requests, served = [], []
    for c in picks:
        gen = c.generated - 1
        t = len(c.arrival.prompt)
        need = t + len(gen)
        pad = min(next((p for p in CHECK_PADS if p >= need), need),
                  config["max_position_embeddings"])
        ids = np.zeros((pad,), np.int32)    # causal: the padding is never seen
        ids[:t] = c.arrival.prompt - 1
        ids[t:need] = gen
        requests.append(ids)
        served.append((np.arange(t - 1, need - 1, dtype=np.int32), gen))
    t0 = time.perf_counter()
    logits, moved, pairs = reference_solar2.forward_requests(
        seed, config, config["assumed"]["serve_dtype"], requests)
    gaps = np.concatenate([
        np.asarray(reference_solar2.gaps_below_best(lg, pos, gen))
        for lg, (pos, gen) in zip(logits, served)])
    # a stream's later tokens alone: what a recurrent state loses in decoding
    # grows with the steps since its prefill (a note, not compared)
    late = np.concatenate([np.arange(len(gen)) >= LATE for _, gen in served])
    out({"check": "served tokens against the plain f32 reference",
         "requests": len(picks), "tokens": int(gaps.size),
         "tokens_not_reference_best": int((gaps > 0).sum()),
         "not_best_share_pct": float((gaps > 0).mean() * 100),
         "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
         "late_tokens": int(late.sum()),
         "gap_mean_late": float(gaps[late].mean()) if late.any() else None,
         "router_picks_moved_by_bf16_rounding_pct": 100.0 * moved / max(pairs, 1),
         "reference_s": time.perf_counter() - t0})
    numbers = {"served_gap_max": float(gaps.max()),
               "served_gap_mean": float(gaps.mean())}
    limits = config["check"]
    return [{"name": k, "value": v, "limit": limits[k],
             "ok": bool(v <= limits[k])} for k, v in numbers.items()]


# -- one run ---------------------------------------------------------------------
def run(bench) -> dict:
    """``bench`` is the harness's ``Run``; see ``serve_lm.run``."""
    from bigdl_tpu.obs.tracer import get_tracer
    config, mix, seed = bench.config, bench.mix, bench.seed
    if mix["kind"] != "closed":
        raise SystemExit("serve_solar2: the cell is a closed loop "
                         f"(mix kind {mix['kind']!r})")
    t0 = time.perf_counter()
    engine = build_engine(config, seed)
    t1 = time.perf_counter()
    _warm(engine, config, np.random.RandomState((seed + 1) % (2 ** 32)))
    # as serve_lm: what set-up left on the heap leaves the collector's sight
    gc.collect()
    gc.freeze()
    preroll = float(mix.get("preroll_s", 0.0))
    state = engine.stats()["state"] or {}
    bench.out({"setup_phases_s": {"weights_and_engine": t1 - t0,
                                  "compile_or_load_and_warm": time.perf_counter() - t1,
                                  "preroll": preroll},
               "decode_attn": engine.decode_attn,
               "kv_arena_bytes": engine.pool.arena_bytes,
               "state_arena_bytes": state.get("bytes"),
               "prefix_cache": engine.stats().get("prefix_cache")})
    tracer = get_tracer()
    clients, stop = [], threading.Event()
    sample_rate = tracer.sample_rate
    if bench.trace:
        # one request in sixteen keeps its own spans: at every request's, 128
        # slots write 140 events a round and the ring (65,536 events) drops
        # the traced sub-window's rounds before they are read
        tracer.set_sample_rate(TRACE_REQUESTS)
        tracer.enable()
        tracer.clear()

    def submit(a):
        return engine.submit(a.prompt, max_new_tokens=a.max_new, temperature=0.0)

    def polled(fired):
        clients.append(_Client(fired, polled=True))
        return clients[-1]

    t_open = time.perf_counter() + preroll
    firing = threading.Thread(
        target=loadgen.closed_loop, daemon=True,
        args=(mix, loadgen.sequence(mix, seed, config["vocab_size"]), submit,
              t_open, polled, stop))
    firing.start()
    bench.sleep_until(t_open)
    before, rounds_before = _lm_counters(engine), engine.rounds_stats()
    bench.open_window(at=t_open)
    bench.sleep_until(t_open + bench.seconds)
    # the window closes where --seconds says, however late this thread woke
    t_close = bench.close_window(at=t_open + bench.seconds)
    after, rounds_after = _lm_counters(engine), engine.rounds_stats()
    # the worker's seconds by phase over the window (the always-on round
    # record): which of a run's tokens/s is the device's and which the host's
    bench.out({"window_rounds": {
        "count": rounds_after["count"] - rounds_before["count"],
        "median_plain_s": rounds_after["median_plain_s"],
        "phase_s": {k: v - rounds_before["phase_s"][k]
                    for k, v in rounds_after["phase_s"].items()}}})
    stop.set()                          # the loop cancels what is in flight
    firing.join(timeout=30)
    bench.sleep_until(t_close + float(mix["follow_s"]), until=lambda: all(
        c.stream is None or c.stream.done() for c in clients))
    for c in clients:
        c.cancel()
    for c in clients:
        c.release()
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
    row_bytes = state.get("row_bytes", 0)
    engine.close()
    del engine, firing, submit
    gc.unfreeze()           # or the engine's cycles would keep its arrays
    gc.collect()
    bench.out({"device_bytes_in_use_after_close": [
        (d.memory_stats() or {}).get("bytes_in_use") for d in bench.devices]})

    # -- what the client saw ---------------------------------------------
    in_window = lambda t: t_open <= t < t_close     # noqa: E731
    tokens_in_window = sum(in_window(t) for c in clients for t in c.stamps)
    # a refusal or an error fails; the window's close cancels the queue
    # (follow_s 0), so a request still waiting was attempted and has not failed
    failed = [c for c in clients if c.error
              or (not c.stamps and float(mix["follow_s"]) > 0)]
    bench.out({"fired": len(clients),
               "finished": sum(c.complete for c in clients),
               "failed": len(failed),
               "errors": sorted({c.error for c in clients if c.error})[:3],
               "tokens_in_window": tokens_in_window,
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
        bench.out({"moe": {k: counters[k] for k in counters if "moe" in k},
                   "state": {"row_steps": counters["lm.state_row_steps"],
                             "row_bytes": row_bytes}})
    # the traced sub-window: what its decode rounds and prefills had to do
    lo, hi = bench.traced_window or (t_open, t_close)
    counters["lm.decode_context_tokens"] = sum(
        len(c.arrival.prompt) + i for c in clients
        for i, t in enumerate(c.stamps) if i > 0 and lo <= t < hi)
    steps = [a for n, s, _, a in events if n == "lm/decode_step" and lo <= s < hi]
    counters["lm.traced_moe_experts_hit"] = sum(
        a.get("moe_experts_hit", 0) for a in steps)
    counters["lm.traced_state_rows"] = sum(a.get("state_rows", 0) for a in steps)
    counters["lm.traced_decode_rounds"] = len(steps)
    # a prefill's true positions: its chunk of the prompt, not its bucket
    counters["lm.traced_prefill_tokens"] = [
        min(a["bucket"], a["prompt_len"] - a["prefix_len"])
        for n, s, _, a in events
        if n == "lm/prefill" and lo <= s < hi and "bucket" in a]
    counters["lm.traced_prefill_moe_assignments"] = sum(
        a.get("moe_assignments", 0) for n, s, _, a in events
        if n == "lm/first_token" and lo <= s < hi)
    checks = check_streams(config, seed, clients, bench.out)
    return {"attempted": len(clients), "failed": len(failed),
            "end_to_end": end_to_end, "checks": checks,
            "memory_peak_bytes": peak, "window": (t_open, t_close),
            "spans": spans, "counters": counters}
