"""Driver ``serve_laguna``: Laguna-S-2.1, cut to one chip's share of a two-chip
expert-parallel deployment (the configuration file says how), as a
``TransformerLM`` with a layer plan behind ``LMServingEngine``, under an
open-loop arrival schedule.

The window, the clients and the notes are ``serve_lm``'s (what could be
imported is; the rest is repeated here and a later ``benchmark`` issue's to
fold).  What differs: how the model is built from the configuration, the
reference the check replays through (``harness/reference_laguna.py``, a layer
at a time), and the counters the routed expert layers add.
"""
import gc
import threading
import time

import numpy as np

import jax.numpy as jnp

from benchmarks.drivers.serve_lm import _Client, _warm
from benchmarks.harness import loadgen, reference_laguna, stats

#: how many finished requests the check replays, besides the longest
CHECK_SAMPLE = 9
#: lengths the replayed requests are padded to (one compilation each a layer)
CHECK_PADS = (1280, 2560)


# -- the configuration as a TransformerLM -----------------------------------------
def layer_plan(c: dict):
    """The program's layer plan from the published lists: the leading dense
    layers one group, then whole periods of the attention pattern stacked."""
    from bigdl_tpu.models.transformer import LayerSpec, RopeSpec
    d = c["head_dim"]

    def rope(kind):
        r = c["rope_parameters"][kind]
        yarn = ((r["factor"], r["original_max_position_embeddings"],
                 r["beta_fast"], r["beta_slow"])
                if r["rope_type"] == "yarn" else None)
        return RopeSpec(theta=r["rope_theta"],
                        rotary_dim=int(d * r["partial_rotary_factor"]), yarn=yarn,
                        attention_factor=r.get("attention_factor", 1.0))

    ropes = {k: rope(k) for k in ("full_attention", "sliding_attention")}
    specs = [LayerSpec(n_head=heads,
                       window=c["sliding_window"] if sliding else None,
                       rope=ropes["sliding_attention" if sliding else "full_attention"],
                       mlp="moe" if sparse else "dense")
             for sliding, heads, sparse in reference_laguna.layer_kinds(c)]
    lead = len(c["mlp_only_layers"])
    rest = specs[lead:]
    period = next(p for p in range(1, len(rest) + 1)
                  if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p))
    plan = [(lead, (specs[0],))] if lead else []
    return plan + [(len(rest) // period, tuple(rest[:period]))]


def build_model(c: dict):
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.parallel.expert import MoESpec
    first, count = reference_laguna.held_experts(c)
    moe = MoESpec(n_experts=c["experts_published"], top_k=c["num_experts_per_tok"],
                  width=c["moe_intermediate_size"],
                  shared_width=c["shared_expert_intermediate_size"],
                  routed_scale=c["moe_routed_scaling_factor"],
                  norm_topk=c["norm_topk_prob"], held=(first, count))
    return TransformerLM(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        n_head=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        ffn_size=c["intermediate_size"], max_len=c["max_position_embeddings"],
        tie_embeddings=c["tie_word_embeddings"], pos_encoding="rope",
        n_kv_head=c["num_key_value_heads"], head_dim=c["head_dim"],
        norm="rmsnorm", norm_eps=c["rms_norm_eps"], mlp_act="swiglu",
        bias=c["attention_bias"], attn_gate=c["gating"] == "per-head", moe=moe,
        layer_plan=layer_plan(c),
        attention_impl=c["assumed"].get("attention_impl", "flash"),
        block_size=c["assumed"].get("flash_block"))


def program_layer(w: dict) -> dict:
    """One reference layer in ``TransformerLM``'s layout (heads flattened)."""
    h = w["wq"].shape[0]
    p = {"ln1": {"weight": w["ln1"]}, "ln2": {"weight": w["ln2"]},
         "attn": {"wq": w["wq"].reshape(h, -1), "wk": w["wk"].reshape(h, -1),
                  "wv": w["wv"].reshape(h, -1), "wo": w["wo"].reshape(-1, h),
                  "wg": w["wg"]}}
    if "router" not in w:
        p["mlp"] = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    else:
        p["moe"] = {"router": w["router"], "w_gate": w["e_gate"],
                    "w_up": w["e_up"], "w_down": w["e_down"],
                    "shared": {"w_gate": w["s_gate"], "w_up": w["s_up"],
                               "w_down": w["s_down"]}}
    return p


def program_params(model, seed: int, c: dict, dtype) -> dict:
    """The benchmark's weights in ``TransformerLM``'s layout, stacked by the
    plan (the assignment a checkpoint loader makes).  A layer at a time, its
    buffers donated to the stacking: two copies of a layer's 2.5 GB beside
    the rest would not fit the chip."""
    import jax
    stack = jax.jit(lambda *a: jnp.stack(a), donate_argnums=0)
    ends = reference_laguna.make_ends(seed, c, dtype)
    groups, base = [], 0
    for repeat, period in model.plan:
        n = len(period)
        groups.append([
            jax.tree_util.tree_map(stack, *[
                program_layer(reference_laguna.make_layer(
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
            "lm.moe_assignments": getattr(m, "moe_assignments", 0),
            "lm.moe_experts_hit": getattr(m, "moe_experts_hit", 0),
            "lm.moe_expert_layer_rounds": getattr(m, "moe_expert_layer_rounds", 0)}


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
    logits, moved, pairs = reference_laguna.forward_requests(
        seed, config, config["assumed"]["serve_dtype"], requests)
    gaps = np.concatenate([
        np.asarray(reference_laguna.gaps_below_best(lg, pos, gen))
        for lg, (pos, gen) in zip(logits, served)])
    out({"check": "served tokens against the plain f32 reference",
         "requests": len(picks), "tokens": int(gaps.size),
         "tokens_not_reference_best": int((gaps > 0).sum()),
         "not_best_share_pct": float((gaps > 0).mean() * 100),
         "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
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
    t0 = time.perf_counter()
    engine = build_engine(config, seed)
    t1 = time.perf_counter()
    _warm(engine, config, np.random.RandomState((seed + 1) % (2 ** 32)))
    bench.out({"setup_phases_s": {"weights_and_engine": t1 - t0,
                                  "compile_or_load_and_warm": time.perf_counter() - t1},
               "decode_attn": engine.decode_attn,
               "kv_arena_bytes": engine.pool.arena_bytes})
    arrivals = loadgen.schedule(mix, seed, bench.seconds, config["vocab_size"])
    tracer = get_tracer()
    clients, stop = [], threading.Event()
    if bench.trace:
        tracer.enable()
        tracer.clear()

    def submit(a):
        return engine.submit(a.prompt, max_new_tokens=a.max_new, temperature=0.0)

    t_open = time.perf_counter()
    firing = threading.Thread(
        target=loadgen.fire, daemon=True,
        args=(arrivals, submit, t_open, lambda f: clients.append(_Client(f)),
              stop))
    firing.start()
    before = _lm_counters(engine)
    bench.open_window(at=t_open)
    bench.sleep_until(t_open + bench.seconds)
    # the window closes where --seconds says, however late this thread woke
    t_close = bench.close_window(at=t_open + bench.seconds)
    after = _lm_counters(engine)
    firing.join(timeout=30)
    stop.set()
    bench.sleep_until(t_close + float(mix["follow_s"]), until=lambda: all(
        c.stream is None or c.stream.done() for c in clients))
    for c in clients:
        if c.stream is not None and not c.stream.done():
            c.stream.cancel()
    for c in clients:
        if c.thread is not None:
            c.thread.join(timeout=300)
        c.release()
    spans, events = [], []
    if bench.trace:
        tracer.disable()
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
    gc.collect()
    bench.out({"device_bytes_in_use_after_close": [
        (d.memory_stats() or {}).get("bytes_in_use") for d in bench.devices]})

    # -- what the client saw ---------------------------------------------
    in_window = lambda t: t_open <= t < t_close     # noqa: E731
    ttft = [(c.stamps[0] - c.due_at) * 1e3 for c in clients if c.stamps]
    itl = [(b - a) * 1e3 for c in clients
           for a, b in zip(c.stamps, c.stamps[1:])]
    tokens_in_window = sum(in_window(t) for c in clients for t in c.stamps)
    failed = [c for c in clients if c.error or not c.stamps]
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
    bench.out({"offered": len(arrivals), "fired": len(clients),
               "finished": sum(c.complete for c in clients),
               "failed": len(failed),
               "errors": sorted({c.error for c in clients if c.error})[:3],
               "ttft_ms": stats.describe(ttft) if ttft else None,
               "itl_ms": stats.describe(itl) if itl else None,
               "itl_percentiles_ms": {str(q): stats.percentile(itl, q) for q in
                                      (50, 90, 93, 94, 95, 95.5, 96, 96.5, 97, 99)} if itl else None,
               "fire_late_ms": stats.describe(
                   [c.late_s * 1e3 for c in clients]) if clients else None,
               "tokens_in_window": tokens_in_window,
               "tokens_in_window_per_s": tokens_in_window / bench.seconds})
    end_to_end = {"out_tokens_per_s": stats.emission_rate(
        [t for c in clients for t in c.stamps], t_open, t_close)}
    if ttft and itl:
        end_to_end.update(ttft_p95_ms=stats.percentile(ttft, 95),
                          itl_p95_ms=stats.percentile(itl, 95))
    counters = {k: after[k] - before[k] for k in after}
    if counters["lm.slot_steps"]:
        counters["lm.slot_occupancy"] = (counters["lm.active_slot_steps"]
                                         / counters["lm.slot_steps"])
    if counters["lm.moe_expert_layer_rounds"]:
        counters["lm.moe_experts_hit_share"] = (
            counters["lm.moe_experts_hit"]
            / (config["num_experts"] * counters["lm.moe_expert_layer_rounds"]))
        bench.out({"moe": {k: counters[k] for k in counters if "moe" in k},
                   "assignments_a_decoded_token_and_layer":
                   counters["lm.moe_assignments"] * counters["lm.decode_steps"]
                   / max(1, counters["lm.active_slot_steps"]
                         * counters["lm.moe_expert_layer_rounds"])})
    # the traced sub-window: what its decode rounds and prefills had to do
    lo, hi = bench.traced_window or (t_open, t_close)
    window = config["sliding_window"]
    contexts = [len(c.arrival.prompt) + i for c in clients
                for i, t in enumerate(c.stamps) if i > 0 and lo <= t < hi]
    counters["lm.decode_context_tokens"] = sum(contexts)
    counters["lm.decode_window_tokens"] = sum(min(n, window) for n in contexts)
    steps = [a for n, s, _, a in events if n == "lm/decode_step" and lo <= s < hi]
    counters["lm.traced_moe_experts_hit"] = sum(
        a.get("moe_experts_hit", 0) for a in steps)
    counters["lm.traced_moe_assignments"] = sum(
        a.get("moe_assignments", 0) for a in steps)
    counters["lm.traced_prefill_tokens"] = [
        a["bucket"] for n, s, _, a in events
        if n == "lm/prefill" and lo <= s < hi and "bucket" in a]
    counters["lm.traced_prefill_moe_assignments"] = sum(
        a.get("moe_assignments", 0) for n, s, _, a in events
        if n == "lm/first_token" and lo <= s < hi)
    checks = check_streams(config, seed, clients, bench.out)
    return {"attempted": len(clients), "failed": len(failed),
            "end_to_end": end_to_end, "checks": checks,
            "memory_peak_bytes": peak, "window": (t_open, t_close),
            "spans": spans, "counters": counters}
