"""Driver ``serve_ling3``: Ling-3.0-flash-VL's language model, cut to one
chip's share (one router group) of an eight-chip expert-parallel deployment
(the configuration file says how), as a ``TransformerLM`` whose layer plan
mixes latent attention (MLA, one layer in six) and KDA (gated delta-rule)
layers behind two leading dense layers, behind ``LMServingEngine`` with a
one-arena LATENT pool beside the state arena, under a closed loop's pool of
clients that decode long contexts.

The clients and the notes are ``serve_solar2``'s closed loop (what could be
imported is -- that driver's ``run`` is one function -- and the rest is
repeated here, a later ``benchmark`` issue's to fold).  What differs: how the
model is built from the configuration, the reference the check replays through
(``harness/reference_ling3.py``, a layer at a time, the served positions' rows
alone), the counters the latent cache adds, the check's reading of THE LATENT
ARENA ITSELF (the sampled streams' cached rows against the reference's), and
WHEN THE WINDOW OPENS: once every one of the mix's clients has its
``window_opens_at_token``-th token, so that every prefill lies in the set-up
and the window starts from the same contexts whatever the prefills' speed.
"""
import gc
import threading
import time

import numpy as np

import jax.numpy as jnp

from benchmarks.drivers.serve_lm import _Client
from benchmarks.drivers.serve_solar2 import LATE, TRACE_REQUESTS
from benchmarks.harness import loadgen, reference_ling3, stats

#: how many streams the check replays: the first of each prompt length that
#: the seeded order names, longest first
CHECK_STREAMS = 4
#: replayed requests are padded to a multiple of this (one compilation a
#: length and layer kind)
CHECK_PAD = 4096
#: cached positions of a replayed stream that the check reads from the latent
#: arena, evenly over its prompt (the prefills' rows) and what it was served
#: by the window's close (the decode step's)
CHECK_ROWS = 512
#: how long the streams may take to reach the window's first token
OPEN_TIMEOUT_S = 1500.0


# -- the configuration as a TransformerLM -----------------------------------------
def layer_plan(c: dict):
    """The program's layer plan: the leading dense layers as one group, then
    whole periods of the published pattern (KDA layers, the MLA layer where
    ``(l + 1) % layer_group_size == 0``), stacked."""
    from bigdl_tpu.models.transformer import LayerSpec, RopeSpec
    heads = c["num_attention_heads"]
    rope = RopeSpec(theta=float(c["rope_theta"]), rotary_dim=c["rotary_dim"])
    specs = [LayerSpec(n_head=heads, rope=rope if kind == "mla" else None,
                       mlp="dense" if reference_ling3.is_dense(c, l) else "moe",
                       mixer=kind)
             for l, kind in enumerate(reference_ling3.layer_kinds(c))]
    dense, rest = (specs[:c["first_k_dense_replace"]],
                   specs[c["first_k_dense_replace"]:])
    period = next(p for p in range(1, len(rest) + 1)
                  if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p))
    plan = [(len(rest) // period, tuple(rest[:period]))]
    if dense:
        if dense != dense[:1] * len(dense):
            raise SystemExit("serve_ling3: the leading dense layers differ")
        plan.insert(0, (len(dense), (dense[0],)))
    return plan


def build_model(c: dict):
    from bigdl_tpu.models.transformer import KDASpec, MLASpec, TransformerLM
    from bigdl_tpu.parallel.expert import MoESpec
    first, count = reference_ling3.held_experts(c)
    moe = MoESpec(n_experts=c["experts_published"], top_k=c["num_experts_per_tok"],
                  width=c["moe_intermediate_size"],
                  shared_width=c["moe_shared_expert_intermediate_size"],
                  routed_scale=c["routed_scaling_factor"],
                  norm_topk=c["norm_topk_prob"], held=(first, count),
                  score=c["score_function"], n_group=c["n_group"],
                  topk_group=c["topk_group"])
    return TransformerLM(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        n_head=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        ffn_size=c["intermediate_size"], max_len=c["max_position_embeddings"],
        tie_embeddings=False, pos_encoding="none", head_dim=c["head_dim"],
        norm="rmsnorm", norm_eps=c["rms_norm_eps"], mlp_act="swiglu", bias=False,
        attn_gate="per-head", moe=moe, layer_plan=layer_plan(c),
        kda_conv=c["short_conv_kernel_size"],
        kda=KDASpec(gate="bounded" if c["kda_safe_gate"] else "softplus",
                    lower_bound=float(c["kda_lower_bound"]),
                    full_rank=bool(c["no_kda_lora"]), beta_scale=1.0),
        mla=MLASpec(kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                    rope=c["qk_rope_head_dim"], v=c["v_head_dim"]))


def program_layer(w: dict) -> dict:
    """One reference layer in ``TransformerLM``'s layout (heads flattened)."""
    h = w["ln1"].shape[0]
    flat = lambda a: a.reshape(a.shape[0], -1)          # noqa: E731
    p = {"ln1": {"weight": w["ln1"]}, "ln2": {"weight": w["ln2"]}}
    if "d_gate" in w:
        p["mlp"] = {"w_gate": w["d_gate"], "w_up": w["d_up"], "w_down": w["d_down"]}
    else:
        p["moe"] = {"router": w["router"], "select_bias": w["router_bias"],
                    "w_gate": w["e_gate"], "w_up": w["e_up"], "w_down": w["e_down"],
                    "shared": {"w_gate": w["s_gate"], "w_up": w["s_up"],
                               "w_down": w["s_down"]}}
    if "conv" in w:
        p["kda"] = {"wq": flat(w["wq"]), "wk": flat(w["wk"]), "wv": flat(w["wv"]),
                    "wo": w["wo"].reshape(-1, h), "conv": flat(w["conv"]),
                    "wf": flat(w["wf"]), "a_log": w["a_log"],
                    "dt_bias": w["dt_bias"].reshape(-1), "wb": w["wb"],
                    "wg": flat(w["wg"]), "norm": w["norm"]}
    else:
        p["mla"] = {"wq": flat(w["wq"]), "w_dkv": w["w_dkv"],
                    "kv_norm": w["kv_norm"], "w_ukv": flat(w["w_ukv"]),
                    "wg": w["wg"], "wo": w["wo"].reshape(-1, h)}
    return p


def program_params(model, seed: int, c: dict, dtype) -> dict:
    """The benchmark's weights in ``TransformerLM``'s layout, stacked by the
    plan (the assignment a checkpoint loader makes).  A layer at a time, its
    buffers donated to the stacking."""
    import jax
    stack = jax.jit(lambda *a: jnp.stack(a), donate_argnums=0)
    ends = reference_ling3.make_ends(seed, c, dtype)
    groups, base = [], 0
    for repeat, period in model.plan:
        n = len(period)
        groups.append([
            jax.tree_util.tree_map(stack, *[
                program_layer(reference_ling3.make_layer(
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


def _warm(engine, config, mix, rng) -> None:
    """Compile (or load) every program this cell's traffic uses, then run each
    once: one prompt of the mix's longest length (its first chunk through the
    whole-prompt prefill, every later one through the suffix prefill against
    the latent arena), a few decode rounds."""
    engine.warmup()
    engine.warmup_prefix(suffix_lens=[engine.prefill_buckets[-1]],
                         prefix_blocks=[engine.table_width])
    engine.submit(rng.randint(1, config["vocab_size"] + 1,
                              size=max(mix["prompt_lens"])),
                  max_new_tokens=4).result(timeout=1200)


def _lm_counters(engine) -> dict:
    m = engine.metrics
    return {"lm.slot_steps": m.slot_steps,
            "lm.active_slot_steps": m.active_slot_steps,
            "lm.decode_steps": m.decode_steps, "lm.prefills": m.prefills,
            "lm.completed": m.completed, "lm.rejected": m.rejected,
            "lm.moe_assignments": m.moe_assignments,
            "lm.moe_experts_hit": m.moe_experts_hit,
            "lm.moe_groups_hit": m.moe_groups_hit,
            "lm.moe_expert_layer_rounds": m.moe_expert_layer_rounds,
            "lm.state_row_steps": m.state_row_steps,
            "lm.latent_rows_read": m.latent_rows_read,
            "lm.latent_bytes_read": m.latent_bytes_read}


# -- the comparison that decides ``correct`` ---------------------------------------
def pick_streams(clients: list) -> list:
    """The streams the check replays: the first of each prompt length that the
    seeded order names (``CHECK_STREAMS`` in all), the longest first; of a
    length, those still in flight before those that have ended (in the cell
    none ends inside a run; an ended stream's slot, and its cached rows, are
    the next one's)."""
    by_len = {}
    for c in clients:
        if not c.error and c.stamps:
            by_len.setdefault(len(c.arrival.prompt), []).append(c)
    each = max(1, CHECK_STREAMS // max(len(by_len), 1))
    return [c for t in sorted(by_len, reverse=True)
            for c in sorted(by_len[t], key=lambda c: c.stream.done())[:each]]


def cached_at_close(engine, picks: list) -> list:
    """At the window's close, while the picked streams still hold their slots:
    where each one's cached rows lie, (its pool chain, ``CHECK_ROWS`` positions
    spread evenly over what the engine has written of it).  A stream with n
    tokens out has the rows of its prompt and of its first n - 1 tokens."""
    out = []
    for c in picks:
        chain = engine.chain_of(c.stream)
        written = len(c.arrival.prompt) + len(c.stamps) - 1
        out.append(None if chain is None else (chain, np.unique(
            np.linspace(0, written - 1, CHECK_ROWS).astype(np.int64))))
    return out


def check_streams(config: dict, seed: int, picks: list, cached: list, out) -> list:
    """Each of ``picks`` (:func:`pick_streams`) replayed once through the plain
    reference, teacher-forced on prompt + served ids.  No stream of this cell
    ends inside a run: what was served up to the cancel is what is compared.
    Numbers compared: over the sample's served tokens, the widest and the mean
    gap by which a served token's reference logit lies below the reference's
    best at its position; and over ``cached`` -- a pick's positions and THE
    ROWS THE LATENT ARENA HELD for them, (latent layers, positions, lanes) --
    the median distance of a cached row from the reference's ``[c ; k_r]`` at
    its position, as a share of that row's length."""
    picks = [(c, at) for c, at in zip(picks, cached)
             if c.generated is not None and len(c.generated)]
    if not picks:
        return [{"name": "served_streams", "value": 0, "limit": 1, "ok": False}]
    requests, rows, gens = [], [], []
    for c, _ in picks:
        gen = c.generated - 1
        t = len(c.arrival.prompt)
        need = t + len(gen)
        pad = min(-(-need // CHECK_PAD) * CHECK_PAD,
                  config["max_position_embeddings"])
        ids = np.zeros((pad,), np.int32)    # causal: the padding is never seen
        ids[:t] = c.arrival.prompt - 1
        ids[t:need] = gen
        requests.append(ids)
        rows.append(np.arange(t - 1, need - 1, dtype=np.int32))
        gens.append(gen)
    t0 = time.perf_counter()
    logits, moved, pairs, latent = reference_ling3.forward_requests(
        seed, config, config["assumed"]["serve_dtype"], requests, rows,
        latent_at=[at[0] if at else np.zeros((0,), np.int64) for _, at in picks])
    gaps = np.concatenate([
        np.asarray(reference_ling3.gaps_below_best(
            lg, np.arange(len(gen)), jnp.asarray(gen)))
        for lg, gen in zip(logits, gens)])
    # had the token after been served in a token's place (a wrong id): the gap
    # that would read (a note: what ``served_gap_max`` can and cannot refuse)
    vocab = config["vocab_size"]
    altered = np.concatenate([
        np.asarray(reference_ling3.gaps_below_best(
            lg, np.arange(len(gen)), jnp.asarray((gen + 1) % vocab)))
        for lg, gen in zip(logits, gens)])
    # a stream's later tokens alone: what a recurrent state loses in decoding
    # grows with the steps since its prefill (a note, not compared)
    late = np.concatenate([np.arange(len(gen)) >= LATE for gen in gens])
    # the latent arena itself: a cached row's distance from the reference's
    off = [np.linalg.norm(held.astype(np.float32) - np.asarray(ref), axis=-1)
           / np.linalg.norm(np.asarray(ref), axis=-1)
           for (_, at), ref in zip(picks, latent) if at for held in [at[1]]]
    if not off:
        return [{"name": "latent_rows_read", "value": 0, "limit": 1, "ok": False}]
    off = np.concatenate([o.reshape(-1) for o in off])
    out({"check": "served tokens against the plain f32 reference",
         "requests": len(picks),
         "prompt_lens": [len(c.arrival.prompt) for c, _ in picks],
         "tokens": int(gaps.size),
         "tokens_not_reference_best": int((gaps > 0).sum()),
         "not_best_share_pct": float((gaps > 0).mean() * 100),
         "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
         "late_tokens": int(late.sum()),
         "gap_mean_late": float(gaps[late].mean()) if late.any() else None,
         "altered_token_gap": {
             "min": float(altered.min()), "p01": float(np.quantile(altered, 0.01)),
             "median": float(np.median(altered)), "mean": float(altered.mean()),
             "share_over_limit_pct": float(
                 (altered > config["check"]["served_gap_max"]).mean() * 100)},
         "latent_rows": {
             "read": int(off.size), "median": float(np.median(off)),
             "mean": float(off.mean()), "p99": float(np.quantile(off, 0.99)),
             "max": float(off.max())},
         "router_picks_moved_by_bf16_rounding_pct": 100.0 * moved / max(pairs, 1),
         "reference_s": time.perf_counter() - t0})
    numbers = {"served_gap_max": float(gaps.max()),
               "served_gap_mean": float(gaps.mean()),
               "latent_row_gap": float(np.median(off))}
    limits = config["check"]
    return [{"name": k, "value": v, "limit": limits[k],
             "ok": bool(v <= limits[k])} for k, v in numbers.items()]


# -- one run ---------------------------------------------------------------------
def run(bench) -> dict:
    """``bench`` is the harness's ``Run``; see ``serve_lm.run``."""
    from bigdl_tpu.obs.tracer import get_tracer
    config, mix, seed = bench.config, bench.mix, bench.seed
    if mix["kind"] != "closed" or "window_opens_at_token" not in mix:
        raise SystemExit("serve_ling3: the cell is a closed loop whose window "
                         "opens at a token count (window_opens_at_token)")
    t0 = time.perf_counter()
    engine = build_engine(config, seed)
    t1 = time.perf_counter()
    _warm(engine, config, mix, np.random.RandomState((seed + 1) % (2 ** 32)))
    # as serve_lm: what set-up left on the heap leaves the collector's sight
    gc.collect()
    gc.freeze()
    t2 = time.perf_counter()
    stats0 = engine.stats()
    state, latent = stats0["state"] or {}, stats0["latent_cache"] or {}
    tracer = get_tracer()
    clients, stop = [], threading.Event()
    sample_rate = tracer.sample_rate
    if bench.trace:
        tracer.set_sample_rate(TRACE_REQUESTS)
        tracer.enable()
        tracer.clear()

    def submit(a):
        return engine.submit(a.prompt, max_new_tokens=a.max_new, temperature=0.0)

    def polled(fired):
        clients.append(_Client(fired, polled=True))
        return clients[-1]

    t_load = time.perf_counter()
    firing = threading.Thread(
        target=loadgen.closed_loop, daemon=True,
        args=(mix, loadgen.sequence(mix, seed, config["vocab_size"]), submit,
              t_load, polled, stop))
    firing.start()
    # the window opens when every client's stream has its n-th token: every
    # prefill, and the first n rounds of every stream, are set-up
    n_open, n_clients = int(mix["window_opens_at_token"]), int(mix["clients"])
    bench.sleep_until(t_load + OPEN_TIMEOUT_S, until=lambda: (
        len(clients) >= n_clients
        and all(c.error or len(c.stamps) >= n_open for c in clients)))
    if (len(clients) < n_clients
            or any(len(c.stamps) < n_open for c in clients)):
        stop.set()
        engine.close()
        raise SystemExit(
            f"serve_ling3: after {OPEN_TIMEOUT_S:.0f} s not every stream has "
            f"its token {n_open}; errors: "
            f"{sorted({c.error for c in clients if c.error})[:3]}")
    t_open = time.perf_counter()
    bench.out({"setup_phases_s": {"weights_and_engine": t1 - t0,
                                  "compile_or_load_and_warm": t2 - t1,
                                  "prefills_and_first_tokens": t_open - t_load},
               "decode_attn": engine.decode_attn,
               "kv_arena_bytes": engine.pool.arena_bytes,
               "kv_pool_row": stats0["kv_pool"]["row"],
               "latent_row_bytes": latent.get("row_bytes"),
               "state_arena_bytes": state.get("bytes"),
               "prefix_cache": stats0.get("prefix_cache")})
    before, rounds_before = _lm_counters(engine), engine.rounds_stats()
    bench.open_window(at=t_open)
    bench.sleep_until(t_open + bench.seconds)
    # the window closes where --seconds says, however late this thread woke
    t_close = bench.close_window(at=t_open + bench.seconds)
    after, rounds_after = _lm_counters(engine), engine.rounds_stats()
    bench.out({"window_rounds": {
        "count": rounds_after["count"] - rounds_before["count"],
        "median_plain_s": rounds_after["median_plain_s"],
        "phase_s": {k: v - rounds_before["phase_s"][k]
                    for k, v in rounds_after["phase_s"].items()}}})
    picks = pick_streams(clients)
    cached = cached_at_close(engine, picks)
    stop.set()                          # the loop cancels what is in flight
    firing.join(timeout=60)
    bench.sleep_until(t_close + 30.0, until=lambda: all(
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
    # the worker has gone: the arena is nobody's to donate, and the cancelled
    # streams' rows lie where they lay (no later stream wrote over them)
    cached = [at and (at[1], engine.pool.rows_at(*at)[0]) for at in cached]
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
    bench.out({"fired": len(clients),
               "finished": sum(c.complete for c in clients),
               "failed": len(failed),
               "errors": sorted({c.error for c in clients if c.error})[:3],
               "tokens_before_window": sum(
                   t < t_open for c in clients for t in c.stamps),
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
            / (config["num_experts"] * counters["lm.moe_expert_layer_rounds"]))
        bench.out({"moe": {k: counters[k] for k in counters if "moe" in k},
                   "state": {"row_steps": counters["lm.state_row_steps"],
                             "row_bytes": row_bytes},
                   "latent": {"rows_read": counters["lm.latent_rows_read"],
                              "bytes_read": counters["lm.latent_bytes_read"]}})
    # the traced sub-window: what its decode rounds had to do, from the args
    # of their lm/decode_step spans (the program's own counts, round by round)
    lo, hi = bench.traced_window or (t_open, t_close)
    steps = [a for n, s, _, a in events if n == "lm/decode_step" and lo <= s < hi]
    counters["lm.traced_decode_rounds"] = len(steps)
    for key, arg in (("lm.traced_moe_experts_hit", "moe_experts_hit"),
                     ("lm.traced_moe_assignments", "moe_assignments"),
                     ("lm.traced_state_rows", "state_rows"),
                     ("lm.traced_latent_positions", "latent_positions"),
                     ("lm.traced_active_slots", "active")):
        counters[key] = sum(a.get(arg, 0) for a in steps)
    checks = check_streams(config, seed, picks, cached, bench.out)
    return {"attempted": len(clients), "failed": len(failed),
            "end_to_end": end_to_end, "checks": checks,
            "memory_peak_bytes": peak, "window": (t_open, t_close),
            "spans": spans, "counters": counters}
