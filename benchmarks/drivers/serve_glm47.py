"""Driver ``serve_glm47``: GLM-4.7-Flash, cut in depth alone (every expert and
the whole vocabulary held: the configuration file says how), as a
``TransformerLM`` whose every layer is latent attention with a compressed query
behind one leading dense layer, WITH ITS PREDICTION MODULE, behind
``LMServingEngine`` with that module as the drafter through the target's own
latent pool (``SpecConfig(k=1)``: a self-drafting round yields one or two
tokens a stream) and the radix prefix cache on, under a closed loop's pool of
agent-loop clients: every prompt one of a few long system-and-tools prefixes
and a tail of its own.

The clients and the notes are ``serve_solar2``'s closed loop (what could be
imported is; the rest is repeated here, a later ``benchmark`` issue's to fold).
What differs: how the model is built from the configuration; the REQUESTS (the
mix's sequence gives the tails, this driver puts one of the run's shared
prefixes before each); the counters self-drafting and the prefix cache add; and
the check: a sample of FINISHED requests replayed through
``harness/reference_glm47.py`` -- both models, teacher-forced on prompt + served
ids -- for the served tokens, the recorded DRAFTS, the acceptance, and the
latent arena's rows (the main layers' and the module's).
"""
import gc
import threading
import time

import numpy as np

import jax.numpy as jnp

from benchmarks.drivers.serve_lm import _Client
from benchmarks.drivers.serve_solar2 import TRACE_REQUESTS
from benchmarks.harness import loadgen, reference_glm47, stats

#: how many FINISHED requests the check replays: at least one of every shared
#: prefix, the longest contexts first
CHECK_STREAMS = 8
#: ... and how many still IN FLIGHT at the window's close beside them, those
#: with the most tokens out: their cached rows are read from the arena
CHECK_INFLIGHT = 2
#: replayed requests are padded to a multiple of this (one compilation a length)
CHECK_PAD = 2048
#: cached positions of a replayed request that the check reads from the latent
#: arena, evenly over its context
CHECK_ROWS = 256


#: seconds the last ``program_params`` took, by part (a note of the set-up)
BUILD_S = {}


def cached_rows(pool, chain, at):
    """The rows the latent arena holds at positions ``at`` of the block chain
    ``chain``, a layer at a time (one gather over the whole six-layer arena
    asks for more memory than the weights leave): -> (layers, positions,
    lanes as the model states them)."""
    arena, = pool.arenas
    lanes = pool.wire_shape[-1]
    blk = jnp.asarray(np.asarray(chain)[at // pool.block_len])
    off = jnp.asarray(at % pool.block_len)
    return np.stack([np.asarray(arena[layer, blk, off, :lanes])
                     for layer in range(arena.shape[0])])


# -- the configuration as a TransformerLM -----------------------------------------
def _spec(c: dict, layer: int):
    from bigdl_tpu.models.transformer import LayerSpec, RopeSpec
    rope = RopeSpec(theta=float(c["rope_theta"]),
                    rotary_dim=c["qk_rope_head_dim"])
    return LayerSpec(n_head=c["num_attention_heads"], rope=rope,
                     mlp="dense" if reference_glm47.is_dense(c, layer) else "moe",
                     mixer="mla")


def layer_plan(c: dict):
    """The leading dense layers as one group, the routed layers as another:
    ONE period of all of them, not a period of one repeated.  A repeated
    period is scanned over its stacked parameters, and the scan slices a
    layer's experts out of the stack before the grouped matmuls can read them:
    three copies of 403 MB a layer, 3.2 ms of a routed layer's 6.5 (my chip run,
    PR 40, call 2: ``dynamic-slice_bitcast_fusion`` beside ``ragged-dot``); a
    period's layers are unrolled and read their own parameters where they lie
    (the other configurations' plans are one period each already)."""
    dense, n = c["first_k_dense_replace"], c["num_hidden_layers"]
    plan = [(1, tuple(_spec(c, l) for l in range(dense, n)))]
    if dense:
        plan.insert(0, (dense, (_spec(c, 0),)))
    return plan


def build_model(c: dict):
    from bigdl_tpu.models.transformer import MLASpec, TransformerLM
    from bigdl_tpu.parallel.expert import MoESpec
    moe = MoESpec(n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
                  width=c["moe_intermediate_size"],
                  shared_width=c["moe_intermediate_size"] * c["n_shared_experts"],
                  routed_scale=c["routed_scaling_factor"],
                  norm_topk=c["norm_topk_prob"],
                  held=(0, c["n_routed_experts"]), score="sigmoid",
                  n_group=c["n_group"], topk_group=c["topk_group"])
    return TransformerLM(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        n_head=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        ffn_size=c["intermediate_size"], max_len=c["max_position_embeddings"],
        tie_embeddings=c["tie_word_embeddings"], pos_encoding="none",
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        norm="rmsnorm", norm_eps=c["rms_norm_eps"], mlp_act="swiglu", bias=False,
        moe=moe, layer_plan=layer_plan(c),
        mla=MLASpec(kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                    rope=c["qk_rope_head_dim"], v=c["v_head_dim"],
                    q_rank=c["q_lora_rank"]),
        mtp=(_spec(c, c["num_hidden_layers"])
             if c["num_nextn_predict_layers"] else None))


def program_layer(w: dict) -> dict:
    """One reference block in ``TransformerLM``'s layout (heads flattened)."""
    h = w["ln1"].shape[0]
    flat = lambda a: a.reshape(a.shape[0], -1)          # noqa: E731
    p = {"ln1": {"weight": w["ln1"]}, "ln2": {"weight": w["ln2"]},
         "mla": {"wq_a": w["wq_a"], "q_norm": w["q_norm"], "wq_b": flat(w["wq_b"]),
                 "w_dkv": w["w_dkv"], "kv_norm": w["kv_norm"],
                 "w_ukv": flat(w["w_ukv"]), "wo": w["wo"].reshape(-1, h)}}
    if "d_gate" in w:
        p["mlp"] = {"w_gate": w["d_gate"], "w_up": w["d_up"], "w_down": w["d_down"]}
    else:
        p["moe"] = {"router": w["router"], "select_bias": w["router_bias"],
                    "w_gate": w["e_gate"], "w_up": w["e_up"], "w_down": w["e_down"],
                    "shared": {"w_gate": w["s_gate"], "w_up": w["s_up"],
                               "w_down": w["s_down"]}}
    return p


def program_params(model, seed: int, c: dict, dtype) -> dict:
    """The benchmark's weights in ``TransformerLM``'s layout, stacked by the
    plan (the assignment a checkpoint loader makes), the prediction module's
    under ``"mtp"``.  A block at a time, its buffers donated to the stacking."""
    import jax
    stack = jax.jit(lambda *a: jnp.stack(a), donate_argnums=0)
    t0 = time.perf_counter()
    ends = reference_glm47.make_ends(seed, c, dtype)
    jax.block_until_ready(ends)
    BUILD_S["embedding_and_head"] = time.perf_counter() - t0
    groups, base = [], 0
    for repeat, period in model.plan:
        n = len(period)
        groups.append([
            jax.tree_util.tree_map(stack, *[
                program_layer(reference_glm47.make_layer(
                    seed, c, base + r * n + i, dtype)) for r in range(repeat)])
            for i in range(n)])
        base += repeat * n
    params = {"embed": ends["embed"], "head": ends["head"],
              "ln_f": {"weight": ends["norm_f"]}, "groups": groups}
    if model.mtp is not None:
        w = reference_glm47.make_layer(seed, c, c["num_hidden_layers"], dtype)
        params["mtp"] = {"enorm": {"weight": w["enorm"]},
                         "hnorm": {"weight": w["hnorm"]},
                         "eh_proj": w["eh_proj"], "block": program_layer(w),
                         "norm": {"weight": w["mtp_norm"]}}
    jax.block_until_ready(params)
    BUILD_S["all_weights"] = time.perf_counter() - t0
    return params


def build_engine(config: dict, seed: int, **overrides):
    """The cell's engine; ``spec=None`` among ``overrides`` serves the same
    model with the drafter off (the builder's one reading of plain rounds)."""
    from bigdl_tpu.serving import LMServingEngine
    from bigdl_tpu.serving.spec import SpecConfig
    model = build_model(config)
    model.params = program_params(model, seed, config,
                                  config["assumed"]["serve_dtype"])
    model.buffers = {}
    model.evaluate()
    args = dict(config["engine"])
    args["prefill_buckets"] = tuple(args["prefill_buckets"])
    k = args.pop("self_draft_k", 0)
    args["spec"] = SpecConfig(k=k) if k else None
    args.update(overrides)
    return LMServingEngine(model, **args)


# -- the requests: a shared prefix, then a tail of its own ------------------------------
def shared_prefixes(mix: dict, seed: int, vocab: int) -> list:
    """The run's system-and-tools prefixes, 1-based ids drawn once from the
    seed (another stream of it than the tails')."""
    rng = np.random.RandomState((seed + 40) % (2 ** 32))
    return [rng.randint(1, vocab + 1, size=int(mix["shared_prefix_len"])
                        ).astype(np.int32)
            for _ in range(int(mix["shared_prefixes"]))]


def agent_requests(mix: dict, seed: int, vocab: int):
    """``loadgen.sequence``'s requests (their prompts are the TAILS), each
    behind one of the shared prefixes: request i behind prefix i mod n."""
    prefixes = shared_prefixes(mix, seed, vocab)
    for a in loadgen.sequence(mix, seed, vocab):
        yield a._replace(prompt=np.concatenate(
            [prefixes[a.index % len(prefixes)], a.prompt]))


def _warm(engine, config, mix, rng) -> None:
    """Compile (or load) every program this cell's traffic uses, then run each
    once: a prompt of a prefix and the longest tail twice (whole and chunked
    prefills, then the radix hit's one-token pass and suffix prefill), a few
    rounds.  The warm-up's prefix is its own: none of the run's is cached by
    it."""
    engine.warmup()
    engine.warmup_prefix(prefix_blocks=[engine.table_width])
    prompt = rng.randint(1, config["vocab_size"] + 1,
                         size=int(mix["shared_prefix_len"]) + max(mix["prompt_lens"]))
    for _ in range(2):
        engine.submit(prompt, max_new_tokens=6).result(timeout=1200)


def _lm_counters(engine) -> dict:
    m, sm = engine.metrics, engine.spec_metrics
    out = {"lm.slot_steps": m.slot_steps,
           "lm.active_slot_steps": m.active_slot_steps,
           "lm.decode_steps": m.decode_steps, "lm.prefills": m.prefills,
           "lm.completed": m.completed, "lm.rejected": m.rejected,
           "lm.tokens": m.tokens,
           "lm.logit_rows_to_host": m.logit_rows_to_host,
           "lm.moe_assignments": m.moe_assignments,
           "lm.moe_experts_hit": m.moe_experts_hit,
           "lm.moe_expert_layer_rounds": m.moe_expert_layer_rounds,
           "lm.latent_rows_read": m.latent_rows_read,
           "lm.latent_bytes_read": m.latent_bytes_read,
           "lm.prompt_tokens": m.prompt_tokens,
           "lm.prefix_matched_tokens": m.prefix_matched_tokens}
    if sm is not None:
        out.update({"lm.spec_drafted": sm.drafted, "lm.spec_accepted": sm.accepted,
                    "lm.spec_emitted": sm.emitted,
                    "lm.spec_verify_rounds": sm.verify_rounds,
                    "lm.spec_draft_steps": sm.draft_steps,
                    "lm.spec_draft_latent_rows_read": sm.draft_latent_rows_read})
    return out


# -- the comparison that decides ``correct`` ---------------------------------------
def pick_requests(clients: list, n_prefixes: int, leave=()) -> list:
    """The requests the check replays: FINISHED ones, the longest context of
    every shared prefix first, then the longest of the rest, ``CHECK_STREAMS``
    in all."""
    done = [c for c in clients if not c.error and c.stream is not None
            and c not in leave
            and c.stream.done() and len(c.stamps) == c.arrival.max_new]
    size = lambda c: len(c.arrival.prompt) + c.arrival.max_new   # noqa: E731
    done.sort(key=size, reverse=True)
    first, seen = [], set()
    for c in done:
        k = c.arrival.index % n_prefixes
        if k not in seen:
            seen.add(k)
            first.append(c)
    rest = [c for c in done if c not in first]
    return (first + rest)[:CHECK_STREAMS]


def inflight_at_close(engine, clients: list) -> list:
    """At the window's close, the ``CHECK_INFLIGHT`` streams in flight with
    the most tokens out, while they still hold their slots: ``(client, its pool
    chain, CHECK_ROWS positions spread evenly over what the engine has written
    of it)``: a matched prefix's rows, a suffix prefill's and the rounds', in
    every arena layer.  A stream with n tokens out has main rows up to its
    n - 1st token's and, in the module's layer, pairs up to there too;
    position 0 aside (the module's layer holds no pair there), and two short
    of the newest (a round in flight)."""
    live = sorted((c for c in clients if not c.error and c.stream is not None
                   and not c.stream.done() and len(c.stamps) >= 3),
                  key=lambda c: len(c.stamps), reverse=True)
    out = []
    for c in live:
        chain = engine.chain_of(c.stream)
        if chain is None:
            continue
        written = len(c.arrival.prompt) + len(c.stamps) - 3
        out.append((c, chain, np.unique(
            np.linspace(1, written - 1, CHECK_ROWS).astype(np.int64))))
        if len(out) == CHECK_INFLIGHT:
            break
    return out


def check_requests(config: dict, seed: int, picks: list, cached: list, out) -> list:
    """Each of ``picks`` replayed once through the plain reference, BOTH
    models, teacher-forced on prompt + served ids.  Numbers compared:

    - ``served_gap_max`` / ``served_gap_mean``: over the served tokens, the gap
      by which a served token's reference logit lies below the reference's best
      at its position (the main model);
    - ``draft_gap_mean``: over the recorded drafts, the same gap for the token
      the engine DRAFTED, under the reference's prediction module;
    - ``accept_gap``: |the engine's acceptance - the reference's| over the same
      positions, the reference's being how often ITS module's best token is
      ITS main model's best token there;
    - ``latent_row_gap``: the median distance of a cached row from the
      reference's, main layers and the module's, as a share of its length."""
    picks = [(c, at) for c, at in zip(picks, cached)
             if c.generated is not None and len(c.generated) > 2]
    drafting = bool(config["engine"].get("self_draft_k"))
    if len(picks) < 1:
        return [{"name": "served_requests", "value": 0, "limit": 1, "ok": False}]
    requests, served, drafted, recs = [], [], [], []
    for c, _ in picks:
        gen = c.generated - 1
        t = len(c.arrival.prompt)
        need = t + len(gen)
        ids = np.zeros((-(-need // CHECK_PAD) * CHECK_PAD,), np.int32)
        ids[:t] = c.arrival.prompt - 1
        ids[t:need] = gen
        requests.append(ids)
        served.append((np.arange(t - 1, need - 1, dtype=np.int32), gen))
        d = np.asarray(c.drafts, np.int64).reshape(-1, 2)
        # the draft for generated token i (position t + i) is pair t + i - 2's
        drafted.append(((t + d[:, 0] - 2).astype(np.int32),
                        (d[:, 1] - 1).astype(np.int32)))
        recs.append((gen, d))
    t0 = time.perf_counter()
    ref = reference_glm47.replay_requests(
        seed, config, config["assumed"]["serve_dtype"], requests, served, drafted,
        latent_at=[at[0] if at else np.zeros((1,), np.int64) for _, at in picks])
    gaps, dgaps, eng_acc, ref_acc, off = [], [], [], [], []
    for (c, at), r, (rows, gen), (pairs, dids), (_, d) in zip(
            picks, ref, served, drafted, recs):
        best, best_id, got = r["main"]
        gaps.append(best - got)
        if len(pairs):
            dbest, dbest_id, dgot = r["mtp"]
            dgaps.append(dbest - dgot)
            eng_acc.append(gen[d[:, 0]] == dids)
            # the reference's own: its module's best against its main model's
            # best for the same position (row t + i - 1 of the main model)
            ref_acc.append(dbest_id == best_id[d[:, 0]])
        if at:
            held = at[1].astype(np.float32)     # (arena layers, positions, lanes)
            want = r["rows"][:held.shape[0]]    # (the module's block's last)
            off.append(np.linalg.norm(held - want, axis=-1)
                       / np.linalg.norm(want, axis=-1))     # (layers, positions)
    gaps = np.concatenate(gaps)
    missing = []
    if not off:
        missing.append("latent_rows_read")
    if drafting and not dgaps:
        missing.append("drafts_read")
    off = np.concatenate(off, axis=1) if off else np.zeros((1, 1))
    by_layer, off = np.median(off, axis=1), off.reshape(-1)
    if not drafting or not dgaps:   # the drafter off: the served tokens and the rows
        dgaps = eng_acc = ref_acc = np.zeros((1,))
    else:
        dgaps = np.concatenate(dgaps)
        eng_acc, ref_acc = np.concatenate(eng_acc), np.concatenate(ref_acc)
    out({"check": "served tokens, drafts and cached rows against the plain f32 "
                  "reference (both models)",
         "requests": len(picks),
         "prompt_lens": [len(c.arrival.prompt) for c, _ in picks],
         "tokens": int(gaps.size), "drafts": int(dgaps.size),
         "tokens_not_reference_best": int((gaps > 0).sum()),
         "gap_max": float(gaps.max()), "gap_mean": float(gaps.mean()),
         "drafts_not_reference_best": int((dgaps > 0).sum()),
         "draft_gap_max": float(dgaps.max()), "draft_gap_mean": float(dgaps.mean()),
         "acceptance": {"engine": float(eng_acc.mean()),
                        "reference": float(ref_acc.mean()),
                        "agree_pct": float((eng_acc == ref_acc).mean() * 100)},
         "latent_rows": {"read": int(off.size), "median": float(np.median(off)),
                         "median_by_arena_layer": [float(m) for m in by_layer],
                         "mean": float(off.mean()),
                         "p99": float(np.quantile(off, 0.99)),
                         "max": float(off.max())},
         "reference_s": time.perf_counter() - t0})
    numbers = {"served_gap_max": float(gaps.max()),
               "served_gap_mean": float(gaps.mean()),
               "draft_gap_mean": float(dgaps.mean()),
               "accept_gap": float(abs(eng_acc.mean() - ref_acc.mean())),
               "latent_row_gap": float(np.median(off))}
    if not drafting:
        del numbers["draft_gap_mean"], numbers["accept_gap"]
    limits = config["check"]
    return ([{"name": k, "value": v, "limit": limits[k],
              "ok": bool(v <= limits[k])} for k, v in numbers.items()]
            + [{"name": k, "value": 0, "limit": 1, "ok": False} for k in missing])


# -- one run ---------------------------------------------------------------------
def run(bench) -> dict:
    """``bench`` is the harness's ``Run``; see ``serve_lm.run``."""
    from bigdl_tpu.obs.tracer import get_tracer
    config, mix, seed = bench.config, bench.mix, bench.seed
    if mix["kind"] != "closed" or "shared_prefixes" not in mix:
        raise SystemExit("serve_glm47: the cell is a closed loop over shared "
                         "prefixes (shared_prefixes, shared_prefix_len)")
    t0 = time.perf_counter()
    engine = build_engine(config, seed)
    t1 = time.perf_counter()
    _warm(engine, config, mix, np.random.RandomState((seed + 1) % (2 ** 32)))
    # as serve_lm: what set-up left on the heap leaves the collector's sight
    gc.collect()
    gc.freeze()
    t2 = time.perf_counter()
    stats0 = engine.stats()
    latent = stats0["latent_cache"] or {}
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

    # the loop starts preroll_s before the window: the pre-roll computes the
    # shared prefixes once each and fills the slots
    t_load = time.perf_counter()
    t_open = t_load + float(mix["preroll_s"])
    firing = threading.Thread(
        target=loadgen.closed_loop, daemon=True,
        args=(mix, agent_requests(mix, seed, config["vocab_size"]), submit,
              t_open, polled, stop))
    firing.start()
    bench.sleep_until(t_open)
    before, rounds_before = _lm_counters(engine), engine.rounds_stats()
    bench.out({"setup_phases_s": {"weights_and_engine": t1 - t0,
                                  "weights_by_part": dict(BUILD_S),
                                  "compile_or_load_and_warm": t2 - t1,
                                  "preroll": t_open - t_load},
               "decode_attn": engine.decode_attn,
               "kv_arena_bytes": engine.pool.arena_bytes,
               "kv_pool_row": stats0["kv_pool"]["row"],
               "latent_layers_in_arena": engine.pool.n_layers,
               "latent_row_bytes": latent.get("row_bytes"),
               "prefix_cache": stats0.get("prefix_cache"),
               "spec": {k: (stats0.get("spec") or {}).get(k)
                        for k in ("drafter", "shares_pool", "k")},
               "preroll_counters": {
                   k: before[k] for k in ("lm.prompt_tokens",
                                          "lm.prefix_matched_tokens",
                                          "lm.prefills", "lm.tokens")}})
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
    inflight = inflight_at_close(engine, clients)
    stop.set()                          # the loop cancels what is in flight
    firing.join(timeout=60)
    bench.sleep_until(t_close + 30.0, until=lambda: all(
        c.stream is None or c.stream.done() for c in clients))
    for c in clients:
        c.cancel()
    picks = pick_requests(clients, int(mix["shared_prefixes"]),
                          leave=[c for c, _, _ in inflight])
    for c in clients:
        c.drafts = list(c.stream.drafts) if c.stream is not None else []
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
    engine.close()
    # the worker has gone: the arena is nobody's to donate, and the cancelled
    # streams' rows lie where they lay (no later stream wrote over them)
    cached = [None] * len(picks) + [
        (at, cached_rows(engine.pool, chain, at)) for _, chain, at in inflight]
    picks = picks + [c for c, _, _ in inflight]
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
        # tokens a seated slot gets a round: 1 + acceptance where every slot drafts
        counters["lm.tokens_per_slot_round"] = (
            (counters["lm.tokens"] - counters["lm.prefills"])
            / max(counters["lm.active_slot_steps"], 1))
    if counters["lm.moe_expert_layer_rounds"]:
        counters["lm.moe_experts_hit_share"] = (
            counters["lm.moe_experts_hit"]
            / (config["n_routed_experts"] * counters["lm.moe_expert_layer_rounds"]))
    if counters.get("lm.spec_drafted"):
        counters["lm.spec_accept_share"] = (counters["lm.spec_accepted"]
                                            / counters["lm.spec_drafted"])
    if counters["lm.prompt_tokens"]:
        counters["lm.prefix_hit_share"] = (counters["lm.prefix_matched_tokens"]
                                           / counters["lm.prompt_tokens"])
    bench.out({"window_counters": {k: counters[k] for k in sorted(counters)
                                   if "moe" in k or "spec" in k or "prefix" in k
                                   or "latent" in k or "logit" in k
                                   or "per_slot" in k
                                   or k in ("lm.prefills", "lm.tokens")}})
    # the traced sub-window: what its rounds had to do, from the args of their
    # lm/verify_step spans (the program's own counts, round by round); a run
    # with the drafter off has lm/decode_step spans instead
    lo, hi = bench.traced_window or (t_open, t_close)
    steps = [a for n, s, _, a in events
             if n in ("lm/verify_step", "lm/decode_step") and lo <= s < hi]
    counters["lm.traced_rounds"] = len(steps)
    counters["lm.traced_draft_rounds"] = sum("drafted" in a for a in steps)
    for key, arg in (("lm.traced_moe_experts_hit", "moe_experts_hit"),
                     ("lm.traced_moe_assignments", "moe_assignments"),
                     ("lm.traced_latent_positions", "latent_positions"),
                     ("lm.traced_active_slots", "active"),
                     ("lm.traced_emitted", "emitted")):
        counters[key] = sum(a.get(arg, 0) for a in steps)
    # what the traced prefills computed, by TRUE lengths (matched prefixes are
    # not computed): a chunk's own tokens, their causal (query, key) pairs over
    # the prefix before them, and a radix hit's one-token pass
    prefills = [a for n, s, _, a in events if n == "lm/prefill" and lo <= s < hi]
    tokens = pairs = chunks = 0
    for a in prefills:
        p = a.get("prefix_len", 0)
        ts = min(a.get("bucket", 0), a.get("prompt_len", 0) - p)
        tokens, pairs, chunks = (tokens + ts, pairs + ts * (p + (ts + 1) / 2),
                                 chunks + 1)
        if a.get("prepass"):
            tokens, pairs, chunks = tokens + 1, pairs + p, chunks + 1
    counters.update({"lm.traced_prefill_tokens": tokens,
                     "lm.traced_prefill_pairs": pairs,
                     "lm.traced_prefill_chunks": chunks})
    checks = check_requests(config, seed, picks, cached, bench.out)
    return {"attempted": len(clients), "failed": len(failed),
            "end_to_end": end_to_end, "checks": checks,
            "memory_peak_bytes": peak, "window": (t_open, t_close),
            "spans": spans, "counters": counters}
