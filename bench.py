"""Benchmark entry: ResNet-50 ImageNet-shape training throughput on the
attached TPU chip(s).  Prints ONE JSON result line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

The default mode runs once, in this process, on the default device.  It
needs a TPU: with no chip (or ``JAX_PLATFORMS=cpu``) it exits non-zero and
prints no result — there is no retry, no cached measurement and no
batch step-down; an out-of-memory step is an error.

Baseline (BASELINE.md): >= 2000 images/sec/chip on v5e — the reference
repo publishes no numbers of its own, so the target is the driver's.

Recipe: bf16 compute (activations + conv/matmul weights feed the MXU in
bf16), f32 master weights and optimizer state (the TPU rendering of the
reference's 'fp16 for transport, f32 for state' split,
parameters/AllReduceParameter.scala); NHWC activations throughout (the
MXU-native layout — the NCHW Torch-parity layout makes XLA insert
relayout ops around every conv).  Timing syncs via a host transfer of
the loss each window.

The other modes (``--serve``, ``--serve-lm ...``, ``--attn``, ``--slo``,
``--memprofile``) are agreement and counting runs; they take the platform
from ``JAX_PLATFORMS`` like everything else and name it in their artifact.
"""
from __future__ import annotations

import json
import os
import sys
import time

#: Per-chip batch of the default recipe.  Decided once, by compiling the
#: step for a described v5e (tests/test_chip_compile.py): 256 takes 8.8 GiB
#: of the chip's 16 GB for one program; BIGDL_TPU_BENCH_BATCH overrides.
_DEFAULT_BATCH = 256


def _scan_steps_env() -> int:
    try:
        return max(1, int(os.environ.get("BIGDL_TPU_BENCH_SCAN_STEPS") or 1))
    except ValueError:
        return 1


def main() -> None:
    _run(int(os.environ.get("BIGDL_TPU_BENCH_BATCH") or _DEFAULT_BATCH))


def _run(batch: int) -> None:
    import jax

    from bigdl_tpu.utils.profiling import device_peaks

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; JAX found platform "
            f"{device.platform!r} — no result")
    peaks = device_peaks(device.device_kind)  # unknown kind: an error
    import jax.numpy as jnp
    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.optim import SGD

    n_chips = jax.device_count()
    model = ResNet(class_num=1000, depth=50, dataset="imagenet",
                   data_format="NHWC").build(seed=1)
    criterion = nn.ClassNLLCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9, dampening=0.0)

    params, buffers = model.params, model.buffers
    opt_state = method.init_state(params)
    rng = jax.random.PRNGKey(0)

    from bigdl_tpu.nn._util import cast_f32_leaves

    def loss_fn(params_f32, buffers, x, y, rng):
        p16 = cast_f32_leaves(params_f32, jnp.bfloat16)  # bf16 compute
        out, nb = model.apply(p16, x, buffers=buffers, training=True, rng=rng)
        return criterion.loss(out.astype(jnp.float32), y), nb

    import functools

    def step_body(params, buffers, opt_state, x, y, rng):
        (loss, nb), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, buffers, x, y, rng)
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        new_params, new_opt = method.update(grads, opt_state, params)
        return new_params, nb, new_opt, loss

    # BIGDL_TPU_BENCH_SCAN_STEPS=K folds K optimizer steps into one
    # device program via lax.scan — quantifies (and, for real training
    # loops that keep their data on device, removes) the per-step
    # dispatch.  K=1 (default) is the reference-comparable per-step
    # dispatch discipline.
    scan_k = _scan_steps_env()

    # donate the carried state: params/buffers/opt_state buffers are
    # reused in place instead of round-tripping through fresh HBM
    if scan_k == 1:
        step = functools.partial(jax.jit, donate_argnums=(0, 1, 2))(step_body)
    else:
        from jax import lax

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def step(params, buffers, opt_state, x, y, rng):
            def body(carry, _):
                p, b, o = carry
                p, b, o, loss = step_body(p, b, o, x, y, rng)
                return (p, b, o), loss
            (params, buffers, opt_state), losses = lax.scan(
                body, (params, buffers, opt_state), None, length=scan_k)
            return params, buffers, opt_state, losses[-1]

    x = jax.device_put(np.random.RandomState(0).randn(
        batch, 224, 224, 3).astype(jnp.bfloat16))
    y = jnp.asarray(np.random.RandomState(1).randint(1, 1001, size=batch)
                    .astype(np.float32))

    from bigdl_tpu.obs import get_tracer
    tracer = get_tracer()

    # compile + warmup (first TPU compile is slow; subsequent cached)
    with tracer.span("bench/warmup", cat="bench", batch=batch):
        for _ in range(3):
            params, buffers, opt_state, loss = step(params, buffers, opt_state, x, y, rng)
        _ = float(loss)  # hard sync

    # step flops per XLA's cost model on the LOWERED (pre-compile) module
    # — compiling again here would redo the full ResNet-50 compile; the
    # lowered estimate tracks
    # the compiled one closely for a conv net (flops live in the convs,
    # which fusion does not remove), which is all the MFU line needs
    try:
        cost = step.lower(params, buffers, opt_state, x, y, rng) \
                   .cost_analysis()
        step_flops = float(cost.get("flops", 0.0) or 0.0)
    except Exception:
        step_flops = 0.0

    iters = int(os.environ.get("BIGDL_TPU_BENCH_ITERS", "20"))
    t0 = time.perf_counter()
    for i in range(iters):
        with tracer.span("bench/step", cat="bench", iteration=i,
                         batch=batch):
            params, buffers, opt_state, loss = step(params, buffers, opt_state, x, y, rng)
    with tracer.span("bench/sync", cat="bench"):
        _ = float(loss)  # hard sync: loss depends on the whole step chain
    dt = time.perf_counter() - t0

    imgs_per_sec = batch * iters * scan_k / dt
    per_chip = imgs_per_sec / n_chips
    baseline = 2000.0  # images/sec/chip target from BASELINE.md
    result = {
        "metric": "resnet50_imagenet_train_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / baseline, 4),
        "batch": batch,
        "n_chips": n_chips,
        "measured_at_unix": int(time.time()),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "scan_steps": scan_k,
        "xla_flags_effective": os.environ.get("XLA_FLAGS", ""),
    }
    if step_flops:
        # the jitted step is a single-device program: its flops all run
        # on the one chip doing the work, so no device_count division.
        # In scan mode the HLO cost model counts the scan body ONCE
        # (trip count is opaque to it) while dt executed scan_k bodies
        # per call — scale accordingly and say so; a cost model that
        # did multiply would make mfu exceed 1 and expose itself.
        achieved = step_flops * iters * scan_k / dt
        result["tflops_per_chip"] = round(achieved / 1e12, 2)
        result["mfu"] = round(achieved / peaks.bf16_flops, 4)
        result["mfu_peak_tflops"] = round(peaks.bf16_flops / 1e12, 1)
        result["mfu_peak_source"] = peaks.source
        if scan_k > 1:
            result["flops_accounting"] = (
                "lowered-body flops x scan_steps (HLO cost analysis "
                "counts a scan body once)")
    line = json.dumps(result)
    print(line)
    if tracer.enabled:
        # --trace (or BIGDL_TPU_TRACE=1): Chrome-trace artifact next to
        # the BENCH_* files — load in Perfetto / chrome://tracing
        trace_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "TRACE_BENCH.json")
        try:
            tracer.export_chrome(trace_path)
            print(f"bench: trace written to {trace_path}", file=sys.stderr)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# --serve: dynamic-batching serving latency/throughput benchmark.
# ---------------------------------------------------------------------------

#: Mixed batch sizes (all <= max batch) cycled across the workload —
#: the compile cache only earns its hit rate if traffic is shape-diverse.
_SERVE_MIXED_SIZES = (1, 2, 4, 3, 8, 5, 16, 7, 1, 12, 6, 2, 9, 4, 1, 8)


def _percentiles_ms(latencies_s) -> dict:
    import numpy as np
    arr = np.asarray(latencies_s, dtype=np.float64) * 1000.0
    return {"p50_ms": round(float(np.percentile(arr, 50)), 3),
            "p99_ms": round(float(np.percentile(arr, 99)), 3),
            "mean_ms": round(float(arr.mean()), 3)}


def _serve_stage_mixed_async(eng, n_requests: int, rng) -> dict:
    """Submit a shape-diverse async workload, measure per-request
    completion latency client-side and end-to-end throughput."""
    import numpy as np
    sizes = [_SERVE_MIXED_SIZES[i % len(_SERVE_MIXED_SIZES)]
             for i in range(n_requests)]
    done_at = [None] * n_requests
    futures = []
    t0 = time.perf_counter()
    submit_at = []
    for i, n in enumerate(sizes):
        x = rng.randn(n, 784).astype(np.float32)
        submit_at.append(time.perf_counter())
        fut = eng.submit(x)
        fut.add_done_callback(
            lambda _f, i=i: done_at.__setitem__(i, time.perf_counter()))
        futures.append(fut)
    for f in futures:
        f.result(timeout=120)
    t1 = time.perf_counter()
    lat = [d - s for d, s in zip(done_at, submit_at)]
    row = _percentiles_ms(lat)
    row["examples"] = int(sum(sizes))
    row["throughput_eps"] = round(sum(sizes) / (t1 - t0), 2)
    return row


def _serve_stage_mixed_sync(eng, model, n_requests: int, rng) -> dict:
    """Sequential predicts (each pays its own max_wait flush) plus a
    correctness probe against the unbatched module forward."""
    import numpy as np
    lat, examples = [], 0
    for i in range(n_requests):
        n = _SERVE_MIXED_SIZES[i % len(_SERVE_MIXED_SIZES)]
        x = rng.randn(n, 784).astype(np.float32)
        t0 = time.perf_counter()
        y = eng.predict(x, timeout=120)
        lat.append(time.perf_counter() - t0)
        examples += n
        if i == 0:
            ref = np.asarray(model.evaluate().forward(x))
            err = float(np.max(np.abs(np.asarray(y) - ref)))
    row = _percentiles_ms(lat)
    row["examples"] = examples
    row["throughput_eps"] = round(examples / max(sum(lat), 1e-9), 2)
    row["max_abs_err_vs_forward"] = err
    return row


def _serve_stage_oversized(eng, n_requests: int, max_batch: int,
                           rng) -> dict:
    """Requests larger than max_batch: served alone, chunked into
    bucket-shaped slices — throughput path, not latency path."""
    import numpy as np
    lat = []
    n = max_batch * 2 + 7
    for _ in range(n_requests):
        x = rng.randn(n, 784).astype(np.float32)
        t0 = time.perf_counter()
        y = eng.predict(x, timeout=120)
        lat.append(time.perf_counter() - t0)
        assert y.shape[0] == n
    row = _percentiles_ms(lat)
    row["examples"] = n * n_requests
    row["request_size"] = n
    row["throughput_eps"] = round(row["examples"] / max(sum(lat), 1e-9), 2)
    return row


def _serve_bench(argv) -> int:
    """Incremental, resumable serving benchmark -> BENCH_SERVE.json.

    Follows the measurement-artifact contract (utils/artifacts.py):
    rewrite after every row, ``complete: false`` until the final flush,
    reuse only rows whose platform + full configuration match.  Runs on
    CPU with JAX_PLATFORMS=cpu."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve")
    ap.add_argument("--json", default=None)
    ap.add_argument("--requests", type=int, default=int(
        os.environ.get("BIGDL_TPU_SERVE_REQUESTS", "160")))
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=3.0)
    ap.add_argument("--quant", nargs="?", const="int8", default=None,
                    choices=("int8", "bf16"),
                    help="serve a weight-only quantized replica; "
                         "writes BENCH_QUANT.json")
    ap.add_argument("--trace", action="store_true",
                    help="record obs spans; write TRACE_SERVE.json")
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_QUANT.json" if args.quant else "BENCH_SERVE.json")

    from bigdl_tpu.obs import get_tracer
    if args.trace:
        get_tracer().enable()

    import jax
    import numpy as np
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    config = {"model": "lenet5", "input": [784],
              "max_batch_size": args.max_batch,
              "max_wait_ms": args.max_wait_ms,
              "requests": args.requests,
              "mixed_sizes": list(_SERVE_MIXED_SIZES),
              "dtype": "float32",
              "quant_dtype": args.quant or "f32"}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": ("serving_mixed_batch_quant" if args.quant
                        else "serving_mixed_batch"),
              "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = LeNet5(class_num=10).build(seed=1)
    served = model.quantize(args.quant) if args.quant else model
    eng = ServingEngine(served, input_shape=(784,),
                        max_batch_size=args.max_batch,
                        max_wait_ms=args.max_wait_ms,
                        max_queue=max(args.requests, 256))
    try:
        t0 = time.perf_counter()
        compiled = eng.warmup()
        rows.append({"stage": "warmup", "buckets": list(eng.batcher.buckets),
                     "compiled": compiled,
                     "warmup_s": round(time.perf_counter() - t0, 3)})
        flush()
        if args.quant:
            # weight-payload accounting: always recomputed (cheap), the
            # number the quantization subsystem exists to win — sync
            # predicts below also report quant error vs the f32 forward
            rep = served.quant_report
            rows.append({
                "stage": "quant",
                "quant_dtype": args.quant,
                "bytes_f32": rep["bytes_orig"],
                "bytes_quant": rep["bytes_quant"],
                "bytes_saved": rep["bytes_saved"],
                "payload_ratio": round(rep["payload_ratio"], 4),
                "bytes_moved_chunked": eng.stats()["quant_bytes_staged"],
                "max_abs_dequant_error": rep["max_abs_dequant_error"],
                "per_layer_max_abs_err": {
                    k: round(v, 6)
                    for k, v in rep["per_layer_max_abs_err"].items()},
            })
            flush()

        stages = {
            "mixed_async": lambda: _serve_stage_mixed_async(
                eng, args.requests, np.random.RandomState(0)),
            "mixed_sync": lambda: _serve_stage_mixed_sync(
                eng, model, max(8, args.requests // 8),
                np.random.RandomState(1)),
            "oversized": lambda: _serve_stage_oversized(
                eng, 3, args.max_batch, np.random.RandomState(2)),
        }
        for name, run in stages.items():
            if name in prev:
                row = dict(prev[name])
                row["reused_from_previous_run"] = True
            else:
                before = eng.cache.stats()
                row = {"stage": name, **run()}
                after = eng.cache.stats()
                served = ((after["hits"] - before["hits"])
                          + (after["misses"] - before["misses"]))
                row["cache"] = {
                    "hits": after["hits"] - before["hits"],
                    "misses": after["misses"] - before["misses"],
                    "hit_rate": round((after["hits"] - before["hits"])
                                      / served, 4) if served else None}
            rows.append(row)
            flush()

        snap = eng.metrics.snapshot(eng.cache.stats())
        headline = next(r for r in rows if r.get("stage") == "mixed_async")
        # a resumed run may have served nothing this process — the
        # headline row's own (possibly reused) cache stats still hold
        hit_rate = (headline.get("cache") or {}).get("hit_rate")
        if hit_rate is None:
            hit_rate = snap["compile_cache"]["hit_rate"]
        result["summary"] = {
            "latency_p50_ms": headline["p50_ms"],
            "latency_p99_ms": headline["p99_ms"],
            "throughput_eps": headline["throughput_eps"],
            "cache_hit_rate": hit_rate,
            "batch_occupancy": snap["batch_occupancy"],
            "queue_wait_p99_s": snap["queue_wait"]["p99_s"],
            "device_time_p50_s": snap["device_time"]["p50_s"],
        }
        if args.quant:
            qrow = next(r for r in rows if r.get("stage") == "quant")
            result["summary"].update({
                "quant_dtype": args.quant,
                "quant_payload_ratio": qrow["payload_ratio"],
                "quant_bytes_saved": qrow["bytes_saved"],
                "quant_bytes_moved_chunked": qrow["bytes_moved_chunked"],
            })
        result["complete"] = True
        flush()
        print(json.dumps({
            "metric": ("lenet5_serving_quant_mixed_throughput_"
                       "examples_per_sec" if args.quant else
                       "lenet5_serving_mixed_throughput_examples_per_sec"),
            "value": headline["throughput_eps"],
            "unit": "examples/sec", "platform": platform,
            **{k: v for k, v in result["summary"].items()
               if k != "throughput_eps"}}), flush=True)
        return 0
    finally:
        eng.close()
        tr = get_tracer()
        if tr.enabled:
            trace_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "TRACE_SERVE.json")
            try:
                tr.export_chrome(trace_path)
                print(f"bench: trace written to {trace_path}",
                      file=sys.stderr)
            except OSError:
                pass


# ---------------------------------------------------------------------------
# --serve --mesh: mesh-sliced serving benchmark -> BENCH_MESH.json
# ---------------------------------------------------------------------------

def _mesh_run_requests(submit, xs, refs, atol=1e-5):
    """Submit every batch, drain in order, measure client-side.

    Returns wall-clock throughput plus per-request latency percentiles
    and the agreement fraction vs the reference outputs.  GSPMD
    guarantees the numerics up to fp reduction reorder: f32 rows agree
    at atol=1e-5; int8 rows get 1e-4 — split-K psum reorder over
    dequantized weights wobbles a few e-5 absolute at width 1024,
    still ~100x below the int8 quantization error itself (~1e-2 vs
    f32).  max_abs_diff is recorded so the tolerance is auditable."""
    import numpy as np
    t_submit, futs = [], []
    t0 = time.perf_counter()
    for x in xs:
        t_submit.append(time.perf_counter())
        futs.append(submit(x))
    lat, outs = [], []
    for ts, f in zip(t_submit, futs):
        y = f.result(timeout=300)
        lat.append(time.perf_counter() - ts)
        outs.append(np.asarray(y))
    wall = time.perf_counter() - t0
    lat = sorted(lat)
    agree = float(np.mean([
        1.0 if np.allclose(o, r, atol=atol) else 0.0
        for o, r in zip(outs, refs)]))
    max_diff = max(float(np.max(np.abs(o - np.asarray(r))))
                   for o, r in zip(outs, refs))
    n_ex = sum(x.shape[0] for x in xs)
    return {
        "requests": len(xs),
        "wall_s": round(wall, 4),
        "throughput_eps": round(n_ex / wall, 2),
        "p50_ms": round(1000 * lat[len(lat) // 2], 3),
        "p99_ms": round(1000 * lat[min(len(lat) - 1,
                                       int(len(lat) * 0.99))], 3),
        "agreement": agree,
        "agreement_atol": atol,
        "max_abs_diff": max_diff,
    }, outs


def _serve_mesh_bench(argv) -> int:
    """--serve --mesh: the mesh-sliced serving proof -> BENCH_MESH.json.

    Carves the device set into tensor-parallel replica slots and serves
    the same workload three ways — single unplaced device (the oracle),
    a 2-slot x TP2 ReplicaSet, and one TP4 slot — for dense AND int8
    params, reporting throughput/latency and the agreement fraction vs
    the oracle outputs.  On CPU the 8-virtual-device fake mesh is forced
    via XLA_FLAGS (set before backend init); on a real backend the live
    device set is carved as-is.  Resumable per stage under the
    measurement-artifact contract (utils/artifacts.py)."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve --mesh")
    ap.add_argument("--json", default=None)
    ap.add_argument("--requests", type=int, default=int(
        os.environ.get("BIGDL_TPU_MESH_REQUESTS", "48")))
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--devices", type=int, default=8,
                    help="fake-mesh width forced on the CPU host platform")
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_MESH.json")

    # the host-platform device count is read at backend init: set it
    # before the first jax.devices() call or the CPU mesh stays width 1
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
            f"{args.devices}").strip()

    import jax
    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.resilience import ReplicaSet
    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.serving.placement import DeviceTopology, PlacementPolicy
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    feat, hidden, classes = 256, 1024, 10
    config = {"model": f"mlp_{feat}x{hidden}x{hidden}x{classes}",
              "batch": args.batch, "requests": args.requests,
              "n_devices": n_dev, "dtype": "float32"}

    if n_dev < 4:
        artifacts.write_artifact(args.json, {
            "bench": "serving_mesh_sliced", "platform": platform,
            "config": config, "rows": [], "complete": False,
            "error": f"needs >= 4 devices for TP slots, got {n_dev}"})
        print(f"bench --serve --mesh: needs >= 4 devices, got {n_dev}",
              file=sys.stderr)
        return 1

    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "serving_mesh_sliced", "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()

    def mk(quant):
        m = nn.Sequential(nn.Linear(feat, hidden), nn.ReLU(),
                          nn.Linear(hidden, hidden), nn.ReLU(),
                          nn.Linear(hidden, classes)).build(seed=7)
        return m.quantize() if quant == "int8" else m

    rng = np.random.RandomState(0)
    xs = [rng.randn(args.batch, feat).astype(np.float32)
          for _ in range(args.requests)]
    eng_kw = dict(input_shape=(feat,), buckets=(args.batch,),
                  max_batch_size=args.batch, max_wait_ms=1.0,
                  max_queue=max(args.requests, 256))

    for quant in ("f32", "int8"):
        atol = 1e-5 if quant == "f32" else 1e-4
        # the oracle's outputs anchor every agreement number, so they
        # are recomputed each run even when its latency row is reused
        with ServingEngine(mk(quant), name=f"oracle_{quant}",
                           **eng_kw) as oracle:
            oracle.warmup()
            refs = [oracle._run_batch(x) for x in xs]
            name = f"single_device_{quant}"
            if name in prev:
                rows.append({**prev[name], "reused_from_previous_run": True})
            else:
                row, _ = _mesh_run_requests(oracle.submit, xs, refs,
                                            atol=atol)
                rows.append({"stage": name, "quant": quant,
                             "placement": None, **row})
            flush()

        name = f"slots2_tp2_{quant}"
        if name in prev:
            rows.append({**prev[name], "reused_from_previous_run": True})
            flush()
        else:
            pol = PlacementPolicy(DeviceTopology.detect(), slots=2, tp=2)
            rs = ReplicaSet(mk(quant), n_replicas=2, placement=pol,
                            **eng_kw)
            try:
                rs.warmup()
                row, _ = _mesh_run_requests(rs.submit, xs, refs,
                                            atol=atol)
                rows.append({"stage": name, "quant": quant,
                             "placement": pol.stats(), **row})
                flush()
            finally:
                rs.close()

        name = f"slots1_tp4_{quant}"
        if name in prev:
            rows.append({**prev[name], "reused_from_previous_run": True})
            flush()
        else:
            pol = PlacementPolicy(DeviceTopology.detect(), slots=1, tp=4)
            with ServingEngine(mk(quant), name=f"tp4_{quant}",
                               placement=pol.acquire(), **eng_kw) as eng:
                eng.warmup()
                row, _ = _mesh_run_requests(eng.submit, xs, refs,
                                            atol=atol)
                rows.append({"stage": name, "quant": quant,
                             "placement": pol.stats(), **row})
                flush()

    by_stage = {r["stage"]: r for r in rows}
    result["summary"] = {
        "agreement_min": min(r["agreement"] for r in rows),
        "single_throughput_eps": by_stage["single_device_f32"]
        ["throughput_eps"],
        "slots2_tp2_throughput_eps": by_stage["slots2_tp2_f32"]
        ["throughput_eps"],
        "slots1_tp4_throughput_eps": by_stage["slots1_tp4_f32"]
        ["throughput_eps"],
    }
    result["complete"] = True
    flush()
    print(json.dumps({
        "metric": "mesh_sliced_serving_agreement",
        "value": result["summary"]["agreement_min"],
        "unit": "fraction", "platform": platform,
        **result["summary"]}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# --serve-lm: continuous-batching LM serving benchmark -> BENCH_LM_SERVE.json
# ---------------------------------------------------------------------------

#: (prompt_len, max_new) menu cycled by the workload RNG — mixed lengths
#: are the whole point: lockstep batching pads every request to the
#: slowest one, continuous batching doesn't.
_LM_PROMPT_LENS = (8, 24, 48)
_LM_MAX_NEWS = (16, 32, 48)


def _lm_workload(n_requests: int, vocab: int, mean_gap_ms: float, rng):
    """Deterministic staggered-arrival workload: (arrive_at_s, prompt
    (1-based ids), max_new) per request."""
    import numpy as np
    work, at = [], 0.0
    for _ in range(n_requests):
        t = _LM_PROMPT_LENS[rng.randint(len(_LM_PROMPT_LENS))]
        m = _LM_MAX_NEWS[rng.randint(len(_LM_MAX_NEWS))]
        prompt = rng.randint(1, vocab + 1, size=t).astype(np.int32)
        work.append((at, prompt, m))
        at += float(rng.exponential(mean_gap_ms / 1000.0))
    return work


def _serve_lm_stage_continuous(eng, model, work, probes: int) -> dict:
    """Replay the arrival schedule against the continuous-batching
    engine; every latency number is measured client-side except slot
    occupancy (mean/peak), which comes from the engine's own
    decode-step gauge."""
    import numpy as np
    from bigdl_tpu.models.transformer.generate import generate

    t0 = time.perf_counter()
    streams = []
    for arrive_at, prompt, max_new in work:
        lag = arrive_at - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        streams.append(eng.submit(prompt, max_new_tokens=max_new))
    outs = [s.result(timeout=600) for s in streams]
    t_end = max(s.finished_at for s in streams)
    useful = int(sum(len(s.generated) for s in streams))
    ttfts = [s.ttft_s for s in streams]
    snap = eng.metrics.snapshot()
    # bit-exactness probe: a served request IS offline generate at B=1
    exact = 0
    for (arrive_at, prompt, max_new), out in list(zip(work, outs))[:probes]:
        ref = np.asarray(generate(model, model.params,
                                  prompt[None], max_new))
        exact += int(np.array_equal(out, ref[0]))
    span = t_end - t0
    spec_snap = (eng.spec_metrics.snapshot()
                 if getattr(eng, "spec_metrics", None) is not None else None)
    return {
        "requests": len(work),
        "tokens": useful,
        "duration_s": round(span, 3),
        "tokens_per_s": round(useful / span, 2),
        "spec": spec_snap is not None,
        "accept_rate": (round(spec_snap["acceptance_rate"], 4)
                        if spec_snap is not None
                        and spec_snap["acceptance_rate"] is not None
                        else None),
        "ttft": _percentiles_ms(ttfts),
        "itl_p50_ms": (round(snap["itl"]["p50_s"] * 1000.0, 3)
                       if snap["itl"]["p50_s"] is not None else None),
        "itl_p99_ms": (round(snap["itl"]["p99_s"] * 1000.0, 3)
                       if snap["itl"]["p99_s"] is not None else None),
        "decode_attn": eng.decode_attn,
        "slot_occupancy_mean": (round(snap["slot_occupancy"], 4)
                                if snap["slot_occupancy"] is not None
                                else None),
        "slot_occupancy_peak": (round(snap["slot_occupancy_peak"], 4)
                                if snap["slot_occupancy_peak"] is not None
                                else None),
        "agreement_probes": probes,
        "agreement": round(exact / probes, 4) if probes else None,
    }


def _serve_lm_stage_static(model, work) -> dict:
    """The lockstep baseline: wait for every arrival, then full-batch
    ``generate`` per prompt-length group (a static server must pad to a
    common prompt length and decode to the group's slowest request).
    Compute is measured; the arrival wait is added arithmetically, so
    the stage doesn't re-sleep the schedule."""
    import numpy as np
    from bigdl_tpu.models.transformer.generate import generate

    groups: dict = {}
    for arrive_at, prompt, max_new in work:
        groups.setdefault(len(prompt), []).append((prompt, max_new))
    last_arrival = max(a for a, _, _ in work)
    gen_s, useful = 0.0, 0
    for t, group in sorted(groups.items()):
        batch = np.stack([p for p, _ in group])
        m = max(mn for _, mn in group)
        generate(model, model.params, batch, m)  # warm the (t, m) trace
        t0 = time.perf_counter()
        out = np.asarray(generate(model, model.params, batch, m))
        gen_s += time.perf_counter() - t0
        assert out.shape == (len(group), t + m)
        # only each request's OWN budget counts — the lockstep batch
        # decodes m tokens for everyone, the excess is padding waste
        useful += sum(mn for _, mn in group)
    span = last_arrival + gen_s
    return {
        "requests": len(work),
        "groups": len(groups),
        "tokens": useful,
        "arrival_wait_s": round(last_arrival, 3),
        "generate_s": round(gen_s, 3),
        "duration_s": round(span, 3),
        "tokens_per_s": round(useful / span, 2),
        # every token lands when the batch finishes
        "ttft": _percentiles_ms([span - a for a, _, _ in work]),
    }


def _serve_lm_bench(argv) -> int:
    """Incremental, resumable LM-serving benchmark -> BENCH_LM_SERVE.json.

    Same artifact contract as --serve: rewrite after every row,
    ``complete: false`` until the final flush, reuse only rows whose
    platform + full configuration match."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve-lm")
    ap.add_argument("--json", default=None)
    ap.add_argument("--requests", type=int, default=int(
        os.environ.get("BIGDL_TPU_SERVE_LM_REQUESTS", "24")))
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-len", type=int, default=16,
                    help="KV block (page) size of the paged cache")
    ap.add_argument("--mean-gap-ms", type=float, default=15.0)
    ap.add_argument("--probes", type=int, default=2,
                    help="requests probed for bit-exactness vs offline "
                         "generate")
    ap.add_argument("--trace", action="store_true",
                    help="record obs spans; write TRACE_LM_SERVE.json")
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_LM_SERVE.json")

    from bigdl_tpu.obs import get_tracer
    if args.trace:
        get_tracer().enable()

    import jax
    import numpy as np
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    # layout + block_len are part of the row-reuse identity: a paged
    # run must never inherit rows measured on the old contiguous
    # per-slot cache (or a different page size)
    config = {"model": "transformer_lm", "vocab": 256, "hidden": 128,
              "heads": 4, "layers": 4, "max_len": args.cache_len,
              "pos": "rope", "slots": args.slots,
              "cache_len": args.cache_len,
              "layout": "paged", "block_len": args.block_len,
              "decode_attn": ["gather", "paged_kernel"],
              "spec": False,
              "requests": args.requests,
              "mean_gap_ms": args.mean_gap_ms,
              "prompt_lens": list(_LM_PROMPT_LENS),
              "max_news": list(_LM_MAX_NEWS)}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "lm_serving_continuous_batching",
              "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    work = _lm_workload(args.requests, config["vocab"],
                        args.mean_gap_ms, np.random.RandomState(0))
    eng = LMServingEngine(model, slots=args.slots,
                          cache_len=args.cache_len,
                          block_len=args.block_len,
                          max_queue=max(args.requests, 256),
                          decode_attn="gather")

    def _traced_stage():
        """Same trace through a fresh engine with request tracing at
        sample rate 1.0 AND the telemetry sampler running — tokens/s
        here vs the plain continuous row prices the observability
        layer (the acceptance bar is <= 3% overhead)."""
        from bigdl_tpu.obs import TimeSeriesSampler, set_sampler
        tr = get_tracer()
        was_enabled, was_rate = tr.enabled, tr.sample_rate
        tr.enable()
        tr.set_sample_rate(1.0)
        sampler = TimeSeriesSampler(interval_s=0.25, capacity=2400)
        prev_sampler = set_sampler(sampler)
        eng3 = LMServingEngine(model, slots=args.slots,
                               cache_len=args.cache_len,
                               block_len=args.block_len,
                               max_queue=max(args.requests, 256),
                               decode_attn="gather",
                               name="lm-traced")
        try:
            eng3.warmup()
            sampler.start()
            row = _serve_lm_stage_continuous(eng3, model, work,
                                             args.probes)
            row["trace_sample_rate"] = 1.0
            row["timeseries_rows"] = len(sampler)
            row["request_span_trees"] = sum(
                1 for ev in tr.events()
                if ev.get("name") == "lm/request" and ev.get("ph") == "X")
            return row
        finally:
            sampler.stop()
            set_sampler(prev_sampler)
            eng3.close()
            tr.set_sample_rate(was_rate)
            tr.enabled = was_enabled

    def _paged_kernel_stage():
        """Same trace through a second engine whose decode attention is
        the Pallas paged kernel (in-place block-table reads instead of
        the dense kc[tables] gather) — tokens/s + the same exactness
        probes, so the row certifies the kernel is token-exact too."""
        eng2 = LMServingEngine(model, slots=args.slots,
                               cache_len=args.cache_len,
                               block_len=args.block_len,
                               max_queue=max(args.requests, 256),
                               decode_attn="paged_kernel",
                               name="lm-paged-kernel")
        try:
            eng2.warmup()
            return _serve_lm_stage_continuous(eng2, model, work,
                                              args.probes)
        finally:
            eng2.close()

    try:
        t0 = time.perf_counter()
        compiled = eng.warmup()
        rows.append({"stage": "warmup",
                     "prefill_buckets": list(eng.prefill_buckets),
                     "prefill_compiled": compiled,
                     "warmup_s": round(time.perf_counter() - t0, 3)})
        flush()

        stages = {
            "continuous": lambda: _serve_lm_stage_continuous(
                eng, model, work, args.probes),
            "continuous_paged_kernel": _paged_kernel_stage,
            "continuous_traced": _traced_stage,
            "static_baseline": lambda: _serve_lm_stage_static(model, work),
        }
        for name, run in stages.items():
            if name in prev:
                row = dict(prev[name])
                row["reused_from_previous_run"] = True
            else:
                row = {"stage": name, **run()}
                if name == "continuous":
                    row["prefill_cache"] = eng.prefill_cache.stats()
            rows.append(row)
            flush()

        cont = next(r for r in rows if r.get("stage") == "continuous")
        paged = next(r for r in rows
                     if r.get("stage") == "continuous_paged_kernel")
        traced = next(r for r in rows
                      if r.get("stage") == "continuous_traced")
        stat = next(r for r in rows
                    if r.get("stage") == "static_baseline")
        trace_ratio = (traced["tokens_per_s"] / cont["tokens_per_s"]
                       if cont["tokens_per_s"] else None)
        speedup = (cont["tokens_per_s"] / stat["tokens_per_s"]
                   if stat["tokens_per_s"] else None)
        kern_speedup = (paged["tokens_per_s"] / cont["tokens_per_s"]
                        if cont["tokens_per_s"] else None)
        result["summary"] = {
            "ttft_p50_ms": cont["ttft"]["p50_ms"],
            "ttft_p99_ms": cont["ttft"]["p99_ms"],
            "itl_p50_ms": cont["itl_p50_ms"],
            "itl_p99_ms": cont["itl_p99_ms"],
            "tokens_per_s": cont["tokens_per_s"],
            "slot_occupancy_mean": cont["slot_occupancy_mean"],
            "slot_occupancy_peak": cont["slot_occupancy_peak"],
            "agreement": cont["agreement"],
            "paged_kernel_tokens_per_s": paged["tokens_per_s"],
            "paged_kernel_agreement": paged["agreement"],
            "paged_kernel_vs_gather": (round(kern_speedup, 3)
                                       if kern_speedup is not None
                                       else None),
            "traced_tokens_per_s": traced["tokens_per_s"],
            "tracing_overhead_ratio": (round(trace_ratio, 4)
                                       if trace_ratio is not None
                                       else None),
            "tracing_within_3pct": (bool(trace_ratio >= 0.97)
                                    if trace_ratio is not None
                                    else None),
            "request_span_trees": traced.get("request_span_trees"),
            "static_tokens_per_s": stat["tokens_per_s"],
            "static_ttft_p50_ms": stat["ttft"]["p50_ms"],
            "continuous_speedup": (round(speedup, 3)
                                   if speedup is not None else None),
            "continuous_beats_static":
                bool(speedup and speedup > 1.0),
        }
        result["complete"] = True
        flush()
        print(json.dumps({
            "metric": "lm_serving_continuous_tokens_per_sec",
            "value": cont["tokens_per_s"],
            "unit": "tokens/sec", "platform": platform,
            **{k: v for k, v in result["summary"].items()
               if k != "tokens_per_s"}}), flush=True)
        return 0
    finally:
        eng.close()
        tr = get_tracer()
        if tr.enabled:
            trace_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "TRACE_LM_SERVE.json")
            try:
                tr.export_chrome(trace_path)
                print(f"bench: trace written to {trace_path}",
                      file=sys.stderr)
            except OSError:
                pass


# ---------------------------------------------------------------------------
# --serve-lm --spec: speculative decoding vs plain decode -> BENCH_SPEC.json
# ---------------------------------------------------------------------------


def _serve_lm_spec_bench(argv) -> int:
    """Speculative-decoding serving benchmark -> BENCH_SPEC.json.

    Replays ONE arrival trace twice: through a spec engine (int8 draft +
    one donated verify executable, k candidates per slot) and through a
    plain engine — same model, same slots, same schedule.  Because
    replay acceptance makes the spec stream the offline trajectory
    bit-for-bit, BOTH stages run the same exactness probes and the
    artifact only certifies (``complete: true``) when the spec stage's
    agreement is exactly 1.0; the speedup number is meaningless if the
    streams diverge.  Same resumable-artifact contract as --serve-lm."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve-lm --spec")
    ap.add_argument("--json", default=None)
    ap.add_argument("--requests", type=int, default=int(
        os.environ.get("BIGDL_TPU_SERVE_LM_REQUESTS", "24")))
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per verify round")
    ap.add_argument("--mean-gap-ms", type=float, default=15.0)
    ap.add_argument("--probes", type=int, default=2,
                    help="requests probed for bit-exactness vs offline "
                         "generate (both stages; spec must score 1.0)")
    ap.add_argument("--drafter-compute", default="dequant",
                    choices=("dequant", "int8", "auto"),
                    help="kernel regime for the int8 drafter clone")
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_SPEC.json")

    import jax
    import numpy as np
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine, SpecConfig
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    config = {"model": "transformer_lm", "vocab": 256, "hidden": 128,
              "heads": 4, "layers": 4, "max_len": args.cache_len,
              "pos": "rope", "slots": args.slots,
              "cache_len": args.cache_len,
              "layout": "paged", "block_len": args.block_len,
              "decode_attn": "gather",
              "spec_k": args.spec_k, "sampling": "replay",
              "drafter": "int8_clone",
              "drafter_compute": args.drafter_compute,
              "requests": args.requests,
              "mean_gap_ms": args.mean_gap_ms,
              "prompt_lens": list(_LM_PROMPT_LENS),
              "max_news": list(_LM_MAX_NEWS)}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "lm_serving_speculative_decoding",
              "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    work = _lm_workload(args.requests, config["vocab"],
                        args.mean_gap_ms, np.random.RandomState(0))

    def _spec_stage():
        eng = LMServingEngine(model, slots=args.slots,
                              cache_len=args.cache_len,
                              block_len=args.block_len,
                              max_queue=max(args.requests, 256),
                              spec=SpecConfig(
                                  k=args.spec_k,
                                  drafter_compute=args.drafter_compute),
                              name="lm-spec")
        try:
            t0 = time.perf_counter()
            eng.warmup()  # prefill buckets + verify exec + drafter
            warm_s = round(time.perf_counter() - t0, 3)
            row = _serve_lm_stage_continuous(eng, model, work, args.probes)
            row["warmup_s"] = warm_s
            spec = eng.stats()["spec"]
            row["draft_overhead"] = (round(spec["draft_overhead"], 4)
                                     if spec["draft_overhead"] is not None
                                     else None)
            row["drafted"] = spec["drafted"]
            row["demotions"] = spec["demotions"]
            row["drafter_compute"] = spec.get("compute_mode")
            row["overflow_risk"] = spec.get("overflow_risk")
            row["verify_compiles"] = eng._verify_compiles
            row["draft_decode_compiles"] = eng.draft.decode_compiles
            return row
        finally:
            eng.close()

    def _plain_stage():
        eng = LMServingEngine(model, slots=args.slots,
                              cache_len=args.cache_len,
                              block_len=args.block_len,
                              max_queue=max(args.requests, 256),
                              name="lm-plain")
        try:
            eng.warmup()
            return _serve_lm_stage_continuous(eng, model, work, args.probes)
        finally:
            eng.close()

    stages = {"spec": _spec_stage, "baseline": _plain_stage}
    for name, run in stages.items():
        if name in prev:
            row = dict(prev[name])
            row["reused_from_previous_run"] = True
        else:
            row = {"stage": name, **run()}
        rows.append(row)
        flush()

    spec_row = next(r for r in rows if r.get("stage") == "spec")
    base_row = next(r for r in rows if r.get("stage") == "baseline")
    if args.probes and spec_row["agreement"] != 1.0:
        print(f"bench: SPEC AGREEMENT {spec_row['agreement']} != 1.0 — "
              "speculative streams diverged from offline generate; "
              "artifact left incomplete", file=sys.stderr)
        flush()
        return 1
    speedup = (spec_row["tokens_per_s"] / base_row["tokens_per_s"]
               if base_row["tokens_per_s"] else None)
    result["summary"] = {
        "tokens_per_s": spec_row["tokens_per_s"],
        "baseline_tokens_per_s": base_row["tokens_per_s"],
        "spec_speedup": round(speedup, 3) if speedup is not None else None,
        "acceptance_rate": spec_row["accept_rate"],
        "draft_overhead": spec_row.get("draft_overhead"),
        "itl_p50_ms": spec_row["itl_p50_ms"],
        "baseline_itl_p50_ms": base_row["itl_p50_ms"],
        "agreement": spec_row["agreement"],
        "baseline_agreement": base_row["agreement"],
        "spec_k": args.spec_k,
    }
    result["complete"] = True
    flush()
    print(json.dumps({
        "metric": "lm_serving_spec_tokens_per_sec",
        "value": spec_row["tokens_per_s"],
        "unit": "tokens/sec", "platform": platform,
        **{k: v for k, v in result["summary"].items()
           if k != "tokens_per_s"}}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# --serve-lm --spec2: adaptive tree verify + prompt lookup -> BENCH_SPEC2.json
# ---------------------------------------------------------------------------

def _spec2_workload(family: str, n_requests: int, vocab: int,
                    mean_gap_ms: float, rng):
    """Deterministic arrival trace for one Speculation 2.0 family:
    (arrive_at_s, prompt, max_new, temperature, seed) per request.

    ``mixed`` alternates greedy and sampled requests, ``sampled`` is
    all-sampled (temperatures 0.7/1.0/1.3 — where Gumbel-coupled
    alternates catch runner-up draws), ``copy`` is greedy over prompts
    built from a repeated n-gram block, the quote-your-input shape
    prompt lookup feeds on."""
    import numpy as np
    work, at = [], 0.0
    for i in range(n_requests):
        if family == "copy":
            base = rng.randint(1, vocab + 1, size=6).astype(np.int32)
            prompt = np.tile(base, 5)[:24].astype(np.int32)
            m, temp, seed = 48, 0.0, None
        else:
            t = _LM_PROMPT_LENS[rng.randint(len(_LM_PROMPT_LENS))]
            m = _LM_MAX_NEWS[rng.randint(len(_LM_MAX_NEWS))]
            prompt = rng.randint(1, vocab + 1, size=t).astype(np.int32)
            if family == "sampled" or (family == "mixed" and i % 2 == 1):
                temp = (0.7, 1.0, 1.3)[rng.randint(3)]
                seed = 1000 + i
            else:
                temp, seed = 0.0, None
        work.append((at, prompt, m, temp, seed))
        at += float(rng.exponential(mean_gap_ms / 1000.0))
    return work


def _noisy_drafter(model, scale: float, seed: int = 11):
    """The weak-drafter proxy: a clone of the target with seeded
    Gaussian noise (``scale`` x per-leaf std) added to every param.
    An int8 clone of a random float target agrees near-100% — no
    headroom for tree alternates to show anything — while a noisy
    clone's acceptance is tunable and its rank-2 pick often IS the
    target's pick where rank-1 isn't, the regime tree verify exists
    for."""
    import jax
    import jax.numpy as jnp

    d = model.clone_module()
    leaves, treedef = jax.tree_util.tree_flatten(model.params)
    key = jax.random.PRNGKey(seed)
    out = []
    for leaf in leaves:
        key, sub = jax.random.split(key)
        out.append(leaf + scale * jnp.std(leaf)
                   * jax.random.normal(sub, leaf.shape, leaf.dtype))
    d.params = jax.tree_util.tree_unflatten(treedef, out)
    return d


def _spec2_stage(eng, model, work, probes: int, warm: int = 2) -> dict:
    """Replay one spec2 trace (temperatures + seeds carried per
    request) and probe the first ``probes`` requests for bit-exactness
    against offline ``generate`` under the SAME temperature/key chain —
    the agreement gate every arm must score 1.0 on.

    The first ``warm`` requests run once UNTIMED at a token budget of 4
    (a warm lap: process-global lazy state — XLA autotuning, thread
    pools, host JIT — otherwise flatters whichever arm runs later),
    and every per-round statistic is a delta across the timed lap."""
    import jax
    import numpy as np
    from bigdl_tpu.models.transformer.generate import generate

    for _, prompt, _, temp, seed in work[:warm]:
        eng.submit(prompt, max_new_tokens=4, temperature=temp,
                   rng=seed).result(timeout=600)
    before = eng.spec_metrics.snapshot()

    t0 = time.perf_counter()
    streams = []
    for arrive_at, prompt, max_new, temp, seed in work:
        lag = arrive_at - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        streams.append(eng.submit(prompt, max_new_tokens=max_new,
                                  temperature=temp, rng=seed))
    outs = [s.result(timeout=600) for s in streams]
    t_end = max(s.finished_at for s in streams)
    useful = int(sum(len(s.generated) for s in streams))
    exact = 0
    for (arrive_at, prompt, max_new, temp, seed), out in (
            list(zip(work, outs))[:probes]):
        kw = {"temperature": temp}
        if seed is not None:
            kw["rng"] = jax.random.PRNGKey(seed)
        ref = np.asarray(generate(model, model.params, prompt[None],
                                  max_new, **kw))
        exact += int(np.array_equal(out, ref[0]))
    span = t_end - t0
    spec = eng.stats()["spec"]

    def delta(key):
        return spec[key] - before[key]

    rounds = delta("verify_rounds")
    drafted = delta("drafted")
    return {
        "requests": len(work),
        "tokens": useful,
        "duration_s": round(span, 3),
        "tokens_per_s": round(useful / span, 2),
        "acceptance_rate": (round(delta("accepted") / drafted, 4)
                            if drafted else None),
        "accepted_per_verify_step": (round(delta("emitted") / rounds, 4)
                                     if rounds else None),
        "draft_steps": delta("draft_steps"),
        "draft_overhead": (round(delta("draft_steps") / delta("emitted"), 4)
                           if delta("emitted") else None),
        "tree_rounds": delta("tree_rounds"),
        "alt_accepts": delta("alt_accepts"),
        "demotions": delta("demotions"),
        "drafter_compute": spec["draft"]["compute_mode"],
        "verify_compiles": spec["verify_compiles"],
        "commit_compiles": spec.get("commit_compiles"),
        "draft_decode_compiles": eng.draft.decode_compiles,
        "agreement_probes": probes,
        "agreement": round(exact / probes, 4) if probes else None,
    }


def _serve_lm_spec2_bench(argv) -> int:
    """Speculation 2.0 benchmark -> BENCH_SPEC2.json.

    Six arms, three trace families, one resumable artifact:

    - ``linear_mixed`` / ``tree_mixed`` and ``linear_sampled`` /
      ``tree_sampled``: fixed linear-k chain vs adaptive-depth token
      tree at EQUAL drafter budget (same spine k, same drafter, same
      trace) — the tree's alternates catch runner-up draws and its
      rung ladder adapts per slot to the acceptance EMA.
    - ``model_copy`` / ``ngram_copy``: int8-clone model drafting vs
      zero-model prompt lookup on the copy-heavy trace; the n-gram arm
      speculates deeper (``--ngram-k``) because its drafts cost zero
      decode steps.

    Every arm runs the same exactness probes; ``complete: true``
    additionally requires the tree to beat linear on >= 1 family, the
    n-gram drafter to beat model drafting on the copy trace, and every
    tree arm to hold exactly one donated verify executable per ladder
    rung."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve-lm --spec2")
    ap.add_argument("--json", default=None)
    ap.add_argument("--requests", type=int, default=int(
        os.environ.get("BIGDL_TPU_SERVE_LM_REQUESTS", "16")),
        help="requests per arm")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--spec-k", type=int, default=4,
                    help="spine budget for the linear AND tree arms")
    ap.add_argument("--ngram-k", type=int, default=8,
                    help="spine budget for the zero-cost n-gram arm")
    ap.add_argument("--drafter-noise", type=float, default=0.5,
                    help="weak-drafter proxy: Gaussian noise scale "
                         "(x per-leaf std) added to the drafter clone")
    ap.add_argument("--promote-above", type=float, default=0.5,
                    help="tree-arm rung promotion threshold")
    ap.add_argument("--mean-gap-ms", type=float, default=15.0)
    ap.add_argument("--probes", type=int, default=3,
                    help="requests probed for bit-exactness per arm "
                         "(every arm must score 1.0)")
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_SPEC2.json")

    import jax
    import numpy as np
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine, SpecConfig
    from bigdl_tpu.serving.spec import default_tree_shapes
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    n_rungs = len(default_tree_shapes(args.spec_k))
    config = {"model": "transformer_lm", "vocab": 256, "hidden": 128,
              "heads": 4, "layers": 4, "max_len": args.cache_len,
              "pos": "rope", "slots": args.slots,
              "cache_len": args.cache_len,
              "layout": "paged", "block_len": args.block_len,
              "spec_k": args.spec_k, "ngram_k": args.ngram_k,
              "drafter_noise": args.drafter_noise,
              "tree_rungs": n_rungs,
              "promote_above": args.promote_above,
              "sampling": "replay",
              "requests": args.requests,
              "mean_gap_ms": args.mean_gap_ms,
              "families": ["mixed", "sampled", "copy"],
              "prompt_lens": list(_LM_PROMPT_LENS),
              "max_news": list(_LM_MAX_NEWS)}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "lm_serving_speculation2",
              "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    traces = {
        fam: _spec2_workload(fam, args.requests, config["vocab"],
                             args.mean_gap_ms,
                             np.random.RandomState(seed))
        for fam, seed in (("mixed", 0), ("sampled", 1), ("copy", 2))}

    drafter = _noisy_drafter(model, args.drafter_noise)

    def _tree_cfg(k):
        shapes = default_tree_shapes(k)
        return SpecConfig(k=k, tree=True, draft=drafter,
                          promote_above=args.promote_above,
                          init_rung=len(shapes) - 1)

    # (stage, family, SpecConfig thunk, expected verify executables).
    # Tree/ngram arms run BEFORE their baselines: residual
    # process-global warm-up the warm lap misses then favors the
    # baseline, so it cannot manufacture the claimed wins.
    arms = [
        ("tree_mixed", "mixed", lambda: _tree_cfg(args.spec_k), n_rungs),
        ("linear_mixed", "mixed",
         lambda: SpecConfig(k=args.spec_k, draft=drafter), 1),
        ("tree_sampled", "sampled",
         lambda: _tree_cfg(args.spec_k), n_rungs),
        ("linear_sampled", "sampled",
         lambda: SpecConfig(k=args.spec_k, draft=drafter), 1),
        ("ngram_copy", "copy",
         lambda: SpecConfig(k=args.ngram_k, drafter_compute="ngram"), 1),
        ("model_copy", "copy",
         lambda: SpecConfig(k=args.spec_k, draft=drafter), 1),
    ]
    for name, family, mk_cfg, expect_verify in arms:
        if name in prev:
            row = dict(prev[name])
            row["reused_from_previous_run"] = True
            rows.append(row)
            flush()
            continue
        eng = LMServingEngine(model, slots=args.slots,
                              cache_len=args.cache_len,
                              block_len=args.block_len,
                              max_queue=max(args.requests, 256),
                              spec=mk_cfg(), name=f"lm-{name}")
        try:
            t0 = time.perf_counter()
            eng.warmup()
            warm_s = round(time.perf_counter() - t0, 3)
            row = {"stage": name, "family": family,
                   **_spec2_stage(eng, model, traces[family],
                                  args.probes)}
            row["warmup_s"] = warm_s
            row["expected_verify_compiles"] = expect_verify
        finally:
            eng.close()
        rows.append(row)
        flush()

    by = {r["stage"]: r for r in rows}
    bad = [n for n, r in by.items() if r["agreement"] != 1.0]
    if args.probes and bad:
        print(f"bench: SPEC2 AGREEMENT != 1.0 on {bad} — speculative "
              "streams diverged from offline generate; artifact left "
              "incomplete", file=sys.stderr)
        flush()
        return 1
    aps = {n: r["accepted_per_verify_step"] for n, r in by.items()}
    tree_beats = {
        fam: (aps[f"tree_{fam}"] or 0) > (aps[f"linear_{fam}"] or 0)
        for fam in ("mixed", "sampled")}
    ngram_beats = (aps["ngram_copy"] or 0) > (aps["model_copy"] or 0)
    exec_ok = all(r["verify_compiles"] == r["expected_verify_compiles"]
                  for r in by.values())
    result["summary"] = {
        "accepted_per_verify_step": aps,
        "tokens_per_s": {n: r["tokens_per_s"] for n, r in by.items()},
        "tree_beats_linear": tree_beats,
        "ngram_beats_model": ngram_beats,
        "ngram_draft_steps": by["ngram_copy"]["draft_steps"],
        "tree_alt_accepts": {n: by[n]["alt_accepts"]
                             for n in ("tree_mixed", "tree_sampled")},
        "verify_executables": {n: r["verify_compiles"]
                               for n, r in by.items()},
        "executables_bounded": exec_ok,
        "agreement": 1.0,
        "spec_k": args.spec_k, "ngram_k": args.ngram_k,
    }
    gates = {"tree_beats_linear_any": any(tree_beats.values()),
             "ngram_beats_model": ngram_beats,
             "executables_bounded": exec_ok}
    failed = [g for g, ok in gates.items() if not ok]
    if failed:
        print(f"bench: SPEC2 gates failed: {failed} — artifact left "
              "incomplete", file=sys.stderr)
        flush()
        return 1
    result["complete"] = True
    flush()
    print(json.dumps({
        "metric": "lm_serving_spec2_accepted_per_verify_step",
        "value": aps["tree_sampled"],
        "unit": "tokens/verify_round", "platform": platform,
        **{k: v for k, v in result["summary"].items()
           if k not in ("accepted_per_verify_step",)},
        "accepted_per_verify_step": aps}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# --serve-lm --spec --qcompute: int8-compute drafter duel -> BENCH_QCOMPUTE.json
# ---------------------------------------------------------------------------

def _serve_lm_qcompute_bench(argv) -> int:
    """True int8-compute benchmark -> BENCH_QCOMPUTE.json.

    Two measurement families in one resumable artifact:

    1. **duel rows** (``duel:{impl}:{m}x{k}x{n}``): the int8-compute vs
       dequant-bf16 matmul duel at drafter-relevant shapes, run through
       ``ops.autotune.autotune_qcompute`` so the verdicts ALSO persist
       in the shared tuning cache — which is what makes the
       ``spec_auto`` stage's ``compute="auto"`` honor the measured
       winner instead of guessing.
    2. **serving stages** (``spec_dequant`` / ``spec_int8`` /
       ``spec_auto`` / ``baseline``): one arrival trace replayed
       through spec engines whose drafter runs each kernel regime,
       plus the plain no-spec engine.  Replay acceptance makes every
       spec stream the offline trajectory bit-for-bit REGARDLESS of
       drafter numerics (the drafter only moves the acceptance rate),
       so the artifact certifies only when every spec stage's
       agreement is exactly 1.0 AND the int8 drafter's overhead
       (drafter steps per emitted token) stays within 0.02 of the
       dequant drafter's.

    Same resumable-artifact contract as every bench: a row per stage,
    flushed as it lands, ``complete: false`` until the final gate."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve-lm --spec "
                                      "--qcompute")
    ap.add_argument("--json", default=None)
    ap.add_argument("--requests", type=int, default=int(
        os.environ.get("BIGDL_TPU_SERVE_LM_REQUESTS", "24")))
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--mean-gap-ms", type=float, default=15.0)
    ap.add_argument("--probes", type=int, default=2,
                    help="requests probed for bit-exactness vs offline "
                         "generate (every spec stage must score 1.0)")
    ap.add_argument("--duel-iters", type=int, default=20)
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_QCOMPUTE.json")

    import jax
    import numpy as np
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.ops import autotune
    from bigdl_tpu.serving import LMServingEngine, SpecConfig
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind
    hidden, ffn = 128, 512
    # the drafter's actual matmul shapes: decode rows are (slots, hidden)
    # against the attention projections and the MLP up/down weights
    duel_shapes = [(args.slots, hidden, hidden),
                   (args.slots, hidden, ffn),
                   (args.slots, ffn, hidden)]
    config = {"model": "transformer_lm", "vocab": 256, "hidden": hidden,
              "heads": 4, "layers": 4, "max_len": args.cache_len,
              "pos": "rope", "slots": args.slots,
              "cache_len": args.cache_len,
              "layout": "paged", "block_len": args.block_len,
              "decode_attn": "gather",
              "spec_k": args.spec_k, "sampling": "replay",
              "drafter": "int8_clone",
              "requests": args.requests,
              "mean_gap_ms": args.mean_gap_ms,
              "duel_shapes": [list(s) for s in duel_shapes],
              "duel_iters": args.duel_iters,
              "prompt_lens": list(_LM_PROMPT_LENS),
              "max_news": list(_LM_MAX_NEWS)}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "lm_serving_qcompute",
              "platform": platform, "device_kind": device_kind,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()

    # -- 1. the duel (through the shared tuning cache) ------------------- #
    duel_keys = ["duel:%s:%dx%dx%d" % (impl, m, k, n)
                 for m, k, n in duel_shapes
                 for impl in ("int8_compute", "dequant_bf16")]
    if all(key in prev for key in duel_keys):
        for key in duel_keys:
            row = dict(prev[key])
            row["reused_from_previous_run"] = True
            rows.append(row)
        flush()
    else:
        # autotune_qcompute is itself resumable against the TUNE doc,
        # so a re-run only re-measures what the cache does not cover
        tune_doc = autotune.autotune_qcompute(
            duel_shapes, iters=args.duel_iters,
            log=lambda m: print("bench: %s" % m, flush=True))
        by_key = {}
        for r in tune_doc.get("rows") or []:
            if r.get("kind") == "qcompute" and "step_s" in r:
                by_key["duel:%s:%dx%dx%d" % (r["impl"], r["m"], r["k"],
                                             r["n"])] = r
        for key in duel_keys:
            r = by_key.get(key)
            if r is None:
                print(f"bench: duel row {key} failed to measure; "
                      "artifact left incomplete", file=sys.stderr)
                flush()
                return 1
            rows.append({"stage": key, "impl": r["impl"], "m": r["m"],
                         "k": r["k"], "n": r["n"],
                         "step_s": r["step_s"],
                         "tokens_per_s": r.get("tokens_per_s")})
            flush()
    # verdicts the spec_auto stage will trace against
    auto_verdicts = {
        "%dx%dx%d" % (m, k, n): autotune.lookup_qcompute(m, k, n)
        for m, k, n in duel_shapes}

    # -- 2. the serving stages ------------------------------------------- #
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=hidden,
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    work = _lm_workload(args.requests, config["vocab"],
                        args.mean_gap_ms, np.random.RandomState(0))

    def _spec_stage(compute):
        eng = LMServingEngine(model, slots=args.slots,
                              cache_len=args.cache_len,
                              block_len=args.block_len,
                              max_queue=max(args.requests, 256),
                              spec=SpecConfig(k=args.spec_k,
                                              drafter_compute=compute),
                              name="lm-q-%s" % compute)
        try:
            t0 = time.perf_counter()
            eng.warmup()
            warm_s = round(time.perf_counter() - t0, 3)
            row = _serve_lm_stage_continuous(eng, model, work, args.probes)
            row["warmup_s"] = warm_s
            spec = eng.stats()["spec"]
            row["drafter_compute"] = spec.get("compute_mode")
            row["overflow_risk"] = spec.get("overflow_risk")
            row["draft_overhead"] = (round(spec["draft_overhead"], 4)
                                     if spec["draft_overhead"] is not None
                                     else None)
            row["drafted"] = spec["drafted"]
            row["demotions"] = spec["demotions"]
            if compute == "auto":
                row["auto_verdicts"] = auto_verdicts
            return row
        finally:
            eng.close()

    def _plain_stage():
        eng = LMServingEngine(model, slots=args.slots,
                              cache_len=args.cache_len,
                              block_len=args.block_len,
                              max_queue=max(args.requests, 256),
                              name="lm-q-plain")
        try:
            eng.warmup()
            return _serve_lm_stage_continuous(eng, model, work, args.probes)
        finally:
            eng.close()

    stages = {"spec_dequant": lambda: _spec_stage("dequant"),
              "spec_int8": lambda: _spec_stage("int8"),
              "spec_auto": lambda: _spec_stage("auto"),
              "baseline": _plain_stage}
    for name, run in stages.items():
        if name in prev:
            row = dict(prev[name])
            row["reused_from_previous_run"] = True
        else:
            row = {"stage": name, **run()}
        rows.append(row)
        flush()

    by_stage = {r["stage"]: r for r in rows if "stage" in r}
    spec_stages = ("spec_dequant", "spec_int8", "spec_auto")
    # gate 1: replay exactness — drafter numerics must never reach the
    # emitted stream, whatever kernels it runs
    if args.probes:
        for name in spec_stages:
            if by_stage[name]["agreement"] != 1.0:
                print(f"bench: {name} AGREEMENT "
                      f"{by_stage[name]['agreement']} != 1.0 — spec "
                      "streams diverged from offline generate; artifact "
                      "left incomplete", file=sys.stderr)
                flush()
                return 1
    # gate 2: the int8 drafter earns its keep — drafter steps per
    # emitted token no worse than the dequant drafter's (PR 10 baseline
    # reference: acceptance 0.9867, draft_overhead 0.16)
    ov_dq = by_stage["spec_dequant"].get("draft_overhead")
    ov_i8 = by_stage["spec_int8"].get("draft_overhead")
    if ov_dq is not None and ov_i8 is not None and ov_i8 > ov_dq + 0.02:
        print(f"bench: int8 drafter overhead {ov_i8} exceeds dequant "
              f"{ov_dq} + 0.02 — acceptance collapsed under activation "
              "quantization; artifact left incomplete", file=sys.stderr)
        flush()
        return 1

    base = by_stage["baseline"]
    result["summary"] = {
        "tokens_per_s_int8": by_stage["spec_int8"]["tokens_per_s"],
        "tokens_per_s_dequant": by_stage["spec_dequant"]["tokens_per_s"],
        "tokens_per_s_auto": by_stage["spec_auto"]["tokens_per_s"],
        "baseline_tokens_per_s": base["tokens_per_s"],
        "acceptance_int8": by_stage["spec_int8"]["accept_rate"],
        "acceptance_dequant": by_stage["spec_dequant"]["accept_rate"],
        "draft_overhead_int8": ov_i8,
        "draft_overhead_dequant": ov_dq,
        "draft_overhead_ref_pr10": 0.16,
        "overflow_risk": by_stage["spec_int8"].get("overflow_risk"),
        "agreement": 1.0 if args.probes else None,
        "auto_verdicts": auto_verdicts,
        "spec_k": args.spec_k,
    }
    result["complete"] = True
    flush()
    print(json.dumps({
        "metric": "lm_serving_qcompute_tokens_per_sec",
        "value": by_stage["spec_int8"]["tokens_per_s"],
        "unit": "tokens/sec", "platform": platform,
        **{k: v for k, v in result["summary"].items()
           if k not in ("tokens_per_s_int8",)}}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# --serve-lm --prefix: shared-system-prompt trace -> BENCH_PREFIX.json
# ---------------------------------------------------------------------------

#: distinct user-tail lengths appended to the shared system prompt
_PREFIX_TAIL_LENS = (8, 16, 24)
_PREFIX_MAX_NEW = 16


def _prefix_workload(n_requests: int, vocab: int, shared_len: int,
                     mean_gap_ms: float, rng):
    """Chat-style trace: every prompt is ONE shared system prompt plus
    a distinct user tail — the radix cache's home turf."""
    import numpy as np
    shared = rng.randint(1, vocab + 1, size=shared_len).astype(np.int32)
    work, at = [], 0.0
    for _ in range(n_requests):
        tl = _PREFIX_TAIL_LENS[rng.randint(len(_PREFIX_TAIL_LENS))]
        tail = rng.randint(1, vocab + 1, size=tl).astype(np.int32)
        work.append((at, np.concatenate([shared, tail]), _PREFIX_MAX_NEW))
        at += float(rng.exponential(mean_gap_ms / 1000.0))
    return work


def _serve_lm_prefix_bench(argv) -> int:
    """Prefix-sharing benchmark -> BENCH_PREFIX.json (resumable).

    Three stages, one fresh engine each: the shared-system-prompt trace
    with radix sharing ON (TTFT + prefill tokens/FLOPs saved), the same
    trace with sharing OFF (the cost of recomputing the shared head),
    and the DISJOINT ``--serve-lm`` trace with sharing on (regression
    guard: the radix plane must not tax traffic that never shares —
    compared against BENCH_LM_SERVE.json when one exists)."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve-lm --prefix")
    ap.add_argument("--json", default=None)
    ap.add_argument("--requests", type=int, default=int(
        os.environ.get("BIGDL_TPU_SERVE_LM_REQUESTS", "24")))
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--shared-len", type=int, default=64,
                    help="shared system-prompt length (tokens); must be "
                         "a multiple of --block-len to share fully")
    ap.add_argument("--mean-gap-ms", type=float, default=15.0)
    ap.add_argument("--probes", type=int, default=2)
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_PREFIX.json")

    import jax
    import numpy as np
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import LMServingEngine
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    config = {"model": "transformer_lm", "vocab": 256, "hidden": 128,
              "heads": 4, "layers": 4, "max_len": args.cache_len,
              "pos": "rope", "slots": args.slots,
              "cache_len": args.cache_len,
              "layout": "paged", "block_len": args.block_len,
              "shared_len": args.shared_len,
              "requests": args.requests,
              "mean_gap_ms": args.mean_gap_ms,
              "tail_lens": list(_PREFIX_TAIL_LENS),
              "max_new": _PREFIX_MAX_NEW}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "lm_serving_prefix_sharing", "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    n_params = sum(int(np.asarray(p).size)
                   for p in jax.tree_util.tree_leaves(model.params))
    rng = np.random.RandomState(11)
    shared_work = _prefix_workload(args.requests, config["vocab"],
                                   args.shared_len, args.mean_gap_ms, rng)
    disjoint_work = _lm_workload(args.requests, config["vocab"],
                                 args.mean_gap_ms, np.random.RandomState(0))

    def run_stage(work, sharing: bool) -> dict:
        eng = LMServingEngine(model, slots=args.slots,
                              cache_len=args.cache_len,
                              block_len=args.block_len,
                              enable_prefix_cache=sharing,
                              max_queue=max(args.requests, 256))
        try:
            eng.warmup()
            if sharing:
                # warm only the (suffix, chain) combos this trace hits
                eng.warmup_prefix(
                    suffix_lens=_PREFIX_TAIL_LENS,
                    prefix_blocks=[args.shared_len // args.block_len])
            # prime EXECUTION (warmup only compiles): first runs pay
            # allocator/runtime costs that would skew whichever stage
            # happens to go first; the duplicate prompt also exercises
            # the radix-hit path when sharing is on
            prime = np.random.RandomState(99).randint(
                1, config["vocab"] + 1,
                size=args.shared_len + _PREFIX_TAIL_LENS[0]).astype(
                    np.int32)
            eng.generate(prime, max_new_tokens=4, timeout=600)
            eng.generate(prime, max_new_tokens=4, timeout=600)
            pre = (eng.kvcache_stats().get("prefix_cache")
                   if sharing else None)
            row = _serve_lm_stage_continuous(eng, model, work, args.probes)
            row["kvcache"] = eng.kvcache_stats()
            rdx = row["kvcache"].get("prefix_cache")
            if rdx and pre:
                # report the MEASURED window only (priming hits out)
                for key in ("lookups", "hits", "prefill_tokens_saved",
                            "inserted_blocks", "evictions"):
                    rdx[key] -= pre[key]
                rdx["hit_rate"] = (round(rdx["hits"] / rdx["lookups"], 4)
                                   if rdx["lookups"] else None)
            return row
        finally:
            eng.close()

    stages = {
        "shared_on": lambda: run_stage(shared_work, True),
        "shared_off": lambda: run_stage(shared_work, False),
        "disjoint": lambda: run_stage(disjoint_work, True),
    }
    for name, run in stages.items():
        if name in prev:
            row = dict(prev[name])
            row["reused_from_previous_run"] = True
        else:
            row = {"stage": name, **run()}
        rows.append(row)
        flush()

    on = next(r for r in rows if r.get("stage") == "shared_on")
    off = next(r for r in rows if r.get("stage") == "shared_off")
    dis = next(r for r in rows if r.get("stage") == "disjoint")
    radix = (on.get("kvcache") or {}).get("prefix_cache") or {}
    saved_tokens = radix.get("prefill_tokens_saved", 0)
    ttft_on = on["ttft"]["p50_ms"]
    ttft_off = off["ttft"]["p50_ms"]
    # disjoint-trace regression guard vs the committed plain serve bench
    baseline = None
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_LM_SERVE.json")
    try:
        with open(base_path) as f:
            doc = json.load(f)
        if doc.get("complete") and doc.get("platform") == platform:
            baseline = doc["summary"]["ttft_p50_ms"]
    except (OSError, KeyError, ValueError):
        pass
    ratio = (round(dis["ttft"]["p50_ms"] / baseline, 3)
             if baseline else None)
    result["summary"] = {
        "prefix_hit_rate": radix.get("hit_rate"),
        "prefill_tokens_saved": saved_tokens,
        # dense-layer MACs dominate at these widths: ~2*params/token
        "prefill_flops_saved_est": int(saved_tokens * 2 * n_params),
        "ttft_p50_ms_sharing_on": ttft_on,
        "ttft_p50_ms_sharing_off": ttft_off,
        "ttft_sharing_speedup": (round(ttft_off / ttft_on, 3)
                                 if ttft_on else None),
        "agreement_sharing_on": on["agreement"],
        "disjoint_ttft_p50_ms": dis["ttft"]["p50_ms"],
        "baseline_ttft_p50_ms": baseline,
        "disjoint_ttft_vs_baseline": ratio,
        "no_disjoint_ttft_regression": (bool(ratio <= 1.25)
                                        if ratio is not None else None),
    }
    result["complete"] = True
    flush()
    print(json.dumps({
        "metric": "lm_prefix_prefill_tokens_saved",
        "value": saved_tokens, "unit": "tokens", "platform": platform,
        **{k: v for k, v in result["summary"].items()
           if k != "prefill_tokens_saved"}}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# --serve-lm --kvtier: host-tier KV offload + hibernation -> BENCH_KVTIER.json
# ---------------------------------------------------------------------------

def _serve_lm_kvtier_bench(argv) -> int:
    """Host-tier KV offload benchmark -> BENCH_KVTIER.json (resumable).

    Three stages, one fresh engine + HostBlockStore each:

    - ``hibernate_exact``: per-probe hibernate/resume mid-decode vs an
      uninterrupted reference run — half the probes also lose their
      session payload on purpose (the prompt-re-prefill + decode-replay
      fallback leg).  AGREEMENT artifact: ``complete`` requires the
      stage's agreement to be exactly 1.0 — a tiered memory that
      changes even one token is not a memory tier, it is a bug.
    - ``resume_vs_reprefill``: TTFT-on-resume (resume() -> next fresh
      token, chain promoted through the 32 MB chunked transfer) vs the
      cold full-prefill TTFT at the same prompt length, plus the
      promote bandwidth.  On CPU the resume must win for the artifact
      to certify.
    - ``oversubscribed``: a 10x-oversubscribed session trace over a
      ~3-chain pool, replayed twice — demoted prefix tails must be
      re-admitted from the tier with a NONZERO hit rate.

    Same resumable-artifact contract as the other serving benches:
    a row flushes after every stage, ``complete: false`` until the
    final gate-checked flush."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve-lm --kvtier")
    ap.add_argument("--json", default=None)
    ap.add_argument("--probes", type=int, default=int(
        os.environ.get("BIGDL_TPU_KVTIER_PROBES", "6")))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--sessions", type=int, default=20,
                    help="oversubscribed-stage session count (10x the "
                         "2 decode slots by default)")
    ap.add_argument("--rounds", type=int, default=2,
                    help="oversubscribed-stage trace replays")
    ap.add_argument("--timing-samples", type=int, default=3)
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_KVTIER.json")

    import jax
    import numpy as np
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import HostBlockStore, LMServingEngine
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    config = {"model": "transformer_lm", "vocab": 256, "hidden": 128,
              "heads": 4, "layers": 4, "max_len": args.cache_len,
              "pos": "rope", "slots": args.slots,
              "cache_len": args.cache_len,
              "layout": "paged", "block_len": args.block_len,
              "probes": args.probes, "sessions": args.sessions,
              "rounds": args.rounds,
              "timing_samples": args.timing_samples}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "lm_serving_kvtier", "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    eng_kw = dict(slots=args.slots, cache_len=args.cache_len,
                  block_len=args.block_len,
                  max_queue=max(args.sessions * args.rounds, 256))

    def _hibernate_exact_stage():
        rng = np.random.RandomState(3)
        plen = max(args.block_len + 1, args.cache_len // 4)
        max_new = min(48, args.cache_len - plen)
        prompts = [rng.randint(1, config["vocab"] + 1,
                               size=plen).astype(np.int32)
                   for _ in range(args.probes)]
        ref_eng = LMServingEngine(model, **eng_kw)
        try:
            ref_eng.warmup()
            refs = [ref_eng.generate(p, max_new_tokens=max_new,
                                     temperature=0.7, rng=i,
                                     timeout=600)
                    for i, p in enumerate(prompts)]
        finally:
            ref_eng.close()
        tier = HostBlockStore(host_bytes=256 << 20, name="bench-hib")
        eng = LMServingEngine(model, kvtier=tier, **eng_kw)
        try:
            eng.warmup()
            exact = hibernated = forced_lost = 0
            for i, p in enumerate(prompts):
                st = eng.submit(p, max_new_tokens=max_new,
                                temperature=0.7, rng=i)
                it = st.tokens(timeout=600)
                next(it)
                next(it)
                if eng.hibernate(st):
                    hibernated += 1
                    if i % 2 == 1:
                        # odd probes lose their payload: exercises the
                        # re-prefill + decode-replay fallback leg
                        if tier.get(("session", st.request_id),
                                    pop=True) is not None:
                            forced_lost += 1
                    eng.resume(st)
                out = st.result(timeout=600)
                exact += int(np.array_equal(out, refs[i]))
            return {"probes": args.probes,
                    "agreement": round(exact / args.probes, 4),
                    "hibernated": hibernated,
                    "forced_lost_payloads": forced_lost,
                    "lost_payload_resumes": eng.resume_re_prefills,
                    "tier": tier.stats()}
        finally:
            eng.close()

    def _resume_vs_reprefill_stage():
        tier = HostBlockStore(host_bytes=256 << 20, name="bench-resume")
        eng = LMServingEngine(model, kvtier=tier, **eng_kw)
        try:
            eng.warmup()
            plen = args.cache_len - 16
            max_new = min(32, args.cache_len - plen)
            depth = max(2, 3 * max_new // 4)
            rng = np.random.RandomState(5)

            def cycle(lose_payload):
                # hibernate ``depth`` tokens into decode, then time
                # resume() -> the next FRESH token.  The payload-lost
                # leg is the engine's own fallback: re-prefill the
                # prompt + replay the emitted tokens through decode
                # steps — the exact cost the host tier avoids.
                q = rng.randint(1, config["vocab"] + 1,
                                size=plen).astype(np.int32)
                st = eng.submit(q, max_new_tokens=max_new)
                it = st.tokens(timeout=600)
                for _ in range(depth):
                    next(it)
                if not eng.hibernate(st):
                    st.result(timeout=600)
                    return None
                for _ in range(len(st.generated) - depth):
                    next(it)       # drain the hibernate-race tokens
                if lose_payload:
                    tier.get(("session", st.request_id), pop=True)
                t0 = time.perf_counter()
                eng.resume(st)
                next(it)           # blocks on the stream cv, no poll
                dt = time.perf_counter() - t0
                st.result(timeout=600)
                return dt

            # warmup cycles on BOTH legs: pay the adopt-scatter /
            # prefill-bucket compiles so the timed samples measure
            # the work, not XLA
            for _ in range(2):
                cycle(False)
                cycle(True)
            resume_s = [t for t in (cycle(False) for _ in
                                    range(args.timing_samples))
                        if t is not None]
            reprefill_s = [t for t in (cycle(True) for _ in
                                       range(args.timing_samples))
                           if t is not None]
            # best-of: residual jit noise lands on the first sample of
            # a new chain shape; min is the steady-state cost
            best = lambda xs: (round(float(min(xs)) * 1000.0, 3)
                               if xs else None)  # noqa: E731
            row = {"prompt_len": plen, "hibernate_depth": depth,
                   "ttft_resume_ms": best(resume_s),
                   "ttft_reprefill_ms": best(reprefill_s),
                   "resume_samples": len(resume_s),
                   "reprefill_samples": len(reprefill_s),
                   "promote_mbs": tier.promote_bandwidth_mbs(),
                   "tier": tier.stats(),
                   "lost_payload_resumes": eng.resume_re_prefills}
            if row["ttft_resume_ms"] and row["ttft_reprefill_ms"]:
                row["resume_speedup"] = round(
                    row["ttft_reprefill_ms"] / row["ttft_resume_ms"], 3)
            return row
        finally:
            eng.close()

    def _oversubscribed_stage():
        tier = HostBlockStore(host_bytes=256 << 20, name="bench-over")
        B = args.block_len
        plen, max_new = 4 * B + 1, 8
        # pool sized to exactly 2 live chains; radix retention from
        # finished sessions overflows it fast, so tails demote
        need = -(-(plen + max_new) // B)
        eng = LMServingEngine(model, slots=2, cache_len=args.cache_len,
                              block_len=args.block_len,
                              num_blocks=1 + 2 * need, kvtier=tier,
                              max_queue=max(args.sessions * args.rounds,
                                            256))
        try:
            eng.warmup()
            rng = np.random.RandomState(0)
            head = rng.randint(1, config["vocab"] + 1, size=2 * B)
            # 4-block + 1 prompts: the evictable leaf block stays
            # inside the matchable range when the session returns
            prompts = [np.concatenate(
                [head, rng.randint(1, config["vocab"] + 1,
                                   size=2 * B + 1)]).astype(np.int32)
                for _ in range(args.sessions)]
            t0 = time.perf_counter()
            for _ in range(args.rounds):
                streams = [eng.submit(p, max_new_tokens=max_new)
                           for p in prompts]
                for s in streams:
                    s.result(timeout=600)
            wall = time.perf_counter() - t0
            ts = tier.stats()
            return {"sessions": args.sessions, "rounds": args.rounds,
                    "wall_s": round(wall, 3),
                    "pool_blocks": eng.pool.capacity,
                    "working_set_blocks": need * args.sessions,
                    "oversubscription": round(
                        need * args.sessions / eng.pool.capacity, 2),
                    "prefix_hit_rate": ts["hit_rate"],
                    "tier": ts,
                    "radix": eng.stats()["kvcache"]["prefix_cache"]}
        finally:
            eng.close()

    stages = {"hibernate_exact": _hibernate_exact_stage,
              "resume_vs_reprefill": _resume_vs_reprefill_stage,
              "oversubscribed": _oversubscribed_stage}
    for name, run in stages.items():
        if name in prev:
            row = dict(prev[name])
            row["reused_from_previous_run"] = True
        else:
            row = {"stage": name, **run()}
        rows.append(row)
        flush()

    hib = next(r for r in rows if r.get("stage") == "hibernate_exact")
    rvs = next(r for r in rows
               if r.get("stage") == "resume_vs_reprefill")
    over = next(r for r in rows if r.get("stage") == "oversubscribed")
    problems = []
    if hib["agreement"] != 1.0:
        problems.append("hibernate/resume agreement %r != 1.0 — "
                        "resumed streams diverged" % (hib["agreement"],))
    if not over["prefix_hit_rate"]:
        problems.append("oversubscribed trace never hit the host tier")
    if (platform == "cpu" and rvs.get("ttft_resume_ms")
            and rvs.get("ttft_reprefill_ms")
            and rvs["ttft_resume_ms"] >= rvs["ttft_reprefill_ms"]):
        problems.append(
            "TTFT-on-resume (%.1f ms) did not beat re-prefill "
            "(%.1f ms) on cpu" % (rvs["ttft_resume_ms"],
                                  rvs["ttft_reprefill_ms"]))
    if problems:
        for p in problems:
            print("bench: KVTIER GATE: " + p + " — artifact left "
                  "incomplete", file=sys.stderr)
        flush()
        return 1
    result["summary"] = {
        "agreement": hib["agreement"],
        "lost_payload_resumes": hib["lost_payload_resumes"],
        "ttft_resume_ms": rvs.get("ttft_resume_ms"),
        "ttft_reprefill_ms": rvs.get("ttft_reprefill_ms"),
        "resume_speedup": rvs.get("resume_speedup"),
        "promote_mbs": rvs.get("promote_mbs"),
        "prefix_hit_rate": over["prefix_hit_rate"],
        "oversubscription": over["oversubscription"],
    }
    result["complete"] = True
    flush()
    print(json.dumps({
        "metric": "lm_serving_kvtier_resume_ttft_ms",
        "value": rvs.get("ttft_resume_ms"),
        "unit": "ms", "platform": platform,
        **{k: v for k, v in result["summary"].items()
           if k != "ttft_resume_ms"}}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# --serve-lm --router: prefix-affinity routing -> BENCH_ROUTER.json
# ---------------------------------------------------------------------------

def _serve_lm_router_bench(argv) -> int:
    """Cache-aware routing benchmark -> BENCH_ROUTER.json (resumable).

    One returning-session trace (S sessions x T turns; every turn's
    prompt is the previous turn's full output plus fresh user tokens),
    replayed through three LMReplicaSet arms:

    - ``blind``: router=None — the radix-blind least-loaded baseline.
      Each replica grows its own RadixCache, so a returning session
      lands wherever the queue is shortest and re-prefills tokens
      another replica already holds.
    - ``routed``: RadixRouter prefix-affinity scoring over the
      per-replica summaries (no session ids — this arm measures the
      SCORE, not stickiness).  Gate: set-level prefix hit rate
      strictly above blind AND TTFT p99 strictly below blind.
    - ``chaos``: routed + session stickiness + per-replica host tiers;
      one replica is killed mid-trace with a session hibernated into
      it.  Gate: zero accepted-request loss (every stream completes,
      the hibernated session re-routes and replays bit-exactly) and
      re_routes >= 1.

    AGREEMENT artifact: every arm's every output must equal the
    single-engine reference (same prompt, seed, temperature) exactly —
    ``complete`` requires agreement 1.0 on every stage."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve-lm --router")
    ap.add_argument("--json", default=None)
    ap.add_argument("--sessions", type=int, default=int(
        os.environ.get("BIGDL_TPU_ROUTER_SESSIONS", "6")))
    ap.add_argument("--turns", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=1024)
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-blocks", type=int, default=32,
                    help="session head length in blocks — long heads "
                         "make TTFT prefill-dominated, which is the "
                         "regime affinity routing targets (short heads "
                         "drown the saved prefill in decode noise)")
    ap.add_argument("--affinity-weight", type=float, default=0.7)
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_ROUTER.json")
    if args.turns < 2 or args.sessions < 2 or args.replicas < 2:
        ap.error("need >= 2 sessions, >= 2 turns, >= 2 replicas")

    import jax
    import numpy as np
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.serving import HostBlockStore, LMServingEngine
    from bigdl_tpu.serving.router import LMReplicaSet, RadixRouter
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    config = {"model": "transformer_lm", "vocab": 256, "hidden": 128,
              "heads": 4, "layers": 4, "max_len": args.cache_len,
              "pos": "rope", "layout": "paged",
              "slots": args.slots, "cache_len": args.cache_len,
              "block_len": args.block_len, "max_new": args.max_new,
              "sessions": args.sessions, "turns": args.turns,
              "replicas": args.replicas,
              "prompt_blocks": args.prompt_blocks,
              "affinity_weight": args.affinity_weight}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "lm_serving_router", "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    eng_kw = dict(slots=args.slots, cache_len=args.cache_len,
                  block_len=args.block_len, max_new_tokens=args.max_new,
                  temperature=0.7,
                  max_queue=max(args.sessions * args.turns, 256))
    TEMP, TIMEOUT = 0.7, 600.0

    def seed(s, t):
        return 1000 * s + t   # one deterministic key chain per request

    # -- the trace + its single-engine reference outputs ----------------- #
    # Built once: turn t's prompt is turn t-1's full reference output
    # plus a fresh user suffix, so the SAME prompts replay through
    # every arm and bit-exactness is checkable per request.
    rng = np.random.RandomState(11)
    suffix = args.block_len + 1   # user turns cross a block boundary
    trace = [[None] * args.sessions for _ in range(args.turns)]
    refs = [[None] * args.sessions for _ in range(args.turns)]
    ref_eng = LMServingEngine(model, **eng_kw)
    try:
        ref_eng.warmup()
        head = args.prompt_blocks * args.block_len + 1
        max_prompt = (head + args.turns * (args.max_new + suffix)
                      + args.max_new)
        if max_prompt > args.cache_len:
            ap.error(f"trace would outgrow cache_len "
                     f"({max_prompt} > {args.cache_len}): shrink "
                     f"--prompt-blocks/--turns/--max-new")
        hist = [rng.randint(1, config["vocab"] + 1,
                            size=head).astype(np.int32)
                for _ in range(args.sessions)]
        for t in range(args.turns):
            for s in range(args.sessions):
                trace[t][s] = hist[s]
                out = ref_eng.generate(hist[s], max_new_tokens=args.max_new,
                                       temperature=TEMP, rng=seed(s, t),
                                       timeout=TIMEOUT)
                refs[t][s] = out
                hist[s] = np.concatenate(
                    [out, rng.randint(1, config["vocab"] + 1,
                                      size=suffix)]).astype(np.int32)
        # the chaos stage's long-running hibernation session
        hib_prompt = rng.randint(1, config["vocab"] + 1,
                                 size=3 * args.block_len + 1) \
            .astype(np.int32)
        hib_max_new = min(48, args.cache_len - len(hib_prompt))
        hib_ref = ref_eng.generate(hib_prompt, max_new_tokens=hib_max_new,
                                   temperature=TEMP, rng=99999,
                                   timeout=TIMEOUT)
    finally:
        ref_eng.close()

    # (suffix-length, chain-depth) pairs the trace can hit: warm the
    # prefix-prefill executables on EVERY arm before the timed replay,
    # so TTFT measures routing, not first-use XLA compiles (both arms
    # get the identical warmup — the comparison stays fair)
    suffix_hints, chain_hints = set(), set()
    for s in range(args.sessions):
        depths = []           # chain depths this session ever published
        for t in range(args.turns):
            plen = len(trace[t][s])
            cap = (plen - 1) // args.block_len
            for d in depths:
                m = min(cap, d)
                if m >= 1:
                    suffix_hints.add(plen - m * args.block_len)
                    chain_hints.add(m)
            depths.append((plen + args.max_new) // args.block_len)

    def _warm(rset):
        rset.warmup()
        if suffix_hints:
            rset.warmup_prefix(sorted(suffix_hints), sorted(chain_hints))

    def _percentiles_ms(ttfts):
        xs = [t for t in ttfts if t is not None]
        if not xs:
            return None, None
        return (round(float(np.percentile(xs, 50)) * 1000.0, 3),
                round(float(np.percentile(xs, 99)) * 1000.0, 3))

    def _run_trace(rset, *, session_ids=False, kill_at_turn=None,
                   kill_name=None):
        """Replay the trace; returns (exact, total, losses, ttfts,
        killed_name).  Turn t's streams are all in flight together, so
        dispatch balance matters; the submission order ROTATES by turn
        — deterministic least-loaded round-robin would otherwise
        reproduce last turn's placement verbatim and hand the blind arm
        perfect affinity by accident (a real front-end's arrival order
        is not stable either).  The kill (when asked) lands while turn
        ``kill_at_turn``'s streams are mid-decode."""
        exact = total = losses = 0
        ttfts = []
        killed = None
        for t in range(args.turns):
            streams = [None] * args.sessions
            for i in range(args.sessions):
                s = (i + t) % args.sessions
                sid = f"sess-{s}" if session_ids else None
                streams[s] = rset.submit(
                    trace[t][s], session_id=sid, temperature=TEMP,
                    rng=seed(s, t))
            if kill_at_turn is not None and t == kill_at_turn:
                killed = kill_name or streams[t % args.sessions] \
                    .replica_name
                rset.kill_replica(killed)
            for s, st in enumerate(streams):
                total += 1
                try:
                    out = st.result(timeout=TIMEOUT)
                except Exception:
                    losses += 1
                    continue
                exact += int(np.array_equal(out, refs[t][s]))
                # TTFT stats cover RETURNING turns only (t >= 1): turn
                # 0 is a cold full prefill in every arm — routing
                # cannot touch it — and on a short trace its queueing
                # jitter owns the p99, drowning the suffix-only wins
                # the gate is supposed to measure.
                if t >= 1:
                    ttfts.append(st.ttft_s)
        return exact, total, losses, ttfts, killed

    def _arm_stage(routed: bool):
        router = (RadixRouter(affinity_weight=args.affinity_weight)
                  if routed else None)
        rset = LMReplicaSet(model, args.replicas, router=router,
                            name="routed" if routed else "blind",
                            **eng_kw)
        try:
            _warm(rset)
            exact, total, losses, ttfts, _ = _run_trace(rset)
            pc = rset.prefix_cache_stats()
            p50, p99 = _percentiles_ms(ttfts)
            row = {"requests": total,
                   "agreement": round(exact / total, 4),
                   "accepted_loss": losses,
                   "prefix_hit_rate": round(pc["hit_rate"] or 0.0, 4),
                   "prefill_tokens_saved": pc["prefill_tokens_saved"],
                   "ttft_scope": "returning_turns",
                   "ttft_p50_ms": p50, "ttft_p99_ms": p99}
            if routed:
                rst = rset.stats()["router"]
                row.update(affinity_hits=rst["affinity_hits"],
                           cold_dispatches=rst["cold_dispatches"])
            return row
        finally:
            rset.close()

    def _chaos_stage():
        tier_mb = 256 << 20
        rset = LMReplicaSet(
            model, args.replicas,
            router=RadixRouter(affinity_weight=args.affinity_weight),
            kvtier_factory=lambda n: HostBlockStore(host_bytes=tier_mb,
                                                    name=n),
            name="chaos", **eng_kw)
        try:
            _warm(rset)
            # a session hibernated into the victim: its tier entry dies
            # with the replica, so resume must re-route + replay
            hib = rset.submit(hib_prompt, session_id="hib-sess",
                              max_new_tokens=hib_max_new,
                              temperature=TEMP, rng=99999)
            it = hib.tokens(timeout=TIMEOUT)
            next(it)
            next(it)
            hibernated = rset.hibernate(hib, timeout=30.0)
            victim = hib.replica_name
            # the kill targets the hibernation holder: a DEAD sticky
            # replica mid-trace, with a session's tier entry inside it
            exact, total, losses, ttfts, killed = _run_trace(
                rset, session_ids=True,
                kill_at_turn=args.turns // 2, kill_name=victim)
            resumed = rset.resume(hib)
            total += 1
            try:
                hib_out = hib.result(timeout=TIMEOUT)
                hib_exact = bool(np.array_equal(hib_out, hib_ref))
                exact += int(hib_exact)
            except Exception:
                losses += 1
                hib_exact = False
            st = rset.stats()
            return {"requests": total,
                    "agreement": round(exact / total, 4),
                    "accepted_loss": losses,
                    "killed_replica": killed,
                    "re_routes": st["sessions"]["re_routes"],
                    "re_dispatches": hib.re_dispatches,
                    "hibernated": bool(hibernated),
                    "resumed": bool(resumed),
                    "hibernated_resume_exact": hib_exact,
                    "resume_re_routes": st["resume_re_routes"],
                    "sticky_hits": st["sessions"]["sticky_hits"]}
        finally:
            rset.close()

    stages = {"blind": lambda: _arm_stage(False),
              "routed": lambda: _arm_stage(True),
              "chaos": _chaos_stage}
    for name, run in stages.items():
        if name in prev:
            row = dict(prev[name])
            row["reused_from_previous_run"] = True
        else:
            row = {"stage": name, **run()}
        rows.append(row)
        flush()

    blind = next(r for r in rows if r.get("stage") == "blind")
    routed = next(r for r in rows if r.get("stage") == "routed")
    chaos = next(r for r in rows if r.get("stage") == "chaos")
    problems = []
    for r in (blind, routed, chaos):
        if r["agreement"] != 1.0:
            problems.append("stage %s agreement %r != 1.0 — routed "
                            "outputs diverged from the single-engine "
                            "reference" % (r["stage"], r["agreement"]))
    if routed["prefix_hit_rate"] <= blind["prefix_hit_rate"]:
        problems.append(
            "routed prefix hit rate %.3f not above blind %.3f — "
            "affinity scoring bought nothing" %
            (routed["prefix_hit_rate"], blind["prefix_hit_rate"]))
    if (routed.get("ttft_p99_ms") and blind.get("ttft_p99_ms")
            and routed["ttft_p99_ms"] >= blind["ttft_p99_ms"]):
        problems.append(
            "routed TTFT p99 (%.1f ms) did not beat blind (%.1f ms)"
            % (routed["ttft_p99_ms"], blind["ttft_p99_ms"]))
    if chaos["accepted_loss"] != 0:
        problems.append("chaos stage lost %d accepted request(s)"
                        % chaos["accepted_loss"])
    if not chaos["re_routes"] and not chaos["resume_re_routes"]:
        problems.append("chaos stage recorded no re-routes — the "
                        "replica death was not exercised")
    if not (chaos["hibernated"] and chaos["resumed"]
            and chaos["hibernated_resume_exact"]):
        problems.append(
            "chaos stage: hibernated session did not survive its "
            "replica's death (hibernated=%r resumed=%r exact=%r)"
            % (chaos["hibernated"], chaos["resumed"],
               chaos["hibernated_resume_exact"]))
    if problems:
        for p in problems:
            print("bench: ROUTER GATE: " + p + " — artifact left "
                  "incomplete", file=sys.stderr)
        flush()
        return 1
    result["summary"] = {
        "agreement": 1.0,
        "prefix_hit_rate": {"blind": blind["prefix_hit_rate"],
                            "routed": routed["prefix_hit_rate"]},
        "ttft_p50_ms": {"blind": blind["ttft_p50_ms"],
                        "routed": routed["ttft_p50_ms"]},
        "ttft_p99_ms": {"blind": blind["ttft_p99_ms"],
                        "routed": routed["ttft_p99_ms"]},
        "ttft_p99_speedup": round(
            blind["ttft_p99_ms"] / routed["ttft_p99_ms"], 3),
        "affinity_hits": routed.get("affinity_hits"),
        "cold_dispatches": routed.get("cold_dispatches"),
        "chaos_zero_accepted_loss": chaos["accepted_loss"] == 0,
        "chaos_re_routes": (chaos["re_routes"]
                            + chaos["resume_re_routes"]),
    }
    result["complete"] = True
    flush()
    print(json.dumps({
        "metric": "lm_serving_router_prefix_hit_rate",
        "value": routed["prefix_hit_rate"],
        "unit": "fraction", "platform": platform,
        **{k: v for k, v in result["summary"].items()
           if k != "prefix_hit_rate"},
        "prefix_hit_rate_blind": blind["prefix_hit_rate"]}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# --serve-lm --deadline: request lifecycle -> BENCH_DEADLINE.json
# ---------------------------------------------------------------------------

def _serve_lm_deadline_bench(argv) -> int:
    """Request-lifecycle benchmark -> BENCH_DEADLINE.json (resumable).

    One seeded open-loop trace (Poisson arrivals; per-request deadline
    budgets and client-disconnect instants drawn from the loadgen's
    lifecycle menus) replayed through three LMReplicaSet arms:

    - ``lifecycle``: honor_lifecycle=True — expired requests shed
      pre-admission as typed ServingDeadlineExceeded, mid-stream
      expiry/cancel finishes the stream with a typed truncation and
      frees the slot the same scheduler round.
    - ``baseline``: honor_lifecycle=False — the ignore-everything
      control: the engines RECORD deadline/cancel events (and count
      every decode step spent on a dead-but-seated stream as wasted)
      but never shed or free early.
    - ``chaos``: lifecycle + hedged dispatch + a serving.cancel
      disconnect storm + a replica killed mid-trace (i.e. mid-hedge
      when the race is on).  Gate: ZERO accepted-request loss — every
      accepted stream ends completed, typed-truncated, or typed-shed.

    AGREEMENT artifact: completed streams must equal the single-engine
    reference (same prompt, seed, temperature) exactly; truncated
    streams must be an exact PREFIX of it — a deadline or disconnect
    may cost tokens, never correctness.  Headline gates: agreement
    exactly 1.0, chaos zero loss, and the lifecycle arm strictly
    beating the baseline on BOTH wasted decode steps and goodput
    under SLO."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve-lm --deadline")
    ap.add_argument("--json", default=None)
    ap.add_argument("--rate", type=float, default=float(
        os.environ.get("BIGDL_TPU_DEADLINE_RATE", "12.0")))
    ap.add_argument("--duration", type=float, default=float(
        os.environ.get("BIGDL_TPU_DEADLINE_DURATION", "3.0")))
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=96)
    ap.add_argument("--block-len", type=int, default=16)
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_DEADLINE.json")
    if args.replicas < 2:
        ap.error("need >= 2 replicas (chaos kills one mid-trace)")

    import threading

    import jax
    import numpy as np
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.resilience.errors import ServingDeadlineExceeded
    from bigdl_tpu.serving import HedgePolicy, LMServingEngine
    from bigdl_tpu.serving.router import LMReplicaSet
    from bigdl_tpu.traffic.loadgen import TraceLoadGenerator
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    #: disconnect storm: every live stream crosses serving.cancel once
    #: per scheduler round; 2% of crossings hang up the client
    storm_spec = "serving.cancel:transient:p=0.02"
    gen = TraceLoadGenerator(
        kind="poisson", rate_rps=args.rate, duration_s=args.duration,
        seed=args.seed, vocab=256, prompt_lens=(8, 16, 24),
        max_news=(12, 20, 28),
        deadline_menu=(0.9, 2.5, None), deadline_fraction=1.0,
        cancel_after_menu=(0.06, 0.15, None, None), cancel_fraction=1.0)
    #: chaos-arm hedge policy: median-wait trigger so queue-delayed
    #: requests actually hedge on this short trace (a p99 trigger needs
    #: a longer window than the storm stage runs)
    hedge_cfg = {"trigger_quantile": 0.5, "window": 128,
                 "min_observations": 8, "max_hedge_fraction": 0.3,
                 "min_trigger_s": 0.002}
    config = {"model": "transformer_lm", "vocab": 256, "hidden": 128,
              "heads": 4, "layers": 2, "max_len": args.cache_len,
              "pos": "rope", "layout": "paged",
              "slots": args.slots, "cache_len": args.cache_len,
              "block_len": args.block_len, "replicas": args.replicas,
              "storm": storm_spec, "hedge": hedge_cfg,
              "trace": gen.config()}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "lm_serving_deadline", "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    trace = gen.trace()
    eng_kw = dict(slots=args.slots, cache_len=args.cache_len,
                  block_len=args.block_len,
                  max_new_tokens=max(gen.max_news),
                  prefill_buckets=(8, 16, 32), temperature=0.7,
                  max_queue=max(len(trace) * 2, 128))
    TEMP, TIMEOUT = 0.7, 600.0

    # -- single-engine reference: one exact answer per arrival -------- #
    refs = [None] * len(trace)
    ref_eng = LMServingEngine(model, **eng_kw)
    try:
        ref_eng.warmup()
        for a in trace:
            refs[a.index] = ref_eng.generate(
                a.prompt, max_new_tokens=a.max_new, temperature=TEMP,
                rng=1000 + a.index, timeout=TIMEOUT)
    finally:
        ref_eng.close()

    def _run_arm(name, *, honor, hedge=None, storm=False,
                 kill_at_s=None):
        """Replay the trace through one arm; returns the stage row."""
        rset = LMReplicaSet(model, args.replicas, hedge=hedge,
                            honor_lifecycle=honor, name=name, **eng_kw)
        timers: list = []
        recs: list = []
        try:
            rset.warmup()
            if storm:
                # arming publishes the spec in the environment first
                # (the injector refuses silent activation, and a `ps e`
                # shows the storm) — same pattern as ChaosReplayer
                os.environ[faults.ENV_SPEC] = storm_spec
                faults.install(faults.FaultInjector(
                    faults.parse_spec(storm_spec), seed=13))
            if kill_at_s is not None:
                t = threading.Timer(
                    kill_at_s, lambda: rset.kill_replica(f"{name}-r1"))
                t.daemon = True
                t.start()
                timers.append(t)

            def _submit(a):
                st = rset.submit(a.prompt, max_new_tokens=a.max_new,
                                 temperature=TEMP, rng=1000 + a.index,
                                 deadline_s=a.deadline_s,
                                 hedgeable=hedge is not None)
                rec = {"a": a, "st": st, "abandoned": False}
                if a.cancel_after_s is not None:
                    def _hangup(rec=rec, st=st):
                        # True only if the client left a LIVE stream —
                        # a post-completion hangup watched it all
                        rec["abandoned"] = bool(st.cancel())
                    ht = threading.Timer(a.cancel_after_s, _hangup)
                    ht.daemon = True
                    ht.start()
                    timers.append(ht)
                recs.append(rec)
                return st

            t0 = time.perf_counter()
            report = gen.run(_submit, trace=trace)
            completed = truncated = typed_shed = losses = 0
            mism = good = 0
            for rec in recs:
                a, st = rec["a"], rec["st"]
                try:
                    st.result(timeout=TIMEOUT)
                    err = None
                except ServingDeadlineExceeded as e:
                    err = e
                except Exception as e:  # noqa: BLE001 — loss below
                    err = e
                ref_gen = refs[a.index][len(a.prompt):]
                if err is None and st.truncation is None:
                    completed += 1
                    if not np.array_equal(st.generated, ref_gen):
                        mism += 1
                    else:
                        lat = st.finished_at - st.submitted_at
                        if (not rec["abandoned"]
                                and (a.deadline_s is None
                                     or lat <= a.deadline_s)):
                            good += 1
                elif err is None:
                    truncated += 1
                    g = st.generated
                    if not np.array_equal(g, ref_gen[:len(g)]):
                        mism += 1
                elif isinstance(err, ServingDeadlineExceeded):
                    typed_shed += 1
                else:
                    losses += 1
            wall = time.perf_counter() - t0
            checked = completed + truncated
            lc = rset.lifecycle_stats()
            st_all = rset.stats()
            row = {
                "honor_lifecycle": bool(honor),
                "offered": report.offered,
                "accepted": len(report.accepted),
                "shed_preadmission": len(report.shed),
                "submit_errors": len(report.errors),
                "completed": completed, "truncated": truncated,
                "typed_shed_postadmission": typed_shed,
                "accepted_loss": losses,
                "agreement": (round((checked - mism) / checked, 4)
                              if checked else None),
                "good_requests": good,
                "wall_s": round(wall, 3),
                "goodput_rps": round(good / wall, 4) if wall else None,
                "wasted_decode_steps": lc["wasted_decode_steps"],
                "lifecycle": lc,
            }
            if hedge is not None:
                row["hedge"] = st_all["hedge"]
            if kill_at_s is not None:
                row["killed_replica"] = f"{name}-r1"
            if storm:
                inj = faults.active()
                row["storm_disconnects"] = (
                    sum(d["fired"] for d in inj.stats().values())
                    if inj else None)
            return row
        finally:
            for t in timers:
                t.cancel()
            if storm:
                faults.install(None)
                os.environ.pop(faults.ENV_SPEC, None)
            rset.close()

    stages = {
        "lifecycle": lambda: _run_arm("deadline", honor=True),
        "baseline": lambda: _run_arm("ignore", honor=False),
        "chaos": lambda: _run_arm(
            "chaos", honor=True, storm=True,
            kill_at_s=args.duration * 0.5,
            hedge=HedgePolicy(**hedge_cfg)),
    }
    for name, run in stages.items():
        if name in prev:
            row = dict(prev[name])
            row["reused_from_previous_run"] = True
        else:
            row = {"stage": name, **run()}
        rows.append(row)
        flush()

    lifecycle = next(r for r in rows if r.get("stage") == "lifecycle")
    baseline = next(r for r in rows if r.get("stage") == "baseline")
    chaos = next(r for r in rows if r.get("stage") == "chaos")
    problems = []
    for r in (lifecycle, baseline, chaos):
        if r["agreement"] != 1.0:
            problems.append(
                "stage %s agreement %r != 1.0 — lifecycle handling "
                "changed surviving tokens" % (r["stage"], r["agreement"]))
        if r["accepted_loss"] != 0:
            problems.append("stage %s lost %d accepted request(s)"
                            % (r["stage"], r["accepted_loss"]))
    if lifecycle["truncated"] + lifecycle["typed_shed_postadmission"] \
            + lifecycle["shed_preadmission"] == 0:
        problems.append("lifecycle stage shed/truncated nothing — the "
                        "trace never exercised the machinery")
    if lifecycle["wasted_decode_steps"] >= baseline["wasted_decode_steps"]:
        problems.append(
            "lifecycle wasted_decode_steps %d not strictly below "
            "baseline %d — honoring lifecycle bought no decode back"
            % (lifecycle["wasted_decode_steps"],
               baseline["wasted_decode_steps"]))
    if not (lifecycle["goodput_rps"] and baseline["goodput_rps"]
            and lifecycle["goodput_rps"] > baseline["goodput_rps"]):
        problems.append(
            "lifecycle goodput %r rps not strictly above baseline %r"
            % (lifecycle["goodput_rps"], baseline["goodput_rps"]))
    if problems:
        for p in problems:
            print("bench: DEADLINE GATE: " + p + " — artifact left "
                  "incomplete", file=sys.stderr)
        flush()
        return 1
    result["summary"] = {
        "agreement": 1.0,
        "wasted_decode_steps": {
            "lifecycle": lifecycle["wasted_decode_steps"],
            "baseline": baseline["wasted_decode_steps"]},
        "goodput_rps": {"lifecycle": lifecycle["goodput_rps"],
                        "baseline": baseline["goodput_rps"]},
        "goodput_gain": round(
            lifecycle["goodput_rps"] / baseline["goodput_rps"], 3),
        "chaos_zero_accepted_loss": chaos["accepted_loss"] == 0,
        "chaos_truncated": chaos["truncated"],
        "hedges_fired": (chaos.get("hedge") or {}).get("hedges_fired"),
        "hedges_won": (chaos.get("hedge") or {}).get("hedges_won"),
    }
    result["complete"] = True
    flush()
    print(json.dumps({
        "metric": "lm_serving_deadline_goodput_gain",
        "value": result["summary"]["goodput_gain"],
        "unit": "x_vs_ignore_baseline", "platform": platform,
        **{k: v for k, v in result["summary"].items()
           if k != "goodput_gain"}}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# --serve-lm --disagg: disaggregated prefill/decode -> BENCH_DISAGG.json
# ---------------------------------------------------------------------------

#: prefill-heavy bursty trace geometry: steady short decode-heavy
#: traffic, punctuated by back-to-back bursts of long prompts — the
#: head-of-line pattern that spikes a co-located engine's ITL
_DISAGG_SHORT_LENS = (8, 16)
_DISAGG_SHORT_MAX_NEW = 32
_DISAGG_LONG_LEN = 96
_DISAGG_LONG_MAX_NEW = 8

#: the chaos arming for the disagg_chaos stage: two transients early
#: (with_backoff retries them) and three lost backends from the 5th
#: export on (payload dropped -> decode-side re-prefill).  Count-based
#: so the stage is deterministic.
_DISAGG_CHAOS_SPEC = ("serving.migrate:transient:count=2;"
                      "serving.migrate:backend_lost:after=5,count=3")


def _disagg_workload(n_requests: int, vocab: int, mean_gap_ms: float,
                     burst_every: int, burst_size: int, rng):
    """Deterministic bursty trace: every ``burst_every``-th arrival
    slot is a burst of ``burst_size`` long prompts landing at once."""
    import numpy as np
    work, at, slot = [], 0.0, 0
    while len(work) < n_requests:
        slot += 1
        if burst_every and slot % burst_every == 0:
            for _ in range(burst_size):
                if len(work) >= n_requests:
                    break
                prompt = rng.randint(1, vocab + 1,
                                     size=_DISAGG_LONG_LEN).astype(np.int32)
                work.append((at, prompt, _DISAGG_LONG_MAX_NEW))
        else:
            t = _DISAGG_SHORT_LENS[rng.randint(len(_DISAGG_SHORT_LENS))]
            prompt = rng.randint(1, vocab + 1, size=t).astype(np.int32)
            work.append((at, prompt, _DISAGG_SHORT_MAX_NEW))
        at += float(rng.exponential(mean_gap_ms / 1000.0))
    return work


def _serve_lm_disagg_bench(argv) -> int:
    """Disaggregated-serving benchmark -> BENCH_DISAGG.json (resumable).

    Four stages over ONE prefill-heavy bursty trace: the co-located
    engine (the ITL-degradation baseline), the co-located engine with
    Sarathi chunked-prefill interleaving, the disaggregated coordinator
    (phase-dedicated replicas + KV-chain migration), and the
    coordinator again with the ``serving.migrate`` fault armed mid-load
    (retry + re-prefill, zero accepted loss).  Every stage runs the
    same bit-exactness probes vs offline generate; the artifact only
    certifies (``complete: true``) when agreement is exactly 1.0 on
    EVERY stage and the chaos stage lost nothing — the latency numbers
    are meaningless if the streams diverge or requests vanish."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --serve-lm --disagg")
    ap.add_argument("--json", default=None)
    ap.add_argument("--requests", type=int, default=int(
        os.environ.get("BIGDL_TPU_SERVE_LM_REQUESTS", "24")))
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--chunk-tokens", type=int, default=32,
                    help="max_prefill_chunk_tokens for the "
                         "chunked_prefill stage")
    ap.add_argument("--mean-gap-ms", type=float, default=15.0)
    ap.add_argument("--burst-every", type=int, default=4,
                    help="every Nth arrival slot is a long-prompt burst")
    ap.add_argument("--burst-size", type=int, default=3)
    ap.add_argument("--probes", type=int, default=2)
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_DISAGG.json")

    import jax
    import numpy as np
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.resilience import faults
    from bigdl_tpu.serving import DisaggCoordinator, LMServingEngine
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    config = {"model": "transformer_lm", "vocab": 256, "hidden": 128,
              "heads": 4, "layers": 4, "max_len": args.cache_len,
              "pos": "rope", "slots": args.slots,
              "cache_len": args.cache_len,
              "layout": "paged", "block_len": args.block_len,
              "chunk_tokens": args.chunk_tokens,
              "requests": args.requests,
              "mean_gap_ms": args.mean_gap_ms,
              "burst_every": args.burst_every,
              "burst_size": args.burst_size,
              "short_lens": list(_DISAGG_SHORT_LENS),
              "short_max_new": _DISAGG_SHORT_MAX_NEW,
              "long_len": _DISAGG_LONG_LEN,
              "long_max_new": _DISAGG_LONG_MAX_NEW,
              "chaos_spec": _DISAGG_CHAOS_SPEC}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "lm_serving_disaggregated", "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    work = _disagg_workload(args.requests, config["vocab"],
                            args.mean_gap_ms, args.burst_every,
                            args.burst_size, np.random.RandomState(5))

    def _split_itl(row, metrics) -> None:
        snap = metrics.snapshot()
        for key in ("itl_decode", "itl_prefill_gap"):
            p99 = snap[key]["p99_s"]
            row[f"{key}_p99_ms"] = (round(p99 * 1000.0, 3)
                                    if p99 is not None else None)
            row[f"{key}_count"] = snap[key]["count"]

    def _engine_stage(chunk_tokens=None):
        eng = LMServingEngine(model, slots=args.slots,
                              cache_len=args.cache_len,
                              block_len=args.block_len,
                              max_prefill_chunk_tokens=chunk_tokens,
                              max_queue=max(args.requests, 256),
                              name="lm-coloc")
        try:
            eng.warmup()
            if chunk_tokens:
                # warm the (suffix bucket, chain bucket) combos the
                # trace's long prompts hit — a chunked prefill past the
                # first chunk runs the suffix executable, and a
                # mid-trace compile would land in the ITL tail this
                # stage exists to measure
                cap = eng._chunk_cap
                bounds = list(range(cap, _DISAGG_LONG_LEN, cap))
                eng.warmup_prefix(
                    suffix_lens=sorted({min(cap, _DISAGG_LONG_LEN - b)
                                        for b in bounds}),
                    prefix_blocks=sorted({b // args.block_len
                                          for b in bounds}))
            row = _serve_lm_stage_continuous(eng, model, work, args.probes)
            _split_itl(row, eng.metrics)
            return row
        finally:
            eng.close()

    def _disagg_stage(chaos=False):
        if chaos:
            prev_spec = os.environ.get(faults.ENV_SPEC)
            os.environ[faults.ENV_SPEC] = _DISAGG_CHAOS_SPEC
            faults.refresh_from_env()
        try:
            co = DisaggCoordinator(model, prefill_replicas=1,
                                   decode_replicas=1, slots=args.slots,
                                   cache_len=args.cache_len,
                                   block_len=args.block_len,
                                   migrate_base_delay_s=0.01,
                                   # decode replicas chunk their (chaos
                                   # path) re-prefills so a lost payload
                                   # can't head-of-line-block the pool
                                   # it was disaggregated to protect
                                   max_prefill_chunk_tokens=(
                                       args.chunk_tokens),
                                   max_queue=max(args.requests, 256),
                                   name="lm-disagg")
            try:
                co.warmup()
                cap = co.decode[0]._chunk_cap
                bounds = list(range(cap, _DISAGG_LONG_LEN, cap))
                if bounds:
                    sls = sorted({min(cap, _DISAGG_LONG_LEN - b)
                                  for b in bounds})
                    pbs = sorted({b // args.block_len for b in bounds})
                    for eng in co.prefill + co.decode:
                        eng.warmup_prefix(suffix_lens=sls,
                                          prefix_blocks=pbs)
                row = _serve_lm_stage_continuous(co, model, work,
                                                 args.probes)
                _split_itl(row, co.decode_metrics)
                st = co.stats()
                row["migrations"] = st["migrations"]
                row["migrated_blocks"] = st["migrated_blocks"]
                row["lost_payloads"] = st["lost_payloads"]
                row["re_prefills"] = st["re_prefills"]
                row["completed"] = st["decode"]["completed"]
                pre = co.prefill_metrics.snapshot()
                row["prefill_slot_occupancy"] = (
                    round(pre["slot_occupancy"], 4)
                    if pre["slot_occupancy"] is not None else None)
                row["decode_slot_occupancy"] = row["slot_occupancy_mean"]
                return row
            finally:
                co.close()
        finally:
            if chaos:
                if prev_spec is None:
                    os.environ.pop(faults.ENV_SPEC, None)
                else:
                    os.environ[faults.ENV_SPEC] = prev_spec
                faults.refresh_from_env()

    stages = {
        "colocated": lambda: _engine_stage(),
        "chunked_prefill": lambda: _engine_stage(args.chunk_tokens),
        "disagg": lambda: _disagg_stage(),
        "disagg_chaos": lambda: _disagg_stage(chaos=True),
    }
    for name, run in stages.items():
        if name in prev:
            row = dict(prev[name])
            row["reused_from_previous_run"] = True
        else:
            row = {"stage": name, **run()}
        rows.append(row)
        flush()

    by = {r["stage"]: r for r in rows}
    if args.probes:
        bad = [n for n, r in by.items() if r["agreement"] != 1.0]
        if bad:
            print(f"bench: DISAGG AGREEMENT != 1.0 on {bad} — streams "
                  "diverged from offline generate; artifact left "
                  "incomplete", file=sys.stderr)
            flush()
            return 1
    chaos_row = by["disagg_chaos"]
    if (chaos_row["completed"] != args.requests
            or chaos_row["re_prefills"] == 0):
        print("bench: DISAGG CHAOS stage must complete every accepted "
              f"request with re-prefills fired (completed="
              f"{chaos_row['completed']}/{args.requests}, re_prefills="
              f"{chaos_row['re_prefills']}); artifact left incomplete",
              file=sys.stderr)
        flush()
        return 1
    coloc, disagg = by["colocated"], by["disagg"]
    chunked = by["chunked_prefill"]

    def _cut(stage_row):
        if coloc["itl_p99_ms"] and stage_row["itl_p99_ms"]:
            return round(coloc["itl_p99_ms"] / stage_row["itl_p99_ms"], 3)
        return None

    result["summary"] = {
        "itl_p99_ms": {n: by[n]["itl_p99_ms"] for n in stages},
        "ttft_p99_ms": {n: by[n]["ttft"]["p99_ms"] for n in stages},
        "itl_p99_speedup_chunked": _cut(chunked),
        "itl_p99_speedup_disagg": _cut(disagg),
        # headline: the better of the two disaggregation strategies --
        # the claim under test is "phase separation cuts the ITL tail",
        # and either chunked interleaving or full disaggregation counts.
        "itl_p99_speedup_best": max(_cut(chunked) or 0.0,
                                    _cut(disagg) or 0.0) or None,
        "tokens_per_s": {n: by[n]["tokens_per_s"] for n in stages},
        "agreement": {n: by[n]["agreement"] for n in stages},
        "migrated_blocks": disagg["migrated_blocks"],
        "prefill_slot_occupancy": disagg["prefill_slot_occupancy"],
        "decode_slot_occupancy": disagg["decode_slot_occupancy"],
        "chaos_re_prefills": chaos_row["re_prefills"],
        "chaos_zero_accepted_loss": (chaos_row["completed"]
                                     == args.requests),
    }
    result["complete"] = True
    flush()
    print(json.dumps({
        "metric": "lm_serving_disagg_itl_p99_speedup",
        "value": result["summary"]["itl_p99_speedup_best"],
        "unit": "x", "platform": platform,
        **{k: v for k, v in result["summary"].items()
           if k != "itl_p99_speedup_best"}}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# --slo: trace-driven load sweep + SLO guardrails + chaos replay
#        -> BENCH_SLO.json
# ---------------------------------------------------------------------------


def _slo_load_point(eng, model, gen, ctrl, slo_s: float) -> dict:
    """One open-loop load point: replay the trace with the controller
    live, then drain every ACCEPTED stream and split completions into
    under/over SLO.  Goodput counts only requests the client actually
    got back under target — sheds and SLO misses are offered load that
    bought nothing."""
    from bigdl_tpu.obs import get_registry

    rej = get_registry().counter("serving/rejected_total", unit="requests")
    rej0 = rej.get()[0]
    with ctrl:
        t_start = time.perf_counter()
        report = gen.run(
            lambda a: eng.submit(a.prompt, max_new_tokens=a.max_new))
        ttfts, ends, lost = [], [], []
        for a, stream in report.accepted:
            try:
                stream.result(timeout=600)
                ttfts.append(stream.ttft_s)
                ends.append(stream.finished_at)
            except Exception as e:  # noqa: BLE001 — loss is data here
                lost.append((a.index, repr(e)))
    span = (max(ends) if ends else time.perf_counter()) - t_start
    under = sum(1 for t in ttfts if t is not None and t <= slo_s)
    return {
        "offered": report.offered,
        "accepted": len(report.accepted),
        "shed": len(report.shed),
        "submit_errors": len(report.errors),
        "completed": len(ttfts),
        "accepted_loss": len(lost),
        "completed_under_slo": under,
        "span_s": round(span, 3),
        "goodput_rps": round(under / span, 3) if span > 0 else None,
        "ttft": _percentiles_ms(ttfts),
        "rejected_total_delta": rej.get()[0] - rej0,
        "controller": ctrl.summary(),
        "slot_limit": eng.slot_limit,
        "max_queue": eng.max_queue,
    }


def _slo_chaos_stage(args, chaos_cfg: dict) -> dict:
    """The chaos row: replay the seeded synthetic incident list mid-load
    against a 2-replica set and account for every accepted request.

    The contract under test is ZERO ACCEPTED-REQUEST LOSS: injected
    transfer/dispatch/enqueue faults may shed new arrivals (typed,
    counted), but anything the server accepted must complete with the
    exact same answer the healthy set gives."""
    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.resilience.replicaset import ReplicaSet
    from bigdl_tpu.traffic import (ChaosReplayer, TraceLoadGenerator,
                                   build_schedule)

    model = nn.Sequential(nn.Linear(8, 4), nn.LogSoftMax()).build(seed=0)
    gen = TraceLoadGenerator(
        kind="poisson", rate_rps=args.chaos_rps,
        duration_s=args.chaos_duration, seed=args.seed)
    schedule = build_schedule(args.chaos_duration, seed=args.chaos_seed)

    def payload(idx: int) -> np.ndarray:
        return np.full((1, 8), (idx % 7) * 0.25, np.float32)

    with ReplicaSet(model, n_replicas=args.replicas, input_shape=(8,),
                    max_batch_size=16, max_queue=args.max_queue,
                    failure_threshold=2, cooldown_s=0.5) as rs:
        rs.warmup()
        # healthy-set reference answers, one per distinct payload
        refs = {i: rs.predict(payload(i), timeout=60) for i in range(7)}
        replayer = ChaosReplayer(schedule, seed=args.chaos_seed)
        with replayer:
            report = gen.run(lambda a: rs.submit(payload(a.index)))
            ok, lost = 0, []
            for a, fut in report.accepted:
                try:
                    y = fut.result(timeout=120)
                    if np.allclose(y, refs[a.index % 7], atol=1e-5):
                        ok += 1
                    else:
                        lost.append((a.index, "result mismatch"))
                except Exception as e:  # noqa: BLE001 — loss is data here
                    lost.append((a.index, repr(e)))
        injected = sum(v["fired"] for v in replayer.injector.stats().values())
    return {
        "config": chaos_cfg,
        "offered": report.offered,
        "accepted": len(report.accepted),
        "shed": len(report.shed),
        "submit_errors": len(report.errors),
        "completed_exact": ok,
        "accepted_loss": len(lost),
        "lost": lost[:8],
        "zero_accepted_loss": not lost,
        "faults_injected": injected,
        "chaos": replayer.summary(),
    }


def _slo_bench(argv) -> int:
    """Goodput-under-SLO vs offered load -> BENCH_SLO.json.

    Open-loop sweep over --loads with the SLOController live (slot
    scale-up, then admission control), then one chaos row replaying the
    seeded synthetic incident list mid-load.  Same resumable-artifact contract
    as the other benches: rewrite after every row, ``complete: false``
    until the final flush, reuse only platform+config-matched rows."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --slo")
    ap.add_argument("--json", default=None)
    ap.add_argument("--loads", default="4,8,16,32,64",
                    help="comma-separated offered loads (requests/s)")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="trace length per load point (s)")
    ap.add_argument("--kind", default="bursty",
                    choices=("poisson", "bursty", "diurnal"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--ttft-slo-ms", type=float, default=500.0)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--tick-ms", type=float, default=50.0)
    ap.add_argument("--chaos-duration", type=float, default=8.0,
                    help="chaos row length (s); 0 skips the chaos row")
    ap.add_argument("--chaos-rps", type=float, default=30.0)
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=2)
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_SLO.json")
    loads = [float(v) for v in args.loads.split(",") if v.strip()]

    import jax
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.obs import get_registry
    from bigdl_tpu.serving import LMServingEngine
    from bigdl_tpu.traffic import SLOController, TraceLoadGenerator, detect_knee
    from bigdl_tpu.utils import artifacts

    platform = jax.devices()[0].platform
    slo_s = args.ttft_slo_ms / 1000.0
    # clamp the length menus so prompt + budget always fits the cache
    # (a small --cache-len smoke run must shed, not error)
    pls = tuple(p for p in _LM_PROMPT_LENS
                if p + min(_LM_MAX_NEWS) <= args.cache_len) or (8,)
    mns = tuple(m for m in _LM_MAX_NEWS
                if max(pls) + m <= args.cache_len) or (8,)
    chaos_cfg = {"duration_s": args.chaos_duration, "rps": args.chaos_rps,
                 "seed": args.chaos_seed, "replicas": args.replicas}
    config = {"model": "transformer_lm", "vocab": 256, "hidden": 128,
              "heads": 4, "layers": 4, "pos": "rope",
              "slots": args.slots, "cache_len": args.cache_len,
              "kind": args.kind, "loads": loads,
              "duration_s": args.duration, "seed": args.seed,
              "ttft_slo_ms": args.ttft_slo_ms,
              "max_queue": args.max_queue, "tick_ms": args.tick_ms,
              # controller policy is part of the row-reuse identity: a
              # row measured under a different ladder is a different
              # experiment
              "controller": {"window": 6, "hot_streak": 1,
                             "cool_s": 2.0, "start": "tightest",
                             "hold_shedding": True, "ladder_floor": 2,
                             "shed_free": "whole_point"},
              "prompt_lens": list(pls),
              "max_news": list(mns),
              "chaos": chaos_cfg}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "slo_traffic_harness", "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)
    # admission ladder: loosest bound first, tightened level by level
    # once slot scale-up is exhausted
    levels = sorted({max(1, args.max_queue >> k) for k in range(6)},
                    reverse=True)
    start_limit = max(1, args.slots // 2)

    eng = LMServingEngine(model, slots=args.slots, cache_len=args.cache_len,
                          max_queue=args.max_queue)
    try:
        t0 = time.perf_counter()
        compiled = eng.warmup()
        rows.append({"stage": "warmup", "prefill_compiled": compiled,
                     "warmup_s": round(time.perf_counter() - t0, 3)})
        flush()

        for load in loads:
            stage = f"load_{load:g}"
            if stage in prev:
                row = dict(prev[stage])
                row["reused_from_previous_run"] = True
            else:
                # fresh actuator state per point: half the slots and the
                # TIGHTEST admission bound (fail-closed) — an open start
                # lets the first burst queue deeper than the whole TTFT
                # budget before the window sees it; cool ticks relax the
                # bound as fast as the p99 actually allows
                eng.set_slot_limit(start_limit)

                def scale_up():
                    # a slot is only real capacity if the paged KV pool
                    # can back one more worst-case context — otherwise
                    # the added slot would just defer on admission
                    if eng.kvcache_headroom() < 1:
                        return False
                    cur = eng.slot_limit
                    return eng.set_slot_limit(cur + 1) > cur

                rej_ctr = get_registry().counter("serving/rejected_total",
                                                 unit="requests")
                # the shed window spans the whole load point: once a
                # point sheds, its offered load has proven itself past
                # capacity, and every relax probe after that accepts
                # doomed-latency requests that the point's p99 keeps
                # forever (a quiet burst gap is not recovery)
                shed_free = max(6, int(round((args.duration + 2.0)
                                             * 1000.0 / args.tick_ms)))
                cool = max(6, int(round(2.0 * 1000.0 / args.tick_ms)))
                # byte-level OOM gating moved from the ad-hoc kvcache
                # check into the memory ledger: the controller refuses
                # scale-up outright when device bytes sit above the
                # watermark, regardless of free KV blocks
                from bigdl_tpu.obs.ledger import get_ledger
                ctrl = SLOController(
                    histogram=eng.metrics.ttft, target_p99_s=slo_s,
                    interval_s=args.tick_ms / 1000.0, window_intervals=6,
                    ledger=get_ledger(),
                    scale_up=scale_up, set_admission=eng.set_max_queue,
                    admission_levels=levels, hot_streak=1,
                    cool_streak=cool, start_level=len(levels) - 1,
                    rejections=lambda: rej_ctr.get()[0],
                    shed_free_intervals=shed_free)
                gen = TraceLoadGenerator(
                    kind=args.kind, rate_rps=load, duration_s=args.duration,
                    seed=args.seed, vocab=config["vocab"],
                    prompt_lens=pls, max_news=mns)
                row = {"stage": stage, "load_rps": load,
                       **_slo_load_point(eng, model, gen, ctrl, slo_s)}
            rows.append(row)
            flush()

        if args.chaos_duration > 0:
            if "chaos" in prev:
                row = dict(prev["chaos"])
                row["reused_from_previous_run"] = True
            else:
                row = {"stage": "chaos",
                       **_slo_chaos_stage(args, chaos_cfg)}
            rows.append(row)
            flush()

        curve = [r for r in rows if r.get("stage", "").startswith("load_")]
        knee = detect_knee(curve, offered_key="load_rps",
                           goodput_key="goodput_rps")
        past_knee = [r for r in curve
                     if knee["knee_rps"] is not None
                     and r["load_rps"] > knee["knee_rps"]
                     and r["ttft"]["p99_ms"] is not None]
        chaos_row = next((r for r in rows if r.get("stage") == "chaos"),
                         None)
        result["summary"] = {
            **knee,
            "slo_ttft_p99_ms": args.ttft_slo_ms,
            "p99_under_slo_past_knee": (
                all(r["ttft"]["p99_ms"] <= args.ttft_slo_ms
                    for r in past_knee) if past_knee else None),
            "total_shed": sum(r["shed"] for r in curve),
            "total_accepted_loss": sum(r["accepted_loss"] for r in curve),
            "chaos_zero_accepted_loss": (
                chaos_row.get("zero_accepted_loss")
                if chaos_row else None),
            "chaos_faults_injected": (
                chaos_row.get("faults_injected") if chaos_row else None),
        }
        result["complete"] = True
        flush()
        print(json.dumps({
            "metric": "slo_peak_goodput_rps",
            "value": result["summary"]["peak_goodput_rps"],
            "unit": "requests/sec", "platform": platform,
            **{k: v for k, v in result["summary"].items()
               if k != "peak_goodput_rps"}}), flush=True)
        return 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# --attn: block-size autotune sweep + BENCH_ATTN regeneration
# ---------------------------------------------------------------------------


def _attn_bench(argv) -> int:
    """Attention-kernel measurement stage: optionally run the resumable
    block-size autotuner (``--autotune`` -> TUNE_ATTN.json winners per
    device kind, plus the paged-decode kernel/gather duel with
    ``--paged``), then regenerate BENCH_ATTN.json with the tuned blocks
    (``--useTuned``) so the headline flash-vs-XLA speedup reflects the
    kernel users actually get through the crossover dispatcher."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --attn")
    ap.add_argument("--sweep", default="2048",
                    help="comma-separated seq lens")
    ap.add_argument("--headDim", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("-b", "--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--autotune", action="store_true",
                    help="run the (block_q, block_k) sweep before the "
                         "BENCH_ATTN regeneration")
    ap.add_argument("--grid", default=None,
                    help="candidate tiles as 'bq:bk,bq:bk,...' "
                         "(default: autotune.DEFAULT_GRID)")
    ap.add_argument("--paged", action="store_true",
                    help="also duel the paged-decode kernel against the "
                         "dense gather")
    ap.add_argument("--paged-iters", type=int, default=20)
    ap.add_argument("--block-len", type=int, default=16,
                    help="KV page size for the paged-decode duel")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=2048)
    ap.add_argument("--json", default=None,
                    help="BENCH_ATTN output path (default: repo root)")
    args = ap.parse_args(argv)

    from bigdl_tpu.ops import autotune

    seq_lens = [int(s) for s in args.sweep.split(",")]
    if args.autotune:
        grid = (autotune.parse_grid(args.grid) if args.grid
                else autotune.DEFAULT_GRID)
        autotune.autotune_attention(
            seq_lens, head_dim=args.headDim, dtype=args.dtype,
            causal=True, batch=args.batch, heads=args.heads,
            iters=args.iters, grid=grid, finalize=not args.paged)
        if args.paged:
            autotune.autotune_paged_decode(
                slots=args.slots, heads=args.heads,
                head_dim=args.headDim, cache_len=args.cache_len,
                block_len=args.block_len, dtype=args.dtype,
                iters=args.paged_iters, finalize=True)

    from bigdl_tpu.models.utils import attention_bench
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_ATTN.json")
    attention_bench.main(
        ["--sweep", ",".join(str(t) for t in seq_lens),
         "--naive", "--useTuned",
         "--headDim", str(args.headDim),
         "--dtype", args.dtype,
         "-b", str(args.batch),
         "--heads", str(args.heads),
         "--iters", str(args.iters),
         "--json", args.json])
    return 0


# ---------------------------------------------------------------------------
# --memprofile: memory-ledger attribution + executable roofline profile
# ---------------------------------------------------------------------------


def _memprofile_bench(argv) -> int:
    """Memory-ledger profile -> PROFILE_MEM.json (resumable).

    Builds the full serving stack on the selected platform — a batch
    ServingEngine (params + host_stager subsystems), an LMServingEngine
    with an int8 speculative drafter and a host KV tier (kvcache + spec
    + kvtier) — drives a small workload through each, then snapshots
    the process-wide MemoryLedger while the engines are still alive:
    the per-subsystem byte attribution table, the per-executable
    memory_analysis()/cost_analysis() roofline rows recorded at
    AOT-lower time, and the reconciliation against the backend
    allocator (``degraded`` on CPU, where ``memory_stats()`` is
    unavailable — drift pinned at 0 by definition).

    Same resumable-artifact contract as the serving benches: workload
    rows are reused across runs when platform + config match; the
    snapshot rows (attribution / executables / reconciliation) are
    always recomputed — they describe THIS process's ledger, and cost
    nothing.  ``complete`` requires >= 5 attributed subsystems and at
    least one executable cost row."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --memprofile")
    ap.add_argument("--json", default=None)
    ap.add_argument("--requests", type=int, default=int(
        os.environ.get("BIGDL_TPU_MEMPROFILE_REQUESTS", "8")))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-len", type=int, default=16)
    ap.add_argument("--spec-k", type=int, default=4)
    args = ap.parse_args(argv)
    if args.json is None:
        args.json = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "PROFILE_MEM.json")

    import jax
    import numpy as np
    from bigdl_tpu.models import LeNet5
    from bigdl_tpu.models.transformer import TransformerLM
    from bigdl_tpu.obs.ledger import get_ledger
    from bigdl_tpu.serving import (HostBlockStore, LMServingEngine,
                                   ServingEngine, SpecConfig)
    from bigdl_tpu.utils import artifacts

    device = jax.devices()[0]
    platform = device.platform
    config = {"serve_model": "lenet5", "lm_model": "transformer_lm",
              "vocab": 256, "hidden": 128, "heads": 4, "layers": 4,
              "slots": args.slots, "cache_len": args.cache_len,
              "block_len": args.block_len, "spec_k": args.spec_k,
              "requests": args.requests}
    prev = artifacts.load_resumable_rows(
        args.json,
        match=lambda doc, r: (doc.get("platform") == platform
                              and doc.get("config") == config
                              and not r.get("error")),
        key=lambda r: r.get("stage"))

    rows: list = []
    result = {"bench": "memory_ledger_profile", "platform": platform,
              "config": config, "rows": rows, "complete": False}

    def flush():
        artifacts.write_artifact(args.json, result)

    flush()
    led = get_ledger()
    serve_model = LeNet5(class_num=10).build(seed=1)
    lm_model = TransformerLM(
        vocab_size=config["vocab"], hidden_size=config["hidden"],
        n_head=config["heads"], n_layers=config["layers"],
        max_len=args.cache_len, pos_encoding="rope").build(seed=7)

    tier = HostBlockStore(host_bytes=64 << 20, name="memprof")
    eng = ServingEngine(serve_model, input_shape=(784,),
                        max_batch_size=8, max_queue=256,
                        name="memprof")
    lm = LMServingEngine(lm_model, slots=args.slots,
                         cache_len=args.cache_len,
                         block_len=args.block_len, max_queue=256,
                         spec=SpecConfig(k=args.spec_k),
                         kvtier=tier, name="memprof-lm")
    try:
        # ---- workload: populate every registrant + compile rows ----
        if "serve" in prev:
            row = dict(prev["serve"])
            row["reused_from_previous_run"] = True
            eng.warmup()
        else:
            t0 = time.perf_counter()
            eng.warmup()
            rng = np.random.RandomState(0)
            for _ in range(args.requests):
                eng.predict(rng.randn(4, 784).astype(np.float32),
                            timeout=600)
            row = {"stage": "serve", "requests": args.requests,
                   "elapsed_s": round(time.perf_counter() - t0, 3)}
        rows.append(row)
        flush()

        if "serve_lm" in prev:
            row = dict(prev["serve_lm"])
            row["reused_from_previous_run"] = True
            lm.warmup()
        else:
            t0 = time.perf_counter()
            lm.warmup()
            rng = np.random.RandomState(1)
            plen = max(args.block_len + 1, args.cache_len // 4)
            max_new = min(16, args.cache_len - plen)
            toks = 0
            for i in range(max(2, args.requests // 2)):
                p = rng.randint(1, config["vocab"] + 1,
                                size=plen).astype(np.int32)
                out = lm.generate(p, max_new_tokens=max_new,
                                  temperature=0.7, rng=i, timeout=600)
                toks += len(out)
            # one hibernate/resume cycle so the kvtier attribution
            # reflects real demote + promote traffic, not an idle tier
            p = rng.randint(1, config["vocab"] + 1,
                            size=plen).astype(np.int32)
            st = lm.submit(p, max_new_tokens=max_new, temperature=0.7,
                           rng=99)
            it = st.tokens(timeout=600)
            next(it)
            next(it)
            hibernated = lm.hibernate(st)
            if hibernated:
                lm.resume(st)
            st.result(timeout=600)
            row = {"stage": "serve_lm",
                   "requests": max(2, args.requests // 2),
                   "tokens": toks, "hibernated": bool(hibernated),
                   "elapsed_s": round(time.perf_counter() - t0, 3)}
        rows.append(row)
        flush()

        # ---- snapshots: taken while BOTH engines are still alive ----
        attribution = led.attribution()
        rows.append({"stage": "attribution",
                     "attribution": attribution,
                     "total_bytes": led.total_bytes(),
                     "table": led.entries()})
        flush()

        exe_rows = led.executables()
        rows.append({"stage": "executables", "count": len(exe_rows),
                     "totals": led.stats()["xcost"],
                     "rows": sorted(exe_rows,
                                    key=lambda r: (r["tag"], r["key"]))})
        flush()

        rec = led.reconcile(device)
        rows.append({"stage": "reconciliation", **rec,
                     "capacity_bytes": led.capacity_bytes(device),
                     "headroom": led.headroom(device),
                     "watermark": led.watermark})
        flush()

        result["summary"] = {
            "subsystems": len(attribution),
            "ledger_bytes": rec["ledger_bytes"],
            "executables": len(exe_rows),
            "drift_bytes": rec["drift_bytes"],
            "verdict": rec["verdict"],
        }
        # the profile only certifies when the whole stack actually
        # reported in: every serving subsystem attributed, at least one
        # roofline row, and a numeric reconciliation drift
        result["complete"] = (
            len(attribution) >= 5 and len(exe_rows) >= 1
            and isinstance(rec["drift_bytes"], int))
        flush()
        print(json.dumps({
            "metric": "memprofile_ledger_bytes",
            "value": rec["ledger_bytes"], "unit": "bytes",
            "platform": platform, **result["summary"]}), flush=True)
        return 0 if result["complete"] else 1
    finally:
        lm.close()
        eng.close()


if __name__ == "__main__":
    if ("--trace" in sys.argv and "--serve" not in sys.argv
            and "--serve-lm" not in sys.argv):
        # training bench: the tracer arms from the environment
        sys.argv = [a for a in sys.argv if a != "--trace"]
        os.environ["BIGDL_TPU_TRACE"] = "1"
    from bigdl_tpu.utils.engine import configure_compile_cache
    configure_compile_cache()       # once, for whichever mode runs
    if "--attn" in sys.argv:
        sys.exit(_attn_bench([a for a in sys.argv[1:] if a != "--attn"]))
    if "--memprofile" in sys.argv:
        sys.exit(_memprofile_bench(
            [a for a in sys.argv[1:] if a != "--memprofile"]))
    if "--slo" in sys.argv:
        sys.exit(_slo_bench([a for a in sys.argv[1:] if a != "--slo"]))
    if "--serve-lm" in sys.argv and "--disagg" in sys.argv:
        sys.exit(_serve_lm_disagg_bench(
            [a for a in sys.argv[1:]
             if a not in ("--serve-lm", "--disagg")]))
    if "--serve-lm" in sys.argv and "--qcompute" in sys.argv:
        sys.exit(_serve_lm_qcompute_bench(
            [a for a in sys.argv[1:]
             if a not in ("--serve-lm", "--spec", "--qcompute")]))
    if "--serve-lm" in sys.argv and "--router" in sys.argv:
        sys.exit(_serve_lm_router_bench(
            [a for a in sys.argv[1:]
             if a not in ("--serve-lm", "--router")]))
    if "--serve-lm" in sys.argv and "--deadline" in sys.argv:
        sys.exit(_serve_lm_deadline_bench(
            [a for a in sys.argv[1:]
             if a not in ("--serve-lm", "--deadline")]))
    if "--serve-lm" in sys.argv and "--kvtier" in sys.argv:
        sys.exit(_serve_lm_kvtier_bench(
            [a for a in sys.argv[1:]
             if a not in ("--serve-lm", "--kvtier")]))
    if "--serve-lm" in sys.argv and "--spec2" in sys.argv:
        sys.exit(_serve_lm_spec2_bench(
            [a for a in sys.argv[1:]
             if a not in ("--serve-lm", "--spec2")]))
    if "--serve-lm" in sys.argv and "--spec" in sys.argv:
        sys.exit(_serve_lm_spec_bench(
            [a for a in sys.argv[1:]
             if a not in ("--serve-lm", "--spec")]))
    if "--serve-lm" in sys.argv and "--prefix" in sys.argv:
        sys.exit(_serve_lm_prefix_bench(
            [a for a in sys.argv[1:]
             if a not in ("--serve-lm", "--prefix")]))
    if "--serve-lm" in sys.argv:
        sys.exit(_serve_lm_bench(
            [a for a in sys.argv[1:] if a != "--serve-lm"]))
    if "--serve" in sys.argv and "--mesh" in sys.argv:
        sys.exit(_serve_mesh_bench(
            [a for a in sys.argv[1:] if a not in ("--serve", "--mesh")]))
    if "--serve" in sys.argv:
        sys.exit(_serve_bench([a for a in sys.argv[1:] if a != "--serve"]))
    else:
        main()
