"""TransformerLM training-throughput harness (tokens/sec) — the LM-family
counterpart of ``models.utils.perf`` (ref DistriOptimizerPerf's role,
models/utils/DistriOptimizerPerf.scala:32-90, which the reference only
ships for its conv nets).

    python -m bigdl_tpu.models.utils.lm_perf -t 2048 -b 8 --flash
    python -m bigdl_tpu.models.utils.lm_perf -t 16384 -b 1 --flash --remat

Prints ONE JSON line: steady-state step time and tokens/sec for a full
train step (forward + backward + SGD/Adam update) at the given shape,
with the bf16-compute / f32-master recipe
(``Optimizer.set_compute_dtype``).
"""
from __future__ import annotations

import argparse
import json
import time


def run_lm_perf(seq_len: int, batch: int, *, vocab: int = 32000,
                hidden: int = 512, heads: int = 8, layers: int = 4,
                flash: bool = False, remat: bool = False,
                optim: str = "adam", dtype: str = "bfloat16",
                iters: int = 10, warmup: int = 2) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import nn
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.optim import Adam, SGD

    model = TransformerLM(
        vocab_size=vocab, hidden_size=hidden, n_head=heads, n_layers=layers,
        max_len=seq_len, remat=remat,
        # pin the baseline arm to the XLA path: "auto" would itself pick
        # flash at long T on TPU, turning the flash-vs-xla sweep into
        # flash-vs-flash exactly where the crossover matters
        attention_impl="flash" if flash else "xla").build(seed=1)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)
    method = (Adam(learning_rate=1e-3) if optim == "adam"
              else SGD(learning_rate=0.1))
    from bigdl_tpu.nn._util import cast_f32_leaves

    params = model.params
    opt_state = method.init_state(params)
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32

    def loss_fn(params, x, y):
        out, _ = model.apply(cast_f32_leaves(params, dt), x)
        return crit.loss(out.astype(jnp.float32), y)

    import functools

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)
        params, opt_state = method.update(grads, opt_state, params)
        return params, opt_state, loss

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(1, vocab + 1, size=(batch, seq_len))
                    .astype(np.float32))
    y = jnp.asarray(rng.randint(1, vocab + 1, size=(batch, seq_len))
                    .astype(np.float32))

    loss = None
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, x, y)
    if loss is not None:
        _ = float(loss)  # hard sync
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, x, y)
    _ = float(loss)
    dt_s = (time.perf_counter() - t0) / iters
    return {"metric": "transformer_lm_train_step",
            "seq_len": seq_len, "batch": batch, "vocab": vocab,
            "hidden": hidden, "heads": heads, "layers": layers,
            "flash": flash, "remat": remat, "optim": optim, "dtype": dtype,
            "iters": iters, "step_s": round(dt_s, 5),
            "tokens_per_s": round(batch * seq_len / dt_s, 1)}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="TransformerLM train throughput")
    p.add_argument("-t", "--seqLen", type=int, default=2048)
    p.add_argument("--sweep", default=None,
                   help="comma-separated seq lens (each timed flash AND "
                        "xla attention); overrides --seqLen/--flash")
    p.add_argument("-b", "--batch", type=int, default=8)
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--hidden", type=int, default=512)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--flash", action="store_true",
                   help="Pallas flash-attention core")
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each block")
    p.add_argument("--optim", default="adam", choices=["sgd", "adam"])
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("-i", "--iteration", type=int, default=10)
    p.add_argument("--json", default=None,
                   help="write the sweep result document to this path")
    args = p.parse_args(argv)

    import jax

    from bigdl_tpu.utils.engine import Engine

    Engine.init()  # the platform is JAX_PLATFORMS' (cpu to rehearse)

    if not args.sweep:
        print(json.dumps(run_lm_perf(
            args.seqLen, args.batch, vocab=args.vocab, hidden=args.hidden,
            heads=args.heads, layers=args.layers, flash=args.flash,
            remat=args.remat, optim=args.optim, dtype=args.dtype,
            iters=args.iteration)))
        return

    plat = jax.devices()[0].platform
    # resume: reuse successful same-config rows from a prior killed
    # sweep so repeated short backend windows make net progress.  Rows
    # from another platform or iteration count never qualify (a CPU
    # debug sweep must not publish as TPU numbers).
    from bigdl_tpu.utils.artifacts import load_resumable_rows
    prev = load_resumable_rows(
        args.json,
        match=lambda old, r: (
            old.get("platform") == plat and "tokens_per_s" in r
            and r.get("vocab") == args.vocab
            and r.get("hidden") == args.hidden
            and r.get("heads") == args.heads
            and r.get("layers") == args.layers
            and r.get("remat") == args.remat
            and r.get("optim") == args.optim
            and r.get("dtype") == args.dtype
            and r.get("iters") == args.iteration),
        key=lambda r: (r.get("seq_len"), r.get("flash"), r.get("batch")))
    rows = []
    result = {"platform": plat, "rows": rows,
              "complete": False}  # flipped by the final flush

    from bigdl_tpu.utils.artifacts import write_artifact

    def flush():
        # rewrite after every row: a sweep killed mid-flight (flaky
        # backend window closing) keeps the rows it measured
        write_artifact(args.json, result)

    for t in (int(s) for s in args.sweep.split(",")):
        for flash in (True, False):
            # long T at fixed batch would OOM the naive path first;
            # keep tokens/step constant by shrinking batch
            eff_batch = max(1, args.batch * args.seqLen // t)
            if (t, flash, eff_batch) in prev:
                row = dict(prev[(t, flash, eff_batch)],
                           reused_from_previous_run=True)
            else:
                row = {"seq_len": t, "flash": flash}
                try:
                    row = run_lm_perf(
                        t, eff_batch, vocab=args.vocab, hidden=args.hidden,
                        heads=args.heads, layers=args.layers, flash=flash,
                        remat=args.remat, optim=args.optim, dtype=args.dtype,
                        iters=args.iteration)
                except Exception as e:
                    row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            rows.append(row)
            flush()
            print(json.dumps(row), flush=True)
    result["complete"] = True
    flush()


if __name__ == "__main__":
    main()
