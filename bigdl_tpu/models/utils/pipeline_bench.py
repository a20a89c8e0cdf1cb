"""Input-pipeline-fed ResNet-50 bench: prove the host path can feed the
chip (ref dataset/DataSet.scala:380-433 SequenceFile
ImageNet path + MTLabeledBGRImgToBatch.scala:52-80 threaded host decode).

    python -m bigdl_tpu.models.utils.pipeline_bench --batch 256 --iters 20

Measures the SAME bf16/NHWC ResNet-50 training step twice: (a) synthetic
device-resident data, (b) fed by the real path — record shards on disk ->
threaded decode/augment -> bounded Prefetcher -> host->device transfer.
Emits one JSON line with both numbers and their ratio.

TPU-first pipeline design (deliberately different from the reference's
host-side float math): the host stays in uint8 HWC end-to-end — shard
read, random 224x224 crop, horizontal flip are all byte slicing — and the
device does normalize + bf16 cast fused into the step.  Host work per
image is a ~150 KB memcpy instead of ~600 KB of float math, and the
host->device link carries 4x fewer bytes.  The reference normalizes on
the host because its executor IS the compute device; on TPU the host's
only job is to keep the MXU fed.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

CROP = 224
STORED = 256
# ImageNet BGR mean/std in the reference's 0..255 scale
MEAN = (104.0, 117.0, 123.0)
STD = (1.0, 1.0, 1.0)


def generate_shards(workdir: str, n_records: int, n_shards: int = 8,
                    seed: int = 0) -> list[str]:
    """Synthetic stored-format dataset: STOREDxSTOREDx3 uint8 BGR images in
    the repo's record-shard format (the role ImageNetSeqFileGenerator
    plays for the reference)."""
    from bigdl_tpu.dataset.seqfile import write_sharded
    from bigdl_tpu.dataset.types import ByteRecord

    rng = np.random.RandomState(seed)
    records = []
    for i in range(n_records):
        img = rng.randint(0, 256, size=(STORED, STORED, 3), dtype=np.uint8)
        records.append(ByteRecord(img.tobytes(), float(i % 1000 + 1)))
    return write_sharded(os.path.join(workdir, "imagenet"), records, n_shards)


def batch_stream(paths: list[str], batch: int, seed: int = 1,
                 n_threads: int = None, depth: int = 8):
    """shards -> threaded crop/flip -> uint8 NHWC batches, prefetched.

    The crop/flip/pack hot loop runs in the native C++ batcher
    (csrc bt_crop_flip_pack: std::thread + memcpy, the role the
    reference's MTLabeledBGRImgToBatch threads play) with a Python
    thread-pool fallback; the Prefetcher overlaps the whole host stage
    with device steps."""
    from concurrent.futures import ThreadPoolExecutor

    from bigdl_tpu.dataset.seqfile import read_shard
    from bigdl_tpu.dataset.transformer import Prefetcher

    if n_threads is None:
        n_threads = max(4, (os.cpu_count() or 8) // 2)
    rng = np.random.RandomState(seed)
    try:
        from bigdl_tpu import native
        lib = native.get()  # None -> python fallback; symbol set verified
    except Exception:       # at load time by _set_prototypes
        lib = None

    def decode_one(args):
        data, label, cy, cx, flip = args
        img = np.frombuffer(data, np.uint8).reshape(STORED, STORED, 3)
        img = img[cy:cy + CROP, cx:cx + CROP]
        if flip:
            img = img[:, ::-1]
        return img, label

    def emit(buf_args, pool):
        y = np.asarray([a[1] for a in buf_args], np.float32)
        if lib is not None:
            x = lib.crop_flip_pack(
                [a[0] for a in buf_args], STORED, STORED, CROP,
                [a[2] for a in buf_args], [a[3] for a in buf_args],
                [a[4] for a in buf_args], n_threads)
            return x, y
        out = list(pool.map(decode_one, buf_args, chunksize=8))
        return np.stack([o[0] for o in out]), y

    def raw_batches():
        pool = (None if lib is not None else
                ThreadPoolExecutor(max_workers=n_threads,
                                   thread_name_prefix="decode"))
        try:
            while True:  # infinite epochs, reshuffled shard order
                order = rng.permutation(len(paths))
                buf_args = []
                for si in order:
                    for rec in read_shard(paths[si]):
                        span = STORED - CROP
                        buf_args.append((rec.data, rec.label,
                                         rng.randint(0, span + 1),
                                         rng.randint(0, span + 1),
                                         bool(rng.randint(2))))
                        if len(buf_args) == batch:
                            yield emit(buf_args, pool)
                            buf_args = []
        finally:
            if pool is not None:
                pool.shutdown(wait=False)

    return Prefetcher(depth)(raw_batches())


def _train_pieces(batch: int):
    import functools

    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.nn._util import cast_f32_leaves
    from bigdl_tpu.optim import SGD

    model = ResNet(class_num=1000, depth=50, dataset="imagenet",
                   data_format="NHWC").build(seed=1)
    criterion = nn.ClassNLLCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9, dampening=0.0)
    params, buffers = model.params, model.buffers
    opt_state = method.init_state(params)

    mean = jnp.asarray(MEAN, jnp.bfloat16)
    std = jnp.asarray(STD, jnp.bfloat16)

    def loss_fn(params_f32, buffers, x_u8, y, rng):
        p16 = cast_f32_leaves(params_f32, jnp.bfloat16)
        x = (x_u8.astype(jnp.bfloat16) - mean) / std  # device-side normalize
        out, nb = model.apply(p16, x, buffers=buffers, training=True, rng=rng)
        return criterion.loss(out.astype(jnp.float32), y), nb

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, buffers, opt_state, x, y, rng):
        (loss, nb), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, buffers, x, y, rng)
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        new_params, new_opt = method.update(grads, opt_state, params)
        return new_params, nb, new_opt, loss

    return step, params, buffers, opt_state


def run(batch: int, iters: int, warmup: int, workdir: str,
        n_records: int) -> dict:
    import jax

    rng = jax.random.PRNGKey(0)
    step, params, buffers, opt_state = _train_pieces(batch)

    # -- synthetic, device-resident ------------------------------------- #
    x_syn = jax.numpy.asarray(
        np.random.RandomState(0).randint(0, 256,
                                         size=(batch, CROP, CROP, 3),
                                         dtype=np.uint8))
    y_syn = jax.numpy.asarray(
        np.random.RandomState(1).randint(1, 1001, size=batch)
        .astype(np.float32))
    for _ in range(warmup):
        params, buffers, opt_state, loss = step(params, buffers, opt_state,
                                                x_syn, y_syn, rng)
    _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, buffers, opt_state, loss = step(params, buffers, opt_state,
                                                x_syn, y_syn, rng)
    _ = float(loss)
    dt_syn = time.perf_counter() - t0
    syn_ips = batch * iters / dt_syn

    # -- pipeline-fed ---------------------------------------------------- #
    paths = generate_shards(workdir, n_records)
    stream = batch_stream(paths, batch)
    for _ in range(warmup):
        x, y = next(stream)
        params, buffers, opt_state, loss = step(params, buffers, opt_state,
                                                jax.numpy.asarray(x),
                                                jax.numpy.asarray(y), rng)
    _ = float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        x, y = next(stream)
        params, buffers, opt_state, loss = step(params, buffers, opt_state,
                                                jax.numpy.asarray(x),
                                                jax.numpy.asarray(y), rng)
    _ = float(loss)
    dt_pipe = time.perf_counter() - t0
    pipe_ips = batch * iters / dt_pipe

    return {
        "metric": "resnet50_pipeline_fed_vs_synthetic",
        "batch": batch, "iterations": iters,
        "synthetic_img_s": round(syn_ips, 2),
        "pipeline_img_s": round(pipe_ips, 2),
        "ratio": round(pipe_ips / syn_ips, 4),
        "stored_records": n_records,
        "unit": "images/sec (single chip)",
    }


def run_host_only(batch: int, iters: int, warmup: int, workdir: str,
                  n_records: int) -> dict:
    """Raw host-side delivery rate: shards -> native crop/flip/pack ->
    Prefetcher, NO device step.  This half of the feed-the-chip proof is
    chip-independent — the number to beat is the device's consumption
    rate (2103.66 img/s/chip measured in round 1), and the headroom
    ratio says whether the host or the chip is the binding constraint.
    Each batch is touched via a strided sample sum (every 32nd pixel
    row/col, ~0.2ms/batch) — enough to force a lazy reader to actually
    produce the array without charging a full 38M-element reduction to
    the delivery rate.  The native batcher materializes eagerly anyway;
    the touch guards against future reader changes."""
    paths = generate_shards(workdir, n_records)
    stream = batch_stream(paths, batch)
    sink = 0
    for _ in range(warmup):
        x, y = next(stream)
        sink += int(x[:, ::32, ::32].sum()) + int(y.sum())
    t0 = time.perf_counter()
    for _ in range(iters):
        x, y = next(stream)
        sink += int(x[:, ::32, ::32].sum()) + int(y.sum())
    dt = time.perf_counter() - t0
    ips = batch * iters / dt
    from bigdl_tpu import native
    return {
        "metric": "input_pipeline_host_delivery_images_per_sec",
        "value": round(ips, 2),
        "unit": "images/sec (host only, no device step)",
        "batch": batch, "iterations": iters, "stored_records": n_records,
        "native_batcher": native.get() is not None,
        "checksum": sink % 1000,
    }


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--records", type=int, default=2048)
    p.add_argument("--host-only", action="store_true",
                   help="measure only the host delivery rate (no device "
                        "step; runs with a wedged or absent accelerator)")
    p.add_argument("--workdir", default=None,
                   help="shard directory (default: fresh temp dir, removed "
                        "afterwards)")
    p.add_argument("--json", default=None)
    args = p.parse_args(argv)

    from bigdl_tpu.utils.engine import Engine

    Engine.init()  # the platform is JAX_PLATFORMS', like the sibling benches

    workdir = args.workdir or tempfile.mkdtemp(prefix="bigdl_tpu_pipebench_")
    cleanup = args.workdir is None
    try:
        if args.host_only:
            result = run_host_only(args.batch, args.iters, args.warmup,
                                   workdir, args.records)
        else:
            result = run(args.batch, args.iters, args.warmup, workdir,
                         args.records)
    finally:
        if cleanup:
            shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(result))
    if args.json:
        from bigdl_tpu.utils import fs
        fs.atomic_write(args.json, (json.dumps(result, indent=2) + "\n")
                        .encode())


if __name__ == "__main__":
    main()
