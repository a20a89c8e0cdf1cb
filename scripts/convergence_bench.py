"""Convergence proof on the current platform.

Trains two flagship configurations END TO END on the current platform
and records their trajectories, the analog of the reference's
"Train with MSE ... should be good" convergence specs
(optim/DistriOptimizerSpec.scala:130-141) run on the real target
hardware:

  * LeNet-5 on held-out synthetic MNIST to >=98% top-1 — real MNIST
    needs network egress this sandbox doesn't have, so the learnable
    synthetic task (dataset/mnist.synthetic: class-keyed blobs + noise,
    DIFFERENT seed for the validation split) stands in; the claim
    proven is the full train->generalize cycle on the chip, not the
    dataset's provenance.
  * VGG on synthetic CIFAR for a short run — the loss trajectory must
    fall to <=0.7x its first epoch.

Measurement-protocol invariants (utils/artifacts.py): the artifact rewrites
atomically after EVERY epoch with ``complete: false`` until the final
flush; rows resume across runs keyed on platform + full config,
backed by the real checkpoint/resume cycle (each epoch runs a fresh
Optimizer restored from the newest model/state pair, so a run
cut at its time limit loses at most one epoch — and the elastic-resume path
gets exercised once per epoch as a side effect).

When a committed CPU reference artifact exists (--cpu-ref, default
CONVERGENCE_CPU.json committed from the rehearsal), the TPU run records
per-epoch loss deltas against it — the numerics-parity comparison.

    python scripts/convergence_bench.py --json CONVERGENCE_r05.json
    JAX_PLATFORMS=cpu python scripts/convergence_bench.py \
        --json CONVERGENCE_CPU.json   # rehearsal / reference trajectory
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_parser():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--json", default="CONVERGENCE_r05.json")
    p.add_argument("--workdir", default=".convergence_work")
    p.add_argument("--cpu-ref", default="CONVERGENCE_CPU.json")
    p.add_argument("--lenet-epochs", type=int, default=8)
    p.add_argument("--lenet-records", type=int, default=4096)
    p.add_argument("--lenet-target", type=float, default=0.98)
    p.add_argument("--vgg-epochs", type=int, default=2)
    p.add_argument("--vgg-records", type=int, default=2048)
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--fresh", action="store_true",
                   help="discard checkpoints/rows and start over")
    return p


def _stage_config(args, stage):
    if stage == "lenet":
        return {"stage": "lenet", "records": args.lenet_records,
                "epochs": args.lenet_epochs, "batch": args.batch,
                "target": args.lenet_target, "jitter": 3}
    return {"stage": "vgg", "records": args.vgg_records,
            "epochs": args.vgg_epochs, "batch": min(args.batch, 64)}


def _build_stage(stage, cfg):
    """(model_factory, criterion, train_ds, val_ds, lr) for a stage."""
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet, image, cifar, mnist

    if stage == "lenet":
        from bigdl_tpu.models.lenet import LeNet5
        jit = cfg.get("jitter", 0)
        train_records = mnist.synthetic(cfg["records"], jitter=jit)
        val_records = mnist.synthetic(max(cfg["records"] // 4, 256), seed=9,
                                      jitter=jit)
        pipeline = (image.BytesToGreyImg(28, 28)
                    >> image.GreyImgNormalizer(60.0, 80.0)
                    >> image.GreyImgToBatch(cfg["batch"]))
        # momentum matters: plain SGD plateaus ~81% on the jittered task
        factory = lambda: LeNet5(10).build(seed=1)
        lr, momentum = 0.05, 0.9
    else:
        from bigdl_tpu.models.vgg import VggForCifar10
        train_records = cifar.synthetic(cfg["records"])
        val_records = cifar.synthetic(max(cfg["records"] // 4, 128), seed=9)
        pipeline = (image.BGRImgNormalizer(cifar.TRAIN_MEAN, cifar.TRAIN_STD)
                    >> image.BGRImgToBatch(cfg["batch"]))
        factory = lambda: VggForCifar10(10).build(seed=1)
        lr, momentum = 0.01, 0.0
    train_ds = DataSet.array(train_records) >> pipeline
    val_ds = DataSet.array(val_records) >> pipeline
    return factory, nn.ClassNLLCriterion(), train_ds, val_ds, lr, momentum


def _epoch_of_state(state_path):
    """Completed epochs recorded in a state.<n> snapshot (its schema:
    {driver_state: {epoch: next-epoch, ...}, optim_state, optim_method})."""
    from bigdl_tpu.utils import file_io
    try:
        snap = file_io.load(state_path)
        return int((snap.get("driver_state") or {}).get("epoch", 1)) - 1
    except Exception:
        return 0


def run_stage(args, stage, doc, platform):
    """Train one configuration epoch-by-epoch, appending a row per epoch
    to doc['sections'][stage] and rewriting the artifact each time."""
    from bigdl_tpu.optim import (Optimizer, SGD, Top1Accuracy, Trigger)
    from bigdl_tpu.optim.optimizer import LocalValidator
    from bigdl_tpu.models.utils import restore_optim_state
    from bigdl_tpu.utils import file_io
    from bigdl_tpu.utils.artifacts import write_artifact
    from bigdl_tpu import nn

    cfg = _stage_config(args, stage)
    section = doc["sections"].get(stage)
    if (section and section.get("config") == cfg
            and section.get("platform") == platform
            and section.get("done")):
        print(f"[{stage}] section complete, reusing", flush=True)
        return
    # checkpoint dir keyed on the full stage config: a run with changed
    # knobs (epochs/records/batch, smoke vs full) must never resume —
    # or let the reconstruct branch below fabricate an "epoch 1" row —
    # from a stale different-config checkpoint
    import hashlib
    cfg_tag = hashlib.sha1(
        json.dumps(cfg, sort_keys=True).encode()).hexdigest()[:10]
    ckpt_dir = os.path.join(args.workdir, f"{stage}-{platform}-{cfg_tag}")
    if args.fresh and os.path.isdir(ckpt_dir):
        shutil.rmtree(ckpt_dir)
    rows = []
    if (section and section.get("config") == cfg
            and section.get("platform") == platform):
        rows = list(section.get("rows", []))

    factory, criterion, train_ds, val_ds, lr, momentum = \
        _build_stage(stage, cfg)

    # resume: trust cached rows only as far as the checkpoints back them
    found = file_io.latest_checkpoint(ckpt_dir)
    done_epochs = _epoch_of_state(found[1]) if found else 0
    rows = [r for r in rows if r["epoch"] <= done_epochs]
    start_epoch = len(rows)
    if start_epoch != done_epochs:
        if found and done_epochs == start_epoch + 1:
            # the kill landed between the optimizer's epoch checkpoint
            # and the artifact write (a wide window: validation + jit run
            # after the flush).  The trained epoch is real — reconstruct
            # its row from the snapshot instead of discarding scarce
            # window training.  A checkpoint pair truncated by the same
            # kill is treated like a corrupt artifact: warn, wipe, and
            # retrain instead of crashing the whole round on an
            # unpicklable file
            try:
                model = nn.Module.load(found[0])
                _, res = LocalValidator(model, val_ds).test(
                    [Top1Accuracy()])[0]
                snap = file_io.load(found[1])
                loss = float(
                    (snap.get("driver_state") or {}).get("loss", 0.0))
            except Exception as e:
                print(f"[{stage}] checkpoint {found[0]} unreadable "
                      f"({type(e).__name__}: {e}) - discarding and "
                      "restarting the stage", flush=True)
                rows, start_epoch = [], 0
                shutil.rmtree(ckpt_dir, ignore_errors=True)
                found = None
            else:
                rows.append({"epoch": done_epochs,
                             "train_loss_last": round(loss, 6),
                             "val_top1": round(float(res.result()[0]), 6),
                             "seconds": None, "reconstructed": True})
                start_epoch = done_epochs
        else:
            # genuinely inconsistent (wiped workdir, older artifact):
            # the checkpoints are the training state — restart the rows
            rows, start_epoch = [], 0
            if found and done_epochs:
                shutil.rmtree(ckpt_dir)
                found = None

    section = {"config": cfg, "platform": platform, "done": False,
               "rows": rows}
    doc["sections"][stage] = section

    for epoch in range(start_epoch + 1, cfg["epochs"] + 1):
        t0 = time.time()
        found = file_io.latest_checkpoint(ckpt_dir)
        if found:
            model = nn.Module.load(found[0])
        else:
            model = factory()
        optimizer = Optimizer.create(model, train_ds, criterion)
        method = SGD(learning_rate=lr, momentum=momentum)
        if found:
            restore_optim_state(optimizer, method, found[1])
        optimizer.set_optim_method(method) \
                 .set_end_when(Trigger.max_epoch(epoch)) \
                 .set_checkpoint(ckpt_dir, Trigger.every_epoch())
        optimizer.optimize()
        loss = float(optimizer.state.get("loss"))
        _, res = LocalValidator(model, val_ds).test([Top1Accuracy()])[0]
        row = {"epoch": epoch, "train_loss_last": round(loss, 6),
               "val_top1": round(float(res.result()[0]), 6),
               "seconds": round(time.time() - t0, 2)}
        rows.append(row)
        print(f"[{stage}] {row}", flush=True)
        write_artifact(args.json, doc)

    final_acc = rows[-1]["val_top1"] if rows else 0.0
    section["final_val_top1"] = final_acc
    if stage == "lenet":
        section["target"] = cfg["target"]
        section["passed"] = final_acc >= cfg["target"]
    else:
        first, last = rows[0]["train_loss_last"], rows[-1]["train_loss_last"]
        section["passed"] = last <= 0.7 * first
        section["loss_first_last"] = [first, last]
    section["done"] = True
    write_artifact(args.json, doc)


def _cpu_parity(args, doc, platform):
    """Record per-epoch deltas vs the committed CPU reference artifact."""
    from bigdl_tpu.utils.artifacts import load_artifact
    if platform == "cpu":
        return
    ref = load_artifact(args.cpu_ref)
    if not ref:
        return
    parity = {}
    for stage, section in doc["sections"].items():
        ref_sec = (ref.get("sections") or {}).get(stage)
        if not ref_sec or ref_sec.get("config") != section.get("config"):
            continue
        pairs = list(zip(section.get("rows", []), ref_sec.get("rows", [])))
        if not pairs:
            continue
        parity[stage] = {
            "cpu_ref": args.cpu_ref,
            "max_abs_loss_delta": max(
                abs(a["train_loss_last"] - b["train_loss_last"])
                for a, b in pairs),
            "final_top1_delta": (section.get("final_val_top1", 0)
                                 - ref_sec.get("final_val_top1", 0)),
        }
    if parity:
        doc["cpu_parity"] = parity


def main(argv=None):
    args = build_parser().parse_args(argv)
    os.makedirs(args.workdir, exist_ok=True)

    from bigdl_tpu import Engine
    Engine.init()
    import jax
    platform = jax.devices()[0].platform

    from bigdl_tpu.utils.artifacts import load_artifact, write_artifact
    doc = load_artifact(args.json) if not args.fresh else None
    if not isinstance(doc, dict) or doc.get("tool") != "convergence_bench":
        doc = {"tool": "convergence_bench", "sections": {}}
    doc["platform"] = platform
    doc["complete"] = False

    for stage in ("lenet", "vgg"):
        run_stage(args, stage, doc, platform)

    _cpu_parity(args, doc, platform)
    sections = doc["sections"]
    doc["complete"] = all(s.get("done") for s in sections.values())
    write_artifact(args.json, doc)
    lenet = sections["lenet"]
    print(json.dumps({
        "metric": "convergence_lenet_val_top1",
        "value": lenet.get("final_val_top1"),
        "unit": "accuracy",
        "platform": platform,
        "passed": bool(lenet.get("passed"))
                  and bool(sections["vgg"].get("passed")),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
