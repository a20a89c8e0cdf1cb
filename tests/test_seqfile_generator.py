"""Record-shard generator CLI test (ref ImageNetSeqFileGenerator)."""
import os

import numpy as np
import pytest


@pytest.fixture
def image_tree(tmp_path):
    PIL = pytest.importorskip("PIL")
    from PIL import Image

    rng = np.random.RandomState(0)
    for split, n_per_class in (("train", 3), ("val", 2)):
        for cls in ["apple", "banana"]:
            d = tmp_path / split / cls
            d.mkdir(parents=True)
            for i in range(n_per_class):
                arr = rng.randint(0, 255, size=(8, 8, 3)).astype(np.uint8)
                Image.fromarray(arr).save(str(d / f"{i}.png"))
    return str(tmp_path)


def test_generate_and_roundtrip(image_tree, tmp_path_factory):
    from bigdl_tpu.dataset import DataSet, image
    from bigdl_tpu.models.utils.seqfile_generator import generate

    out = str(tmp_path_factory.mktemp("shards"))
    counts = generate(image_tree, out, parallel=2,
                      splits=["train", "val"], validate=True)
    assert counts == {"train": 6, "val": 4}
    shards = sorted(os.listdir(out))
    assert shards == ["train-00000", "train-00001", "val-00000", "val-00001"]

    # consume through the normal pipeline: shards -> decoded batches
    ds = DataSet.record_files([os.path.join(out, s) for s in shards
                               if s.startswith("train")])
    batches = list((ds >> (image.BytesToBGRImg()
                           >> image.BGRImgToBatch(3))).data(train=False))
    assert sum(b.size() for b in batches) == 6
    labels = sorted(float(l) for b in batches for l in b.labels)
    assert labels == [1.0, 1.0, 1.0, 2.0, 2.0, 2.0]  # 1-based by class


def test_cli_main(image_tree, tmp_path_factory, capsys):
    from bigdl_tpu.models.utils.seqfile_generator import main

    out = str(tmp_path_factory.mktemp("shards2"))
    main(["-f", image_tree, "-o", out, "-p", "1", "--splits", "val",
          "--validate"])
    assert "val: 4 records -> 1 shards" in capsys.readouterr().out


def test_pipeline_bench_stream_shapes(tmp_path):
    """The pipeline-fed bench's host path: shards -> threaded uint8
    crop/flip -> prefetched NHWC uint8 batches (device normalize is the
    step's job)."""
    import numpy as np

    import bigdl_tpu.models.utils.pipeline_bench as pb
    crop, stored = pb.CROP, pb.STORED
    pb.CROP, pb.STORED = 16, 24
    try:
        paths = pb.generate_shards(str(tmp_path), 32, n_shards=2)
        stream = pb.batch_stream(paths, 8)
        x, y = next(stream)
        assert x.shape == (8, 16, 16, 3) and x.dtype == np.uint8
        assert y.shape == (8,) and y.min() >= 1.0
        for _ in range(8):  # crosses an epoch boundary (32 records / 8)
            x, y = next(stream)
        assert x.shape == (8, 16, 16, 3)
    finally:
        pb.CROP, pb.STORED = crop, stored


def test_pipeline_bench_host_only_mode(tmp_path):
    """--host-only measures delivery with no device step (it must work
    with a wedged accelerator: no jax backend use anywhere on the path)
    and assumes no chip rate: the comparison belongs to whoever has
    measured one."""
    import bigdl_tpu.models.utils.pipeline_bench as pb
    crop, stored = pb.CROP, pb.STORED
    pb.CROP, pb.STORED = 16, 24
    try:
        r = pb.run_host_only(batch=8, iters=6, warmup=2,
                             workdir=str(tmp_path), n_records=32)
    finally:
        pb.CROP, pb.STORED = crop, stored
    assert r["value"] > 0
    assert r["metric"] == "input_pipeline_host_delivery_images_per_sec"
    assert not any("chip" in k for k in r)
    assert isinstance(r["native_batcher"], bool)
