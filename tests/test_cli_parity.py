"""Train+Test CLI parity for rnn/autoencoder/textclassifier (the
reference ships both mains per model family, e.g. models/rnn/Test.scala)
and the Hadoop SequenceFile reader for the reference's ImageNet layout
(dataset/DataSet.scala:380-433, image/BGRImgToLocalSeqFile.scala)."""
import io
import struct

import numpy as np
import pytest


class TestModelTestClis:
    def test_rnn_train_then_test(self, tmp_path, capsys):
        from bigdl_tpu.models.rnn import test as rnn_test
        from bigdl_tpu.models.rnn import train as rnn_train

        model_dir = tmp_path / "ckpt"
        model_dir.mkdir()
        rnn_train.main(["--synthetic", "-e", "1", "-b", "8",
                        "--hiddenSize", "8", "--seqLength", "8",
                        "--checkpoint", str(model_dir)])
        ckpts = sorted(model_dir.glob("model.*"),
                       key=lambda p: int(p.name.split(".")[-1]))
        assert ckpts, "train CLI must write a checkpoint"
        dict_path = model_dir / "dictionary.json"
        assert dict_path.exists(), "train CLI must save the dictionary"
        rnn_test.main(["--model", str(ckpts[-1]), "--synthetic",
                       "--dictionary", str(dict_path),
                       "-b", "8", "--seqLength", "8"])
        assert "Loss" in capsys.readouterr().out

    def test_autoencoder_train_then_test(self, tmp_path, capsys):
        from bigdl_tpu.models.autoencoder import test as ae_test
        from bigdl_tpu.models.autoencoder import train as ae_train

        model_dir = tmp_path / "ckpt"
        model_dir.mkdir()
        ae_train.main(["--synthetic", "-e", "1", "-b", "64",
                       "--checkpoint", str(model_dir)])
        ckpts = sorted(model_dir.glob("model.*"),
                       key=lambda p: int(p.name.split(".")[-1]))
        assert ckpts
        ae_test.main(["--model", str(ckpts[-1]), "--synthetic", "-b", "64"])
        assert "Loss" in capsys.readouterr().out

    def test_textclassifier_train_then_test(self, tmp_path, capsys):
        from bigdl_tpu import nn
        from bigdl_tpu.models.textclassifier import TextClassifier
        from bigdl_tpu.models.textclassifier import test as tc_test

        # train CLI has no checkpoint flag in the reference either — the
        # test CLI evaluates a saved model; save a fresh one
        model = TextClassifier(5, 16, 50).build(seed=0)
        path = str(tmp_path / "tc.bin")
        model.save(path, overwrite=True)
        tc_test.main(["--model", path, "--synthetic", "-b", "32",
                      "--seqLength", "50", "--embedDim", "16",
                      "--classNum", "5"])
        assert "Top1Accuracy" in capsys.readouterr().out


def _hand_encoded_seqfile(records, sync=b"0123456789abcdef"):
    """Byte-level SequenceFile encoder written independently of the
    production writer (both must agree with Hadoop's format)."""
    def vint(n):
        assert 0 <= n <= 127
        return struct.pack("b", n)

    out = io.BytesIO()
    out.write(b"SEQ\x06")
    for cls in (b"org.apache.hadoop.io.Text",) * 2:
        out.write(vint(len(cls)))
        out.write(cls)
    out.write(b"\x00\x00")
    out.write(struct.pack(">i", 0))
    out.write(sync)
    for i, (key, value) in enumerate(records):
        if i == 2:  # exercise the sync-escape path
            out.write(struct.pack(">i", -1))
            out.write(sync)
        kser = vint(len(key)) + key
        vser = vint(len(value)) + value
        out.write(struct.pack(">i", len(kser) + len(vser)))
        out.write(struct.pack(">i", len(kser)))
        out.write(kser)
        out.write(vser)
    return out.getvalue()


class TestHadoopSeqFile:
    def _bgr_value(self, w, h, seed):
        rng = np.random.RandomState(seed)
        pixels = rng.randint(0, 256, size=(h, w, 3), dtype=np.uint8)
        return struct.pack(">ii", w, h) + pixels.tobytes(), pixels

    def test_reads_hand_encoded_fixture(self, tmp_path):
        from bigdl_tpu.dataset.hadoop_seqfile import (decode_bgr_value,
                                                      parse_key,
                                                      read_sequence_file)

        vals = [self._bgr_value(4, 3, i) for i in range(4)]
        records = [(str(i % 2 + 1).encode(), v[0]) for i, v in enumerate(vals)]
        p = tmp_path / "fixture_0.seq"
        p.write_bytes(_hand_encoded_seqfile(records))
        got = list(read_sequence_file(str(p)))
        assert len(got) == 4
        for (key, value), (want_v, want_px), i in zip(got, vals, range(4)):
            name, label = parse_key(key)
            assert name is None and label == float(i % 2 + 1)
            img = decode_bgr_value(value)
            assert img.shape == (3, 3, 4)
            np.testing.assert_array_equal(
                img.transpose(1, 2, 0).astype(np.uint8), want_px)

    def test_name_label_key(self):
        from bigdl_tpu.dataset.hadoop_seqfile import parse_key
        assert parse_key(b"42") == (None, 42.0)
        assert parse_key(b"n01440764_10026.JPEG\n7") == \
            ("n01440764_10026.JPEG", 7.0)

    def test_writer_reader_roundtrip_with_sync(self, tmp_path):
        from bigdl_tpu.dataset.hadoop_seqfile import (read_sequence_file,
                                                      write_sequence_file)

        records = [(f"{i}".encode(), bytes([i]) * (i + 1))
                   for i in range(10)]
        p = str(tmp_path / "rt_0.seq")
        write_sequence_file(p, records, sync_interval=3)
        assert list(read_sequence_file(p)) == records

    def test_folder_records_to_training_pipeline(self, tmp_path):
        """The migration path end-to-end: reference-layout seq files ->
        records -> decode -> batches -> one training step."""
        from bigdl_tpu import nn
        from bigdl_tpu.dataset import DataSet, image
        from bigdl_tpu.dataset.hadoop_seqfile import (SeqBytesToBGRImg,
                                                      SeqFileFolder,
                                                      write_sequence_file)
        from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger

        records = []
        for i in range(16):
            v, _ = self._bgr_value(8, 8, i)
            records.append((str(i % 2 + 1).encode(), v))
        write_sequence_file(str(tmp_path / "imagenet_0.seq"), records[:8])
        write_sequence_file(str(tmp_path / "imagenet_1.seq"), records[8:])

        recs = SeqFileFolder.records(str(tmp_path))
        assert len(recs) == 16
        ds = DataSet.array(recs) >> (
            SeqBytesToBGRImg()
            >> image.BGRImgNormalizer((128.0,) * 3, (64.0,) * 3)
            >> image.BGRImgToBatch(8))
        m = nn.Sequential(nn.Reshape((8 * 8 * 3,)), nn.Linear(8 * 8 * 3, 2),
                          nn.LogSoftMax())
        opt = LocalOptimizer(m, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learning_rate=0.01)) \
           .set_end_when(Trigger.max_iteration(2))
        opt.optimize()
        assert np.isfinite(opt.state["loss"])

    def test_write_bgr_images_matches_reference_layout(self, tmp_path):
        from bigdl_tpu.dataset.hadoop_seqfile import (SeqFileFolder,
                                                      decode_bgr_value,
                                                      parse_key,
                                                      read_sequence_file)
        from bigdl_tpu.dataset.types import LabeledImage

        rng = np.random.RandomState(0)
        imgs = [LabeledImage(rng.randint(0, 255, size=(3, 5, 7))
                             .astype(np.float32), float(i + 1))
                for i in range(5)]
        paths = SeqFileFolder.write_bgr_images(
            imgs, str(tmp_path / "im"), block_size=2)
        assert len(paths) == 3  # 2+2+1
        seen = []
        for p in paths:
            for key, value in read_sequence_file(p):
                _, label = parse_key(key)
                seen.append((label, decode_bgr_value(value)))
        assert [s[0] for s in seen] == [1.0, 2.0, 3.0, 4.0, 5.0]
        np.testing.assert_array_equal(seen[0][1], imgs[0].data)


class TestNativeHadoopIndexer:
    def test_native_matches_python_reader(self, tmp_path):
        from bigdl_tpu import native
        from bigdl_tpu.dataset.hadoop_seqfile import (parse_key,
                                                      read_sequence_file,
                                                      write_sequence_file)

        lib = native.get()
        if lib is None:
            pytest.skip("native library unavailable")
        records = [(b"3", b"abc"), (b"name.JPEG\n7", b"0123456789" * 50),
                   (b"1", b""), (b"2", bytes(range(100)))]
        p = str(tmp_path / "n_0.seq")
        write_sequence_file(p, records, sync_interval=2)
        buf = open(p, "rb").read()
        offsets, lengths, labels = lib.hadoop_seq_index(buf)
        got = [(buf[o:o + n], float(l))
               for o, n, l in zip(offsets, lengths, labels)]
        want = [(v, parse_key(k)[1]) for k, v in read_sequence_file(p)]
        assert got == want
        assert [l for _, l in got] == [3.0, 7.0, 1.0, 2.0]

    def test_native_rejects_malformed(self):
        from bigdl_tpu import native

        lib = native.get()
        if lib is None:
            pytest.skip("native library unavailable")
        with pytest.raises(ValueError):
            lib.hadoop_seq_index(b"NOTASEQFILE")
        with pytest.raises(NotImplementedError):
            # version 5 header flavor
            lib.hadoop_seq_index(b"SEQ\x05" + b"\x00" * 64)

    def test_native_rejects_non_numeric_label(self, tmp_path):
        from bigdl_tpu import native
        from bigdl_tpu.dataset.hadoop_seqfile import write_sequence_file

        lib = native.get()
        if lib is None:
            pytest.skip("native library unavailable")
        p = str(tmp_path / "bad_0.seq")
        write_sequence_file(p, [(b"not-a-number", b"payload")])
        with pytest.raises(ValueError, match="non-numeric label"):
            lib.hadoop_seq_index(open(p, "rb").read())

    def test_folder_records_uses_same_results_either_path(self, tmp_path,
                                                          monkeypatch):
        from bigdl_tpu.dataset import hadoop_seqfile as hs

        records = [(str(i % 3 + 1).encode(), bytes([i]) * 8)
                   for i in range(9)]
        hs.write_sequence_file(str(tmp_path / "x_0.seq"), records)
        fast = hs.SeqFileFolder.records(str(tmp_path))
        monkeypatch.setenv("BIGDL_TPU_NO_NATIVE", "1")
        # force the pure-python branch by nulling the native lib handle
        import bigdl_tpu.native as native_mod
        monkeypatch.setattr(native_mod.lib, "_dll", None)
        monkeypatch.setattr(native_mod.lib, "_tried", True)
        slow = hs.SeqFileFolder.records(str(tmp_path))
        assert [(r.data, r.label) for r in fast] == \
            [(r.data, r.label) for r in slow]


class TestCompressedSeqFile:
    """Record/block-compressed SequenceFile flavors (round-3 interop: real
    Hadoop ImageNet dumps are often compressed with the default codec)."""

    def _hand_encoded_record_compressed(self, records,
                                        sync=b"fedcba9876543210"):
        import zlib

        def vint(n):
            assert 0 <= n <= 127
            return struct.pack("b", n)

        out = io.BytesIO()
        out.write(b"SEQ\x06")
        for cls in (b"org.apache.hadoop.io.Text",) * 2:
            out.write(vint(len(cls)))
            out.write(cls)
        out.write(b"\x01\x00")  # record-compressed
        codec = b"org.apache.hadoop.io.compress.DefaultCodec"
        out.write(vint(len(codec)))
        out.write(codec)
        out.write(struct.pack(">i", 0))
        out.write(sync)
        for key, value in records:
            kser = vint(len(key)) + key
            vser = zlib.compress(vint(len(value)) + value)
            out.write(struct.pack(">i", len(kser) + len(vser)))
            out.write(struct.pack(">i", len(kser)))
            out.write(kser)
            out.write(vser)
        return out.getvalue()

    def test_reads_hand_encoded_record_compressed(self, tmp_path):
        from bigdl_tpu.dataset.hadoop_seqfile import read_sequence_file
        records = [(f"{i}".encode(), bytes([65 + i]) * (20 + i))
                   for i in range(5)]
        p = tmp_path / "rc_0.seq"
        p.write_bytes(self._hand_encoded_record_compressed(records))
        assert list(read_sequence_file(str(p))) == records

    def test_record_compressed_roundtrip(self, tmp_path):
        from bigdl_tpu.dataset.hadoop_seqfile import (read_sequence_file,
                                                      write_sequence_file)
        records = [(f"k{i}".encode(), np.random.RandomState(i).bytes(200))
                   for i in range(7)]
        p = str(tmp_path / "rc_1.seq")
        write_sequence_file(p, records, sync_interval=3, compression="record")
        assert list(read_sequence_file(p)) == records

    def test_block_compressed_roundtrip(self, tmp_path):
        from bigdl_tpu.dataset.hadoop_seqfile import (read_sequence_file,
                                                      write_sequence_file)
        records = [(f"key-{i}".encode(), np.random.RandomState(i).bytes(150))
                   for i in range(11)]
        p = str(tmp_path / "bc_0.seq")
        write_sequence_file(p, records, sync_interval=4, compression="block")
        assert list(read_sequence_file(p)) == records

    def test_unknown_codec_fails_loudly(self, tmp_path):
        import pytest

        def vint(n):
            return struct.pack("b", n)

        out = io.BytesIO()
        out.write(b"SEQ\x06")
        for cls in (b"org.apache.hadoop.io.Text",) * 2:
            out.write(vint(len(cls)))
            out.write(cls)
        out.write(b"\x01\x00")
        codec = b"com.example.SnappyCodec"
        out.write(vint(len(codec)))
        out.write(codec)
        out.write(struct.pack(">i", 0))
        out.write(b"0" * 16)
        p = tmp_path / "bad_0.seq"
        p.write_bytes(out.getvalue())
        from bigdl_tpu.dataset.hadoop_seqfile import read_sequence_file
        with pytest.raises(ValueError, match="SnappyCodec"):
            list(read_sequence_file(str(p)))

    def test_folder_records_handles_compressed(self, tmp_path):
        """SeqFileFolder.records must fall back from the native indexer to
        the python reader for compressed files."""
        from bigdl_tpu.dataset.hadoop_seqfile import (SeqFileFolder,
                                                      encode_bgr_image,
                                                      write_sequence_file)
        from bigdl_tpu.dataset.image import LabeledImage
        rng = np.random.RandomState(0)
        imgs = [LabeledImage(rng.rand(3, 4, 4).astype(np.float32) * 255,
                             float(i + 1)) for i in range(4)]
        records = [(str(int(im.label)).encode(), encode_bgr_image(im.data))
                   for im in imgs]
        write_sequence_file(str(tmp_path / "part_0.seq"), records,
                            compression="record")
        got = SeqFileFolder.records(str(tmp_path))
        assert [r.label for r in got] == [1.0, 2.0, 3.0, 4.0]


class TestSeqFolderTraining:
    def test_inception_style_training_from_seq_folder(self, tmp_path):
        """The reference's primary ImageNet path end-to-end: Hadoop .seq
        folder -> record_files dispatch -> SeqBytesToBGRImg decode ->
        crop/flip/normalize -> a few training iterations (tiny model
        stand-in; the CLI wires the same pieces)."""
        from bigdl_tpu import nn
        from bigdl_tpu.dataset import DataSet, image
        from bigdl_tpu.dataset.hadoop_seqfile import (SeqBytesToBGRImg,
                                                      encode_bgr_image,
                                                      write_sequence_file)
        from bigdl_tpu.dataset.image import LabeledImage
        from bigdl_tpu.optim import SGD, LocalOptimizer, Trigger

        rng = np.random.RandomState(0)
        records = []
        for i in range(16):
            img = LabeledImage(
                rng.rand(3, 10, 10).astype(np.float32) * 255,
                float(i % 2 + 1))
            records.append((str(int(img.label)).encode(),
                            encode_bgr_image(img.data)))
        write_sequence_file(str(tmp_path / "train_0.seq"), records,
                            compression="record")

        ds = DataSet.record_files([str(tmp_path / "train_0.seq")])
        pipe = (SeqBytesToBGRImg()
                >> image.BGRImgCropper(8, 8)
                >> image.BGRImgNormalizer((104.0, 117.0, 123.0),
                                          (1.0, 1.0, 1.0))
                >> image.BGRImgToBatch(8))
        model = nn.Sequential(
            nn.Reshape((3 * 8 * 8,)), nn.Linear(3 * 8 * 8, 2),
            nn.LogSoftMax()).build(seed=1)
        opt = LocalOptimizer(model, ds >> pipe, nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learning_rate=0.01)) \
           .set_end_when(Trigger.max_iteration(3))
        opt.optimize()
        assert np.isfinite(opt.state["loss"])

    def test_mixed_native_and_seq_folder(self, tmp_path):
        """A folder mixing the repo's shard flavor (encoded-image records)
        with reference .seq shards (raw framed pixels) must decode
        per-record through AnyBytesToBGRImg."""
        import io as _io

        from PIL import Image

        from bigdl_tpu.dataset import DataSet, image
        from bigdl_tpu.dataset.hadoop_seqfile import (AnyBytesToBGRImg,
                                                      encode_bgr_image,
                                                      write_sequence_file)
        from bigdl_tpu.dataset.seqfile import write_shard
        from bigdl_tpu.dataset.types import ByteRecord

        rng = np.random.RandomState(0)
        # native shard: PNG-encoded records
        png_records = []
        for i in range(3):
            arr = rng.randint(0, 256, size=(10, 10, 3), dtype=np.uint8)
            buf = _io.BytesIO()
            Image.fromarray(arr).save(buf, format="PNG")
            png_records.append(ByteRecord(buf.getvalue(), float(i + 1)))
        write_shard(str(tmp_path / "train_a.shard"), png_records)
        # reference shard: framed raw BGR
        seq_records = [(b"1", encode_bgr_image(
            rng.rand(3, 10, 10).astype(np.float32) * 255)) for _ in range(3)]
        write_sequence_file(str(tmp_path / "train_b.seq"), seq_records)

        ds = DataSet.record_files([str(tmp_path / "train_a.shard"),
                                   str(tmp_path / "train_b.seq")])
        pipe = AnyBytesToBGRImg() >> image.BGRImgCropper(8, 8)
        imgs = list(pipe(ds.data(train=False)))
        assert len(imgs) == 6
        for im in imgs:
            assert im.data.shape == (3, 8, 8)
            assert np.isfinite(im.data).all()


class TestResnetCli:
    def test_cifar_synthetic_one_iteration(self, tmp_path, monkeypatch):
        """The resnet CLI end-to-end incl. the EpochSchedule multiplier
        regimes (regression: float regimes crashed at the first LR
        computation and no test drove this CLI)."""
        from bigdl_tpu.models.resnet import train as cli

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        # tiny run: trim the synthetic dataset so one epoch is 2 batches
        from bigdl_tpu.dataset import cifar
        real_synth = cifar.synthetic
        monkeypatch.setattr(cifar, "synthetic",
                            lambda n, seed=1: real_synth(min(n, 64), seed=seed))
        cli.main(["--synthetic", "-b", "32", "-e", "1", "--depth", "8"])

    @pytest.mark.slow
    def test_imagenet_seq_folder_one_iteration(self, tmp_path, monkeypatch):
        """ResNet ImageNet mode reads the reference .seq layout (bench
        config #3's training path)."""
        from bigdl_tpu.dataset.hadoop_seqfile import (encode_bgr_image,
                                                      write_sequence_file)
        from bigdl_tpu.models.resnet import train as cli

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        rng = np.random.RandomState(0)
        records = [(str(i % 4 + 1).encode(),
                    encode_bgr_image((rng.rand(3, 256, 256) * 255)
                                     .astype(np.float32)))
                   for i in range(4)]
        write_sequence_file(str(tmp_path / "train_0.seq"), records)
        write_sequence_file(str(tmp_path / "val_0.seq"), records[:2])
        cli.main(["--dataset", "imagenet", "-f", str(tmp_path),
                  "--depth", "18", "--classNumber", "4", "-b", "2",
                  "-e", "1"])


class TestSeqFileRobustness:
    def test_reader_rejects_corrupt_bytes(self, tmp_path):
        """Corrupted SequenceFiles raise ValueError-class errors, never
        hang or crash (same contract as the t7 reader).  Mutated buffers
        parse in memory via read_sequence_file(data=...)."""
        import zlib

        from bigdl_tpu.dataset.hadoop_seqfile import (read_sequence_file,
                                                      write_sequence_file)
        from tests.conftest import corrupt_variants

        p = str(tmp_path / "good.seq")
        records = [(f"{i}".encode(), bytes([i]) * 50) for i in range(8)]
        write_sequence_file(p, records, sync_interval=3,
                            compression="record")
        good = open(p, "rb").read()
        detected = 0
        for trial, data in corrupt_variants(good, 30, seed=1):
            try:
                list(read_sequence_file("<fuzz>", data=data))
            except (ValueError, EOFError, IndexError, struct.error,
                    MemoryError, OSError, zlib.error):
                detected += 1
        assert detected >= 8
