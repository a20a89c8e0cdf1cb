"""Whole-model import parity oracles (ModelValidator equivalent).

The reference validates imported pretrained nets end-to-end
(example/loadmodel/ModelValidator.scala runs AlexNet/Inception/ResNet
through the Torch and Caffe loaders and checks predictions;
models/AlexNetSpec.scala asserts whole-net output parity against the
source framework).  This environment has no network egress, so instead
of downloading torchvision/BVLC weights the SOURCE FRAMEWORK runs
live: full torch twins of our model factories are built
layer-for-layer, their (seeded, torch-default-initialized) weights are
imported through each loader path, and whole-net predictions must
agree — the same mechanism as ModelValidator, with torch as the
resident oracle instead of a downloaded artifact.

Three import paths are oracled at the whole-net level:
  1. load_torch_state_dict  (PyTorch state dict -> our model)
  2. load_torch_checkpoint  (torch.save file -> our model)
  3. Module.load_caffe      (synthesized caffemodel carrying the SAME
                             torch weights -> our model)
"""
import numpy as np
import pytest
import torch

import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.models.alexnet import AlexNet
from bigdl_tpu.models.resnet import ResNet
from bigdl_tpu.utils.torch_import import (export_torch_state_dict,
                                          group_state_dict,
                                          load_torch_state_dict)

# whole-net fp32 tolerance: hundreds of accumulated convs/GEMMs diverge
# in the last couple of mantissa bits; top-1 agreement is the product
# claim and is asserted exactly
TOL = dict(rtol=1e-3, atol=1e-3)


def _predict_ours(model, x_np):
    y, _ = model.apply(model.params, jnp.asarray(x_np),
                       buffers=model.buffers, training=False)
    return np.asarray(y)


def _assert_prediction_parity(ours_logp, torch_logp):
    np.testing.assert_allclose(ours_logp, torch_logp, **TOL)
    assert (ours_logp.argmax(-1) == torch_logp.argmax(-1)).all()


# --------------------------------------------------------------------- #
# AlexNet: the two-group Caffe variant (ref AlexNet.scala twin)         #
# --------------------------------------------------------------------- #
def _torch_alexnet(n_classes: int) -> torch.nn.Sequential:
    return torch.nn.Sequential(
        torch.nn.Conv2d(3, 96, 11, 4),
        torch.nn.ReLU(),
        torch.nn.LocalResponseNorm(5, alpha=0.0001, beta=0.75, k=1.0),
        torch.nn.MaxPool2d(3, 2),
        torch.nn.Conv2d(96, 256, 5, padding=2, groups=2),
        torch.nn.ReLU(),
        torch.nn.LocalResponseNorm(5, alpha=0.0001, beta=0.75, k=1.0),
        torch.nn.MaxPool2d(3, 2),
        torch.nn.Conv2d(256, 384, 3, padding=1),
        torch.nn.ReLU(),
        torch.nn.Conv2d(384, 384, 3, padding=1, groups=2),
        torch.nn.ReLU(),
        torch.nn.Conv2d(384, 256, 3, padding=1, groups=2),
        torch.nn.ReLU(),
        torch.nn.MaxPool2d(3, 2),
        torch.nn.Flatten(),
        torch.nn.Linear(256 * 6 * 6, 4096),
        torch.nn.ReLU(),
        torch.nn.Dropout(),
        torch.nn.Linear(4096, 4096),
        torch.nn.ReLU(),
        torch.nn.Dropout(),
        torch.nn.Linear(4096, n_classes),
        torch.nn.LogSoftmax(dim=-1),
    )


@pytest.fixture(scope="module")
def alexnet_pair():
    torch.manual_seed(7)
    twin = _torch_alexnet(10).eval()
    model = AlexNet(10).build(0)
    load_torch_state_dict(model, twin.state_dict())
    x = np.random.RandomState(3).randn(2, 3, 227, 227).astype(np.float32) * 0.1
    with torch.no_grad():
        ref = twin(torch.from_numpy(x)).numpy()
    return model, twin, x, ref


def test_alexnet_state_dict_import_parity(alexnet_pair):
    model, _, x, ref = alexnet_pair
    _assert_prediction_parity(_predict_ours(model, x), ref)


def test_alexnet_checkpoint_file_import(alexnet_pair, tmp_path):
    _, twin, x, ref = alexnet_pair
    path = tmp_path / "alexnet.pth"
    torch.save({"state_dict": twin.state_dict()}, path)
    model = AlexNet(10).build(1)
    model.load_pytorch(str(path))  # Module-level convenience entry
    _assert_prediction_parity(_predict_ours(model, x), ref)


def test_alexnet_caffe_import_parity(alexnet_pair, tmp_path):
    """Config #3 of BASELINE.json (Caffe model import -> TPU) at the
    whole-net level: a caffemodel binary carrying the torch twin's
    weights loads through CaffeLoader and reproduces its predictions."""
    from test_caffe_loader import _blob, _layer_v2
    _, twin, x, ref = alexnet_pair
    sd = twin.state_dict()
    layers = b""
    names = ["conv1", "conv2", "conv3", "conv4", "conv5", "fc6", "fc7", "fc8"]
    prefixes = ["0", "4", "8", "10", "12", "16", "19", "22"]
    for name, pre in zip(names, prefixes):
        w = sd[f"{pre}.weight"].numpy()
        b = sd[f"{pre}.bias"].numpy()
        kind = "InnerProduct" if name.startswith("fc") else "Convolution"
        layers += _layer_v2(name, kind,
                            [_blob(w.shape, w.ravel()),
                             _blob(b.shape, b.ravel())])
    model_path = tmp_path / "alexnet.caffemodel"
    model_path.write_bytes(layers)
    def_path = tmp_path / "deploy.prototxt"
    def_path.write_text('name: "alexnet"\n')

    model = AlexNet(10).build(2)
    model.load_caffe(str(def_path), str(model_path), match_all=False)
    _assert_prediction_parity(_predict_ours(model, x), ref)


# --------------------------------------------------------------------- #
# ResNet: torch twin of our factory (ConcatTable main-then-shortcut     #
# order = torchvision's conv1..bn2-then-downsample state-dict order)    #
# --------------------------------------------------------------------- #
class _TorchBasicBlock(torch.nn.Module):
    def __init__(self, n_in, n_out, stride):
        super().__init__()
        self.conv1 = torch.nn.Conv2d(n_in, n_out, 3, stride, 1, bias=True)
        self.bn1 = torch.nn.BatchNorm2d(n_out)
        self.conv2 = torch.nn.Conv2d(n_out, n_out, 3, 1, 1, bias=True)
        self.bn2 = torch.nn.BatchNorm2d(n_out)
        self.downsample = None
        if n_in != n_out:  # shortcut type B
            self.downsample = torch.nn.Sequential(
                torch.nn.Conv2d(n_in, n_out, 1, stride, bias=True),
                torch.nn.BatchNorm2d(n_out))

    def forward(self, x):
        y = self.bn2(self.conv2(torch.relu(self.bn1(self.conv1(x)))))
        s = x if self.downsample is None else self.downsample(x)
        return torch.relu(y + s)


class _TorchBottleneck(torch.nn.Module):
    def __init__(self, n_in, n_mid, stride):
        super().__init__()
        n_out = n_mid * 4
        self.conv1 = torch.nn.Conv2d(n_in, n_mid, 1, bias=True)
        self.bn1 = torch.nn.BatchNorm2d(n_mid)
        self.conv2 = torch.nn.Conv2d(n_mid, n_mid, 3, stride, 1, bias=True)
        self.bn2 = torch.nn.BatchNorm2d(n_mid)
        self.conv3 = torch.nn.Conv2d(n_mid, n_out, 1, bias=True)
        self.bn3 = torch.nn.BatchNorm2d(n_out)
        self.downsample = None
        if n_in != n_out:
            self.downsample = torch.nn.Sequential(
                torch.nn.Conv2d(n_in, n_out, 1, stride, bias=True),
                torch.nn.BatchNorm2d(n_out))

    def forward(self, x):
        y = torch.relu(self.bn1(self.conv1(x)))
        y = torch.relu(self.bn2(self.conv2(y)))
        y = self.bn3(self.conv3(y))
        s = x if self.downsample is None else self.downsample(x)
        return torch.relu(y + s)


def _torch_resnet(depth: int, n_classes: int) -> torch.nn.Sequential:
    cfgs = {18: ([2, 2, 2, 2], 512, _TorchBasicBlock),
            50: ([3, 4, 6, 3], 2048, _TorchBottleneck)}
    blocks, n_features, block = cfgs[depth]
    layers = [torch.nn.Conv2d(3, 64, 7, 2, 3, bias=True),
              torch.nn.BatchNorm2d(64),
              torch.nn.ReLU(),
              torch.nn.MaxPool2d(3, 2, padding=1)]
    widths = [64, 128, 256, 512]
    n_in = 64
    for i, (n_blocks, width) in enumerate(zip(blocks, widths)):
        for j in range(n_blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            layers.append(block(n_in, width, stride))
            n_in = width * 4 if block is _TorchBottleneck else width
    layers += [torch.nn.AvgPool2d(7),
               torch.nn.Flatten(),
               torch.nn.Linear(n_features, n_classes),
               torch.nn.LogSoftmax(dim=-1)]
    return torch.nn.Sequential(*layers)


def _resnet_parity(depth):
    torch.manual_seed(depth)
    twin = _torch_resnet(depth, 10)
    # warm the BN running statistics so the buffer import is load-bearing
    twin.train()
    with torch.no_grad():
        for i in range(2):
            twin(torch.from_numpy(
                np.random.RandomState(20 + i).randn(4, 3, 224, 224)
                .astype(np.float32)))
    twin.eval()

    model = ResNet(class_num=10, depth=depth, shortcut_type="B",
                   dataset="imagenet").build(0)
    load_torch_state_dict(model, twin.state_dict())

    x = np.random.RandomState(9).randn(2, 3, 224, 224).astype(np.float32)
    with torch.no_grad():
        ref = twin(torch.from_numpy(x)).numpy()
    _assert_prediction_parity(_predict_ours(model, x), ref)


def test_resnet18_state_dict_import_parity():
    _resnet_parity(18)


@pytest.mark.slow
def test_resnet50_state_dict_import_parity():
    _resnet_parity(50)


# --------------------------------------------------------------------- #
# importer contract                                                     #
# --------------------------------------------------------------------- #
def test_group_state_dict_orders_and_groups():
    sd = {"a.weight": np.ones(2), "a.bias": np.zeros(2),
          "b.bn.running_mean": np.zeros(3), "b.bn.weight": np.ones(3),
          "b.bn.num_batches_tracked": np.array(5)}
    groups = group_state_dict(sd)
    assert [g[0] for g in groups] == ["a", "b.bn"]
    assert sorted(groups[1][1]) == ["running_mean", "weight"]


def test_count_mismatch_raises():
    model = nn.Sequential(nn.Linear(3, 4), nn.Linear(4, 2)).build(0)
    sd = {"0.weight": np.zeros((4, 3), np.float32)}
    with pytest.raises(ValueError, match="count mismatch"):
        load_torch_state_dict(model, sd)


def test_shape_mismatch_raises():
    model = nn.Sequential(nn.Linear(3, 4)).build(0)
    sd = {"fc.weight": np.zeros((5, 3), np.float32),
          "fc.bias": np.zeros(5, np.float32)}
    with pytest.raises(ValueError, match="shape"):
        load_torch_state_dict(model, sd)


def test_export_state_dict_roundtrip_to_torch():
    """Reverse direction: OUR trained weights load into the torch twin
    and reproduce our predictions (the export half of the interop
    story; same mechanism as the reference's saveTorch)."""
    torch.manual_seed(11)
    model = nn.Sequential(
        nn.SpatialConvolution(1, 4, 3, 3),
        nn.ReLU(),
        nn.SpatialBatchNormalization(4),
        nn.View(4 * 6 * 6),
        nn.Linear(4 * 6 * 6, 5),
        nn.LogSoftMax()).build(3)
    sd = export_torch_state_dict(model)
    twin = torch.nn.Sequential(
        torch.nn.Conv2d(1, 4, 3), torch.nn.ReLU(), torch.nn.BatchNorm2d(4),
        torch.nn.Flatten(), torch.nn.Linear(4 * 6 * 6, 5),
        torch.nn.LogSoftmax(dim=-1))
    # rename positional keys onto the twin's own names, order-aligned
    twin_keys = [k for k in twin.state_dict() if "num_batches" not in k]
    assert len(twin_keys) == len(sd)
    mapped = {tk: torch.from_numpy(v.copy())
              for tk, v in zip(twin_keys, sd.values())}
    twin.load_state_dict(mapped, strict=False)
    twin.eval()
    x = np.random.RandomState(2).randn(3, 1, 8, 8).astype(np.float32)
    ours = _predict_ours(model, x)
    with torch.no_grad():
        ref = twin(torch.from_numpy(x)).numpy()
    np.testing.assert_allclose(ours, ref, rtol=1e-4, atol=1e-5)


def test_export_roundtrip_nested_leaf_params():
    """Scale holds nested {cmul, cadd} param dicts: export and the
    positional loader must agree on the grouping."""
    m1 = nn.Sequential(nn.Linear(3, 4), nn.Scale((4,))).build(0)
    sd = export_torch_state_dict(m1)
    assert "1.cmul.weight" in sd and "1.cadd.bias" in sd
    m2 = nn.Sequential(nn.Linear(3, 4), nn.Scale((4,))).build(9)
    load_torch_state_dict(m2, sd)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 3).astype(np.float32))
    y1, _ = m1.apply(m1.params, x, training=False)
    y2, _ = m2.apply(m2.params, x, training=False)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))


def test_export_key_order_survives_tree_map():
    """jax pytree ops return dicts with ALPHABETICAL keys (bias before
    weight); export must emit definition order regardless, or a
    positional rename onto a torch twin swaps weight and bias."""
    import jax
    model = nn.Sequential(nn.Linear(3, 4)).build(0)
    model.params = jax.tree_util.tree_map(lambda w: w * 1.0, model.params)
    assert list(model.params["0"]) == ["bias", "weight"]  # the hazard
    assert list(export_torch_state_dict(model)) == ["0.weight", "0.bias"]


def test_export_unbuilt_model_raises():
    with pytest.raises(ValueError, match="no params to export"):
        export_torch_state_dict(nn.Sequential(nn.Linear(3, 4)))


def test_non_strict_partial_import():
    model = nn.Sequential(nn.Linear(3, 4), nn.Linear(4, 2)).build(0)
    w = np.arange(12, dtype=np.float32).reshape(4, 3)
    sd = {"fc1.weight": w, "fc1.bias": np.zeros(4, np.float32)}
    load_torch_state_dict(model, sd, strict=False)
    np.testing.assert_array_equal(np.asarray(model.params["0"]["weight"]), w)


@pytest.mark.parametrize("seed", range(6))
def test_export_import_roundtrip_random_compositions(seed):
    """Composition fuzzer for the positional walk: randomly nested
    containers (Sequential depth, ConcatTable+JoinTable branches,
    parameterized and param-free layers interleaved) must round-trip
    export -> load with bit-exact predictions."""
    r = np.random.RandomState(100 + seed)

    def random_tail(dim, depth):
        mods = []
        for _ in range(r.randint(1, 4)):
            kind = r.randint(0, 4)
            if kind == 0:
                out = int(r.randint(2, 7))
                mods.append(nn.Linear(dim, out))
                dim = out
            elif kind == 1:
                mods.append(nn.Tanh())
            elif kind == 2:
                mods.append(nn.BatchNormalization(dim))
            elif kind == 3 and depth > 0:
                out = int(r.randint(2, 7))
                branch1, d1 = random_tail(dim, depth - 1)
                branch2 = nn.Linear(dim, d1)  # align widths for join
                mods.append(nn.Sequential(
                    nn.ConcatTable(nn.Sequential(*branch1), branch2),
                    nn.JoinTable(2)))
                dim = 2 * d1
        return mods, dim

    mods, out_dim = random_tail(5, 2)
    model = nn.Sequential(*mods).build(seed)
    from bigdl_tpu.utils.torch_import import export_torch_state_dict
    sd = export_torch_state_dict(model)
    # a structurally identical fresh model: rebuild from the same recipe
    r = np.random.RandomState(100 + seed)
    mods2, _ = random_tail(5, 2)
    clone = nn.Sequential(*mods2).build(seed + 999)
    load_torch_state_dict(clone, sd)
    x = jnp.asarray(np.random.RandomState(7).randn(3, 5).astype(np.float32))
    y1, _ = model.apply(model.params, x, buffers=model.buffers, training=False)
    y2, _ = clone.apply(clone.params, x, buffers=clone.buffers, training=False)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))


def test_device_array_is_a_plain_put():
    """Imported weights go to the device in one put, values and dtype
    intact (the 32 MB slicing for the old backend is gone)."""
    import jax
    from bigdl_tpu.utils.torch_import import device_array
    a = np.arange(7 * 5, dtype=np.float32).reshape(7, 5)
    out = device_array(a)
    assert isinstance(out, jax.Array) and out.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(out), a)
    half = device_array(a, np.float16)
    assert half.dtype == np.float16
    scalar = device_array(np.float32(3.0))
    assert float(scalar) == 3.0


# --------------------------------------------------------------------- #
# Inception-v1 (config #4's family) + the NHWC interchange claim        #
# --------------------------------------------------------------------- #
class _TorchInceptionModule(torch.nn.Module):
    """Branch order mirrors our Concat child order (definition order =
    state-dict order = the positional walk's pairing order)."""

    def __init__(self, n_in, cfg):
        super().__init__()
        (c1,), (c3r, c3), (c5r, c5), (cp,) = cfg
        S, C, R = torch.nn.Sequential, torch.nn.Conv2d, torch.nn.ReLU
        self.b1 = S(C(n_in, c1, 1), R())
        self.b2 = S(C(n_in, c3r, 1), R(), C(c3r, c3, 3, padding=1), R())
        self.b3 = S(C(n_in, c5r, 1), R(), C(c5r, c5, 5, padding=2), R())
        self.b4 = S(torch.nn.MaxPool2d(3, 1, 1, ceil_mode=True),
                    C(n_in, cp, 1), R())

    def forward(self, x):
        return torch.cat([self.b1(x), self.b2(x), self.b3(x), self.b4(x)], 1)


def _torch_inception_v1(n_classes):
    S = torch.nn.Sequential
    mods = [torch.nn.Conv2d(3, 64, 7, 2, 3), torch.nn.ReLU(),
            torch.nn.MaxPool2d(3, 2, ceil_mode=True),
            torch.nn.LocalResponseNorm(5, alpha=0.0001, beta=0.75, k=1.0),
            torch.nn.Conv2d(64, 64, 1), torch.nn.ReLU(),
            torch.nn.Conv2d(64, 192, 3, padding=1), torch.nn.ReLU(),
            torch.nn.LocalResponseNorm(5, alpha=0.0001, beta=0.75, k=1.0),
            torch.nn.MaxPool2d(3, 2, ceil_mode=True),
            _TorchInceptionModule(192, ((64,), (96, 128), (16, 32), (32,))),
            _TorchInceptionModule(256, ((128,), (128, 192), (32, 96), (64,))),
            torch.nn.MaxPool2d(3, 2, ceil_mode=True),
            _TorchInceptionModule(480, ((192,), (96, 208), (16, 48), (64,))),
            _TorchInceptionModule(512, ((160,), (112, 224), (24, 64), (64,))),
            _TorchInceptionModule(512, ((128,), (128, 256), (24, 64), (64,))),
            _TorchInceptionModule(512, ((112,), (144, 288), (32, 64), (64,))),
            _TorchInceptionModule(528, ((256,), (160, 320), (32, 128), (128,))),
            torch.nn.MaxPool2d(3, 2, ceil_mode=True),
            _TorchInceptionModule(832, ((256,), (160, 320), (32, 128), (128,))),
            _TorchInceptionModule(832, ((384,), (192, 384), (48, 128), (128,))),
            torch.nn.AvgPool2d(7),
            torch.nn.Dropout(0.4),
            torch.nn.Flatten(),
            torch.nn.Linear(1024, n_classes),
            torch.nn.LogSoftmax(dim=-1)]
    return S(*mods)


@pytest.mark.slow
def test_inception_v1_state_dict_import_parity():
    """ModelValidator parity for the GoogLeNet family (BASELINE config
    #4): 57 conv/linear leaves across 9 four-branch Concat modules."""
    from bigdl_tpu.models.inception import Inception_v1
    torch.manual_seed(15)
    twin = _torch_inception_v1(10).eval()
    model = Inception_v1(10).build(0)
    load_torch_state_dict(model, twin.state_dict())
    x = np.random.RandomState(4).randn(2, 3, 224, 224).astype(np.float32) * 0.1
    with torch.no_grad():
        ref = twin(torch.from_numpy(x)).numpy()
    _assert_prediction_parity(_predict_ours(model, x), ref)


def test_resnet18_nhwc_import_same_checkpoint():
    """The NHWC (TPU-fast) variant keeps an identical param tree, so
    the SAME torch checkpoint imports into it and predicts identically
    (modulo the input layout transpose) — the interchange claim in
    models/resnet's docstring."""
    torch.manual_seed(18)
    twin = _torch_resnet(18, 10).eval()
    model = ResNet(class_num=10, depth=18, shortcut_type="B",
                   dataset="imagenet", data_format="NHWC").build(0)
    load_torch_state_dict(model, twin.state_dict())
    x = np.random.RandomState(12).randn(2, 3, 224, 224).astype(np.float32)
    with torch.no_grad():
        ref = twin(torch.from_numpy(x)).numpy()
    ours = _predict_ours(model, x.transpose(0, 2, 3, 1))  # NHWC input
    _assert_prediction_parity(ours, ref)


# --------------------------------------------------------------------- #
# LeNet-5 (config #1) and VggForCifar10 (config #2) twins — with these,
# every BASELINE.json config family has a whole-net import oracle
# --------------------------------------------------------------------- #
def test_lenet5_state_dict_import_parity():
    from bigdl_tpu.models.lenet import LeNet5
    torch.manual_seed(22)
    twin = torch.nn.Sequential(
        torch.nn.Conv2d(1, 6, 5), torch.nn.Tanh(),
        torch.nn.MaxPool2d(2, 2),
        torch.nn.Conv2d(6, 12, 5), torch.nn.Tanh(),
        torch.nn.MaxPool2d(2, 2),
        torch.nn.Flatten(),
        torch.nn.Linear(12 * 4 * 4, 100), torch.nn.Tanh(),
        torch.nn.Linear(100, 10),
        torch.nn.LogSoftmax(dim=-1)).eval()
    model = LeNet5(10).build(0)
    load_torch_state_dict(model, twin.state_dict())
    x = np.random.RandomState(1).randn(4, 1, 28, 28).astype(np.float32)
    with torch.no_grad():
        ref = twin(torch.from_numpy(x)).numpy()
    # our LeNet5 reshapes (B,1,28,28) itself from flat input
    _assert_prediction_parity(_predict_ours(model, x.reshape(4, -1)), ref)


def test_vgg_cifar_state_dict_import_parity():
    from bigdl_tpu.models.vgg import VggForCifar10
    torch.manual_seed(23)
    cfg = [(3, 64), (64, 64), "M", (64, 128), (128, 128), "M",
           (128, 256), (256, 256), (256, 256), "M",
           (256, 512), (512, 512), (512, 512), "M",
           (512, 512), (512, 512), (512, 512), "M"]
    mods = []
    for item in cfg:
        if item == "M":
            mods.append(torch.nn.MaxPool2d(2, 2, ceil_mode=True))
        else:
            n_in, n_out = item
            mods += [torch.nn.Conv2d(n_in, n_out, 3, padding=1),
                     torch.nn.BatchNorm2d(n_out, eps=1e-3),
                     torch.nn.ReLU()]
    mods += [torch.nn.Flatten(), torch.nn.Dropout(0.5),
             torch.nn.Linear(512, 512), torch.nn.BatchNorm1d(512),
             torch.nn.ReLU(), torch.nn.Dropout(0.5),
             torch.nn.Linear(512, 10), torch.nn.LogSoftmax(dim=-1)]
    twin = torch.nn.Sequential(*mods)
    # warm BN running stats so the buffer import is load-bearing
    twin.train()
    with torch.no_grad():
        for i in range(2):
            twin(torch.from_numpy(
                np.random.RandomState(30 + i).randn(8, 3, 32, 32)
                .astype(np.float32)))
    twin.eval()
    model = VggForCifar10(10).build(0)
    load_torch_state_dict(model, twin.state_dict())
    x = np.random.RandomState(2).randn(2, 3, 32, 32).astype(np.float32)
    with torch.no_grad():
        ref = twin(torch.from_numpy(x)).numpy()
    _assert_prediction_parity(_predict_ours(model, x), ref)


# --------------------------------------------------------------------- #
# recurrent import (config #5's family): torch nn.LSTM/GRU modules map
# onto our fused-gate cells (transpose + bias merge)
# --------------------------------------------------------------------- #
def test_recurrent_lstm_import_parity():
    torch.manual_seed(31)

    class Twin(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.lstm = torch.nn.LSTM(5, 7, batch_first=True)
            self.fc = torch.nn.Linear(7, 3)

        def forward(self, x):
            y, _ = self.lstm(x)
            return torch.log_softmax(self.fc(y[:, -1]), dim=-1)

    twin = Twin().eval()
    model = nn.Sequential(
        nn.Recurrent(nn.LSTM(5, 7)),
        nn.Select(2, -1),        # last timestep
        nn.Linear(7, 3),
        nn.LogSoftMax()).build(0)
    load_torch_state_dict(model, twin.state_dict())
    x = np.random.RandomState(3).randn(4, 6, 5).astype(np.float32)
    with torch.no_grad():
        ref = twin(torch.from_numpy(x)).numpy()
    _assert_prediction_parity(_predict_ours(model, x), ref)


def test_recurrent_gru_import_parity_and_nonzero_bias_hh_rejected():
    torch.manual_seed(32)
    layer = torch.nn.GRU(4, 6, batch_first=True)
    with torch.no_grad():
        layer.bias_hh_l0[2 * 6:].zero_()  # representable case
    model = nn.Sequential(nn.Recurrent(nn.GRU(4, 6))).build(0)
    load_torch_state_dict(model, {k: v for k, v in layer.state_dict().items()})
    x = np.random.RandomState(5).randn(2, 5, 4).astype(np.float32)
    with torch.no_grad():
        ref, _ = layer(torch.from_numpy(x))
    y, _ = model.apply(model.params, jnp.asarray(x), training=False)
    np.testing.assert_allclose(np.asarray(y), ref.numpy(),
                               rtol=1e-4, atol=1e-5)
    # a nonzero n-gate bias_hh slice cannot map onto the fused layout
    torch.manual_seed(33)
    bad = torch.nn.GRU(4, 6, batch_first=True)
    m2 = nn.Sequential(nn.Recurrent(nn.GRU(4, 6))).build(0)
    with pytest.raises(ValueError, match="reset"):
        load_torch_state_dict(m2, {k: v for k, v in bad.state_dict().items()})


def test_recurrent_lstm_biasfree_import():
    """bias=False torch checkpoints map to an exact ZERO fused bias —
    the random-init bias must not survive the import."""
    torch.manual_seed(34)
    layer = torch.nn.LSTM(5, 7, batch_first=True, bias=False)
    model = nn.Sequential(nn.Recurrent(nn.LSTM(5, 7))).build(0)
    load_torch_state_dict(model, dict(layer.state_dict()))
    assert not np.any(np.asarray(model.params["0"]["cell"]["bias"]))
    x = np.random.RandomState(6).randn(2, 5, 5).astype(np.float32)
    with torch.no_grad():
        ref, _ = layer(torch.from_numpy(x))
    y, _ = model.apply(model.params, jnp.asarray(x), training=False)
    np.testing.assert_allclose(np.asarray(y), ref.numpy(),
                               rtol=1e-4, atol=1e-5)


def test_recurrent_multilayer_clear_error():
    torch.manual_seed(35)
    layer = torch.nn.GRU(4, 6, num_layers=2, batch_first=True)
    model = nn.Sequential(nn.Recurrent(nn.GRU(4, 6))).build(0)
    with pytest.raises(ValueError, match="layer-by-layer"):
        load_torch_state_dict(model, dict(layer.state_dict()),
                              strict=False)


def test_save_pytorch_roundtrip(tmp_path):
    """Module.save_pytorch writes a torch.load-able state dict that
    round-trips through load_pytorch with identical predictions."""
    model = nn.Sequential(nn.Linear(4, 6), nn.Tanh(),
                          nn.Linear(6, 2)).build(5)
    p = tmp_path / "model.pth"
    model.save_pytorch(str(p))
    clone = nn.Sequential(nn.Linear(4, 6), nn.Tanh(),
                          nn.Linear(6, 2)).build(8)
    clone.load_pytorch(p)
    x = jnp.asarray(np.random.RandomState(0).randn(3, 4).astype(np.float32))
    y1, _ = model.apply(model.params, x, training=False)
    y2, _ = clone.apply(clone.params, x, training=False)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
    # and torch itself can read it
    sd = torch.load(str(p), weights_only=True)
    assert sorted(sd) == ["0.bias", "0.weight", "2.bias", "2.weight"]
