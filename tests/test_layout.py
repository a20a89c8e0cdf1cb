"""NHWC/NCHW layout parity: the channels-last fast path must compute the
same function as the Torch-parity NCHW path (weights are OIHW in both, so
the same param pytree drives both layouts)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.models import ResNet


def to_nhwc(x):
    return jnp.transpose(x, (0, 2, 3, 1))


def to_nchw(x):
    return jnp.transpose(x, (0, 3, 1, 2))


@pytest.mark.parametrize("stride,pad,group", [(1, 1, 1), (2, 3, 1), (1, 0, 2)])
def test_conv_layout_parity(nprng, stride, pad, group):
    x = jnp.asarray(nprng.randn(2, 4, 11, 9).astype(np.float32))
    m_nchw = nn.SpatialConvolution(4, 8, 3, 3, stride, stride, pad, pad,
                                   n_group=group).build(seed=3)
    m_nhwc = nn.SpatialConvolution(4, 8, 3, 3, stride, stride, pad, pad,
                                   n_group=group, data_format="NHWC")
    y_ref = m_nchw.forward(x)
    y_fast = m_nhwc.f(m_nchw.params, to_nhwc(x))
    np.testing.assert_allclose(np.asarray(to_nchw(y_fast)), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_dilated_conv_layout_parity(nprng):
    x = jnp.asarray(nprng.randn(2, 3, 12, 12).astype(np.float32))
    m_nchw = nn.SpatialDilatedConvolution(3, 5, 3, 3, 1, 1, 2, 2,
                                          dilation_w=2, dilation_h=2).build(seed=0)
    m_nhwc = nn.SpatialDilatedConvolution(3, 5, 3, 3, 1, 1, 2, 2,
                                          dilation_w=2, dilation_h=2,
                                          data_format="NHWC")
    y_ref = m_nchw.forward(x)
    y_fast = m_nhwc.f(m_nchw.params, to_nhwc(x))
    np.testing.assert_allclose(np.asarray(to_nchw(y_fast)), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ceil_mode", [False, True])
def test_maxpool_layout_parity(nprng, ceil_mode):
    x = jnp.asarray(nprng.randn(2, 3, 11, 13).astype(np.float32))
    m_nchw = nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1)
    m_nhwc = nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1, data_format="NHWC")
    if ceil_mode:
        m_nchw.ceil()
        m_nhwc.ceil()
    y_ref = m_nchw.f({}, x)
    y_fast = m_nhwc.f({}, to_nhwc(x))
    np.testing.assert_allclose(np.asarray(to_nchw(y_fast)), np.asarray(y_ref))


def test_avgpool_layout_parity(nprng):
    x = jnp.asarray(nprng.randn(2, 3, 8, 8).astype(np.float32))
    m_nchw = nn.SpatialAveragePooling(2, 2, 2, 2)
    m_nhwc = nn.SpatialAveragePooling(2, 2, 2, 2, data_format="NHWC")
    y_ref = m_nchw.f({}, x)
    y_fast = m_nhwc.f({}, to_nhwc(x))
    np.testing.assert_allclose(np.asarray(to_nchw(y_fast)), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6)


def test_batchnorm_layout_parity(nprng):
    x = jnp.asarray(nprng.randn(4, 6, 5, 5).astype(np.float32))
    m_nchw = nn.SpatialBatchNormalization(6).build(seed=7)
    m_nhwc = nn.SpatialBatchNormalization(6, data_format="NHWC")
    y_ref, buf_ref = m_nchw.apply(m_nchw.params, x,
                                  buffers=m_nchw.init_buffers(), training=True)
    y_fast, buf_fast = m_nhwc.apply(m_nchw.params, to_nhwc(x),
                                    buffers=m_nhwc.init_buffers(), training=True)
    np.testing.assert_allclose(np.asarray(to_nchw(y_fast)), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    for k in buf_ref:
        np.testing.assert_allclose(np.asarray(buf_fast[k]), np.asarray(buf_ref[k]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_resnet_layout_parity_forward_and_grad(nprng):
    """Same params, same input -> same logits and same param gradients in
    both layouts (the NHWC model takes NHWC input)."""
    m_ref = ResNet(class_num=10, depth=8, dataset="cifar10").build(seed=11)
    m_fast = ResNet(class_num=10, depth=8, dataset="cifar10",
                    data_format="NHWC")
    x = jnp.asarray(nprng.randn(4, 3, 32, 32).astype(np.float32))
    y = jnp.asarray((nprng.randint(0, 10, 4) + 1).astype(np.float32))
    crit = nn.ClassNLLCriterion()

    def loss_ref(p):
        out, _ = m_ref.apply(p, x, buffers=m_ref.buffers, training=False)
        return crit.loss(out, y)

    def loss_fast(p):
        out, _ = m_fast.apply(p, to_nhwc(x), buffers=m_ref.buffers,
                              training=False)
        return crit.loss(out, y)

    l_ref, g_ref = jax.value_and_grad(loss_ref)(m_ref.params)
    l_fast, g_fast = jax.value_and_grad(loss_fast)(m_ref.params)
    np.testing.assert_allclose(float(l_fast), float(l_ref), rtol=1e-5)
    flat_ref = jax.tree_util.tree_leaves(g_ref)
    flat_fast = jax.tree_util.tree_leaves(g_fast)
    assert len(flat_ref) == len(flat_fast)
    for a, b in zip(flat_ref, flat_fast):
        assert a.shape == b.shape  # identical pytree incl. OIHW weights
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_resnet_imagenet_nhwc_builds(nprng):
    m = ResNet(class_num=1000, depth=50, dataset="imagenet",
               data_format="NHWC").build(seed=1)
    x = jnp.asarray(nprng.randn(2, 17, 17, 3).astype(np.float32))
    # tiny spatial size still exercises the stem; avg-pool kernel needs 7x7
    # input so use the real 224 path only for shapes via eval_shape (no
    # compute): the driver bench runs the full-size step on hardware.
    full = jax.eval_shape(
        lambda p, xx: m.apply(p, xx, buffers=m.buffers, training=False)[0],
        m.params, jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32))
    assert full.shape == (2, 1000)


@pytest.mark.slow
def test_vgg_cifar_layout_parity(nprng):
    from bigdl_tpu.models import VggForCifar10
    m_ref = VggForCifar10(10).build(seed=5)
    m_fast = VggForCifar10(10, data_format="NHWC")
    x = jnp.asarray(nprng.randn(2, 3, 32, 32).astype(np.float32))
    y_ref, _ = m_ref.apply(m_ref.params, x, buffers=m_ref.buffers, training=False)
    y_fast, _ = m_fast.apply(m_ref.params, to_nhwc(x), buffers=m_ref.buffers,
                             training=False)
    np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_vgg16_imagenet_layout_pytree_and_shape(nprng):
    from bigdl_tpu.models import Vgg_16
    m_ref = Vgg_16(1000)
    m_fast = Vgg_16(1000, data_format="NHWC")
    p_ref = jax.eval_shape(lambda: m_ref.init(jax.random.PRNGKey(0)))
    p_fast = jax.eval_shape(lambda: m_fast.init(jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(p_ref) == jax.tree_util.tree_structure(p_fast)
    out = jax.eval_shape(
        lambda p, xx: m_fast.apply(p, xx, buffers=m_fast.init_buffers(),
                                   training=False)[0],
        p_fast, jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32))
    assert out.shape == (2, 1000)


def test_inception_module_layout_parity(nprng):
    from bigdl_tpu.models.inception import _inception_v1_module
    m_ref = _inception_v1_module(16, ((4,), (4, 8), (2, 4), (4,))).build(seed=2)
    m_fast = _inception_v1_module(16, ((4,), (4, 8), (2, 4), (4,)), "NHWC")
    x = jnp.asarray(nprng.randn(2, 16, 9, 9).astype(np.float32))
    y_ref, _ = m_ref.apply(m_ref.params, x, buffers=m_ref.buffers, training=False)
    y_fast, _ = m_fast.apply(m_ref.params, to_nhwc(x), buffers=m_ref.buffers,
                             training=False)
    np.testing.assert_allclose(np.asarray(to_nchw(y_fast)), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_lrn_layout_parity(nprng):
    x = jnp.asarray(nprng.randn(2, 8, 6, 6).astype(np.float32))
    m_ref = nn.SpatialCrossMapLRN(5, 0.0001, 0.75)
    m_fast = nn.SpatialCrossMapLRN(5, 0.0001, 0.75, data_format="NHWC")
    y_ref = m_ref.f({}, x)
    y_fast = m_fast.f({}, to_nhwc(x))
    np.testing.assert_allclose(np.asarray(to_nchw(y_fast)), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_inception_v1_nhwc_builds():
    from bigdl_tpu.models import Inception_v1
    m = Inception_v1(1000, data_format="NHWC")
    p = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
    out = jax.eval_shape(
        lambda pp, xx: m.apply(pp, xx, buffers=m.init_buffers(),
                               training=False)[0],
        p, jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32))
    assert out.shape == (2, 1000)


@pytest.mark.slow
def test_bench_recipe_lock_tpu_hlo():
    """Recipe lock for the flagship bench step (MFU work, VERDICT r3 #3):
    the TPU-lowered StableHLO of the ResNet-50 NHWC bf16 train step must
    keep every convolution's inputs in bf16 (MXU operands) and contain
    NO rank-4 activation transposes (layout churn around convs is the
    classic NCHW tax the bf16/NHWC recipe exists to avoid; the only
    transposes allowed are 2-D weight transposes from the classifier
    head's matmul grad).  Runs the real TPU lowering via jax.export on
    the CPU host — no chip needed, so the recipe cannot silently rot
    between hardware windows."""
    import re

    from jax import export as jax_export

    from bigdl_tpu.models import ResNet
    from bigdl_tpu.nn._util import cast_f32_leaves
    from bigdl_tpu.optim import SGD

    model = ResNet(class_num=1000, depth=50, dataset="imagenet",
                   data_format="NHWC").build(seed=1)
    crit = nn.ClassNLLCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9, dampening=0.0)
    params, buffers = model.params, model.buffers
    opt = method.init_state(params)

    def step(params, buffers, opt_state, x, y, rng):
        def loss_fn(p, b):
            out, nb = model.apply(cast_f32_leaves(p, jnp.bfloat16), x,
                                  buffers=b, training=True, rng=rng)
            return crit.loss(out.astype(jnp.float32), y), nb
        (loss, nb), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, buffers)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)
        new_params, new_opt = method.update(grads, opt_state, params)
        return new_params, nb, new_opt, loss

    sds = lambda a: jax.ShapeDtypeStruct(jnp.asarray(a).shape,  # noqa: E731
                                         jnp.asarray(a).dtype)
    jtu = jax.tree_util
    exp = jax_export.export(jax.jit(step), platforms=["tpu"])(
        jtu.tree_map(sds, params), jtu.tree_map(sds, buffers),
        jtu.tree_map(sds, opt),
        jax.ShapeDtypeStruct((32, 224, 224, 3), jnp.bfloat16),
        jax.ShapeDtypeStruct((32,), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    text = exp.mlir_module()

    conv_lines = [l for l in text.splitlines()
                  if "stablehlo.convolution" in l]
    assert len(conv_lines) > 100  # fwd + dgrad/wgrad of 53 convs
    f32_convs = [l for l in conv_lines
                 if "xf32>" in l.split("->")[0]]
    assert not f32_convs, (
        f"{len(f32_convs)} convolution(s) take f32 operands - the bf16 "
        f"MXU recipe regressed: {f32_convs[0][:200]}")

    rank4_transposes = []
    for l in text.splitlines():
        if "stablehlo.transpose" not in l:
            continue
        m = re.search(r"tensor<([0-9x]+)x(?:bf16|f32)>", l)
        if m and m.group(1).count("x") >= 3:
            rank4_transposes.append(l)
    assert not rank4_transposes, (
        f"{len(rank4_transposes)} rank-4 transpose(s) in the lowered "
        f"step - activation relayout crept back in: "
        f"{rank4_transposes[0][:200]}")
