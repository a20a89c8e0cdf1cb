"""Container semantics, OO shell (forward/backward/getParameters), gradient
checks (ref nn/ container specs + GradientChecker)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.utils.table import T, Table
from tests.gradcheck import check_gradient


class TestSequential:
    def test_forward_chain(self, rng):
        model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
        params = model.init(rng)
        x = jnp.ones((2, 4))
        y, _ = model.apply(params, x)
        assert y.shape == (2, 3)

    def test_oo_shell(self):
        model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 3)).build(seed=1)
        x = jnp.ones((2, 4))
        y = model.forward(x)
        assert y.shape == (2, 3)
        g = model.backward(x, jnp.ones_like(y))
        assert g.shape == x.shape
        w, grads = model.parameters()
        assert len(w) == 4 and len(grads) == 4

    def test_get_parameters_flatten(self):
        model = nn.Sequential(nn.Linear(4, 8), nn.Linear(8, 3)).build(seed=0)
        flat_w, flat_g, unravel = model.get_parameters()
        assert flat_w.shape == flat_g.shape == ((4 * 8 + 8) + (8 * 3 + 3),)
        p2 = unravel(flat_w)
        chex_equal = jax.tree_util.tree_all(
            jax.tree_util.tree_map(lambda a, b: jnp.allclose(a, b), p2, model.params))
        assert chex_equal


    def test_gradient_buffers_are_made_on_first_use(self, tmp_path):
        """build() and load() allocate no second copy of the weights (a
        served model never reads one); the OO readers see zeros before any
        backward, and backward accumulates as before."""
        model = nn.Sequential(nn.Linear(4, 3)).build(seed=0)
        assert model.grad_params is None
        model.save(str(tmp_path / "m"))
        assert nn.Module.load(str(tmp_path / "m")).grad_params is None
        _, flat_g, _ = model.get_parameters()
        assert flat_g.shape == (4 * 3 + 3,) and not flat_g.any()
        fresh = nn.Sequential(nn.Linear(4, 3)).build(seed=0)
        x = jnp.ones((2, 4))
        for m in (model, fresh):        # zeros made above / never made
            y = m.forward(x)
            m.backward(x, jnp.ones_like(y))
            m.backward(x, jnp.ones_like(y))
        g1, g2 = model.get_parameters()[1], fresh.get_parameters()[1]
        assert jnp.allclose(g1, g2) and bool(g1.any())
        entry = model.get_parameters_table()["Linear@0"]
        assert jnp.allclose(entry["gradBias"], g1[-3:])


class TestBranches:
    def test_concat(self, rng):
        m = nn.Concat(2, nn.Linear(4, 3), nn.Linear(4, 5))
        params = m.init(rng)
        y, _ = m.apply(params, jnp.ones((2, 4)))
        assert y.shape == (2, 8)

    def test_concat_table_and_cadd(self, rng):
        m = nn.Sequential(
            nn.ConcatTable(nn.Linear(4, 4), nn.Identity()),
            nn.CAddTable(),
        )
        params = m.init(rng)
        y, _ = m.apply(params, jnp.ones((2, 4)))
        assert y.shape == (2, 4)

    def test_parallel_table(self, rng):
        m = nn.ParallelTable(nn.Linear(4, 2), nn.Linear(3, 2))
        params = m.init(rng)
        y, _ = m.apply(params, T(jnp.ones((2, 4)), jnp.ones((2, 3))))
        assert isinstance(y, Table)
        assert y[1].shape == (2, 2) and y[2].shape == (2, 2)

    def test_map_table_shares_params(self, rng):
        m = nn.MapTable(nn.Linear(4, 2))
        params = m.init(rng)
        y, _ = m.apply(params, T(jnp.ones((2, 4)), 2 * jnp.ones((2, 4))))
        np.testing.assert_allclose(np.asarray(y[2] + params["0"]["bias"]),
                                   np.asarray(2 * y[1]), rtol=1e-5)

    def test_split_join_roundtrip(self):
        x = jnp.arange(24.0).reshape(2, 3, 4)
        split = nn.SplitTable(2)  # split over dim 2 (1-based) = axis 1
        joined, _ = nn.Sequential(split, nn.JoinTable(1, 2)).apply({}, x)
        # split into 3 (2,4) pieces then join on dim 1 of 2D = axis 0
        assert joined.shape == (6, 4)

    def test_select_narrow_table(self):
        xs = T(jnp.ones((2,)), 2 * jnp.ones((2,)), 3 * jnp.ones((2,)))
        y, _ = nn.SelectTable(2).apply({}, xs)
        np.testing.assert_allclose(np.asarray(y), 2 * np.ones(2))
        y, _ = nn.SelectTable(-1).apply({}, xs)
        np.testing.assert_allclose(np.asarray(y), 3 * np.ones(2))
        sub, _ = nn.NarrowTable(2, 2).apply({}, xs)
        assert sub.length() == 2
        np.testing.assert_allclose(np.asarray(sub[1]), 2 * np.ones(2))

    def test_flatten_table(self):
        nested = T(jnp.ones(2), T(jnp.zeros(3), jnp.ones(1)))
        flat, _ = nn.FlattenTable().apply({}, nested)
        assert flat.length() == 3

    def test_mixture_table(self):
        gater = jnp.asarray([[0.3, 0.7], [0.5, 0.5]])
        e1 = jnp.ones((2, 4))
        e2 = 3 * jnp.ones((2, 4))
        y, _ = nn.MixtureTable().apply({}, T(gater, T(e1, e2)))
        np.testing.assert_allclose(np.asarray(y[0]), 0.3 * 1 + 0.7 * 3 * np.ones(4), rtol=1e-5)

    def test_bottle(self, rng):
        m = nn.Bottle(nn.Linear(4, 2), 2, 2)
        params = m.init(rng)
        y, _ = m.apply(params, jnp.ones((3, 5, 4)))
        assert y.shape == (3, 5, 2)


class TestShapeOps:
    def test_reshape_view(self):
        x = jnp.arange(24.0).reshape(2, 3, 4)
        y, _ = nn.Reshape((12,)).apply({}, x)
        assert y.shape == (2, 12)
        y, _ = nn.View(12).apply({}, x)
        assert y.shape == (2, 12)

    def test_squeeze_unsqueeze(self):
        x = jnp.ones((2, 1, 3))
        y, _ = nn.Squeeze(2).apply({}, x)
        assert y.shape == (2, 3)
        y, _ = nn.Unsqueeze(2).apply({}, jnp.ones((2, 3)))
        assert y.shape == (2, 1, 3)

    def test_transpose(self):
        x = jnp.ones((2, 3, 4))
        y, _ = nn.Transpose([(1, 3)]).apply({}, x)
        assert y.shape == (4, 3, 2)

    def test_narrow_select(self):
        x = jnp.arange(24.0).reshape(2, 3, 4)
        y, _ = nn.Narrow(2, 2, 2).apply({}, x)
        assert y.shape == (2, 2, 4)
        y, _ = nn.Select(2, 3).apply({}, x)
        assert y.shape == (2, 4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x[:, 2, :]))

    def test_padding(self):
        x = jnp.ones((2, 3))
        y, _ = nn.Padding(2, 2, value=-1.0).apply({}, x)
        assert y.shape == (2, 5)
        assert float(y[0, 4]) == -1.0
        y, _ = nn.Padding(2, -2, value=0.5).apply({}, x)
        assert float(y[0, 0]) == 0.5

    def test_spatial_zero_padding(self):
        x = jnp.ones((1, 2, 3, 3))
        y, _ = nn.SpatialZeroPadding(1, 2, 3, 4).apply({}, x)
        assert y.shape == (1, 2, 10, 6)

    def test_reverse_replicate(self):
        x = jnp.arange(6.0).reshape(2, 3)
        y, _ = nn.Reverse(2).apply({}, x)
        np.testing.assert_allclose(np.asarray(y[0]), [2, 1, 0])
        y, _ = nn.Replicate(4, 1).apply({}, x)
        assert y.shape == (4, 2, 3)

    def test_index(self):
        x = jnp.arange(10.0)
        idx = jnp.asarray([3, 1], dtype=jnp.int32)
        y, _ = nn.Index(1).apply({}, T(x, idx))
        np.testing.assert_allclose(np.asarray(y), [2.0, 0.0])


class TestGradients:
    """Finite-difference gradient checks (ref nn/GradientChecker.scala)."""

    @pytest.mark.parametrize("layer_fn,shape", [
        (lambda: nn.Linear(6, 4), (3, 6)),
        (lambda: nn.SpatialConvolution(2, 3, 3, 3, 1, 1, 1, 1), (2, 2, 5, 5)),
        (lambda: nn.SpatialMaxPooling(2, 2, 2, 2), (2, 2, 6, 6)),
        (lambda: nn.Sequential(nn.Linear(6, 5), nn.Tanh(), nn.Linear(5, 2)), (3, 6)),
        (lambda: nn.SoftMax(), (3, 6)),
        (lambda: nn.BatchNormalization(6), (4, 6)),
    ])
    def test_input_gradient(self, rng, layer_fn, shape):
        m = layer_fn()
        params = m.init(rng)
        x = jax.random.normal(jax.random.fold_in(rng, 7), shape)

        def fn(xx):
            y, _ = m.apply(params, xx, training=True)
            return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape) * 0.1))

        assert check_gradient(fn, x)

    def test_param_gradient_linear(self, rng):
        m = nn.Linear(5, 3)
        params = m.init(rng)
        x = jax.random.normal(jax.random.fold_in(rng, 3), (4, 5))

        def fn(w):
            y, _ = m.apply({"weight": w, "bias": params["bias"]}, x)
            return jnp.sum(jnp.tanh(y))

        assert check_gradient(fn, params["weight"])

    def test_lstm_gradient(self, rng):
        m = nn.Recurrent(nn.LSTM(4, 3))
        params = m.init(rng)
        x = jax.random.normal(jax.random.fold_in(rng, 5), (2, 6, 4))

        def fn(xx):
            y, _ = m.apply(params, xx)
            return jnp.sum(jnp.sin(y))

        assert check_gradient(fn, x)


class TestRecurrent:
    def test_rnn_shapes(self, rng):
        m = nn.Recurrent(nn.RnnCell(5, 7))
        params = m.init(rng)
        y, _ = m.apply(params, jnp.ones((3, 10, 5)))
        assert y.shape == (3, 10, 7)

    def test_lstm_vs_torch(self, nprng):
        import torch
        B, T_, I, H = 2, 5, 4, 3
        x = nprng.randn(B, T_, I).astype(np.float32)
        m = nn.Recurrent(nn.LSTM(I, H))
        tl = torch.nn.LSTM(I, H, batch_first=True)
        w_ih = nprng.randn(4 * H, I).astype(np.float32) * 0.3
        w_hh = nprng.randn(4 * H, H).astype(np.float32) * 0.3
        b = nprng.randn(4 * H).astype(np.float32) * 0.1
        # torch gate order: i, f, g, o — same as ours
        tl.weight_ih_l0.data = torch.from_numpy(w_ih)
        tl.weight_hh_l0.data = torch.from_numpy(w_hh)
        tl.bias_ih_l0.data = torch.from_numpy(b)
        tl.bias_hh_l0.data = torch.zeros(4 * H)
        params = {"cell": {"w_ih": jnp.asarray(w_ih.T), "w_hh": jnp.asarray(w_hh.T),
                           "bias": jnp.asarray(b)}}
        y, _ = m.apply(params, jnp.asarray(x))
        ref, _ = tl(torch.from_numpy(x))
        np.testing.assert_allclose(np.asarray(y), ref.detach().numpy(), rtol=1e-3, atol=1e-4)

    def test_gru_vs_torch(self, nprng):
        import torch
        B, T_, I, H = 2, 5, 4, 3
        x = nprng.randn(B, T_, I).astype(np.float32)
        m = nn.Recurrent(nn.GRU(I, H))
        tl = torch.nn.GRU(I, H, batch_first=True)
        w_ih = nprng.randn(3 * H, I).astype(np.float32) * 0.3
        w_hh = nprng.randn(3 * H, H).astype(np.float32) * 0.3
        b = nprng.randn(3 * H).astype(np.float32) * 0.1
        tl.weight_ih_l0.data = torch.from_numpy(w_ih)
        tl.weight_hh_l0.data = torch.from_numpy(w_hh)
        tl.bias_ih_l0.data = torch.from_numpy(b)
        tl.bias_hh_l0.data = torch.zeros(3 * H)
        params = {"cell": {"w_ih": jnp.asarray(w_ih.T), "w_hh": jnp.asarray(w_hh.T),
                           "bias": jnp.asarray(b)}}
        y, _ = m.apply(params, jnp.asarray(x))
        ref, _ = tl(torch.from_numpy(x))
        np.testing.assert_allclose(np.asarray(y), ref.detach().numpy(), rtol=1e-3, atol=1e-4)

    def test_birecurrent(self, rng):
        m = nn.BiRecurrent(nn.RnnCell(4, 4))
        params = m.init(rng)
        y, _ = m.apply(params, jnp.ones((2, 6, 4)))
        assert y.shape == (2, 6, 4)

    def test_time_distributed(self, rng):
        m = nn.TimeDistributed(nn.Linear(4, 2))
        params = m.init(rng)
        y, _ = m.apply(params, jnp.ones((3, 7, 4)))
        assert y.shape == (3, 7, 2)


class TestDropout:
    def test_eval_identity(self):
        x = jnp.ones((4, 4))
        y, _ = nn.Dropout(0.5).apply({}, x, training=False)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x))

    def test_train_scale(self, rng):
        x = jnp.ones((100, 100))
        y, _ = nn.Dropout(0.3).apply({}, x, training=True, rng=rng)
        arr = np.asarray(y)
        kept = arr[arr != 0]
        np.testing.assert_allclose(kept, 1.0 / 0.7, rtol=1e-5)
        assert abs((arr != 0).mean() - 0.7) < 0.03

    def test_gradient_reversal(self):
        m = nn.GradientReversal(2.0)
        x = jnp.ones((3,))
        g = jax.grad(lambda xx: jnp.sum(m.f({}, xx)))(x)
        np.testing.assert_allclose(np.asarray(g), -2.0 * np.ones(3))

    def test_l1_penalty_grad(self):
        m = nn.L1Penalty(0.1)
        x = jnp.asarray([1.0, -2.0, 3.0])
        g = jax.grad(lambda xx: jnp.sum(m.f({}, xx)))(x)
        np.testing.assert_allclose(np.asarray(g), 1.0 + 0.1 * np.sign(np.asarray(x)), rtol=1e-5)


class TestNms:
    def test_basic(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 10, 10], [50, 50, 60, 60]], dtype=np.float32)
        scores = np.array([0.9, 0.8, 0.7], dtype=np.float32)
        keep = nn.Nms(0.5, 10)(boxes, scores)
        assert keep.tolist() == [1, 3]  # 1-based


class TestCheckpointRemat:
    def test_grads_identical_with_remat(self):
        import jax
        import jax.numpy as jnp

        x = np.random.RandomState(0).randn(4, 8).astype(np.float32)

        def build(remat):
            m = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 8))
            if remat:
                m.checkpoint()
            return m.build(seed=1)

        def grads(m):
            def loss(p):
                return jnp.sum(m.apply(p, jnp.asarray(x), training=True)[0] ** 2)
            return jax.grad(loss)(m.params)

        g1, g2 = grads(build(True)), grads(build(False))
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g2)):
            # remat replays the forward; XLA may fuse the replay
            # differently, so allow a few ULPs (seen on jax 0.4.x CPU)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
