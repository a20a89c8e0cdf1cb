"""Distributed engine tests on the 8-virtual-device CPU mesh — the analog
of the reference's simulated-multinode suite (DistriOptimizerSpec runs 4
"nodes" in one JVM, optim/DistriOptimizerSpec.scala:39-43)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.transformer import SampleToBatch
from bigdl_tpu.optim import SGD, Trigger, Top1Accuracy, LocalOptimizer
from bigdl_tpu.parallel import (
    AllReduceParameter, CompressedTensor, DistriOptimizer, DistriValidator,
    create_mesh, data_parallel_mesh,
)
from bigdl_tpu.parallel.mesh import DATA_AXIS


class TestMesh:
    def test_default_all_devices(self):
        mesh = data_parallel_mesh()
        assert mesh.devices.size == 8
        assert mesh.axis_names == (DATA_AXIS,)

    def test_multi_axis(self):
        mesh = create_mesh({"data": 4, "model": 2})
        assert mesh.devices.shape == (4, 2)

    def test_minus_one_axis(self):
        mesh = create_mesh({"data": -1, "model": 2})
        assert mesh.devices.shape == (4, 2)

    def test_bad_sizes(self):
        with pytest.raises(ValueError):
            create_mesh({"data": 3})


class TestCompressedTensor:
    def test_roundtrip_precision(self):
        x = np.random.RandomState(0).randn(100).astype(np.float32)
        for dtype in ("bf16", "fp16"):
            back = CompressedTensor(x, dtype).decompress()
            np.testing.assert_allclose(back, x, rtol=2e-2, atol=1e-2)

    def test_add(self):
        a = CompressedTensor(np.ones(10, np.float32))
        b = CompressedTensor(2 * np.ones(10, np.float32))
        np.testing.assert_allclose(a.add(b).decompress(), 3.0)

    def test_bytes(self):
        assert CompressedTensor(np.ones(10, np.float32)).bytes_size() == 20

    def test_bad_dtype(self):
        with pytest.raises(ValueError):
            CompressedTensor(np.ones(2), "fp8")


class TestAllReduceParameter:
    def test_shard_roundtrip(self, rng):
        params = nn.Sequential(nn.Linear(5, 7), nn.Linear(7, 3)).init(rng)
        arp = AllReduceParameter(params, 8)
        shards = arp.init_shards(params)
        assert shards.shape == (8, arp.slice_size)
        back = arp.to_pytree(shards)
        for a, b in zip(jax.tree_util.tree_leaves(back),
                        jax.tree_util.tree_leaves(params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_collective_cycle_in_shard_map(self, rng):
        """gather -> grad -> scatter reproduces a plain all-reduce mean."""
        from jax.sharding import PartitionSpec as P
        params = {"w": jax.random.normal(rng, (23,))}
        mesh = data_parallel_mesh()
        arp = AllReduceParameter(params, 8)
        w_flat = jnp.reshape(arp.init_shards(params), (-1,))

        def cycle(w_shard, g):
            w_full = arp.gather_weights(w_shard)
            g_shard = arp.scatter_gradients({"w": g[: arp.size]}, mean=True)
            return w_full, g_shard

        mapped = jax.shard_map(cycle, mesh=mesh,
                               in_specs=(P(DATA_AXIS), P()),
                               out_specs=(P(), P(DATA_AXIS)),
                               check_vma=False)
        grads = jnp.arange(arp.padded_size, dtype=jnp.float32)
        w_full, g_scat = mapped(w_flat, grads)
        # every device contributed the same grads; mean over 8 devices = grads
        np.testing.assert_allclose(np.asarray(g_scat)[: arp.size],
                                   np.asarray(grads)[: arp.size], rtol=1e-2, atol=1e-1)
        # gather restores weights (через bf16, so loose tolerance)
        np.testing.assert_allclose(np.asarray(w_full), np.asarray(w_flat)[: arp.size],
                                   rtol=1e-2, atol=1e-2)


def _classification_data(n=128, dim=6, seed=3):
    rng = np.random.RandomState(seed)
    samples = []
    for i in range(n):
        label = i % 2
        x = rng.randn(dim).astype(np.float32) + label * 2.0
        samples.append(Sample(x, np.asarray(label + 1.0, dtype=np.float32)))
    return samples


class TestDistriOptimizer:
    def test_convergence_8_devices(self):
        samples = _classification_data()
        ds = DataSet.array(samples, seed=1) >> SampleToBatch(32, drop_last=True)
        model = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 2), nn.LogSoftMax())
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learning_rate=0.5)) \
           .set_end_when(Trigger.max_epoch(5))
        trained = opt.optimize()
        res = DistriValidator(trained, ds).test([Top1Accuracy()])
        assert res[0][1].result()[0] > 0.95

    def test_matches_local_optimizer(self):
        """Ref-optimizer equivalence (ref RefDistriOptimizer): distributed
        training must match the single-process result when both see the
        same batches.  bf16 transport => loose-ish tolerance."""
        samples = _classification_data(n=64)
        model_d = nn.Sequential(nn.Linear(6, 4), nn.Tanh(), nn.Linear(4, 2)).build(seed=7)
        model_l = nn.Sequential(nn.Linear(6, 4), nn.Tanh(), nn.Linear(4, 2)).build(seed=7)

        ds_d = DataSet.array(samples, seed=5) >> SampleToBatch(32, drop_last=True)
        ds_l = DataSet.array(samples, seed=5) >> SampleToBatch(32, drop_last=True)
        crit = nn.MSECriterion()

        def one_hot_labels(ds):
            # regression-ify: use x->x targets instead (simpler determinism)
            return ds

        opt_d = DistriOptimizer(model_d, ds_d, nn.ClassNLLCriterion())
        opt_d.set_optim_method(SGD(learning_rate=0.1)).set_end_when(Trigger.max_iteration(10))
        sm = nn.Sequential(nn.LogSoftMax())
        # attach logsoftmax inside model for NLL
        model_d.add(nn.LogSoftMax())
        model_l.add(nn.LogSoftMax())
        model_d.build(seed=7)
        model_l.build(seed=7)
        opt_d = DistriOptimizer(model_d, ds_d, nn.ClassNLLCriterion())
        opt_d.set_optim_method(SGD(learning_rate=0.1)).set_end_when(Trigger.max_iteration(10))
        opt_l = LocalOptimizer(model_l, ds_l, nn.ClassNLLCriterion())
        opt_l.set_optim_method(SGD(learning_rate=0.1)).set_end_when(Trigger.max_iteration(10))
        opt_d.optimize()
        opt_l.optimize()
        wd = np.asarray(model_d.params["0"]["weight"])
        wl = np.asarray(model_l.params["0"]["weight"])
        np.testing.assert_allclose(wd, wl, rtol=5e-2, atol=5e-3)

    def test_batchnorm_buffers_synced(self):
        samples = _classification_data(n=64)
        ds = DataSet.array(samples, seed=1) >> SampleToBatch(32, drop_last=True)
        model = nn.Sequential(nn.Linear(6, 4), nn.BatchNormalization(4), nn.Linear(4, 2),
                              nn.LogSoftMax())
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learning_rate=0.1)).set_end_when(Trigger.max_iteration(4))
        trained = opt.optimize()
        rm = np.asarray(trained.buffers["1"]["running_mean"])
        assert np.any(rm != 0)

    def test_factory_dispatch_distributed(self):
        from bigdl_tpu.dataset.dataset import DistributedDataSet
        from bigdl_tpu.optim import Optimizer
        samples = _classification_data(n=32)
        ds = DistributedDataSet(samples, process_index=0, process_count=1)
        batched = ds >> SampleToBatch(16)
        opt = Optimizer.create(nn.Linear(6, 2), batched, nn.MSECriterion())
        assert isinstance(opt, DistriOptimizer)


def test_repad_refuses_foreign_larger_state():
    """Elastic restore trims only the zero padding tail; nonzero values
    past the model's parameter size mean a different (larger) model's
    checkpoint and must refuse loudly."""
    import jax.numpy as jnp
    import pytest

    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
    from bigdl_tpu.parallel.parameters import AllReduceParameter

    params = {"w": jnp.zeros((10,))}
    arp = AllReduceParameter(params, 4)  # size 10, padded 12
    # genuine re-pad from a 3-slot run (padded 12 -> same) or 5-slot
    ok = jnp.arange(10.0)
    bigger_padded = jnp.concatenate([ok, jnp.zeros((5,))])  # old padding
    out = DistriOptimizer._repad_flat_leaf(bigger_padded, arp)
    assert out.shape == (12,)
    np.testing.assert_array_equal(np.asarray(out[:10]), np.asarray(ok))
    # foreign model: nonzero beyond the parameter size
    foreign = jnp.concatenate([ok, jnp.ones((5,))])
    with pytest.raises(ValueError, match="larger model"):
        DistriOptimizer._repad_flat_leaf(foreign, arp)


def test_pin_xla_attention_guard():
    from bigdl_tpu.models import TransformerLM
    from bigdl_tpu.parallel import pin_xla_attention
    import pytest

    m = TransformerLM(vocab_size=11, hidden_size=8, n_head=2, n_layers=1,
                      max_len=4)
    assert m._mha.attention_impl == "auto"
    pin_xla_attention(m)
    assert m._mha.attention_impl == "xla"
    flash = TransformerLM(vocab_size=11, hidden_size=8, n_head=2,
                          n_layers=1, max_len=4, attention_impl="flash")
    with pytest.raises(ValueError, match="shard_map"):
        pin_xla_attention(flash)
