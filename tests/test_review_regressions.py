"""Regression tests for review findings (stale vjp cache, simplex build,
Reshape batch-of-1, PReLU CHW, module save/load, LSTM gate dropout)."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import nn


def test_backward_uses_fresh_rng_each_call():
    d = nn.Dropout(0.5).build(seed=0)
    d.training()
    x = jnp.ones((8, 32))
    g = jnp.ones((8, 32))
    grads = [np.asarray(d.backward(x, g)) for _ in range(3)]
    assert not (np.array_equal(grads[0], grads[1]) and np.array_equal(grads[1], grads[2]))


def test_backward_sees_current_buffers():
    bn = nn.BatchNormalization(4).build(seed=0)
    bn.evaluate()
    x = jnp.asarray(np.random.RandomState(0).randn(6, 4).astype(np.float32))
    g1 = np.asarray(bn.backward(x, jnp.ones((6, 4))))
    # change running stats; eval-mode backward must reflect them
    bn.buffers = {"running_mean": jnp.full((4,), 5.0), "running_var": jnp.full((4,), 9.0)}
    g2 = np.asarray(bn.backward(x, jnp.ones((6, 4))))
    assert not np.allclose(g1, g2)


def test_class_simplex_geometry():
    for n in (2, 3, 5):
        s = np.asarray(nn.ClassSimplexCriterion(n).simplex, dtype=np.float64)
        norms = np.linalg.norm(s, axis=1)
        np.testing.assert_allclose(norms, 1.0, atol=1e-5)
        for i in range(n):
            for j in range(i + 1, n):
                np.testing.assert_allclose(s[i] @ s[j], -1.0 / n, atol=1e-5)


def test_reshape_keeps_singleton_batch():
    y, _ = nn.Reshape((2, 2)).apply({}, jnp.ones((1, 4)))
    assert y.shape == (1, 2, 2)
    y, _ = nn.Reshape((2, 2)).apply({}, jnp.ones((3, 4)))
    assert y.shape == (3, 2, 2)
    y, _ = nn.Reshape((2, 2), batch_mode=False).apply({}, jnp.ones((1, 4)))
    assert y.shape == (2, 2)
    y, _ = nn.View(2, 2).apply({}, jnp.ones((1, 4)))
    assert y.shape == (1, 2, 2)


def test_prelu_chw_unbatched():
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    m = nn.PReLU(4)
    x = -jnp.ones((4, 5, 6))
    y, _ = m.apply({"weight": w}, x)
    np.testing.assert_allclose(np.asarray(y[2]), -0.3, rtol=1e-6)
    # batched NCHW still axis 1
    xb = -jnp.ones((2, 4, 5, 6))
    y, _ = m.apply({"weight": w}, xb)
    np.testing.assert_allclose(np.asarray(y[0, 3]), -0.4, rtol=1e-6)


def test_module_save_load_roundtrip(tmp_path):
    m = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2)).build(seed=3)
    x = jnp.ones((2, 4))
    y1 = np.asarray(m.forward(x))
    path = str(tmp_path / "model.bin")
    m.save(path)
    m2 = nn.Module.load(path)
    y2 = np.asarray(m2.forward(x))
    np.testing.assert_allclose(y1, y2, rtol=1e-6)
    with pytest.raises(FileExistsError):
        m.save(path)
    m.save(path, overwrite=True)


def test_lstm_gate_dropout_active():
    cell = nn.LSTM(8, 8, p=0.9)
    m = nn.Recurrent(cell)
    params = m.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, 5, 8))
    y_eval, _ = m.apply(params, x, training=False)
    y_train, _ = m.apply(params, x, training=True, rng=jax.random.PRNGKey(1))
    assert not np.allclose(np.asarray(y_eval), np.asarray(y_train))
    # two different keys -> different outputs
    y_train2, _ = m.apply(params, x, training=True, rng=jax.random.PRNGKey(2))
    assert not np.allclose(np.asarray(y_train), np.asarray(y_train2))


def test_prefetcher_propagates_errors():
    from bigdl_tpu.dataset.transformer import Prefetcher

    def bad_gen():
        yield 1
        yield 2
        raise RuntimeError("decode failed")

    it = Prefetcher(2)(bad_gen())
    assert next(it) == 1 and next(it) == 2
    with pytest.raises(RuntimeError, match="decode failed"):
        next(it)


def test_dictionary_empty_constructor():
    from bigdl_tpu.dataset.text import Dictionary
    d = Dictionary()
    assert d.get_index("anything") == 0  # unk


def test_sgd_dampening_default_is_momentum():
    from bigdl_tpu.optim import SGD
    s = SGD(learning_rate=0.1, momentum=0.9)
    assert s.dampening == 0.9  # Torch-Lua/BigDL default
    s2 = SGD(learning_rate=0.1, momentum=0.9, dampening=0.0)
    assert s2.dampening == 0.0


def test_label_padding_is_valid_class():
    import numpy as np
    from bigdl_tpu.dataset.text import LabeledSentenceToSample
    from bigdl_tpu.dataset.types import LabeledSentence
    tr = LabeledSentenceToSample(5, fixed_length=6, pad_label=3.0)
    s = tr.transform_one(LabeledSentence(np.asarray([0.0, 1.0]), np.asarray([1.0, 2.0])))
    assert s.label.tolist() == [2.0, 3.0, 3.0, 3.0, 3.0, 3.0]
    with pytest.raises(ValueError):
        LabeledSentenceToSample(5, pad_label=0.0)


def test_lbfgs_epoch_accounting_terminates():
    import numpy as np
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.optim import LBFGS, Trigger, LocalOptimizer
    rng = np.random.RandomState(0)
    samples = [Sample(rng.randn(2).astype(np.float32), rng.randn(2).astype(np.float32))
               for _ in range(8)]
    ds = DataSet.array(samples) >> SampleToBatch(8)
    opt = LocalOptimizer(nn.Linear(2, 2), ds, nn.MSECriterion())
    opt.set_optim_method(LBFGS(max_iter=2)).set_end_when(Trigger.max_epoch(2))
    opt.optimize()
    assert opt.state["epoch"] == 3  # terminated after 2 epochs


def test_epoch_rollover_keeps_iterator_and_reshuffles():
    import numpy as np
    from bigdl_tpu.dataset import DataSet
    ds = DataSet.array(list(range(10)))
    it = ds.data(train=True)
    first = [next(it) for _ in range(10)]
    ds.shuffle()  # as the optimizer does at rollover — same iterator object
    second = [next(it) for _ in range(10)]
    assert sorted(second) == list(range(10))
    assert first != second  # new permutation picked up without rebinding


def test_mt_batch_enforces_size():
    import numpy as np
    from bigdl_tpu.dataset import image
    from bigdl_tpu.dataset.types import LabeledImage
    imgs = [LabeledImage(np.random.rand(3, s, s).astype(np.float32), 1.0)
            for s in (40, 20, 32)]
    tr = image.MTLabeledBGRImgToBatch(32, 32, 3, image.HFlip(0.0))
    (batch,) = list(tr(iter(imgs)))
    assert batch.data.shape == (3, 3, 32, 32)


def test_stateful_trigger_polled_once_per_iteration():
    import numpy as np
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.parallel import DistriOptimizer

    calls = []

    def latch(state):
        calls.append(state["neval"])
        return False

    rng = np.random.RandomState(0)
    samples = [Sample(rng.randn(4).astype(np.float32), np.asarray(1.0, np.float32))
               for _ in range(16)]
    ds = DataSet.array(samples) >> SampleToBatch(8, drop_last=True)
    m = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
    opt = DistriOptimizer(m, ds, nn.ClassNLLCriterion())
    opt.set_optim_method(SGD(learning_rate=0.1)) \
       .set_end_when(Trigger.max_iteration(3)) \
       .set_validation(Trigger(latch), ds, [])
    opt.optimize()
    assert calls == sorted(set(calls))  # each neval polled exactly once


def test_invoke_and_wait2_reraises_task_errors():
    """VERDICT r1 weak #3: only timeouts are straggler-dropped; a task
    that raises must surface, not vanish (one bad decode thread in
    MTLabeledBGRImgToBatch was silent data loss)."""
    import pytest
    from bigdl_tpu.utils.engine import ThreadPool

    pool = ThreadPool(2)
    try:
        def ok():
            return 42

        def boom():
            raise ValueError("decode failed")

        with pytest.raises(ValueError, match="decode failed"):
            pool.invoke_and_wait2([ok, boom], timeout=5.0)

        # timeouts still swallowed: a slow task is returned unfinished
        import time as _time

        def slow():
            _time.sleep(2.0)
            return 1

        futures = pool.invoke_and_wait2([ok, slow], timeout=0.05)
        assert futures[0].done()
    finally:
        pool.shutdown()


def test_validator_jit_is_cached_across_test_calls():
    """VERDICT r1 weak #7: validation-every-epoch must not recompile; the
    jitted forward is built once per validator."""
    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet, Sample
    from bigdl_tpu.dataset.transformer import SampleToBatch
    from bigdl_tpu.optim import Top1Accuracy
    from bigdl_tpu.optim.optimizer import LocalValidator
    from bigdl_tpu.parallel.distri_optimizer import DistriValidator

    rng = np.random.RandomState(0)
    samples = [Sample(rng.randn(4).astype(np.float32),
                      np.asarray(1.0, np.float32)) for _ in range(8)]
    ds = DataSet.array(samples) >> SampleToBatch(8, drop_last=True)
    m = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax()).build(seed=0)
    for val in (LocalValidator(m, ds), DistriValidator(m, ds)):
        val.test([Top1Accuracy()])
        fwd1 = val._fwd
        val.test([Top1Accuracy()])
        assert val._fwd is fwd1  # same jitted callable, no rebuild


def test_one_instrument():
    """PR 30: numbers come from benchmarks/run.py on a chip and nothing
    else.  The pre-chip instrument, its CPU artifacts and every pointer
    to them stay gone (``models/utils/lm_perf.py`` and its like are other
    files and stay)."""
    import glob
    import re
    repo = os.path.join(os.path.dirname(__file__), os.pardir)
    assert not os.path.exists(os.path.join(repo, "bench.py"))
    for pat in ("BENCH_*.json", "MULTICHIP_*.json", "PROFILE_MEM.json"):
        assert not glob.glob(os.path.join(repo, pat)), pat
    word = re.compile(r"(?<![\w/])bench\.py|BENCH_")
    files = [os.path.join(repo, f) for f in (
        "chip_smoke.py", "CLAUDE.md", "README.md",
        os.path.join(".claude", "skills", "verify", "SKILL.md"))]
    for root in ("bigdl_tpu", "scripts"):
        files += [os.path.join(d, f)
                  for d, _, fs in os.walk(os.path.join(repo, root))
                  for f in fs if f.endswith((".py", ".sh", ".md"))]
    hits = []
    for fn in files:
        with open(fn, errors="replace") as f:
            hits += [f"{os.path.relpath(fn, repo)}:{i}: {line.strip()}"
                     for i, line in enumerate(f, 1) if word.search(line)]
    assert not hits, hits


def test_one_way_to_choose_a_kernel():
    """PR 46: a kernel is chosen by one function beside the code that
    branches on it, from the platform and the shapes.  The tuning cache, its
    command line, its file, its environment variable and the one-head paged
    kernel stay gone, with every pointer to them; and no Pallas module
    reaches into a neighbour for the helpers they share
    (``ops/_pallas.py``)."""
    import re
    repo = os.path.join(os.path.dirname(__file__), os.pardir)
    for gone in ("TUNE_ATTN.json", "bigdl_tpu/ops/autotune.py",
                 "bigdl_tpu/ops/paged_attention.py",
                 "bigdl_tpu/models/utils/attention_bench.py",
                 "tests/test_autotune_attention.py",
                 "tests/test_measurement_resume.py"):
        assert not os.path.exists(os.path.join(repo, gone)), gone
    word = re.compile(r"autotune|TUNE_ATTN|TUNE_CACHE|paged_decode_attention"
                      r"|lookup_paged|lookup_qcompute|attention_bench")
    files = [os.path.join(repo, f) for f in (
        "chip_smoke.py", "CLAUDE.md", "README.md", "MIGRATION.md",
        os.path.join(".claude", "skills", "verify", "SKILL.md"))]
    for root in ("bigdl_tpu", "scripts"):
        files += [os.path.join(d, f)
                  for d, _, fs in os.walk(os.path.join(repo, root))
                  for f in fs if f.endswith((".py", ".sh", ".md"))]
    hits = []
    for fn in files:
        with open(fn, errors="replace") as f:
            hits += [f"{os.path.relpath(fn, repo)}:{i}: {line.strip()}"
                     for i, line in enumerate(f, 1) if word.search(line)]
    assert not hits, hits
    import importlib
    for name in ("flash_attention", "grouped_attention", "grouped_matmul",
                 "kda_step", "latent_attention"):
        mod = importlib.import_module("bigdl_tpu.ops." + name)
        assert not hasattr(mod, "_use_interpret"), name
        assert mod._pallas.use_interpret() is True      # the CPU, here
