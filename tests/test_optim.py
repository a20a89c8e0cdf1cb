"""Optimization engine tests (ref optim/ specs: SGD/Adagrad/LBFGS specs,
TriggerSpec, ValidationSpec, LocalOptimizerSpec with the reference-
optimizer-equivalence strategy: compare against a naive update)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu import nn
from bigdl_tpu.dataset import DataSet, Sample
from bigdl_tpu.dataset.transformer import SampleToBatch
from bigdl_tpu.optim import (
    SGD, Adagrad, Adam, AdamW, LBFGS, Default, Poly, Step, EpochStep,
    EpochSchedule, Regime, Trigger, Top1Accuracy, Top5Accuracy, Loss,
    LocalOptimizer, LocalValidator, Optimizer,
)


class TestSGD:
    def test_plain_matches_reference_update(self):
        """Ref-optimizer equivalence (ref optim/RefLocalOptimizer.scala):
        w' = w - lr*g."""
        sgd = SGD(learning_rate=0.1)
        params = {"w": jnp.asarray([1.0, 2.0])}
        grads = {"w": jnp.asarray([0.5, -1.0])}
        state = sgd.init_state(params)
        new_params, _ = sgd.update(grads, state, params)
        np.testing.assert_allclose(np.asarray(new_params["w"]), [0.95, 2.1], rtol=1e-6)

    def test_momentum_matches_torch(self):
        import torch
        w0 = np.array([1.0, -2.0, 3.0], dtype=np.float32)
        g_seq = [np.array([0.1, 0.2, -0.3], dtype=np.float32),
                 np.array([-0.2, 0.1, 0.4], dtype=np.float32),
                 np.array([0.3, -0.1, 0.2], dtype=np.float32)]
        tw = torch.tensor(w0.copy(), requires_grad=True)
        topt = torch.optim.SGD([tw], lr=0.05, momentum=0.9, weight_decay=0.01)
        sgd = SGD(learning_rate=0.05, momentum=0.9, weight_decay=0.01, dampening=0.0)
        params = {"w": jnp.asarray(w0)}
        state = sgd.init_state(params)
        for g in g_seq:
            tw.grad = torch.tensor(g.copy())
            topt.step()
            params, state = sgd.update({"w": jnp.asarray(g)}, state, params)
        np.testing.assert_allclose(np.asarray(params["w"]), tw.detach().numpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_nesterov_matches_torch(self):
        import torch
        w0 = np.array([0.5, -0.5], dtype=np.float32)
        tw = torch.tensor(w0.copy())
        topt = torch.optim.SGD([tw], lr=0.1, momentum=0.9, nesterov=True)
        sgd = SGD(learning_rate=0.1, momentum=0.9, nesterov=True)
        params = {"w": jnp.asarray(w0)}
        state = sgd.init_state(params)
        for i in range(4):
            g = np.array([0.1 * (i + 1), -0.05], dtype=np.float32)
            tw.grad = torch.tensor(g.copy())
            topt.step()
            params, state = sgd.update({"w": jnp.asarray(g)}, state, params)
        np.testing.assert_allclose(np.asarray(params["w"]), tw.numpy(), rtol=1e-5, atol=1e-6)

    def test_schedules(self):
        assert float(Default(0.1).rate(1.0, 10, 1)) == pytest.approx(1.0 / 2.0)
        assert float(Poly(2.0, 100).rate(1.0, 50, 1)) == pytest.approx(0.25)
        assert float(Step(10, 0.5).rate(1.0, 25, 1)) == pytest.approx(0.25)
        assert float(EpochStep(2, 0.1).rate(1.0, 0, 5)) == pytest.approx(0.01)
        sched = EpochSchedule([Regime(1, 3, {"learning_rate": 1e-2}),
                               Regime(4, 7, {"learning_rate": 5e-3})])
        assert float(sched.rate(0.1, 0, 5)) == pytest.approx(5e-3)


class TestAdagrad:
    def test_matches_torch(self):
        import torch
        w0 = np.array([1.0, 2.0], dtype=np.float32)
        tw = torch.tensor(w0.copy())
        topt = torch.optim.Adagrad([tw], lr=0.1, eps=1e-10)
        ours = Adagrad(learning_rate=0.1)
        params = {"w": jnp.asarray(w0)}
        state = ours.init_state(params)
        for i in range(3):
            g = np.array([0.5, -0.2 * (i + 1)], dtype=np.float32)
            tw.grad = torch.tensor(g.copy())
            topt.step()
            params, state = ours.update({"w": jnp.asarray(g)}, state, params)
        np.testing.assert_allclose(np.asarray(params["w"]), tw.numpy(), rtol=1e-5, atol=1e-6)


class TestAdam:
    def _run_pair(self, ours, topt_factory, steps=5, wd=0.0):
        import torch
        w0 = np.array([1.0, -2.0, 0.5], dtype=np.float32)
        tw = torch.tensor(w0.copy(), requires_grad=True)
        topt = topt_factory([tw])
        params = {"w": jnp.asarray(w0)}
        state = ours.init_state(params)
        rng = np.random.RandomState(0)
        for i in range(steps):
            g = rng.randn(3).astype(np.float32)
            tw.grad = torch.tensor(g.copy())
            topt.step()
            params, state = ours.update({"w": jnp.asarray(g)}, state, params)
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   tw.detach().numpy(), rtol=1e-5, atol=1e-6)

    def test_matches_torch_adam(self):
        import torch
        self._run_pair(Adam(learning_rate=0.01),
                       lambda p: torch.optim.Adam(p, lr=0.01))

    def test_matches_torch_adam_weight_decay(self):
        import torch
        self._run_pair(Adam(learning_rate=0.01, weight_decay=0.1),
                       lambda p: torch.optim.Adam(p, lr=0.01,
                                                  weight_decay=0.1))

    def test_matches_torch_adamw(self):
        import torch
        self._run_pair(AdamW(learning_rate=0.01, weight_decay=0.1),
                       lambda p: torch.optim.AdamW(p, lr=0.01,
                                                   weight_decay=0.1))

    def test_local_optimizer_convergence(self):
        model = nn.Linear(2, 2, with_bias=False)
        ds = _toy_regression_dataset()
        opt = LocalOptimizer(model, ds, nn.MSECriterion())
        opt.set_optim_method(Adam(learning_rate=0.05)) \
           .set_end_when(Trigger.max_iteration(200))
        trained = opt.optimize()
        w = np.asarray(trained.params["weight"])
        np.testing.assert_allclose(w, [[2.0, -1.0], [0.5, 1.5]], atol=0.05)

    def test_resume_refuses_optim_method_mismatch(self, tmp_path):
        """A state snapshot records its optimizer class; restoring into a
        different method must fail loudly (Adam m/v fed to SGD would be
        silently dropped)."""
        import os

        from bigdl_tpu.models.utils import restore_optim_state

        model = nn.Linear(2, 2, with_bias=False)
        opt = LocalOptimizer(model, _toy_regression_dataset(),
                             nn.MSECriterion())
        opt.set_optim_method(Adam(learning_rate=0.01)) \
           .set_end_when(Trigger.max_iteration(2)) \
           .set_checkpoint(str(tmp_path), Trigger.several_iteration(1))
        opt.optimize()
        states = sorted(f for f in os.listdir(tmp_path)
                        if f.startswith("state."))
        assert states
        path = str(tmp_path / states[-1])
        # matching method restores fine AND the loop consumes it: the
        # resumed run continues the step counter (3 saved + 1 new = 4)
        # instead of silently re-initialising moments and schedule
        opt2 = LocalOptimizer(model, _toy_regression_dataset(),
                              nn.MSECriterion())
        m2 = Adam(learning_rate=0.01)
        restore_optim_state(opt2, m2, path)
        assert "m" in m2._state
        opt2.set_optim_method(m2).set_end_when(Trigger.max_iteration(4))
        opt2.optimize()
        assert int(m2._state["iteration"]) == 4
        # mismatched method refuses
        with pytest.raises(SystemExit, match="Adam"):
            restore_optim_state(opt2, SGD(learning_rate=0.01), path)

    def test_distri_resume_consumes_state(self, tmp_path):
        """The mesh path re-shards a restored flat state over the slots
        and continues the counter, same contract as the local loop."""
        import os

        from bigdl_tpu.models.utils import restore_optim_state
        from bigdl_tpu.parallel import DistriOptimizer, create_mesh
        from bigdl_tpu.parallel.mesh import DATA_AXIS

        mesh = create_mesh({DATA_AXIS: 4}, devices=jax.devices()[:4])
        model = nn.Linear(2, 2, with_bias=False)
        opt = DistriOptimizer(model, _toy_regression_dataset(),
                              nn.MSECriterion(), mesh=mesh)
        opt.set_optim_method(Adam(learning_rate=0.01)) \
           .set_end_when(Trigger.max_iteration(2)) \
           .set_checkpoint(str(tmp_path), Trigger.several_iteration(1))
        opt.optimize()
        states = sorted(f for f in os.listdir(tmp_path)
                        if f.startswith("state."))
        path = str(tmp_path / states[-1])
        m2 = Adam(learning_rate=0.01)
        opt2 = DistriOptimizer(model, _toy_regression_dataset(),
                               nn.MSECriterion(), mesh=mesh)
        restore_optim_state(opt2, m2, path)
        opt2.set_optim_method(m2).set_end_when(Trigger.max_iteration(3))
        opt2.optimize()
        assert int(m2._state["iteration"]) == 3

    def test_distri_optimizer_sharded_adam_state(self):
        """Adam's m/v ride the ZeRO-1 cycle: per-shard slices of the flat
        parameter vector, updated locally after the bf16 reduce-scatter
        exactly like SGD's momentum."""
        from bigdl_tpu.parallel import DistriOptimizer, create_mesh
        from bigdl_tpu.parallel.mesh import DATA_AXIS

        mesh = create_mesh({DATA_AXIS: 4}, devices=jax.devices()[:4])
        model = nn.Linear(2, 2, with_bias=False)
        ds = _toy_regression_dataset()
        opt = DistriOptimizer(model, ds, nn.MSECriterion(), mesh=mesh)
        opt.set_optim_method(Adam(learning_rate=0.05)) \
           .set_end_when(Trigger.max_iteration(200))
        trained = opt.optimize()
        w = np.asarray(trained.params["weight"])
        np.testing.assert_allclose(w, [[2.0, -1.0], [0.5, 1.5]], atol=0.1)


class TestLBFGS:
    def test_rosenbrock(self):
        """Classic LBFGS sanity check (the reference tests LBFGS on
        rosenbrock too, optim/LBFGSSpec)."""
        def feval(x):
            v = 100.0 * (x[1] - x[0] ** 2) ** 2 + (1 - x[0]) ** 2
            g = jax.grad(lambda xx: 100.0 * (xx[1] - xx[0] ** 2) ** 2 + (1 - xx[0]) ** 2)(x)
            return float(v), g

        x = jnp.asarray([-1.2, 1.0])
        opt = LBFGS(max_iter=100, line_search=True)
        x, hist = opt.optimize(feval, x)
        assert hist[-1] < 1e-5
        np.testing.assert_allclose(np.asarray(x), [1.0, 1.0], atol=1e-2)

    def test_quadratic_no_linesearch(self):
        A = jnp.asarray([[3.0, 0.5], [0.5, 1.0]])
        b = jnp.asarray([1.0, -2.0])

        def feval(x):
            v = 0.5 * x @ A @ x - b @ x
            return float(v), A @ x - b

        opt = LBFGS(max_iter=50)
        x, hist = opt.optimize(feval, jnp.zeros(2))
        expected = np.linalg.solve(np.asarray(A), np.asarray(b))
        np.testing.assert_allclose(np.asarray(x), expected, atol=1e-3)


class TestTrigger:
    def test_triggers(self):
        assert Trigger.max_epoch(3)({"epoch": 4, "neval": 1})
        assert not Trigger.max_epoch(3)({"epoch": 3, "neval": 1})
        assert Trigger.max_iteration(10)({"epoch": 1, "neval": 11})
        assert Trigger.several_iteration(5)({"epoch": 1, "neval": 10})
        assert not Trigger.several_iteration(5)({"epoch": 1, "neval": 9})
        assert Trigger.every_epoch()({"epoch_finished": True})


class TestValidationMethods:
    def test_top1(self):
        out = jnp.asarray([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]])
        target = jnp.asarray([2.0, 1.0, 1.0])
        r = Top1Accuracy()(out, target)
        assert r.result() == (2 / 3, 3)

    def test_top5(self):
        out = jnp.asarray(np.random.RandomState(0).randn(4, 10))
        target = jnp.asarray([float(np.argsort(-np.asarray(out[i]))[3] + 1) for i in range(4)])
        r = Top5Accuracy()(out, target)
        assert r.result()[0] == 1.0

    def test_perplexity(self):
        from bigdl_tpu.optim import Perplexity

        out = jnp.log(jnp.asarray([[0.25, 0.75], [0.5, 0.5]]))
        tgt = jnp.asarray([2.0, 1.0])
        # mean NLL = -(log .75 + log .5)/2; perplexity = exp of that
        want = float(np.exp(-(np.log(0.75) + np.log(0.5)) / 2))
        r = Perplexity(nn.ClassNLLCriterion())(out, tgt)
        np.testing.assert_allclose(r.result()[0], want, rtol=1e-6)
        # the DEFAULT consumes (B, T, V) LM outputs (time-distributed)
        r3 = Perplexity()(out[:, None, :], tgt[:, None])
        np.testing.assert_allclose(r3.result()[0], want, rtol=1e-6)
        # monoid: accumulating batches equals one big batch
        r2 = r + Perplexity(nn.ClassNLLCriterion())(out, tgt)
        np.testing.assert_allclose(r2.result()[0], want, rtol=1e-6)
        assert r2.result()[1] == 2

    def test_monoid_add(self):
        from bigdl_tpu.optim.validation import AccuracyResult
        r = AccuracyResult(3, 10) + AccuracyResult(2, 5)
        assert r.result() == (5 / 15, 15)


def _toy_regression_dataset(n=64, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    W = np.array([[2.0, -1.0], [0.5, 1.5]], dtype=np.float32)
    samples = []
    for _ in range(n):
        x = rng.randn(2).astype(np.float32)
        samples.append(Sample(x, (W @ x).astype(np.float32)))
    return DataSet.array(samples, seed=seed) >> SampleToBatch(batch)


class TestLocalOptimizer:
    def test_sgd_convergence(self):
        """'Train with MSE and SGD should be good'
        (ref optim/LocalOptimizerSpec)."""
        model = nn.Linear(2, 2, with_bias=False)
        ds = _toy_regression_dataset()
        opt = LocalOptimizer(model, ds, nn.MSECriterion())
        opt.set_optim_method(SGD(learning_rate=0.1)) \
           .set_end_when(Trigger.max_iteration(100))
        trained = opt.optimize()
        w = np.asarray(trained.params["weight"])
        np.testing.assert_allclose(w, [[2.0, -1.0], [0.5, 1.5]], atol=0.05)

    def test_lbfgs_convergence(self):
        """'Train with MSE and LBFGS should be good'
        (ref optim/DistriOptimizerSpec.scala:130-141)."""
        model = nn.Linear(2, 2, with_bias=False)
        ds = _toy_regression_dataset(n=64, batch=64)
        opt = LocalOptimizer(model, ds, nn.MSECriterion())
        opt.set_optim_method(LBFGS(max_iter=20, line_search=True)) \
           .set_end_when(Trigger.max_iteration(5))
        trained = opt.optimize()
        w = np.asarray(trained.params["weight"])
        np.testing.assert_allclose(w, [[2.0, -1.0], [0.5, 1.5]], atol=0.02)

    def test_classification_with_validation_and_checkpoint(self, tmp_path):
        rng = np.random.RandomState(1)
        samples = []
        for i in range(80):
            label = i % 2
            x = rng.randn(4).astype(np.float32) + label * 2.5
            samples.append(Sample(x, np.asarray(label + 1.0, dtype=np.float32)))
        train = DataSet.array(samples[:64], seed=1) >> SampleToBatch(16)
        val = DataSet.array(samples[64:], seed=1) >> SampleToBatch(16)
        model = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2), nn.LogSoftMax())
        opt = LocalOptimizer(model, train, nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learning_rate=0.5)) \
           .set_end_when(Trigger.max_epoch(6)) \
           .set_validation(Trigger.every_epoch(), val, [Top1Accuracy(), Loss()]) \
           .set_checkpoint(str(tmp_path), Trigger.every_epoch())
        trained = opt.optimize()
        results = LocalValidator(trained, val).test([Top1Accuracy()])
        acc = results[0][1].result()[0]
        assert acc > 0.9
        import os
        assert any(f.startswith("model.") for f in os.listdir(tmp_path))
        assert any(f.startswith("state.") for f in os.listdir(tmp_path))

    def test_factory_dispatch(self):
        ds = _toy_regression_dataset()
        opt = Optimizer.create(nn.Linear(2, 2), ds, nn.MSECriterion())
        assert isinstance(opt, LocalOptimizer)

    def test_epoch_accounting(self):
        model = nn.Linear(2, 2)
        ds = _toy_regression_dataset(n=32, batch=16)
        opt = LocalOptimizer(model, ds, nn.MSECriterion())
        opt.set_optim_method(SGD(learning_rate=0.01)) \
           .set_end_when(Trigger.max_epoch(3))
        opt.optimize()
        assert opt.state["epoch"] == 4  # stopped after finishing 3 epochs
        assert opt.state["neval"] == 3 * 2 + 1


class TestGradientClipping:
    def _opt(self):
        return LocalOptimizer(nn.Linear(2, 2, with_bias=False),
                              _toy_regression_dataset(), nn.MSECriterion())

    def test_l2_norm_matches_torch(self):
        import torch

        tree = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.asarray([[12.0]])}
        opt = self._opt().set_gradient_clipping_by_l2_norm(6.5)
        clipped = opt._clip_gradients(tree)
        ta = torch.tensor([3.0, 4.0], requires_grad=True)
        tb = torch.tensor([[12.0]], requires_grad=True)
        ta.grad, tb.grad = torch.tensor([3.0, 4.0]), torch.tensor([[12.0]])
        torch.nn.utils.clip_grad_norm_([ta, tb], 6.5)
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   ta.grad.numpy(), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(clipped["b"]),
                                   tb.grad.numpy(), rtol=1e-6)
        # norm below the limit: untouched
        small = opt._clip_gradients({"a": jnp.asarray([0.3, 0.4])})
        np.testing.assert_allclose(np.asarray(small["a"]), [0.3, 0.4],
                                   rtol=1e-6)

    def test_constant_clip(self):
        opt = self._opt().set_constant_gradient_clipping(-1.0, 1.0)
        g = opt._clip_gradients({"w": jnp.asarray([-5.0, 0.5, 7.0])})
        np.testing.assert_allclose(np.asarray(g["w"]), [-1.0, 0.5, 1.0])

    def test_distri_l2_clip_matches_local(self):
        """The sharded clip (per-slot slice + psum'd global norm) must
        train identically to the local whole-tree clip."""
        from bigdl_tpu.parallel import DistriOptimizer, create_mesh
        from bigdl_tpu.parallel.mesh import DATA_AXIS

        def train(cls, **kw):
            model = nn.Linear(2, 2, with_bias=False)
            opt = cls(model, _toy_regression_dataset(), nn.MSECriterion(),
                      **kw)
            opt.set_optim_method(SGD(learning_rate=0.1)) \
               .set_end_when(Trigger.max_iteration(5)) \
               .set_gradient_clipping_by_l2_norm(0.05)  # low: always active
            return np.asarray(opt.optimize().params["weight"])

        mesh = create_mesh({DATA_AXIS: 4}, devices=jax.devices()[:4])
        w_local = train(LocalOptimizer)
        w_distri = train(DistriOptimizer, mesh=mesh)
        np.testing.assert_allclose(w_distri, w_local, atol=1e-4)


class TestPreemption:
    """handle_preemption: SIGTERM -> finish the iteration, checkpoint,
    return cleanly (the preemptible-pod recovery story, SURVEY.md §5.3)."""

    @pytest.fixture(autouse=True)
    def _restore_sigterm(self):
        """The production handler stays installed for the process by
        design; the TEST must give SIGTERM back its default so a CI
        timeout can still terminate pytest after this class runs."""
        import signal

        orig = signal.getsignal(signal.SIGTERM)
        yield
        signal.signal(signal.SIGTERM, orig)

    def test_local_sigterm_checkpoints_and_stops(self, tmp_path):
        import os
        import signal
        import threading

        model = nn.Linear(2, 2, with_bias=False)
        ds = _toy_regression_dataset()
        opt = LocalOptimizer(model, ds, nn.MSECriterion())
        opt.set_optim_method(SGD(learning_rate=0.01)) \
           .set_end_when(Trigger.max_iteration(100000)) \
           .set_checkpoint(str(tmp_path), Trigger.several_iteration(10 ** 9)) \
           .handle_preemption()
        # deliver the eviction notice shortly after training starts
        threading.Timer(1.0, lambda: os.kill(os.getpid(),
                                             signal.SIGTERM)).start()
        opt.optimize()  # returns instead of running 100k iterations
        assert opt.state["neval"] < 100000
        ckpts = [f for f in os.listdir(tmp_path) if f.startswith("model.")]
        states = [f for f in os.listdir(tmp_path) if f.startswith("state.")]
        assert ckpts and states, "preemption must write a final checkpoint"
        # and the pair is resumable
        from bigdl_tpu.models.utils import restore_optim_state
        m2 = SGD(learning_rate=0.01)
        opt2 = LocalOptimizer(nn.Linear(2, 2, with_bias=False), ds,
                              nn.MSECriterion())
        restore_optim_state(
            opt2, m2,
            str(tmp_path / sorted(states,
                                  key=lambda f: int(f.split(".")[1]))[-1]))
        assert opt2.state["neval"] == opt.state["neval"]

    def test_lbfgs_sigterm_checkpoints_and_stops(self, tmp_path):
        """The LBFGS host loop honors the same preemption contract."""
        import os
        import signal
        import threading

        model = nn.Linear(2, 2, with_bias=False)
        opt = LocalOptimizer(model, _toy_regression_dataset(),
                             nn.MSECriterion())
        opt.set_optim_method(LBFGS(max_iter=5)) \
           .set_end_when(Trigger.max_iteration(100000)) \
           .set_checkpoint(str(tmp_path), Trigger.several_iteration(10 ** 9)) \
           .handle_preemption()
        threading.Timer(1.0, lambda: os.kill(os.getpid(),
                                             signal.SIGTERM)).start()
        opt.optimize()
        assert opt.state["neval"] < 100000
        assert any(f.startswith("state.") for f in os.listdir(tmp_path))

    def test_lbfgs_refuses_gradient_clipping(self):
        """Clipped gradients are inconsistent with the Wolfe line search
        and curvature pairs — LBFGS must refuse loudly, not degrade."""
        opt = LocalOptimizer(nn.Linear(2, 2, with_bias=False),
                             _toy_regression_dataset(), nn.MSECriterion())
        opt.set_optim_method(LBFGS(max_iter=2)) \
           .set_end_when(Trigger.max_iteration(1)) \
           .set_gradient_clipping_by_l2_norm(1.0)
        with pytest.raises(ValueError, match="LBFGS"):
            opt.optimize()

    def test_distri_sigterm_checkpoints_and_stops(self, tmp_path):
        import os
        import signal
        import threading

        from bigdl_tpu.parallel import DistriOptimizer, create_mesh
        from bigdl_tpu.parallel.mesh import DATA_AXIS

        mesh = create_mesh({DATA_AXIS: 4}, devices=jax.devices()[:4])
        opt = DistriOptimizer(nn.Linear(2, 2, with_bias=False),
                              _toy_regression_dataset(), nn.MSECriterion(),
                              mesh=mesh)
        opt.set_optim_method(SGD(learning_rate=0.01)) \
           .set_end_when(Trigger.max_iteration(100000)) \
           .set_checkpoint(str(tmp_path), Trigger.several_iteration(10 ** 9)) \
           .handle_preemption()
        threading.Timer(1.0, lambda: os.kill(os.getpid(),
                                             signal.SIGTERM)).start()
        opt.optimize()
        assert opt.state["neval"] < 100000
        assert any(f.startswith("state.") for f in os.listdir(tmp_path))


class TestMixedPrecision:
    """set_compute_dtype: bf16 forward/backward, f32 master weights (the
    TPU mixed-precision recipe, first-class API)."""

    def _job(self, cls, dtype=None, mesh=None):
        import jax.numpy as jnp
        from bigdl_tpu.dataset import DataSet, Sample
        from bigdl_tpu.dataset.transformer import SampleToBatch

        rng = np.random.RandomState(0)
        samples = [Sample(rng.randn(6).astype(np.float32),
                          np.asarray(float(i % 3) + 1, np.float32))
                   for i in range(24)]
        ds = DataSet.array(samples) >> SampleToBatch(8, drop_last=True)
        m = nn.Sequential(nn.Linear(6, 16), nn.Tanh(), nn.Linear(16, 3),
                          nn.LogSoftMax())
        kwargs = {"mesh": mesh} if mesh is not None else {}
        opt = cls(m, ds, nn.ClassNLLCriterion(), **kwargs)
        opt.set_optim_method(SGD(learning_rate=0.1)) \
           .set_end_when(Trigger.max_iteration(6))
        if dtype is not None:
            opt.set_compute_dtype(dtype)
        model = opt.optimize()
        return float(opt.state["loss"]), model

    def test_local_bf16_compute_keeps_f32_masters(self):
        import jax.numpy as jnp

        loss16, model = self._job(LocalOptimizer, jnp.bfloat16)
        loss32, _ = self._job(LocalOptimizer, None)
        assert np.isfinite(loss16)
        # master weights stay f32 despite bf16 compute
        for leaf in jax.tree_util.tree_leaves(model.params):
            assert leaf.dtype == jnp.float32
        # bf16 rounding wiggles the trajectory but not the outcome
        assert abs(loss16 - loss32) < 0.05 * max(abs(loss32), 1.0)

    def test_distri_bf16_compute(self):
        import jax.numpy as jnp
        from bigdl_tpu.parallel import DistriOptimizer, create_mesh
        from bigdl_tpu.parallel.mesh import DATA_AXIS

        mesh = create_mesh({DATA_AXIS: 4}, devices=jax.devices()[:4])
        loss16, model = self._job(DistriOptimizer, jnp.bfloat16, mesh=mesh)
        loss32, _ = self._job(DistriOptimizer, None, mesh=mesh)
        assert np.isfinite(loss16)
        assert abs(loss16 - loss32) < 0.05 * max(abs(loss32), 1.0)

    def test_conv_model_bf16_compute(self):
        """Conv models are the regression case: lax.conv_general_dilated
        requires matching operand dtypes, so bf16 weights demand the input
        batch be cast too (a params-only cast is a trace-time TypeError),
        and the bf16 path must actually run in bf16, not silently promote
        back to f32."""
        import jax.numpy as jnp
        from bigdl_tpu.dataset import DataSet, Sample
        from bigdl_tpu.dataset.transformer import SampleToBatch

        rng = np.random.RandomState(0)
        samples = [Sample(rng.randn(1, 8, 8).astype(np.float32),
                          np.asarray(float(i % 2) + 1, np.float32))
                   for i in range(8)]
        ds = DataSet.array(samples) >> SampleToBatch(4, drop_last=True)
        m = nn.Sequential(
            nn.SpatialConvolution(1, 4, 3, 3), nn.ReLU(),
            nn.Reshape((4 * 6 * 6,)), nn.Linear(4 * 6 * 6, 2),
            nn.LogSoftMax())
        opt = LocalOptimizer(m, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(SGD(learning_rate=0.05)) \
           .set_end_when(Trigger.max_iteration(4)) \
           .set_compute_dtype(jnp.bfloat16)
        model = opt.optimize()
        assert np.isfinite(opt.state["loss"])
        for leaf in jax.tree_util.tree_leaves(model.params):
            assert leaf.dtype == jnp.float32

    def test_recurrent_model_bf16_compute(self):
        """The cell GEMMs must align operands to the weight dtype (a f32
        one-hot input would otherwise promote the bf16 gates back to f32
        and silently no-op the mixed precision), and the scan carry must
        keep one dtype across steps."""
        import jax.numpy as jnp
        from bigdl_tpu.dataset import DataSet, Sample
        from bigdl_tpu.dataset.transformer import SampleToBatch

        rng = np.random.RandomState(0)
        vocab, t = 5, 4
        samples = []
        for i in range(8):
            ids = rng.randint(0, vocab, size=t)
            feat = np.zeros((t, vocab), np.float32)
            feat[np.arange(t), ids] = 1.0
            samples.append(Sample(feat, (ids + 1).astype(np.float32)))
        ds = DataSet.array(samples) >> SampleToBatch(4, drop_last=True)
        for cell in (nn.LSTM(vocab, 8), nn.GRU(vocab, 8),
                     nn.RnnCell(vocab, 8)):
            m = nn.Sequential(
                nn.Recurrent(cell),
                nn.TimeDistributed(nn.Sequential(nn.Linear(8, vocab),
                                                 nn.LogSoftMax())))
            opt = LocalOptimizer(
                m, ds, nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                                   True))
            opt.set_optim_method(SGD(learning_rate=0.1)) \
               .set_end_when(Trigger.max_iteration(3)) \
               .set_compute_dtype(jnp.bfloat16)
            model = opt.optimize()
            assert np.isfinite(opt.state["loss"])
            for leaf in jax.tree_util.tree_leaves(model.params):
                assert leaf.dtype == jnp.float32
        # the cell really runs in bf16: a recurrent forward with bf16
        # params yields bf16 states, not silently-promoted f32 ones
        rec = nn.Recurrent(nn.LSTM(vocab, 8))
        p16 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            rec.init(jax.random.PRNGKey(0)))
        y = rec.f(p16, jnp.asarray(samples[0].feature)[None])
        assert y.dtype == jnp.bfloat16

    def test_float_encoded_ids_survive_bf16_compute(self):
        """Regression: the batch must NOT be blanket-cast to the compute
        dtype — float-encoded 1-based LookupTable ids above bf16's exact
        integer range (256) would silently round to the wrong row.  The
        MXU layers align dtypes at the weight instead."""
        import jax.numpy as jnp

        table = nn.LookupTable(600, 4).build(seed=0)
        p16 = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), table.params)
        ids = jnp.asarray([[513.0, 514.0]], jnp.float32)  # not bf16-exact
        out = np.asarray(table.f(p16, ids), np.float32)
        want = np.asarray(table.params["weight"], np.float32)[[512, 513]]
        np.testing.assert_allclose(out[0], want.astype(np.float32)
                                   .astype(jnp.bfloat16).astype(np.float32),
                                   atol=1e-2)
        assert not np.allclose(out[0, 0], out[0, 1])  # distinct rows
