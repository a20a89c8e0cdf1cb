"""Tests for Table, RNG, Engine (ref utils/ test specs)."""
import numpy as np
import pytest

from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.rng import RandomGenerator
from bigdl_tpu.utils.table import T, Table


class TestTable:
    def test_builder_and_1based_array_part(self):
        t = T(10, 20, 30)
        assert t[1] == 10 and t[2] == 20 and t[3] == 30
        assert t.length() == 3

    def test_insert_remove(self):
        t = T(1, 2, 3)
        t.insert(2, 99)
        assert t.to_seq() == [1, 99, 2, 3]
        assert t.remove(2) == 99
        assert t.to_seq() == [1, 2, 3]

    def test_str_keys(self):
        t = T(epoch=1, lr=0.1)
        assert t["epoch"] == 1
        t["neval"] = 5
        assert t["neval"] == 5

    def test_pytree_roundtrip(self):
        import jax
        t = T(np.ones(3), np.zeros(2), lr=0.5)
        leaves, treedef = jax.tree_util.tree_flatten(t)
        t2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert t2["lr"] == 0.5
        np.testing.assert_array_equal(t2[1], np.ones(3))

    def test_equality(self):
        assert T(1, 2) == T(1, 2)
        assert T(1, 2) != T(1, 3)


class TestRandomGenerator:
    def test_mt19937_reference_vector(self):
        # Standard MT19937, seed 5489: canonical first outputs.
        g = RandomGenerator(5489)
        expected = [3499211612, 581869302, 3890346734, 3586334585, 545404204]
        got = [g.random_int() for _ in range(5)]
        assert got == expected

    def test_determinism_and_reseed(self):
        g = RandomGenerator(42)
        a = [g.random() for _ in range(10)]
        g.set_seed(42)
        b = [g.random() for _ in range(10)]
        assert a == b
        assert all(0.0 <= x < 1.0 for x in a)

    def test_uniform_range(self):
        g = RandomGenerator(1)
        xs = [g.uniform(-2, 3) for _ in range(100)]
        assert all(-2 <= x < 3 for x in xs)

    def test_normal_moments(self):
        g = RandomGenerator(7)
        xs = np.array([g.normal(1.0, 2.0) for _ in range(4000)])
        assert abs(xs.mean() - 1.0) < 0.15
        assert abs(xs.std() - 2.0) < 0.15

    def test_randperm_is_permutation(self):
        g = RandomGenerator(3)
        p = g.randperm(10)
        assert sorted(p.tolist()) == list(range(1, 11))

    def test_bernoulli(self):
        g = RandomGenerator(11)
        xs = [g.bernoulli(0.3) for _ in range(2000)]
        assert 0.2 < np.mean(xs) < 0.4


class TestEngine:
    def test_init_defaults(self):
        Engine.init()
        assert Engine.node_number() == 1
        assert Engine.core_number() >= 1

    def test_explicit_init(self):
        Engine.init(node_number=4, core_number=2)
        assert Engine.node_number() == 4
        assert Engine.core_number() == 2

    def test_thread_pool(self):
        Engine.init()
        results = Engine.default().invoke_and_wait([lambda i=i: i * i for i in range(8)])
        assert results == [i * i for i in range(8)]

    def test_singleton_guard(self):
        import os
        os.environ["BIGDL_TPU_CHECK_SINGLETON"] = "1"
        Engine.reset()
        assert Engine.check_singleton() is True
        assert Engine.check_singleton() is False
        os.environ["BIGDL_TPU_CHECK_SINGLETON"] = "0"

    def test_require_init(self):
        with pytest.raises(RuntimeError):
            Engine.node_number()


def test_engine_diagnose_tpu_smoke():
    """The stale-chip scan must run without touching the jax backend and
    return a human-readable report string."""
    from bigdl_tpu.utils.engine import Engine
    report = Engine.diagnose_tpu()
    assert isinstance(report, str) and report
