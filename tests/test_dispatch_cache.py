"""Dispatch fast path (core/dispatch plan cache) + persistent
compilation cache (core/compile_cache).

The plan cache is the ~110 µs/op lever (DESIGN.md "Dispatch fast path"): a
hit must skip flattening/jit re-dispatch yet stay bit-identical with the
general path; keys must split on everything that changes the compiled
program (shapes, dtypes, stop_gradient, scalar statics AND their types,
grad mode, flags epoch). The persistent cache must let a cold process
against a warm FLAGS_compile_cache_dir skip recompilation.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPlanCache:
    def test_nograd_hit_and_value_parity(self):
        v = np.random.RandomState(0).randn(6, 6).astype("float32")
        x = paddle.to_tensor(v)
        with paddle.no_grad():
            a = paddle.matmul(x, x)
            i0 = dispatch.plan_cache_info()
            b = paddle.matmul(x, x)
            i1 = dispatch.plan_cache_info()
        assert i1["hits"] >= i0["hits"] + 1
        np.testing.assert_array_equal(a.numpy(), b.numpy())
        np.testing.assert_allclose(a.numpy(), v @ v, rtol=1e-5)

    def test_shape_change_replans(self):
        with paddle.no_grad():
            a = paddle.to_tensor(np.ones((2, 3), "float32"))
            b = paddle.to_tensor(np.ones((3, 4), "float32"))
            out1 = paddle.matmul(a, b)
            i0 = dispatch.plan_cache_info()
            c = paddle.to_tensor(np.ones((2, 5), "float32"))
            d = paddle.to_tensor(np.ones((5, 4), "float32"))
            out2 = paddle.matmul(c, d)
            i1 = dispatch.plan_cache_info()
        assert i1["misses"] == i0["misses"] + 1  # new shapes, new plan
        assert out1.shape == [2, 4] and out2.shape == [2, 4]
        np.testing.assert_allclose(out2.numpy(), np.full((2, 4), 5.0))

    def test_scalar_static_type_distinction(self):
        """2 and 2.0 hash equal but bake different static constants — the
        key must keep them distinct (result dtype differs under x64)."""
        x = paddle.to_tensor(np.arange(4, dtype="int32"))
        with paddle.no_grad():
            yi = x * 2
            yf = x * 2.0
        assert np.asarray(yi.numpy()).dtype.kind == "i"
        assert np.asarray(yf.numpy()).dtype.kind == "f"

    def test_stop_gradient_flip_keys_separately(self):
        v = np.random.RandomState(1).randn(3, 3).astype("float32")
        w = paddle.to_tensor(v)
        xf = paddle.to_tensor(v, stop_gradient=True)
        y1 = paddle.matmul(xf, w)
        assert y1._grad_node is None and y1.stop_gradient
        xg = paddle.to_tensor(v, stop_gradient=False)
        y2 = paddle.matmul(xg, w)
        assert y2._grad_node is not None and not y2.stop_gradient
        y2.sum().backward()
        np.testing.assert_allclose(xg.grad.numpy(), np.ones((3, 3)) @ v.T,
                                   rtol=1e-5)

    def test_multi_output_and_container_args(self):
        """topk (multi-output) rides the plan in no-grad mode; concat
        (list arg) must bypass the planner and still be correct."""
        v = np.array([3.0, 1.0, 2.0], "float32")
        x = paddle.to_tensor(v)
        with paddle.no_grad():
            vals1, idx1 = paddle.topk(x, k=2)
            vals2, idx2 = paddle.topk(x, k=2)
            np.testing.assert_array_equal(vals1.numpy(), vals2.numpy())
            np.testing.assert_array_equal(idx1.numpy(), [0, 2])

            a = paddle.to_tensor(np.ones((2, 2), "float32"))
            c = paddle.concat([a, a], axis=0)
            assert c.shape == [4, 2]

    def test_cache_disabled_via_flag(self):
        prev = paddle.get_flags("FLAGS_eager_op_jit")["FLAGS_eager_op_jit"]
        x = paddle.to_tensor(np.ones((2, 2), "float32"))
        try:
            paddle.set_flags({"FLAGS_eager_op_jit": False})
            i0 = dispatch.plan_cache_info()
            with paddle.no_grad():
                y = x + x
            i1 = dispatch.plan_cache_info()
            assert (i1["hits"], i1["misses"]) == (i0["hits"], i0["misses"])
            np.testing.assert_array_equal(y.numpy(), 2 * np.ones((2, 2)))
        finally:
            paddle.set_flags({"FLAGS_eager_op_jit": prev})

    def test_grad_mode_second_order_still_works(self):
        """create_graph re-tapes through plan-cached nodes' recompute
        tuples — double backward must survive the fast path."""
        x = paddle.to_tensor(np.array([1.0, 2.0], "float32"),
                             stop_gradient=False)
        y = (x ** 3).sum()
        (g1,) = paddle.grad(y, x, create_graph=True)
        (g2,) = paddle.grad(g1.sum(), x)
        np.testing.assert_allclose(g2.numpy(), [6.0, 12.0], rtol=1e-6)


_CHILD = r"""
import json, sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.core import compile_cache, dispatch

x = paddle.to_tensor(np.random.RandomState(0).randn(16, 16)
                     .astype("float32"), stop_gradient=False)
w = paddle.to_tensor(np.random.RandomState(1).randn(16, 16)
                     .astype("float32"))
y = (paddle.matmul(x, w) * paddle.tanh(x)).sum()
y.backward()
x.grad._data.block_until_ready()
print(json.dumps({"persistent": compile_cache.stats(),
                  "plan": dispatch.plan_cache_info(),
                  "grad0": float(np.asarray(x.grad.numpy()).ravel()[0])}))
"""


class TestPersistentCompileCache:
    def test_cold_restart_skips_recompilation(self, tmp_path):
        """Same program, two processes: the first populates
        FLAGS_compile_cache_dir, the second (cold interpreter, warm dir)
        must serve every compile from disk — hits>0, misses==0 — and
        produce identical gradients."""
        from _cpu_env import cpu_subprocess_env

        env = cpu_subprocess_env(
            FLAGS_compile_cache_dir=str(tmp_path / "cc"))

        def run():
            out = subprocess.run(
                [sys.executable, "-c", _CHILD], capture_output=True,
                text=True, timeout=300, cwd=REPO, env=env)
            assert out.returncode == 0, out.stdout + out.stderr
            return json.loads(out.stdout.strip().splitlines()[-1])

        r1 = run()
        assert r1["persistent"]["enabled"]
        assert r1["persistent"]["misses"] > 0   # cold dir: everything compiles
        assert r1["persistent"]["entries"] > 0  # ...and lands on disk
        assert r1["plan"]["misses"] > 0

        r2 = run()
        assert r2["persistent"]["hits"] > 0, r2
        assert r2["persistent"]["misses"] == 0, (
            "cold process against a warm compile-cache dir recompiled "
            f"{r2['persistent']['misses']} programs")
        assert r2["grad0"] == r1["grad0"]

    @pytest.mark.parametrize("placed", [True, False],
                             ids=["env-placed", "in-checkout-default"])
    def test_cache_dir_placement(self, tmp_path, placed):
        """JAX_COMPILATION_CACHE_DIR places the cache from outside: after
        `import paddle_tpu` jax's directory is still the env's (even with
        FLAGS_compile_cache_dir pointing elsewhere) and stats() reports
        it. Unset, the directory is the one fixed path inside the
        checkout — never under ~ or a temp name."""
        from _cpu_env import cpu_subprocess_env

        env = cpu_subprocess_env()
        env.pop("FLAGS_compile_cache_dir", None)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if placed:
            want = str(tmp_path / "placed")
            env["JAX_COMPILATION_CACHE_DIR"] = want
            env["FLAGS_compile_cache_dir"] = str(tmp_path / "flag")
        else:
            want = os.path.join(REPO, ".jax_cache")
        out = subprocess.run(
            [sys.executable, "-c",
             "import json, jax, paddle_tpu\n"
             "from paddle_tpu.core import compile_cache as cc\n"
             "print(json.dumps([jax.config.jax_compilation_cache_dir, "
             "cc.stats()['dir'], cc.stats()['enabled']]))"],
            capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
        assert out.returncode == 0, out.stdout + out.stderr
        jax_dir, stats_dir, enabled = json.loads(
            out.stdout.strip().splitlines()[-1])
        assert jax_dir == want and stats_dir == want and enabled
        if placed:
            assert not (tmp_path / "flag").exists()

    def test_disabled_by_empty_flag(self, tmp_path):
        from paddle_tpu.core import compile_cache

        assert compile_cache.setup("") is False

    def test_stats_shape(self):
        st = dispatch.dispatch_cache_stats()
        assert "plan" in st and "persistent" in st
        for k in ("hits", "misses", "size"):
            assert k in st["plan"]


class TestProfilerCacheCounters:
    def test_summary_dict_carries_dispatch_cache(self):
        from paddle_tpu import profiler

        p = profiler.Profiler(timer_only=True)
        p.start()
        x = paddle.to_tensor(np.ones((4, 4), "float32"))
        with paddle.no_grad():
            (x + x)._data.block_until_ready()
        p.step()
        p.stop()
        d = p.summary_dict()
        dc = d.get("dispatch_cache")
        assert dc and "plan" in dc and "persistent" in dc
        text = p.summary()
        assert "Dispatch Cache Summary" in text
