"""HLO analyzer: loop multipliers, collective bytes, roofline terms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo as hlo_lib
from repro.analysis import roofline


class TestAnalyzer:
    def test_scan_trip_count_multiplies_flops(self):
        D, L = 64, 10

        def f(x, w):
            def body(c, wi):
                return jnp.tanh(c @ wi), ()
            y, _ = jax.lax.scan(body, x, w)
            return y.sum()

        compiled = jax.jit(f).lower(
            jnp.ones((8, D)), jnp.ones((L, D, D))).compile()
        cost = hlo_lib.analyze(compiled.as_text())
        analytic = L * 2 * 8 * D * D
        assert analytic <= cost.flops <= 1.3 * analytic, cost.flops
        assert L in cost.trip_counts.values()
        # raw cost_analysis counts the body once — the reason we exist
        raw = compiled.cost_analysis()
        assert raw["flops"] < cost.flops / 3

    def test_nested_loops_multiply(self):
        def f(x):
            def outer(c, _):
                def inner(d, _):
                    return jnp.tanh(d @ d), ()
                d, _ = jax.lax.scan(inner, c, None, length=4)
                return d, ()
            y, _ = jax.lax.scan(outer, x, None, length=3)
            return y.sum()

        compiled = jax.jit(f).lower(jnp.ones((16, 16))).compile()
        cost = hlo_lib.analyze(compiled.as_text())
        analytic = 3 * 4 * 2 * 16 * 16 * 16
        assert analytic <= cost.flops <= 1.5 * analytic, cost.flops

    def test_dot_flops_exact(self):
        def f(a, b):
            return a @ b

        compiled = jax.jit(f).lower(jnp.ones((32, 64)),
                                    jnp.ones((64, 128))).compile()
        cost = hlo_lib.analyze(compiled.as_text())
        assert cost.flops == pytest.approx(2 * 32 * 64 * 128, rel=0.01)


class TestRoofline:
    def test_terms_and_dominance(self):
        t = roofline.terms(flops_per_device=197e12,     # 1s of compute
                           hbm_bytes_per_device=819e9 * 0.5,
                           collective_bytes_per_device=50e9 * 0.25,
                           model_flops_total=197e12 * 256,
                           n_devices=256)
        assert t.compute_s == pytest.approx(1.0)
        assert t.memory_s == pytest.approx(0.5)
        assert t.collective_s == pytest.approx(0.25)
        assert t.dominant == "compute"
        assert t.roofline_fraction == pytest.approx(1.0)
        assert t.useful_ratio == pytest.approx(1.0)

    def test_model_flops_train_vs_decode(self):
        from repro.configs import SHAPES, get_config
        cfg = get_config("llama3.2-1b")
        n = 1_200_000_000
        train = roofline.model_flops(cfg, SHAPES["train_4k"], n)
        decode = roofline.model_flops(cfg, SHAPES["decode_32k"], n)
        # train: 6*N*B*S dominates
        assert train > 6 * n * 256 * 4096
        # decode: 2*N per token x batch
        assert decode == pytest.approx(
            2 * n * 128 + 4 * 128 * 32768 * 32 * 64 * 16, rel=0.01)
