"""HLO cost engine: loop-trip scaling, dot FLOPs, collective parsing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_costs import analyze_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scanned_matmul_flops_exact():
    L, M, K = 7, 128, 256

    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    c = _compile(f, jnp.zeros((L, K, K)), jnp.zeros((M, K)))
    cost = analyze_hlo(c.as_text())
    expected = L * 2 * M * K * K
    assert cost.flops == pytest.approx(expected, rel=1e-6)
    assert cost.n_while == 1
    assert list(cost.trip_counts.values()) == [L]
    # XLA's own analysis undercounts by ~L (this is why the engine exists)
    xla = float(c.cost_analysis().get("flops", 0.0))
    assert xla < expected / 2


def test_nested_scan_flops():
    Lo, Li, M = 3, 5, 32

    def f(ws, x):
        def outer(x, wo):
            def inner(x, wi):
                return x @ wi, None
            y, _ = jax.lax.scan(inner, x, wo)
            return y, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y.sum()

    c = _compile(f, jnp.zeros((Lo, Li, M, M)), jnp.zeros((M, M)))
    cost = analyze_hlo(c.as_text())
    assert cost.flops == pytest.approx(Lo * Li * 2 * M ** 3, rel=1e-6)


def test_grad_flops_factor():
    M = 64

    def f(w, x):
        return jnp.tanh(x @ w).sum()

    c = _compile(jax.grad(f, argnums=(0, 1)), jnp.zeros((M, M)), jnp.zeros((M, M)))
    cost = analyze_hlo(c.as_text())
    # fwd dot + two bwd dots = 3x
    assert cost.flops == pytest.approx(3 * 2 * M ** 3, rel=1e-6)


def test_bytes_positive_and_sane():
    def f(x):
        return (x @ x).sum()

    x = jnp.zeros((256, 256))
    cost = analyze_hlo(_compile(f, x).as_text())
    assert cost.bytes >= 2 * 256 * 256 * 4  # at least read x twice-ish
    assert cost.flops == pytest.approx(2 * 256 ** 3, rel=1e-6)
