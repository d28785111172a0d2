"""Optimizer + roofline-analyzer unit tests."""
import jax
import jax.numpy as jnp
import pytest

from repro.optim import adafactor, adamw, clip_by_global_norm, cosine_schedule
from repro.roofline.hlo_analysis import analyze_hlo


def _quadratic_problem():
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros((3,)), "m": jnp.zeros((4, 3))}

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2) + jnp.sum(p["m"] ** 2)

    return params, loss


@pytest.mark.parametrize("make", [adamw, adafactor])
def test_optimizer_converges(make):
    params, loss = _quadratic_problem()
    opt = make(lambda s: 0.05, weight_decay=0.0)
    state = opt.init(params)
    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params)
    assert float(loss(params)) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 20.0) < 1e-4
    total = jnp.sqrt(jnp.sum(clipped["a"] ** 2))
    assert abs(float(total) - 1.0) < 1e-4


def test_adafactor_state_is_factored():
    opt = adafactor(lambda s: 1e-3)
    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
    st = opt.init(params)
    assert st.inner["w"]["vr"].shape == (64,)
    assert st.inner["w"]["vc"].shape == (32,)
    assert st.inner["b"]["v"].shape == (32,)
    # memory: factored state is O(m+n), not O(mn)
    n_state = sum(x.size for x in jax.tree_util.tree_leaves(st.inner))
    assert n_state == 64 + 32 + 32


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup_steps=10, total_steps=100, min_ratio=0.1)
    assert float(lr(jnp.asarray(0))) == 0.0
    assert abs(float(lr(jnp.asarray(10))) - 1.0) < 0.11
    assert float(lr(jnp.asarray(100))) <= 0.11
    assert float(lr(jnp.asarray(5))) < float(lr(jnp.asarray(10)))


# ---------------------------------------------------------------------------
# roofline HLO analyzer
# ---------------------------------------------------------------------------
def test_analyzer_counts_scan_trip_counts():
    def body(x, w):
        def step(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(step, x, None, length=7)
        return y

    s = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(body).lower(s, s).compile()
    m = analyze_hlo(compiled.as_text())
    expect = 7 * 2 * 128 ** 3
    assert abs(m.dot_flops - expect) / expect < 0.01
    assert m.unknown_trip_whiles == 0
    # naive cost_analysis must NOT match (documents why the analyzer exists)
    naive = compiled.cost_analysis()["flops"]
    assert naive < expect / 2


def test_analyzer_nested_scans_multiply():
    def body(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(body).lower(s, s).compile()
    m = analyze_hlo(compiled.as_text())
    expect = 15 * 2 * 64 ** 3
    assert abs(m.dot_flops - expect) / expect < 0.02


def test_analyzer_hbm_model_reasonable():
    """A big matmul's modeled traffic ~= operands + result."""
    def f(a, b):
        return a @ b

    s = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    compiled = jax.jit(f).lower(s, s).compile()
    m = analyze_hlo(compiled.as_text())
    expect = 3 * 1024 * 1024 * 4
    assert m.hbm_bytes <= expect * 2.5
    assert m.hbm_bytes >= expect * 0.9
