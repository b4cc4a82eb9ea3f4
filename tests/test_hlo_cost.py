"""Validate the trip-count-aware HLO cost model on hand-computable cases."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze

pytestmark = pytest.mark.slow  # JAX tracing/compilation; fast lane: -m 'not slow'


def _cost(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return analyze(txt)


W = jax.ShapeDtypeStruct((512, 512), jnp.float32)
X = jax.ShapeDtypeStruct((256, 512), jnp.float32)
MM_FLOPS = 2 * 256 * 512 * 512


def test_single_matmul():
    c = _cost(lambda w, x: x @ w, W, X)
    assert c.flops == pytest.approx(MM_FLOPS, rel=0.02)


def test_scan_multiplies_by_trip_count():
    def fn(w, x):
        def body(cr, _):
            return jnp.tanh(cr @ w), None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return jnp.sum(out)
    c = _cost(fn, W, X)
    assert c.flops == pytest.approx(7 * MM_FLOPS, rel=0.02)


def test_nested_scan():
    def fn(w, x):
        def outer(cr, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None
            c2, _ = jax.lax.scan(inner, cr, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return jnp.sum(out)
    c = _cost(fn, W, X)
    assert c.flops == pytest.approx(15 * MM_FLOPS, rel=0.02)


def test_grad_counts_fwd_and_bwd():
    def fn(w, x):
        return jnp.sum(jnp.tanh(x @ w))
    c = _cost(jax.grad(fn, argnums=(0, 1)), W, X)
    # fwd + dW + dX = 3 matmuls
    assert c.flops == pytest.approx(3 * MM_FLOPS, rel=0.02)


def test_grad_of_scan():
    def fn(w, x):
        def body(cr, _):
            return jnp.tanh(cr @ w), None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return jnp.sum(out)
    c = _cost(jax.grad(fn), W, X)
    # per step: fwd dot + dcarry dot + dW dot = 3; total 12 matmuls
    assert c.flops == pytest.approx(12 * MM_FLOPS, rel=0.05)


def test_collectives_counted_with_trips():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((len(jax.devices()),), ("model",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = len(jax.devices())
    xs = jax.ShapeDtypeStruct((8, 64 * n), jnp.float32,
                              sharding=NamedSharding(mesh, P(None, "model")))

    def fn(x):
        def body(cr, _):
            return cr + jnp.sum(x, axis=1, keepdims=True), None  # all-reduce
        out, _ = jax.lax.scan(body, jnp.zeros((8, 1)), None, length=5)
        return out
    with jax.set_mesh(mesh):
        txt = jax.jit(fn).lower(xs).compile().as_text()
    c = analyze(txt)
    if n > 1:
        assert "all-reduce" in c.coll
        assert c.coll["all-reduce"][0] >= 5  # counted per trip
