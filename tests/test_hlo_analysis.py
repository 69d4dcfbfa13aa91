"""Trip-count-aware HLO analyzer: the roofline's measurement backbone."""
import jax
import jax.numpy as jnp

from jax import make_mesh, shard_map
from jax.sharding import AxisType
from repro.launch.hlo_analysis import analyze_hlo


def _compile(f, *shapes):
    return jax.jit(f).lower(*shapes).compile().as_text()


def test_scan_equals_unroll():
    def f_scan(w, x):
        def body(x, _):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return jnp.sum(y ** 2)

    def f_unroll(w, x):
        for _ in range(10):
            x = jnp.tanh(x @ w)
        return jnp.sum(x ** 2)

    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cs = analyze_hlo(_compile(f_scan, w, x))
    cu = analyze_hlo(_compile(f_unroll, w, x))
    assert abs(cs.flops - cu.flops) / cu.flops < 0.01
    analytic = 10 * 2 * 128 ** 3
    assert abs(cs.flops - analytic) / analytic < 0.05


def test_grad_flops_ratio():
    def f(w, x):
        def body(x, _):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, None, length=8)
        return jnp.sum(y ** 2)

    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    fwd = analyze_hlo(_compile(f, w, x))
    vg = analyze_hlo(_compile(lambda w, x: jax.value_and_grad(f)(w, x), w, x))
    # dL/dw: 2 matmuls per layer in bwd + 1 fwd -> ~3x
    assert 2.5 < vg.flops / fwd.flops < 3.6


def test_nested_scan_multiplies():
    def f(x):
        def outer(x, _):
            def inner(x, _):
                return x @ x, None
            y, _ = jax.lax.scan(inner, x, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return jnp.sum(y)

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = analyze_hlo(_compile(f, x))
    analytic = 15 * 2 * 64 ** 3
    assert abs(c.flops - analytic) / analytic < 0.05


def test_collectives_counted():
    mesh = make_mesh((1,), ("d",), axis_types=(AxisType.Auto,))

    def f(x):
        return jax.lax.psum(x @ x, "d")

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    with mesh:
        g = shard_map(f, mesh=mesh, in_specs=(jax.sharding.PartitionSpec(),),
                      out_specs=jax.sharding.PartitionSpec(), check_vma=False)
        txt = jax.jit(g).lower(x).compile().as_text()
    c = analyze_hlo(txt)
    # single-device psum may fold away; just check the parser doesn't crash
    assert c.flops > 0
