"""The chip's peaks, keyed by ``device_kind``, and the f32 compute rate.

``peaks.json`` holds the published numbers.  A float32 compute rate is
published for no TPU v5e unit, so a traced run measures it with two
microkernels and takes the higher: a chain of multiply-adds on tiles
that stay in VMEM (the vector unit), and a large float32 matrix product
at ``Precision.HIGHEST`` (the matrix unit, several bfloat16 passes).
"""
from __future__ import annotations

import functools
import json
import time
from pathlib import Path
from typing import Callable

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str, table: Path = TABLE) -> dict:
    """The table's row for ``device_kind``; ``KeyError`` when absent."""
    with open(table) as f:
        rows = json.load(f)
    if device_kind not in rows:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table {sorted(rows)}")
    return rows[device_kind]


# vector-unit microkernel: CHAINS independent multiply-add chains on a
# (ROWS, LANES) f32 block (8 vector registers each, so all stay in
# registers), ITERS links each, over GRID blocks
ROWS, LANES, CHAINS, ITERS, GRID = 64, 128, 4, 8192, 512
UNROLL = 8
DOT_N = 8192


def _vpu_kernel(x_ref, o_ref):
    import jax

    a = x_ref[...]
    chains = tuple(a + k for k in range(CHAINS))

    def body(_, cs):
        for _ in range(UNROLL):
            cs = tuple(c * 0.9999 + 0.0001 for c in cs)
        return cs

    chains = jax.lax.fori_loop(0, ITERS // UNROLL, body, chains)
    o_ref[...] = sum(chains)


@functools.lru_cache(maxsize=None)
def _vpu_call():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    return jax.jit(pl.pallas_call(
        _vpu_kernel, grid=(GRID,),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ROWS, LANES), jnp.float32)))


def _rate(fn, arg, flops: float, reps: int) -> float:
    import jax

    jax.block_until_ready(fn(arg))        # compile and warm
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(arg)
    jax.block_until_ready(out)
    return flops * reps / (time.perf_counter() - t0)


def measure_f32_flops(log: Callable[[str], None]) -> float:
    """Measure both microkernels on the default device; log each rate
    and return the higher, in FLOP/s."""
    import jax
    import jax.numpy as jnp

    x = jnp.full((ROWS, LANES), 0.5, jnp.float32)
    vpu = _rate(_vpu_call(), x, 2.0 * CHAINS * ITERS * ROWS * LANES * GRID,
                reps=5)
    a = jnp.full((DOT_N, DOT_N), 0.5, jnp.float32)
    dot = jax.jit(lambda m: jnp.dot(m, m,
                                    precision=jax.lax.Precision.HIGHEST))
    mxu = _rate(dot, a, 2.0 * DOT_N ** 3, reps=10)
    del a, x
    log(f"peaks: f32 vector-unit multiply-add chain {vpu!r} FLOP/s; "
        f"f32 matmul at HIGHEST {mxu!r} FLOP/s; taking {max(vpu, mxu)!r}")
    return max(vpu, mxu)
