"""Measure the chip's float32 VPU element-op peak for ``bench/peaks.json``.

    python3 bench/calibrate.py

A Pallas kernel runs a dependent chain ``x = x * a + b`` (two element
operations) for many iterations on a tile that stays in VMEM and
vregs, over a grid of tiles; the rate is operations over the best
wall time of several calls.  Prints one JSON line with the best rate of
the tile shapes tried.  Exits 2 without a TPU.
"""
from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

ITERS = 20000
UNROLL = 8
BLOCKS = 64
SHAPES = ((8, 1024), (32, 512), (64, 512), (64, 1024))


def _chain(x_ref, o_ref, *, iters):
    a = jnp.float32(0.999999)
    b = jnp.float32(1e-7)

    def body(_i, x):
        for _ in range(UNROLL):
            x = x * a + b
        return x

    o_ref[...] = lax.fori_loop(0, iters // UNROLL, body, x_ref[...])


def rate(rows: int, cols: int, iters: int = ITERS, reps: int = 5) -> float:
    """Element operations per second of the chain on (rows, cols) tiles."""
    x = jnp.ones((rows * BLOCKS, cols), jnp.float32)
    call = jax.jit(pl.pallas_call(
        functools.partial(_chain, iters=iters), grid=(BLOCKS,),
        in_specs=[pl.BlockSpec((rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="vpu_chain"))
    call(x).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        call(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 2.0 * x.size * iters / best


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    rates = {f"{r}x{c}": rate(r, c) for r, c in SHAPES}
    for shape, r in rates.items():
        print(f"[calibrate] tile {shape}: {r:.6g} element ops/s",
              file=sys.stderr)
    print(json.dumps({"kind": dev.device_kind,
                      "vpu_f32_ops_per_s": max(rates.values()),
                      "rates": rates, "iters": ITERS, "blocks": BLOCKS}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
