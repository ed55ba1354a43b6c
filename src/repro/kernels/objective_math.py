"""Kernel-side objective math for the registry objectives.

Shared by the Pallas kernel (``metropolis_sweep.py``) and the pure-jnp
oracle (``ref.py``) so both compute identical floating-point expressions.

Accumulator layout (uniform across objectives, unused slots stay zero):
  S    : pair of (..., 1) sum accumulators (S0, S1)
  logP : (..., 1)  log-magnitude of the product accumulator
  sgnP : (..., 1)  sign (+-1) of the product accumulator

Every expression stays on 2-D ``(chains, dim)`` tiles or ``(chains, 1)``
columns: rank-3 ``(..., dim, k)`` intermediates pad every ``(dim, k)``
slice to a full vreg tile, which at ``blk=256`` overflows VMEM.  The
Griewank product is a static tree of lane slices (``_lane_tree``): the
Pallas TPU lowering has no ``reduce_prod``.

Two dispatch surfaces per primitive:

* static (``full_eval``, ``term``, ``init_acc``, ``combine``, ``BOX``) —
  ``kid`` is a Python int, one branch is traced.  Compile-time specialised;
  adding an objective recompiles every caller.
* runtime (``*_rt``, ``box_rt``) — ``kid`` is a traced int32 (a scalar read
  from SMEM in the kernel, a per-chain column in the oracle).  Every
  registry branch is evaluated and the right one is chosen with a
  branchless ``jnp.where`` chain, so one compiled program serves all
  registry objectives and growing the registry never costs a recompile.
  Each branch computes the *identical* floating-point expression as its
  static counterpart (select returns the branch value verbatim; garbage in
  unselected branches is discarded, never propagated).  Two callers using
  runtime dispatch are therefore bit-exact with each other — the serving
  engine's placement/preemption/migration invariants rest on this.  A
  runtime-dispatch program versus the *static* single-branch lowering is
  the same math in two different XLA programs: trajectories (states and
  accept/reject decisions) agree bitwise, but fusion may contract the
  delta-variant's cached accumulator differently at the last ULP, so that
  comparison is held to ULP tolerance in tests, not bitwise.
"""
from __future__ import annotations

import operator

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

KID_SCHWEFEL = 0
KID_RASTRIGIN = 1
KID_ACKLEY = 2
KID_GRIEWANK = 3
KID_EXPONENTIAL = 4
KID_SALOMON = 5

KID_BY_NAME = {
    "schwefel": KID_SCHWEFEL,
    "rastrigin": KID_RASTRIGIN,
    "ackley": KID_ACKLEY,
    "griewank": KID_GRIEWANK,
    "exponential": KID_EXPONENTIAL,
    "salomon": KID_SALOMON,
}
# Uniform box per registry objective.
BOX = {
    KID_SCHWEFEL: (-512.0, 512.0),
    KID_RASTRIGIN: (-5.12, 5.12),
    KID_ACKLEY: (-30.0, 30.0),
    KID_GRIEWANK: (-600.0, 600.0),
    KID_EXPONENTIAL: (-1.0, 1.0),
    KID_SALOMON: (-100.0, 100.0),
}
N_KIDS = len(KID_BY_NAME)

_PI = np.float32(np.pi)
_E = np.float32(np.e)
_TINY = np.float32(1e-30)


def full_eval(kid: int, x, dim: int):
    """Full objective evaluation; x: (..., dim) -> (..., 1)."""
    if kid == KID_SCHWEFEL:
        f = -jnp.sum(x * jnp.sin(jnp.sqrt(jnp.abs(x))), -1, keepdims=True) / dim
    elif kid == KID_RASTRIGIN:
        f = 10.0 * dim + jnp.sum(x * x - 10.0 * jnp.cos(2 * _PI * x), -1, keepdims=True)
    elif kid == KID_ACKLEY:
        s1 = jnp.sum(x * x, -1, keepdims=True)
        s2 = jnp.sum(jnp.cos(2 * _PI * x), -1, keepdims=True)
        f = (-20.0 * jnp.exp(-0.2 * jnp.sqrt(s1 / dim))
             - jnp.exp(s2 / dim) + 20.0 + _E)
    elif kid == KID_GRIEWANK:
        # In-trace iota (not a jnp.arange constant): Pallas kernels reject
        # captured non-scalar constants, so the index vector must be an op.
        i = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1).astype(x.dtype)
        s = jnp.sum(x * x, -1, keepdims=True) / 4000.0
        f = 1.0 + s - _lane_tree(jnp.cos(x / jnp.sqrt(i + 1.0)),
                                  operator.mul)
    elif kid == KID_EXPONENTIAL:
        f = -jnp.exp(-0.5 * jnp.sum(x * x, -1, keepdims=True))
    elif kid == KID_SALOMON:
        r = jnp.sqrt(jnp.sum(x * x, -1, keepdims=True))
        f = 1.0 - jnp.cos(2 * _PI * r) + 0.1 * r
    else:
        raise ValueError(f"unknown kernel objective id {kid}")
    return f.astype(x.dtype)


def _lane_tree(v, op):
    """Reduce the last axis with ``op`` over a static halving tree of lane
    slices: ``log2(dim)`` full-width elementwise steps in an order fixed
    here, so every program rounds them the same way whatever its shape."""
    odd = []
    while v.shape[-1] > 1:
        w = v.shape[-1]
        h = w // 2
        if w % 2:
            odd.append(v[..., w - 1:w])
        v = op(v[..., :h], v[..., h:2 * h])
    for col in odd:
        v = op(v, col)
    return v


def lane_sum(v):
    """Sum over the last axis, keepdims: the kernel's lane reduction."""
    return jnp.sum(v, -1, keepdims=True)


def lane_sum_tree(v):
    """Sum over the last axis, keepdims, in :func:`_lane_tree` order: the
    oracle's form.  XLA:CPU rounds a fused row reduction differently from
    one program shape to another, and the serving oracle compares a
    packed batch with a standalone one bitwise."""
    return _lane_tree(v, operator.add)


def term(kid: int, xi, d):
    """Per-coordinate contributions, elementwise over any shape.

    Returns ``((s0, s1), p)``: the two sum terms and the product term,
    each shaped like ``xi``.
    """
    z = jnp.zeros_like(xi)
    one = jnp.ones_like(xi)
    if kid == KID_SCHWEFEL:
        return (xi * jnp.sin(jnp.sqrt(jnp.abs(xi))), z), one
    if kid == KID_RASTRIGIN:
        return (xi * xi - 10.0 * jnp.cos(2 * _PI * xi), z), one
    if kid == KID_ACKLEY:
        return (xi * xi, jnp.cos(2 * _PI * xi)), one
    if kid == KID_GRIEWANK:
        p = jnp.cos(xi / jnp.sqrt(d.astype(xi.dtype) + 1.0))
        return (xi * xi / 4000.0, z), p
    if kid in (KID_EXPONENTIAL, KID_SALOMON):
        # Both reduce to the radial sum S0 = Σ x_i²; combine() does the rest.
        return (xi * xi, z), one
    raise ValueError(f"unknown kernel objective id {kid}")


def init_acc(kid: int, x, reduce=lane_sum):
    """Exact O(dim) accumulator init from the state block x: (..., dim).

    ``reduce`` is :func:`lane_sum` (the kernel) or :func:`lane_sum_tree`
    (the oracle).
    """
    d = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1).astype(x.dtype)
    (s0, s1), p = term(kid, x, d)
    S = (reduce(s0), reduce(s1))
    logP = reduce(jnp.log(jnp.maximum(jnp.abs(p), _TINY)))
    # The sign of a product of +-1 factors is the parity of the negative
    # count: exact, and a sum where a product would need reduce_prod.
    n_neg = reduce(jnp.where(p < 0, 1.0, 0.0))
    sgnP = jnp.where((n_neg.astype(jnp.int32) & 1) == 1, -1.0, 1.0)
    return S, logP, sgnP.astype(x.dtype)


def combine(kid: int, S, logP, sgnP, dim: int):
    """Accumulators -> objective value (..., 1)."""
    S0, S1 = S
    if kid == KID_SCHWEFEL:
        return -S0 / dim
    if kid == KID_RASTRIGIN:
        return 10.0 * dim + S0
    if kid == KID_ACKLEY:
        return (-20.0 * jnp.exp(-0.2 * jnp.sqrt(S0 / dim))
                - jnp.exp(S1 / dim) + 20.0 + _E)
    if kid == KID_GRIEWANK:
        P = sgnP * jnp.exp(logP)
        return 1.0 + S0 - P
    if kid == KID_EXPONENTIAL:
        return -jnp.exp(-0.5 * S0)
    if kid == KID_SALOMON:
        r = jnp.sqrt(S0)
        return 1.0 - jnp.cos(2 * _PI * r) + 0.1 * r
    raise ValueError(f"unknown kernel objective id {kid}")


# --------------------------------------------------------------------------
# Runtime dispatch: kid is a traced int32, not a Python int.  Every branch
# below is the *static* implementation above, so a select at runtime yields
# the same bits as compiling the branch in.  Branchless by construction —
# no lax.switch — which keeps the Pallas TPU lowering trivial (the VPU has
# no divergence to worry about, only redundant lanes).
def box_rt(kid, dtype=jnp.float32):
    """Per-kid box bounds. kid: traced int (any shape). Returns (lo, hi)
    broadcast to kid's shape."""
    lo = jnp.full_like(kid, BOX[0][0], dtype=dtype)
    hi = jnp.full_like(kid, BOX[0][1], dtype=dtype)
    for k in range(1, N_KIDS):
        lo = jnp.where(kid == k, np.float32(BOX[k][0]), lo)
        hi = jnp.where(kid == k, np.float32(BOX[k][1]), hi)
    return lo, hi


def full_eval_rt(kid, x, dim: int):
    """Runtime-kid full_eval; kid broadcastable to (..., 1)."""
    f = full_eval(0, x, dim)
    for k in range(1, N_KIDS):
        f = jnp.where(kid == k, full_eval(k, x, dim), f)
    return f


def _select(kid, k, new, old):
    """``jnp.where(kid == k, new, old)`` leaf by leaf over a pytree."""
    return jax.tree.map(lambda a, b: jnp.where(kid == k, a, b), new, old)


def term_rt(kid, xi, d):
    """Runtime-kid term; kid broadcastable to xi."""
    out = term(0, xi, d)
    for k in range(1, N_KIDS):
        out = _select(kid, k, term(k, xi, d), out)
    return out


def init_acc_rt(kid, x, reduce=lane_sum):
    """Runtime-kid init_acc; kid broadcastable to (..., 1)."""
    out = init_acc(0, x, reduce)
    for k in range(1, N_KIDS):
        out = _select(kid, k, init_acc(k, x, reduce), out)
    return out


def combine_rt(kid, S, logP, sgnP, dim: int):
    """Runtime-kid combine; kid broadcastable to (..., 1)."""
    f = combine(0, S, logP, sgnP, dim)
    for k in range(1, N_KIDS):
        f = jnp.where(kid == k, combine(k, S, logP, sgnP, dim), f)
    return f
