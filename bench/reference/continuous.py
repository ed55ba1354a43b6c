"""Plain reference for the continuous family: parallel SA with sync exchange.

Written from the specification of a continuous request, independent of
the program under test (it imports nothing from ``repro``):

* the six test-suite objectives and their boxes;
* initial states: ``numpy.random.default_rng(seed).random((chains, dim),
  float32)`` scaled into the box, chain ``c`` in row ``c``;
* random streams: counter-based threefry2x32 (20 rounds).  Step ``s`` of
  chain ``c`` draws ``threefry(key=(seed, 2s), ctr=(c, 0))`` for the
  coordinate (first word, mod dim) and the new value (second word), and
  ``threefry(key=(seed, 2s+1), ctr=(c, 1))`` (first word) for the
  accept test; a uniform is ``(bits >> 8) * 2**-24``;
* level ``l`` runs ``N`` Metropolis steps per chain at ``T_l``, with
  ``T_0 = T0`` and ``T_{l+1} = rho * T_l`` (float64, rounded to float32),
  on the step counter ``l*N + i``; a proposal replaces one coordinate by
  a uniform value in the box and is accepted when ``u <= exp(-df/T)``;
* after each level the champion (lowest value, first chain on ties)
  is adopted by every chain (the paper's V2) and the best-so-far is
  kept (a new champion replaces it only when strictly lower).

Each proposal is evaluated in full, the paper's own formulation.  The
objective in float64 (:func:`objective_f64`) judges a served answer.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: Box of each objective (the paper's test suite).
BOX = {
    "schwefel": (-512.0, 512.0),
    "rastrigin": (-5.12, 5.12),
    "ackley": (-30.0, 30.0),
    "griewank": (-600.0, 600.0),
    "exponential": (-1.0, 1.0),
    "salomon": (-100.0, 100.0),
}

_ROTATIONS = (13, 15, 26, 6, 17, 29, 16, 24)


def objective(name: str, x, xp=jnp):
    """Objective value of each row of ``x`` (rows, dim) -> (rows,).

    ``xp`` is ``jax.numpy`` (the annealer, in the dtype of ``x``) or
    ``numpy`` (the float64 judge)."""
    dim = x.shape[-1]
    pi = xp.asarray(math.pi, x.dtype)
    if name == "schwefel":          # normalized: divided by the dimension
        return -xp.sum(x * xp.sin(xp.sqrt(xp.abs(x))), axis=-1) / dim
    if name == "rastrigin":
        return 10.0 * dim + xp.sum(x * x - 10.0 * xp.cos(2 * pi * x), axis=-1)
    if name == "ackley":
        r2 = xp.sum(x * x, axis=-1) / dim
        c = xp.sum(xp.cos(2 * pi * x), axis=-1) / dim
        return (-20.0 * xp.exp(-0.2 * xp.sqrt(r2)) - xp.exp(c) + 20.0
                + xp.asarray(math.e, x.dtype))
    if name == "griewank":
        i = xp.arange(1, dim + 1, dtype=x.dtype)
        return (1.0 + xp.sum(x * x, axis=-1) / 4000.0
                - xp.prod(xp.cos(x / xp.sqrt(i)), axis=-1))
    if name == "exponential":
        return -xp.exp(-0.5 * xp.sum(x * x, axis=-1))
    if name == "salomon":
        r = xp.sqrt(xp.sum(x * x, axis=-1))
        return 1.0 - xp.cos(2 * pi * r) + 0.1 * r
    raise ValueError(f"no reference for objective {name!r}")


def objective_f64(name: str, x) -> np.ndarray:
    """The objective in float64 on the host: the judge of an answer."""
    return objective(name, np.asarray(x, np.float64), xp=np)


def initial_states(name: str, dim: int, n_chains: int, seed: int):
    """The request's initial states, float32 (n_chains, dim)."""
    lo, hi = BOX[name]
    u = np.random.default_rng(seed).random((n_chains, dim), dtype=np.float32)
    return (lo + u * (hi - lo)).astype(np.float32)


def ladder(T0: float, rho: float, n_levels: int) -> np.ndarray:
    """Level temperatures, iterated in float64 and rounded to float32."""
    out, t = [], float(T0)
    for _ in range(n_levels):
        out.append(t)
        t *= rho
    return np.asarray(out, np.float64).astype(np.float32)


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32, 20 rounds, on uint32 arrays."""
    def rotl(v, r):
        return (v << np.uint32(r)) | (v >> np.uint32(32 - r))

    ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(20):
        x0 = x0 + x1
        x1 = rotl(x1, _ROTATIONS[i % 8]) ^ x0
        if i % 4 == 3:
            j = i // 4 + 1
            x0 = x0 + ks[j % 3]
            x1 = x1 + ks[(j + 1) % 3] + np.uint32(j)
    return x0, x1


def _uniform(bits, dtype):
    return ((bits >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * np.float32(2.0 ** -24)).astype(dtype)


@partial(jax.jit, static_argnames=("name",))
def _level(x, f, T, seed, step0, n_steps, *, name: str):
    """``n_steps`` Metropolis steps of every chain at temperature ``T``."""
    rows, dim = x.shape
    dtype = x.dtype
    lo, hi = (jnp.asarray(b, dtype) for b in BOX[name])
    chain = jnp.arange(rows, dtype=jnp.uint32)
    col = jnp.arange(dim, dtype=jnp.uint32)[None, :]
    zeros = jnp.zeros_like(chain)

    def step(i, carry):
        x, f = carry
        s = step0 + i.astype(jnp.uint32)
        a, b = threefry2x32(seed, s * np.uint32(2), chain, zeros)
        c, _ = threefry2x32(seed, s * np.uint32(2) + np.uint32(1), chain,
                            zeros + np.uint32(1))
        coord = a % np.uint32(dim)
        value = lo + _uniform(b, dtype) * (hi - lo)
        x1 = jnp.where(col == coord[:, None], value[:, None], x)
        f1 = objective(name, x1)
        accept = _uniform(c, dtype) <= jnp.exp(jnp.minimum(-(f1 - f) / T, 0))
        return (jnp.where(accept[:, None], x1, x), jnp.where(accept, f1, f))

    return lax.fori_loop(0, n_steps, step, (x, f))


@partial(jax.jit, static_argnames=("name",))
def _evaluate(x, *, name: str):
    return objective(name, x)


def anneal(name: str, dim: int, n_chains: int, seed: int, T0: float,
           rho: float, N: int, levels: int, dtype=jnp.float32, start=None):
    """Anneal one request for ``levels`` levels, computing in ``dtype``.

    ``start``, ``(level0, x0)``, resumes at level ``level0`` from the chain
    states ``x0`` (n_chains, dim) instead of starting from the request's
    initial states at level 0.  Returns ``(history, x_best, f_best)``: the
    best-so-far value after each level run (float64 list), and the
    champion state and value.
    """
    level0, x = ((0, initial_states(name, dim, n_chains, seed))
                 if start is None else start)
    x = jnp.asarray(x, dtype)
    f = _evaluate(x, name=name)
    key = np.uint32(seed & 0xFFFFFFFF)
    best_f, best_x, history = math.inf, None, []
    temps = ladder(T0, rho, level0 + levels)[level0:]
    for lvl, T in enumerate(temps, start=level0):
        x, f = _level(x, f, jnp.asarray(T, dtype), key,
                      np.uint32(lvl * N), np.int32(N), name=name)
        i = int(jnp.argmin(f))
        champ_f = float(f[i])
        if champ_f < best_f:
            best_f, best_x = champ_f, np.asarray(x[i], np.float64)
        history.append(best_f)
        x = jnp.broadcast_to(x[i], x.shape)            # sync: all adopt
        f = jnp.broadcast_to(f[i], f.shape)
    return history, best_x, best_f


def _gap(a: float, b: float) -> float:
    """|a - b| relative to |b|, or absolute where |b| is below 1."""
    return abs(a - b) / max(abs(b), 1.0)


def judge(req, result, history_levels, late=None) -> dict:
    """The numbers of one served answer (``result``: ``x_best``,
    ``f_best``, ``champion_history`` and ``granted_chains``), against
    this reference in float32.

    * ``value_gap``: the answer's value against the objective of its own
      state in float64 (``inf`` for a state outside the box or not
      finite);
    * ``history_gap``: the widest gap of the served best-so-far against
      this reference's replay of the same levels: the first
      ``history_levels`` levels (None: all) replayed from the request's
      seed, and, where ``late`` is ``(level0, x0)``, the levels served
      after ``level0`` replayed from the chain states ``x0`` the program
      held there.  It covers the served path's sweep, exchange and fold,
      level by level.
    """
    name = req.objective
    lo, hi = BOX[name]
    x = np.asarray(result.x_best, np.float64)
    f = float(result.f_best)
    if (x.shape != (req.dim,) or not np.all(np.isfinite(x))
            or np.any(x < lo) or np.any(x > hi) or not math.isfinite(f)):
        value_gap = math.inf
    else:
        value_gap = _gap(f, float(objective_f64(name, x[None])[0]))
    served = [float(h) for h in result.champion_history]
    chains = int(result.granted_chains)
    head = served[:history_levels]
    ref, _, _ = anneal(name, req.dim, chains, req.seed, req.T0, req.rho,
                       req.N, len(head))
    gaps = [_gap(h, r) for h, r in zip(head, ref)]
    if late is not None:
        level0, x0 = late
        tail = served[level0:]
        before = served[level0 - 1] if level0 else math.inf
        ref, _, _ = anneal(name, req.dim, chains, req.seed, req.T0, req.rho,
                           req.N, len(tail), start=(level0, x0))
        gaps += [_gap(h, min(before, r)) for h, r in zip(tail, ref)]
        gaps += [math.inf] * (not tail)
    return {"value_gap": value_gap,
            "history_gap": max(gaps, default=math.inf)}
