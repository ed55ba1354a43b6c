"""Simulated annealing driver (paper §2): V0 sequential, V1 asynchronous,
V2 synchronous — all as one configurable engine.

The CUDA design launches one kernel per temperature level (V2) or one kernel
for the whole ladder (V1).  On TPU we compile the *entire* annealing ladder
into a single XLA program: ``lax.scan`` over the geometric temperature
ladder, each step being a Metropolis sweep + (optional) exchange collective.
This removes the per-level host round trip entirely (DESIGN.md §8.1).

Communication semantics are faithful to the paper:
* ``async`` (V1): zero communication until a single final champion reduce.
* ``sync``  (V2): one champion all-gather per temperature level.
* best-so-far tracking is purely local; the final reduce folds it in.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import exchange as exch
from repro.core import metropolis
from repro.objectives.base import Objective


@dataclasses.dataclass(frozen=True)
class SAConfig:
    """Annealing schedule + parallelization configuration (paper notation)."""

    T0: float = 1000.0          # initial temperature
    T_min: float = 0.01         # target (stop) temperature
    rho: float = 0.99           # geometric cooling factor
    N: int = 100                # Markov chain length per level
    n_chains: int = 16384       # w: number of parallel chains (b*g in paper)
    exchange: str = "sync"      # 'async' (V1) | 'sync' (V2) | 'sos'
    exchange_period: int = 1    # levels between exchanges (1 = every level)
    seed: int = 0
    dtype: str = "float32"      # paper Table 7: fp32 default
    use_delta_eval: bool = False  # beyond-paper O(1) delta evaluation
    record_history: bool = True   # per-level champion trace (plots/benchmarks)
    unroll: bool = False          # unroll ladder+sweeps (cost measurement)

    @property
    def n_levels(self) -> int:
        """Number of executed temperature levels (paper's do/while loop)."""
        return max(1, int(math.ceil(math.log(self.T_min / self.T0)
                                    / math.log(self.rho))))

    @property
    def n_evals(self) -> int:
        """Total objective evaluations (paper's 'function evaluations')."""
        return self.n_levels * self.N * self.n_chains

    def ladder(self) -> np.ndarray:
        k = np.arange(self.n_levels)
        return (self.T0 * self.rho ** k).astype(self.dtype)


@dataclasses.dataclass
class SAResult:
    x_best: np.ndarray        # (dim,)
    f_best: float
    history_f: Optional[np.ndarray]  # per-level champion objective value
    n_evals: int
    config: SAConfig
    objective_name: str = ""


def _level_body(carry, xs, *, objective, cfg: SAConfig, axis_names):
    """One temperature level: Metropolis sweep of length N, then exchange."""
    T, lvl = xs
    key, x, fx, best_x, best_f = carry
    sweep = metropolis.sweep_delta if cfg.use_delta_eval else metropolis.sweep_full
    key, x, fx = sweep(key, x, fx, T, objective=objective, n_steps=cfg.N,
                       unroll=cfg.unroll)

    key, kx = jax.random.split(key)
    if cfg.exchange != "async":
        exchange_fn = exch.EXCHANGES[cfg.exchange]
        if cfg.exchange_period > 1:
            do_ex = (lvl % cfg.exchange_period) == 0
            x2, fx2 = exchange_fn(kx, x, fx, T, axis_names)
            x = jnp.where(do_ex, x2, x)
            fx = jnp.where(do_ex, fx2, fx)
        else:
            x, fx = exchange_fn(kx, x, fx, T, axis_names)

    # Local best-so-far tracking (no communication; the final reduce is global).
    xb, fb = exch.local_champion(x, fx)
    better = fb < best_f
    best_x = jnp.where(better, xb, best_x)
    best_f = jnp.where(better, fb, best_f)

    y = best_f if cfg.record_history else ()
    return (key, x, fx, best_x, best_f), y


def _run_ladder(key, x0, *, objective: Objective, cfg: SAConfig,
                axis_names: Optional[Sequence[str]] = None):
    """Run the full annealing ladder on a local block of chains.

    Callable directly (single device) or inside ``shard_map`` (chains axis
    sharded over the mesh; ``axis_names`` names the mesh axes to reduce over).
    """
    ladder = jnp.asarray(cfg.ladder())
    levels = jnp.arange(cfg.n_levels, dtype=jnp.int32)
    fx = objective(x0)
    best_x, best_f = exch.local_champion(x0, fx)
    body = partial(_level_body, objective=objective, cfg=cfg, axis_names=axis_names)
    carry0 = (key, x0, fx, best_x, best_f)
    (key, x, fx, best_x, best_f), hist = lax.scan(
        body, carry0, (ladder, levels),
        unroll=cfg.n_levels if cfg.unroll else 1)

    # Single final champion reduce (the paper V1's reduceMin; a refinement
    # no-op for V2).  Folds the carried best into the candidate set.
    xa = jnp.concatenate([x, best_x[None, :]], axis=0)
    fa = jnp.concatenate([fx, best_f[None]], axis=0)
    best_x, best_f = exch.global_champion(xa, fa, axis_names)
    return best_x, best_f, hist


def sa_minimize(objective: Objective, cfg: SAConfig,
                key: Optional[jax.Array] = None,
                x0: Optional[jnp.ndarray] = None,
                mesh: Optional[jax.sharding.Mesh] = None,
                mesh_axes: Optional[Sequence[str]] = None) -> SAResult:
    """Minimize ``objective`` with parallel SA.

    Without ``mesh``: all chains run on the local default device.
    With ``mesh``: chains are sharded over ``mesh_axes`` via ``shard_map``;
    the exchange becomes a hierarchical champion all-gather (DESIGN.md §2).
    """
    if key is None:
        key = jax.random.PRNGKey(cfg.seed)
    dtype = jnp.dtype(cfg.dtype)

    key, k0 = jax.random.split(key)
    if x0 is None:
        x0c = objective.sample_uniform(k0, (cfg.n_chains,)).astype(dtype)
    else:
        x0c = jnp.broadcast_to(jnp.asarray(x0, dtype), (cfg.n_chains, objective.dim))

    if mesh is None:
        run = jax.jit(partial(_run_ladder, objective=objective, cfg=cfg))
        best_x, best_f, hist = run(key, x0c)
    else:
        run = jax.jit(build_sharded_ladder(objective, cfg, mesh, mesh_axes))
        best_x, best_f, hist = run(key, x0c)

    has_hist = cfg.record_history and not isinstance(hist, tuple)
    return SAResult(
        x_best=np.asarray(best_x),
        f_best=float(best_f),
        history_f=np.asarray(hist) if has_hist else None,
        n_evals=cfg.n_evals,
        config=cfg,
        objective_name=objective.name,
    )


def build_sharded_ladder(objective: Objective, cfg: SAConfig,
                         mesh: jax.sharding.Mesh,
                         mesh_axes: Optional[Sequence[str]] = None):
    """The shard_map'd annealing program: chains sharded over ``mesh_axes``.

    Returned callable takes (key, x0_global) and is what the multi-pod
    dry-run lowers (launch/dryrun.py, SA production cell).
    """
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh_axes if mesh_axes is not None else mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    if cfg.n_chains % n_shards:
        raise ValueError(
            f"n_chains={cfg.n_chains} not divisible by mesh size {n_shards}")

    # Distributed V1 must stay communication-free mid-run: a per-level global
    # history would contradict it, so disable history there (DESIGN.md §8).
    cfg_l = cfg
    if cfg.exchange == "async" and cfg.record_history:
        cfg_l = dataclasses.replace(cfg, record_history=False)

    def sharded(key, x0c):
        # Per-shard independent streams: fold the shard index in.
        idx = lax.axis_index(axes)
        key_local = jax.random.fold_in(key, idx)
        bx, bf, hist = _run_ladder(key_local, x0c, objective=objective,
                                   cfg=cfg_l, axis_names=axes)
        return bx, bf, hist

    hist_spec = P() if cfg_l.record_history else ()
    return jax.shard_map(
        sharded, mesh=mesh,
        in_specs=(P(), P(axes)),
        out_specs=(P(), P(), hist_spec),
        check_vma=False,
    )
