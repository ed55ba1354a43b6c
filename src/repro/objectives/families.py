"""Problem families: the representation layer of the serving stack.

A *family* owns everything about a problem class that the representation
determines — the chain-state dtype and per-chain shape, the deterministic
initial-state sampler, the known optimum lookup, the per-block operands a
packed group hands its sweep, and which sweep kernel runs — while the
serving machinery above it (slots, scheduler, engine tick loop, exchange
operators, checkpoint/restore) stays family-agnostic.  A request names its
family (``SARequest.family``) and an objective *within* that family;
dispatch groups are keyed by ``(family, dim, N)``, so heterogeneous
families co-batch in one fleet with one compiled device program per family
per shape.

Registered families
-------------------
``continuous``  : the six registry objectives (objective_math) — float32
                  states in a box, per-coordinate Metropolis moves, one
                  sweep program for the whole registry (runtime ``kid``).
``permutation`` : QAP instances (objectives/qap.py) — int32 permutation
                  states, pairwise-exchange Metropolis moves with O(n)
                  delta evaluation (kernels/qap_sweep.py), flow/distance
                  matrices threaded as per-request constant operands.

Both families ride the same placement-invariant counter-based RNG and the
same segmented exchange, so the engine's bit-exactness oracle
(``run_standalone`` / ``serve_sa --check``) holds for either —
across preemption, migration, drain, resize and macro-K fusion.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from repro.kernels import objective_math as om
from repro.kernels import ops
from repro.objectives import qap

FAMILY_CONTINUOUS = "continuous"
FAMILY_PERMUTATION = "permutation"

#: Known optima of the continuous registry objectives, by name (Schwefel is
#: the paper's normalized form, so its optimum is dim-free).  A continuous
#: request may set ``target_error`` only on an objective listed here
#: (``SAServeEngine.submit`` checks it through :meth:`ProblemFamily.f_opt`).
F_OPT_BY_NAME = {
    "schwefel": -418.982887,
    "rastrigin": 0.0,
    "ackley": 0.0,
    "griewank": 0.0,
    "exponential": -1.0,
    "salomon": 0.0,
}


class ProblemFamily:
    """One problem representation: state layout + samplers + optima +
    its sweep.

    Subclasses are stateless singletons; every method takes the request so
    a family never caches per-tenant data.  ``validate`` runs inside
    ``SARequest.__post_init__`` — family-incompatible fields fail eagerly
    with a typed ValueError at construction, never mid-tick.  A family is
    a static pytree (no leaves): handed to a jitted program as an
    ordinary argument, it selects that program's sweep and is part of its
    compiled signature, so each family compiles its own program.
    """

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        jax.tree_util.register_static(cls)

    #: family name — the ``SARequest.family`` value and dispatch-group key
    name: str = ""
    #: chain-state dtype of this family's slot blocks
    state_dtype: np.dtype = np.dtype(np.float32)

    def servable(self) -> Tuple[str, ...]:
        """Objective names servable under this family."""
        raise NotImplementedError

    def validate(self, req) -> None:
        """Family-specific request validation (typed ValueErrors)."""
        raise NotImplementedError

    def sample_x0(self, req, n_chains: int) -> np.ndarray:
        """Deterministic (n_chains, dim) initial states from ``req.seed``,
        independent of slot placement."""
        raise NotImplementedError

    def f_opt(self, req) -> Optional[float]:
        """Known optimum for ``req.objective`` (None if unregistered)."""
        raise NotImplementedError

    def block_operands(self, reqs: Sequence, n_padded: int) -> tuple:
        """The per-block runtime operands of one packed group's sweep:
        ``reqs[b]`` is the request of block ``b``; pad blocks, up to
        ``n_padded``, repeat block 0's.  Runtime inputs, so the group's
        objectives or instances never cause a recompile."""
        raise NotImplementedError

    def sweep(self, x, operands: tuple, T, seeds, step0s, chain_base, *,
              n_steps: int, blk: int, variant: str, use_pallas: bool,
              interpret: bool, live, T_chain):
        """One N-step sweep of every block of a packed group (traced
        inside the engine's group program): ``operands`` as
        :meth:`block_operands` packed them, the per-block controls of
        ``ops.metropolis_sweep_slots``.  Returns ``(x, fx)``."""
        raise NotImplementedError


class ContinuousFamily(ProblemFamily):
    """The paper's family: registry objectives over a float32 box."""

    name = FAMILY_CONTINUOUS
    state_dtype = np.dtype(np.float32)

    def servable(self) -> Tuple[str, ...]:
        return tuple(sorted(om.KID_BY_NAME))

    def validate(self, req) -> None:
        if req.objective not in om.KID_BY_NAME:
            raise ValueError(
                f"objective {req.objective!r} not servable; "
                f"one of {self.servable()}")

    def sample_x0(self, req, n_chains: int) -> np.ndarray:
        lo, hi = om.BOX[om.KID_BY_NAME[req.objective]]
        r = np.random.default_rng(req.seed)
        return (lo + r.random((n_chains, req.dim), dtype=np.float32)
                * (hi - lo)).astype(np.float32)

    def f_opt(self, req) -> Optional[float]:
        return F_OPT_BY_NAME.get(req.objective)

    def block_operands(self, reqs: Sequence, n_padded: int) -> tuple:
        kid = np.full((n_padded,), om.KID_BY_NAME[reqs[0].objective],
                      np.int32)
        kid[:len(reqs)] = [om.KID_BY_NAME[r.objective] for r in reqs]
        return (kid,)

    def sweep(self, x, operands, T, seeds, step0s, chain_base, *, n_steps,
              blk, variant, use_pallas, interpret, live, T_chain):
        (kid,) = operands
        return ops.metropolis_sweep_slots(
            x, kid, T, seeds, step0s, chain_base, n_steps=n_steps, blk=blk,
            variant=variant, use_pallas=use_pallas, interpret=interpret,
            live=live, T_chain=T_chain)


class PermutationFamily(ProblemFamily):
    """QAP: int32 permutation states, pairwise-exchange moves.

    Method restrictions are representational, not incidental: parallel
    tempering's rung layout and population annealing's Boltzmann-resample
    weights are defined on this stack only for the continuous sweep today,
    so ``method`` must be ``'sa'`` (all three ``exchange`` policies work —
    champion adoption copies permutations verbatim).
    """

    name = FAMILY_PERMUTATION
    state_dtype = np.dtype(np.int32)

    def servable(self) -> Tuple[str, ...]:
        return tuple(sorted(qap.INSTANCES))

    def validate(self, req) -> None:
        if req.objective not in qap.INSTANCES:
            raise ValueError(
                f"objective {req.objective!r} not servable by the "
                f"permutation family; one of {self.servable()}")
        inst = qap.INSTANCES[req.objective]
        if req.dim != inst.n:
            raise ValueError(
                f"request dim {req.dim} does not match QAP instance "
                f"{req.objective!r} size n={inst.n}")
        if req.pa_ess_ratio != 0.0:
            raise ValueError(
                "pa_ess_ratio is a population-annealing control and is "
                "invalid on a permutation-family request")
        if req.method != "sa":
            raise ValueError(
                f"method {req.method!r} is not supported by the "
                "permutation family (no temperature-rung replica layout "
                "or resampling weights for permutation states); use "
                "method='sa'")

    def sample_x0(self, req, n_chains: int) -> np.ndarray:
        # One generator, chains drawn in logical chain order — the
        # permutation analogue of the continuous box sampler, equally
        # placement-invariant.
        r = np.random.default_rng(req.seed)
        return np.stack(
            [r.permutation(req.dim) for _ in range(n_chains)]
        ).astype(np.int32)

    def f_opt(self, req) -> Optional[float]:
        return float(qap.INSTANCES[req.objective].best_known)

    def block_operands(self, reqs: Sequence, n_padded: int) -> tuple:
        # Flow and distance packed (n_padded * n, n): block b reads rows
        # [b*n, (b+1)*n), so mixed instances co-batch in one program.
        n = reqs[0].dim
        F = np.empty((n_padded * n, n), np.float32)
        D = np.empty((n_padded * n, n), np.float32)
        for b in range(n_padded):
            inst = qap.get(reqs[b if b < len(reqs) else 0].objective)
            F[b * n:(b + 1) * n] = inst.F
            D[b * n:(b + 1) * n] = inst.D
        return F, D

    def sweep(self, x, operands, T, seeds, step0s, chain_base, *, n_steps,
              blk, variant, use_pallas, interpret, live, T_chain):
        # Always delta-evaluated (bitwise a full evaluation), so no
        # variant; no T_chain, since a permutation request is never PT.
        F, D = operands
        return ops.qap_sweep_slots(
            x, F, D, T, seeds, step0s, chain_base, n_steps=n_steps, blk=blk,
            use_pallas=use_pallas, interpret=interpret, live=live)


CONTINUOUS = ContinuousFamily()
PERMUTATION = PermutationFamily()

#: The family registry: ``SARequest.family`` values -> singleton.
FAMILIES = {f.name: f for f in (CONTINUOUS, PERMUTATION)}


def get_family(name: str) -> ProblemFamily:
    if name not in FAMILIES:
        raise ValueError(
            f"unknown problem family {name!r}; one of "
            f"{tuple(sorted(FAMILIES))}")
    return FAMILIES[name]
