"""Smoke test of the SA serving engine on TPU chips, end to end.

Drives ``SAServeEngine.submit`` / ``run_stream`` with the Pallas kernels
(not interpret mode) and checks what comes out.  Run from the checkout
root on a machine with a TPU::

    python chip_smoke.py               # one chip: paper-scale job + mix
    python chip_smoke.py --four-chips  # the sharded slot pool, 4 chips

One chip runs two phases on ``EngineConfig(n_slots=64,
chains_per_slot=256)``, 16384 resident chains:

* **paper**: one normalized-Schwefel job at the paper's full chain budget
  and width (d=512, 16384 chains, T0=1000, rho=0.99, N=100;
  ``benchmarks/table1_accuracy.py``), with ``T_min`` raised so the ladder
  is 40 levels;
* **mix**: ``serve_sa.make_mix``'s 32-request multi-tenant mix (all six
  objectives, d 8-32, the three ``MIX_SCHEDULES``) under seeded open-loop
  Poisson arrivals.

``--four-chips`` runs only the sharded pool: the mix as a stream on
``EngineConfig(n_devices=4)`` with one shard drained mid-stream.

Every phase checks that each champion is finite and bit-exact against
its single-chip ``run_standalone`` replay; the one-chip phases also serve
the same requests through XLA's jnp reference (``use_pallas=False``) and
compare.  All work is generated from ``--seed``.  The numbers printed are
smoke numbers from one run, not benchmark results.  The last line of
stdout is one JSON object: ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits non-zero before any work and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.service import engine as engine_mod  # noqa: E402
from repro.service.arrivals import ArrivalProcess  # noqa: E402
from repro.service.engine import EngineConfig, SAServeEngine  # noqa: E402
from repro.service.request import SARequest  # noqa: E402
from repro.service.serve_sa import make_mix, standalone_replay  # noqa: E402
from repro.service.telemetry import compile_seconds  # noqa: E402

#: The one-chip engine shape: 64 slots x 256 chains = 16384 resident
#: chains, the paper's chain budget (benchmarks/table1_accuracy.py).
CHIP_CFG = EngineConfig(n_slots=64, chains_per_slot=256)

#: The paper's full-budget Schwefel job.  The paper anneals to
#: T_min=0.01 (916 levels); T_min=670 keeps the first 40 of them.
PAPER_REQ = SARequest(req_id=0, objective="schwefel", dim=512,
                      n_chains=16384, T0=1000.0, T_min=670.0, rho=0.99,
                      N=100)

MIX_REQUESTS = 32
MIX_RATE = 0.5          # Poisson arrivals per tick (serve_sa's default)
DRAIN_TICK = 12         # four-chip phase: drain the last shard here

#: Kernel vs XLA reference: ``|f_kernel - f_ref| <= REF_ATOL + REF_RTOL *
#: |f_ref|`` on each champion value, the kernel-vs-oracle tolerance of
#: the interpret-mode tests (tests/test_kernels_pallas.py).  The two
#: programs compute the same float32 expressions on the same random
#: streams, but Mosaic and XLA implement sin/cos/exp/log and sum lanes
#: differently, so values differ in the last bits; an accept test whose
#: uniform falls inside that gap flips and the two trajectories part,
#: ending in the same basin at the ladder's cold end.  The absolute term
#: covers champions at an optimum of 0, where a relative bound means
#: nothing.  On a v5e the widest gaps were 3.05e-5 (schwefel at -419,
#: one float32 ULP, same state) and 7.6e-6 (rastrigin d=8, 1.282e-3 vs
#: 1.289e-3, trajectories parted).
REF_RTOL = 2e-3
REF_ATOL = 2e-3


class CheckFailed(Exception):
    """A smoke check did not hold; the message says which."""


@dataclasses.dataclass
class Served:
    """One engine's run over a list of requests."""

    engine: SAServeEngine
    reqs: list
    results: dict           # req_id -> RequestResult
    wall_s: float
    compile_s: float        # backend compile seconds during the run
    shard_devices: list     # each shard's device when the run started


def serve(cfg: EngineConfig, reqs, arrivals=None, setup=None) -> Served:
    """Serve ``reqs`` on a fresh engine: closed loop, or open loop on
    ``arrivals``.  ``setup(engine)`` may script fleet operations first."""
    engine = SAServeEngine(cfg)
    devices = [s.device for s in engine.shards]
    if setup is not None:
        setup(engine)
    c0, t0 = compile_seconds(), time.perf_counter()
    if arrivals is None:
        for req in reqs:
            engine.submit(req)
        results = engine.run()
    else:
        results = engine.run_stream(arrivals)
    wall = time.perf_counter() - t0
    return Served(engine, list(reqs), {r.req_id: r for r in results}, wall,
                  compile_seconds() - c0, devices)


def check_served(name: str, served: Served, cfg: EngineConfig) -> None:
    """Every request completed with a finite champion that equals its
    single-chip standalone replay bitwise."""
    bad = []
    for req in served.reqs:
        res = served.results.get(req.req_id)
        if res is None or not res.completed:
            bad.append(f"req {req.req_id} did not complete")
            continue
        if not math.isfinite(res.f_best):
            bad.append(f"req {req.req_id} f_best={res.f_best}")
            continue
        solo = standalone_replay(req, res, cfg)
        if res.f_best != solo.f_best or not np.array_equal(
                res.x_best, solo.x_best):
            bad.append(f"req {req.req_id} packed f_best={res.f_best!r} != "
                       f"standalone {solo.f_best!r}")
    if bad:
        raise CheckFailed(f"{name}: " + "; ".join(bad))


def compare_reference(name: str, kern: Served, ref: Served) -> list:
    """Kernel champions against the XLA reference's: one report line per
    request, and CheckFailed outside :data:`REF_ATOL` + :data:`REF_RTOL`."""
    lines, bad = [], []
    for req in kern.reqs:
        fk = kern.results[req.req_id].f_best
        fr = ref.results[req.req_id].f_best
        if not math.isfinite(fr):
            bad.append(f"req {req.req_id} reference f_best={fr}")
            continue
        rel = abs(fk - fr) / max(abs(fr), 1e-30)
        same_x = np.array_equal(kern.results[req.req_id].x_best,
                                ref.results[req.req_id].x_best)
        lines.append(f"{name} req{req.req_id} {req.objective} d={req.dim} "
                     f"kernel={fk!r} reference={fr!r} rel={rel:.3e} "
                     f"same_x={same_x}")
        if abs(fk - fr) > REF_ATOL + REF_RTOL * abs(fr):
            bad.append(f"req {req.req_id} |{fk!r} - {fr!r}| > "
                       f"{REF_ATOL} + {REF_RTOL} * |{fr!r}|")
    if bad:
        raise CheckFailed(f"{name} vs reference: " + "; ".join(bad))
    return lines


def mix_requests(cfg: EngineConfig, n_requests: int, seed: int):
    reqs = make_mix(n_requests, cfg.chains_per_slot, seed=seed)
    return reqs, ArrivalProcess.poisson(reqs, rate=MIX_RATE, seed=seed)


def paper_phase(cfg: EngineConfig, req: SARequest) -> tuple:
    """The paper-scale job, its replay and its reference run."""
    kern = serve(cfg, [req])
    check_served("paper", kern, cfg)
    ref = serve(dataclasses.replace(cfg, use_pallas=False), [req])
    return kern, compare_reference("paper", kern, ref)


def mix_phase(cfg: EngineConfig, n_requests: int, seed: int) -> tuple:
    """The multi-tenant mix as an open-loop stream, replays, reference."""
    reqs, arrivals = mix_requests(cfg, n_requests, seed)
    kern = serve(cfg, reqs, arrivals)
    check_served("mix", kern, cfg)
    _, arrivals = mix_requests(cfg, n_requests, seed)
    ref = serve(dataclasses.replace(cfg, use_pallas=False), reqs, arrivals)
    return kern, compare_reference("mix", kern, ref)


def four_chip_phase(cfg: EngineConfig, n_requests: int, seed: int,
                    drain_tick: int = DRAIN_TICK) -> tuple:
    """The mix on a sharded pool with the last shard drained mid-stream;
    every champion against its single-chip replay.  Returns the run and
    the number of jobs the drain had to evacuate."""
    reqs, arrivals = mix_requests(cfg, n_requests, seed)
    evacuated = []

    def drain_last(engine):
        last = engine.shards[-1]

        def drain():
            evacuated.append(len(last.jobs))
            engine.drain(last.index)
        engine.schedule_op(drain_tick, drain)

    kern = serve(cfg, reqs, arrivals, setup=drain_last)
    if not evacuated or not evacuated[0]:
        raise CheckFailed(f"four-chip: the shard drained at tick "
                          f"{drain_tick} held no job")
    if not kern.engine.retired_shards:
        raise CheckFailed("four-chip: the drained shard never retired")
    check_served("four-chip", kern, cfg)
    return kern, evacuated[0]


# ------------------------------------------------------------ device checks
_PROGRAMS = ("_group_tick", "_group_tick_fused", "_group_tick_qap",
             "_group_tick_qap_fused")


@contextlib.contextmanager
def capture_programs():
    """Record the abstract arguments of the first call of each engine
    device program, so the same programs can be compiled again and
    their HLO inspected (:func:`check_kernels_in_programs`)."""
    captured = {}
    originals = {n: getattr(engine_mod, n) for n in _PROGRAMS}

    def spy(name, fn):
        def call(*args, **kwargs):
            if name not in captured:
                captured[name] = (jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype),
                    args), kwargs)
            return fn(*args, **kwargs)
        return call

    for name, fn in originals.items():
        setattr(engine_mod, name, spy(name, fn))
    try:
        yield captured
    finally:
        for name, fn in originals.items():
            setattr(engine_mod, name, fn)


def check_kernels_in_programs(captured: dict) -> None:
    """Each captured engine program compiles to HLO holding a Pallas
    kernel: a ``tpu_custom_call``, not the jnp reference."""
    if not captured:
        raise CheckFailed("no engine device program ran")
    for name, (args, kwargs) in sorted(captured.items()):
        text = getattr(engine_mod, name).lower(*args, **kwargs) \
            .compile().as_text()
        if "tpu_custom_call" not in text:
            raise CheckFailed(f"{name} compiled without a tpu_custom_call")


def check_device_path(served: Served, n_devices: int) -> None:
    """The engine ran the compiled Pallas kernels, each shard on its own
    TPU device (no round-robin onto a shared one)."""
    engine = served.engine
    if not engine.use_pallas or engine.cfg.interpret:
        raise CheckFailed(f"engine resolved use_pallas={engine.use_pallas}, "
                          f"interpret={engine.cfg.interpret}")
    placed = set(served.shard_devices)
    if len(placed) != n_devices or any(d.platform != "tpu" for d in placed):
        raise CheckFailed(f"shards sit on {sorted(map(str, placed))}, "
                          f"want {n_devices} distinct TPU devices")


def _report(name: str, served: Served) -> None:
    stats = served.engine.stats()
    print(f"[smoke] {name}: {stats['completed']}/{len(served.reqs)} "
          f"requests completed, wall {served.wall_s:.3f} s (backend "
          f"compile {served.compile_s:.3f} s of it), "
          f"{stats['chain_steps_per_s']:.4g} chain-steps/s, "
          f"{stats['ticks']} ticks (smoke numbers, not a benchmark)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-pool phase, on 4 chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every request and arrival time")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's devices are "
              f"{dev.platform} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 1
    n_chips = 4 if args.four_chips else 1
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} TPU chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    cache = use_compile_cache()
    print(f"[smoke] device_kind={dev.device_kind} platform={dev.platform} "
          f"devices={len(devices)} compile cache={cache}")

    try:
        with capture_programs() as captured:
            if args.four_chips:
                cfg = dataclasses.replace(CHIP_CFG, n_devices=4)
                kern, evacuated = four_chip_phase(cfg, MIX_REQUESTS,
                                                  args.seed)
                check_device_path(kern, 4)
                _report("four-chip mix with a drain", kern)
                print(f"[smoke] drained at tick {DRAIN_TICK} with "
                      f"{evacuated} resident jobs; retired shards (index, "
                      f"tick): {kern.engine.retired_shards}; shard devices "
                      f"{[str(d) for d in kern.shard_devices]}")
            else:
                print(f"[smoke] paper job: schwefel d={PAPER_REQ.dim}, "
                      f"{PAPER_REQ.n_chains} chains, N={PAPER_REQ.N}, "
                      f"T0={PAPER_REQ.T0}, rho={PAPER_REQ.rho}, T_min "
                      f"raised to {PAPER_REQ.T_min} -> "
                      f"{PAPER_REQ.n_levels} levels")
                kern, lines = paper_phase(CHIP_CFG, PAPER_REQ)
                check_device_path(kern, 1)
                _report("paper", kern)
                print("\n".join(lines))
                kern, lines = mix_phase(CHIP_CFG, MIX_REQUESTS, args.seed)
                check_device_path(kern, 1)
                _report("mix", kern)
                print("\n".join(lines))
        check_kernels_in_programs(captured)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[smoke] all checks passed; kernels found in "
          f"{sorted(captured)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
