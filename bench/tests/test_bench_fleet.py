"""The four-chip suite cell (``suite-fleet4``) on the CPU at a tiny size,
and the readers of its ``.fleet`` metrics.

Four logical shards of 4 slots x 8 chains, the Pallas kernels in
interpret mode, and three of the suite's widths: the plain reference
agrees with what the timed path served, and a fault planted on one shard
alone makes ``correct`` come out false."""
import dataclasses
import time
import types

import jax
import pytest
from jax.profiler import ProfileData

from bench import harness, spec, tracefleet
from repro.service import engine as engine_mod
from test_bench_tracereduce import _plane

SEED = 2**31 + 77
#: The shard the faults are planted on.
FAULTY = 1


def tiny():
    """``suite-fleet4`` cut to four CPU-sized shards (same traffic, checks).

    Widths 4, 10 and 30 of the suite; T0 cooled (to 1, as
    ``test_bench_harness.tiny`` cools the paper job) so that a tiny
    population descends and the exchange moves its champion, and a ladder
    of 8 levels so that jobs retire and queued ones are placed inside the
    window."""
    cell = spec.load_cell("suite-fleet4")
    cfg = dict(cell.config)
    cfg["engine"] = dict(cfg["engine"], n_slots=4, chains_per_slot=8,
                         use_pallas=True, interpret=True)
    cfg["problems"] = [["exponential", 4], ["salomon", 10], ["ackley", 30]]
    cfg["schedules"] = [dict(cfg["schedules"][0], T0=1.0, T_min=0.93)]
    cfg["slots_per_request"] = [4]
    return dataclasses.replace(cell, config=cfg)


def run(seconds=2.0):
    return harness.run_cell(tiny(), SEED, seconds, False, time.perf_counter(),
                            require_chip=False)


@pytest.fixture
def fresh_programs():
    """Trace the engine's programs anew around a test that patches what
    they call."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_tiny_fleet_agrees_with_the_reference():
    assert tiny().config["engine"]["n_devices"] == 4
    out = run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 8 and out["failed"] == 0  # jobs retired
    for v in out["checks"].values():     # well inside every limit
        assert v["value"] < v["limit"] / 10
    assert out["metrics"]["chain_steps_per_s"]["value"] > 0


def _never_launched(monkeypatch):
    """The faulty shard launches each job's group once; after that the
    tick collects the stale results of that first launch."""
    orig = engine_mod.SAServeEngine._launch_group
    first = {}

    def launch(self, shard, family, dim, n_steps, jobs):
        if shard.index != FAULTY:
            return orig(self, shard, family, dim, n_steps, jobs)
        key = tuple(j.req.req_id for j in jobs)
        if key not in first:
            first[key] = orig(self, shard, family, dim, n_steps, jobs)
        return first[key]
    monkeypatch.setattr(engine_mod.SAServeEngine, "_launch_group", launch)


def _exchange_left_out(monkeypatch):
    """The faulty shard's group program adopts no champion; the other
    shards run the sound program."""
    orig_launch = engine_mod.SAServeEngine._launch_group
    orig_exchange = engine_mod.exch.serving_exchange
    sound_tick = engine_mod._group_tick
    body = sound_tick.__wrapped__

    def tick_without_exchange(*a, **kw):
        return body(*a, **kw)
    faulty_tick = jax.jit(tick_without_exchange,
                          static_argnames=("n_steps", "blk", "variant",
                                           "use_pallas", "interpret",
                                           "num_segments"),
                          donate_argnums=(0,))
    on = types.SimpleNamespace(faulty=False)

    def exchange(x, fx, seg, num_segments, adopt, *a):
        if on.faulty:
            adopt = jax.numpy.zeros_like(adopt)
        return orig_exchange(x, fx, seg, num_segments, adopt, *a)

    def launch(self, shard, *a):
        on.faulty = shard.index == FAULTY
        engine_mod._group_tick = faulty_tick if on.faulty else sound_tick
        try:
            return orig_launch(self, shard, *a)
        finally:
            on.faulty = False
            engine_mod._group_tick = sound_tick
    monkeypatch.setattr(engine_mod.exch, "serving_exchange", exchange)
    monkeypatch.setattr(engine_mod.SAServeEngine, "_launch_group", launch)


FAULTS = {"one_shard_never_launched": _never_launched,
          "exchange_left_out_on_one_shard": _exchange_left_out}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_on_one_shard_makes_run_incorrect(fault, monkeypatch,
                                                fresh_programs):
    FAULTS[fault](monkeypatch)
    out = run()
    assert not out["correct"], (fault, out["checks"])


# ----------------------------------------------------------- the readers
def _fleet_profile():
    """Two ticks over three devices: in the first, the programs end at
    400, 450 and 520 us; in the second only device 0 runs one."""
    host = _plane(1, "/host:CPU", {"python": [
        ("bench.window", 100, 1100),
        ("bench.tick", 100, 600), ("bench.tick", 600, 1000),
        ("bench.sleep", 1000, 1100)]})
    ends = [(450, 800), (400,), (520,)]
    devices = [_plane(2 + d, f"/device:TPU:{d}", {
        "XLA Ops": [("fusion.1", e - 50, e) for e in mine],
        "XLA Modules": [(f"jit__group_tick({d})", e - 100, e)
                        for e in mine]})
        for d, mine in enumerate(ends)]
    return ProfileData.from_text_proto("\n".join([host] + devices))


def test_lockstep_is_the_spread_of_the_program_ends_per_tick():
    got = tracefleet.reduce_profile(_fleet_profile(), n_devices=3)
    assert got["lockstep_s"] == pytest.approx([120e-6, 0.0])
    assert got["program_s"] == pytest.approx({
        "/device:TPU:0": 200e-6, "/device:TPU:1": 100e-6,
        "/device:TPU:2": 100e-6})
    assert tracefleet.lockstep([(0, 10), (10, 20)], [[5, 15], [7]]) == \
        pytest.approx([2e-9, 0.0])


def test_lockstep_of_one_device_is_zero_on_the_chip_trace():
    path = spec.BENCH_DIR / "testdata" / "paper-d512-1s.xplane.pb"
    got = tracefleet.reduce_profile(ProfileData.from_file(str(path)), 1)
    assert got["lockstep_s"] and not any(got["lockstep_s"])
    assert got["program_s"]["/device:TPU:0"] > 0


def _run(trace, phases=None, ticks=4, n_devices=3):
    cell = types.SimpleNamespace(
        name="x", config={"engine": {"n_devices": n_devices}})
    return types.SimpleNamespace(cell=cell, trace=trace, ticks=ticks,
                                 phases=phases or {})


def test_fleet_readers_on_hand_made_runs():
    read = {name: spec.metric_reader(name) for name in (
        "host_ms_per_tick.fleet", "admit_ms_per_tick.fleet",
        "device_idle_share_max.fleet", "lockstep_wait_ms_per_tick.fleet")}
    phases = {"schedule": 0.001, "admit": 0.004, "dispatch": 0.003,
              "device_wait": 0.5, "materialize": 0.002, "retire": 0.0}
    run = _run({"window_s": 2.0, "devices_busy_s": [1.5, 1.0, 1.8]}, phases)
    assert read["host_ms_per_tick.fleet"](run) == pytest.approx(2.5)
    assert read["admit_ms_per_tick.fleet"](run) == pytest.approx(1.0)
    assert read["device_idle_share_max.fleet"](run) == pytest.approx(0.5)
    # A device that ran nothing in the window leaves no busy entry.
    short = _run({"window_s": 2.0, "devices_busy_s": [1.5, 1.0]})
    assert read["device_idle_share_max.fleet"](short) == 1.0
    # An untraced run reports none of them.
    for name, fn in read.items():
        assert fn(_run(None)) is None, name


def test_lockstep_reader_reads_the_runs_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(tracefleet, "reduce_dir",
                        lambda logdir, n: tracefleet.reduce_profile(
                            _fleet_profile(), n))
    run = _run({"window_s": 1e-3, "devices_busy_s": [1e-4] * 3}, ticks=2)
    read = spec.metric_reader("lockstep_wait_ms_per_tick.fleet")
    assert read(run) == pytest.approx(0.06)
