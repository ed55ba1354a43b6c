"""The engine's spans and scopes reduced from a hand-made profile (exact
numbers), from a trace recorded on the chip (``bench/testdata``), and
through the readers that report them."""
import math

import pytest
from jax.profiler import ProfileData

from bench import harness, spec, tracereduce, tracescopes
from test_bench_tracereduce import profile as plain_profile

US = 1_000_000      # picoseconds per microsecond
TESTDATA = spec.BENCH_DIR / "testdata" / "paper-d512-1s.xplane.pb"
SWEEP = "jit(_group_tick)/sa.sweep/jit(_metropolis_sweep_slots)/kernel"
RESAMPLE = "jit(_group_tick)/sa.exchange/pa_resample/jit(searchsorted)"
CHAMPION = "jit(_group_tick)/sa.exchange/champion/scatter-min:"

#: The host's spans: a tick's phases and sub-spans, then 200 us outside.
HOST = [("bench.window", 100, 1100), ("bench.tick", 100, 1000),
        ("sa.dispatch", 100, 300), ("sa.dispatch.pack", 100, 200),
        ("sa.dispatch.h2d", 200, 280), ("sa.device_wait", 300, 600),
        ("sa.materialize", 600, 800), ("sa.materialize.d2h", 600, 700),
        ("sa.materialize.scatter", 700, 780)]
#: (name, start us, end us, scope path): a while loop holds its body's
#: operation; the last one runs past the window.
OPS = [("sweep", 300, 550, SWEEP), ("while.4", 550, 590, None),
       ("fusion.48", 555, 585, RESAMPLE), ("fusion.12", 590, 600, CHAMPION),
       ("copy.3", 1050, 1200, None)]


def _text(host, ops, ref_value=False):
    """A text proto of one host and one device plane; the scope paths in
    the operations' metadata, as ``str_value`` or through ``ref_value``."""
    names = sorted({n for n, *_ in host})
    hmeta = {n: i + 1 for i, n in enumerate(names)}
    hev = "\n".join(f"events {{ metadata_id: {hmeta[n]} offset_ps: {a * US}"
                    f" duration_ps: {(b - a) * US} }}" for n, a, b in host)
    hmd = "\n".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                    f'name: "{n}" }} }}' for n, k in hmeta.items())
    host_plane = (f'planes {{ id: 1 name: "/host:CPU"\n'
                  f'lines {{ id: 1 name: "python3" timestamp_ns: 0\n{hev} }}'
                  f'\n{hmd} }}')
    paths = sorted({p for *_, p in ops if p})
    ref = {p: 20 + i for i, p in enumerate(paths)}
    dev, md = [], []
    for k, (name, a, b, path) in enumerate(ops, start=1):
        dev.append(f"events {{ metadata_id: {k} offset_ps: {a * US} "
                   f"duration_ps: {(b - a) * US} }}")
        stat = ""
        if path:
            value = (f"ref_value: {ref[path]}" if ref_value
                     else f'str_value: "{path}"')
            stat = f" stats {{ metadata_id: 9 {value} }}"
        md.append(f'event_metadata {{ key: {k} value {{ id: {k} '
                  f'name: "{name}"{stat} }} }}')
    smd = ['stat_metadata { key: 9 value { id: 9 name: "tf_op" } }']
    smd += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{p}" }} }}'
            for p, i in ref.items()]
    modules = ('lines { id: 2 name: "XLA Modules" timestamp_ns: 0\n'
               f'events {{ metadata_id: 99 offset_ps: {300 * US} '
               f'duration_ps: {300 * US} }} }}\n'
               'event_metadata { key: 99 value { id: 99 '
               'name: "jit__group_tick(1)" } }')
    dev_plane = (f'planes {{ id: 2 name: "/device:TPU:0"\n'
                 f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n'
                 + "\n".join(dev) + " }\n" + modules + "\n"
                 + "\n".join(md + smd) + " }")
    return host_plane + "\n" + dev_plane


def _write(tmp_path, text, name="t"):
    path = tmp_path / name / "plugins" / "profile" / "x" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


@pytest.mark.parametrize("ref_value", [False, True])
def test_spans_scopes_and_idle_of_a_hand_made_profile(tmp_path, ref_value):
    r = tracescopes.reduce_file(
        _write(tmp_path, _text(HOST, OPS, ref_value)), n_devices=1)
    assert r["spans"] == {
        "sa.dispatch": pytest.approx(200e-6),
        "sa.dispatch.pack": pytest.approx(100e-6),
        "sa.dispatch.h2d": pytest.approx(80e-6),
        "sa.device_wait": pytest.approx(300e-6),
        "sa.materialize": pytest.approx(200e-6),
        "sa.materialize.d2h": pytest.approx(100e-6),
        "sa.materialize.scatter": pytest.approx(80e-6)}
    # Each instant of device time to the innermost operation: the while
    # keeps 10 us of its own; the copy is clipped to the window.
    assert r["scopes"] == {
        "sa.sweep": pytest.approx(250e-6),
        "sa.exchange": pytest.approx(40e-6),
        "sa.exchange.pa_resample": pytest.approx(30e-6),
        "sa.exchange.champion": pytest.approx(10e-6),
        "none": pytest.approx(60e-6)}
    # Idle [100, 300] and [600, 1050], under the innermost open span.
    assert r["idle_by_span"] == {
        "sa.dispatch.pack": pytest.approx(100e-6),
        "sa.dispatch.h2d": pytest.approx(80e-6),
        "sa.dispatch": pytest.approx(20e-6),
        "sa.materialize.d2h": pytest.approx(100e-6),
        "sa.materialize.scatter": pytest.approx(80e-6),
        "sa.materialize": pytest.approx(20e-6),
        "none": pytest.approx(250e-6)}


def test_the_sums_close_against_the_trace_reduction(tmp_path):
    path = _write(tmp_path, _text(HOST, OPS))
    r = tracescopes.reduce_file(path, n_devices=1)
    old = tracereduce.reduce_profile(ProfileData.from_file(str(path)), 1)
    top = sum(s for k, s in r["scopes"].items() if k.count(".") <= 1)
    assert top == pytest.approx(old["busy_s"])
    assert sum(r["idle_by_span"].values()) == \
        pytest.approx(old["window_s"] - old["busy_s"])


def test_existing_keys_are_the_same_with_and_without_sa_events(tmp_path):
    """The trace reduction the harness runs gives the same numbers whether
    or not the program adds its spans and scope stats."""
    bare = [(n, a, b, None) for n, a, b, _p in OPS]
    with_sa = tracereduce.reduce_profile(
        ProfileData.from_text_proto(_text(HOST, OPS)), 1)
    without = tracereduce.reduce_profile(
        ProfileData.from_text_proto(_text(HOST[:2], bare)), 1)
    assert with_sa == without
    # Each gap is named after the harness annotation over most of it.
    assert with_sa["idle_by_host"] == {"bench.tick": pytest.approx(650e-6)}


def test_a_program_without_spans_or_scopes_gives_nothing(tmp_path):
    bare = [(n, a, b, None) for n, a, b, _p in OPS]
    r = tracescopes.reduce_file(_write(tmp_path, _text(HOST[:2], bare)), 1)
    assert r["spans"] == {} and r["scopes"] == {}
    assert r["idle_by_span"] == {"none": pytest.approx(650e-6)}
    # The plain fixture of the trace reduction's own test, too.
    pd = plain_profile()
    assert tracescopes.reduce_profile(pd, 1, {})["spans"] == {}


def test_scope_of():
    assert tracescopes.scope_of(CHAMPION) == ("sa.exchange", "champion")
    assert tracescopes.scope_of(SWEEP) == ("sa.sweep", None)
    assert tracescopes.scope_of(
        "jit(_group_tick_fused)/while/body/sa.controls/repeat:") == \
        ("sa.controls", None)
    assert tracescopes.scope_of("jit(f)/mul:") is None
    assert tracescopes.scope_of("") is None


# ---------------------------------------------------------------- readers
def _run(tmp_path, monkeypatch, text, levels=2):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    cell = spec.load_cell("paper-d512")
    _write(tmp_path, text, name=cell.name)
    return harness.Run(cell=cell, setup_s=1.0, t0=0.0, t_close=1.0,
                       records=[], ticks=levels,
                       job_levels=[("schwefel", 512, 100, 16384, levels)],
                       phases={}, trace={}, peaks=None)


READERS = {
    "sweep_ms_per_level.paper": 250e-6 * 1e3 / 2,
    "exchange_scope_ms_per_level.paper": 40e-6 * 1e3 / 2,
    "state_copy_ms_per_level.paper": (80e-6 + 100e-6) * 1e3 / 2,
    "pack_ms_per_level.paper": (100e-6 + 80e-6) * 1e3 / 2,
    "idle_unspanned_ms_per_level.paper": 250e-6 * 1e3 / 2,
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_made_run(tmp_path, monkeypatch, name):
    run = _run(tmp_path, monkeypatch, _text(HOST, OPS))
    assert spec.metric_reader(name)(run) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reports_nothing_without_the_programs_spans(
        tmp_path, monkeypatch, name):
    bare = [(n, a, b, None) for n, a, b, _p in OPS]
    run = _run(tmp_path, monkeypatch, _text(HOST[:2], bare))
    assert spec.metric_reader(name)(run) is None
    run.trace = None                       # an untraced run
    assert spec.metric_reader(name)(run) is None


# ------------------------------------------------- a trace from the chip
def test_chip_trace_finds_the_sweep_and_the_exchange():
    """One second of traced ``paper-d512`` on a v5e: the ops under
    ``sa.sweep`` and ``sa.exchange`` are (within 2%) the group program's
    whole time, and the idle stretches add up to the window's idle."""
    r = tracescopes.reduce_file(TESTDATA, n_devices=1)
    old = tracereduce.reduce_profile(
        ProfileData.from_file(str(TESTDATA)), n_devices=1)
    program = sum(s for n, s in old["modules"].items() if "_group_tick" in n)
    sweep, exchange = r["scopes"]["sa.sweep"], r["scopes"]["sa.exchange"]
    assert sweep > 10 * exchange > 0
    assert math.isclose(sweep + exchange, program, rel_tol=0.02)
    assert sum(r["idle_by_span"].values()) == \
        pytest.approx(old["window_s"] - old["busy_s"], rel=1e-6)
    for span in ("sa.dispatch.pack", "sa.dispatch.h2d", "sa.materialize.d2h",
                 "sa.device_wait"):
        assert r["spans"][span] > 0
