"""The trace reduction on a hand-made profile (exact numbers) and on a
trace recorded on the chip (``bench/testdata``)."""
import pytest
from jax.profiler import ProfileData

from bench import spec, tracereduce

US = 1_000_000      # picoseconds per microsecond


def _events(meta, spans):
    return "\n".join(
        f"events {{ metadata_id: {meta[n]} offset_ps: {a * US} "
        f"duration_ps: {(b - a) * US} }}" for n, a, b in spans)


def _plane(pid, name, lines):
    """lines: {line name: [(event name, start us, end us)]}."""
    names = sorted({n for spans in lines.values() for n, _, _ in spans})
    meta = {n: i + 1 for i, n in enumerate(names)}
    body = "\n".join(
        f'lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 0\n'
        f"{_events(meta, spans)} }}" for i, (ln, spans) in
        enumerate(lines.items()))
    md = "\n".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                   f'name: "{n}" }} }}' for n, k in meta.items())
    return f'planes {{ id: {pid} name: "{name}"\n{body}\n{md} }}'


def profile(n_devices=1):
    host = _plane(1, "/host:CPU", {"python": [
        ("bench.window", 100, 1100),
        ("bench.tick", 100, 600), ("bench.sleep", 600, 1100)]})
    devices = [_plane(2 + d, f"/device:TPU:{d}", {
        "XLA Ops": [("metropolis_sweep_delta", 150, 350),
                    ("metropolis_sweep_delta", 300, 400),   # overlaps
                    ("fusion.1", 700, 750),
                    ("fusion.1", 1050, 1300)],              # clipped
        "XLA Modules": [("jit__group_tick(1)", 140, 450)]})
        for d in range(n_devices)]
    return ProfileData.from_text_proto("\n".join([host] + devices))


def test_busy_ops_modules_and_gaps():
    r = tracereduce.reduce_profile(profile(), n_devices=1)
    assert r["window_s"] == pytest.approx(1000e-6)
    # union: [150, 400] + [700, 750] + [1050, 1100] = 250 + 50 + 50 us
    assert r["busy_s"] == pytest.approx(350e-6)
    assert r["ops"]["metropolis_sweep_delta"] == pytest.approx(300e-6)
    assert r["ops"]["fusion.1"] == pytest.approx(100e-6)
    assert r["modules"]["jit__group_tick(1)"] == pytest.approx(310e-6)
    assert tracereduce.seconds_named(r["ops"], "metropolis_sweep") == \
        pytest.approx(300e-6)
    # gaps: [100,150] under the tick; [400,700] mostly under the tick
    # (200 us of it, 100 us under the sleep); [750,1050] under the sleep
    assert r["idle_by_host"]["bench.tick"] == pytest.approx(350e-6)
    assert r["idle_by_host"]["bench.sleep"] == pytest.approx(300e-6)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps == [["bench.tick", pytest.approx(300e-6)],
                    ["bench.sleep", pytest.approx(300e-6)],
                    ["bench.tick", pytest.approx(50e-6)]]
    assert len(r["breakdown"]["device_ops"]) == 2


def test_busy_is_the_mean_over_devices():
    r = tracereduce.reduce_profile(profile(n_devices=4), n_devices=4)
    assert r["devices_busy_s"] == pytest.approx([350e-6] * 4)
    assert r["busy_s"] == pytest.approx(350e-6)
    assert r["ops"]["fusion.1"] == pytest.approx(4 * 100e-6)


def test_kernel_found_by_name_or_by_its_custom_call_details():
    def trace(name, detail):
        host = _plane(1, "/host:CPU", {"python": [("bench.window", 0, 100)]})
        dev = (f'planes {{ id: 2 name: "/device:TPU:0"\n'
               f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n'
               f'events {{ metadata_id: 1 offset_ps: {10 * US} '
               f'duration_ps: {40 * US} stats {{ metadata_id: 7 '
               f'str_value: "{detail}" }} }}\n'
               f'events {{ metadata_id: 2 offset_ps: {60 * US} '
               f'duration_ps: {10 * US} }} }}\n'
               f'event_metadata {{ key: 1 value {{ id: 1 name: "{name}" }} }}\n'
               f'event_metadata {{ key: 2 value {{ id: 2 name: "fusion.2" }} }}\n'
               f'stat_metadata {{ key: 7 value {{ id: 7 name: "long_name" }} }} }}')
        return tracereduce.reduce_profile(
            ProfileData.from_text_proto(host + "\n" + dev), n_devices=1)

    by_name = trace("metropolis_sweep_delta", "")
    by_detail = trace("custom-call.3", "kernel metropolis_sweep_delta_lv")
    other = trace("custom-call.3", "kernel qap_sweep_n8")
    for r in (by_name, by_detail):
        assert tracereduce.kernel_seconds(r, "metropolis_sweep") == \
            pytest.approx(40e-6)
    assert tracereduce.kernel_seconds(other, "metropolis_sweep") == 0


def test_no_window_no_numbers():
    host = _plane(1, "/host:CPU", {"python": [("bench.tick", 0, 10)]})
    assert tracereduce.reduce_profile(
        ProfileData.from_text_proto(host), n_devices=1) is None


def test_union_and_clip():
    assert tracereduce._union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tracereduce._clip(0, 10, 5, 20) == (5, 10)
    assert tracereduce._clip(0, 4, 5, 20) is None


def test_newest_xplane_of_an_empty_dir(tmp_path):
    assert tracereduce.newest_xplane(tmp_path) is None
    assert tracereduce.reduce_dir(tmp_path, 1) is None
    assert spec.BENCH_DIR.is_dir()
