"""The trace reduction on small synthesised ``.xplane.pb`` files."""

import pytest

from bench import xtrace


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _plane(pid, name, lines, names):
    meta = " ".join(f"event_metadata {{ key: {k} value {{ id: {k} "
                    f'name: "{n}" }} }}' for k, n in names.items())
    body = " ".join(
        f'lines {{ id: {i} name: "{ln}" timestamp_ns: 0 '
        + " ".join(_event(*e) for e in evs) + " }"
        for i, (ln, evs) in enumerate(lines))
    return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'


def write_xspace(path, planes):
    """Serialise an XSpace given as text-proto planes to ``path``."""
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(" ".join(planes))
    with open(path, "wb") as f:
        f.write(raw)
    return path


@pytest.fixture
def trace_file(tmp_path):
    """One device with overlapping ops inside a 100 us window, and host
    annotations naming what ran in each gap."""
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Ops", [(1, 10_000, 20_000), (2, 25_000, 10_000),
                     (1, 60_000, 10_000), (3, 150_000, 5_000)]),
        ("XLA Modules", [(4, 5_000, 40_000), (5, 55_000, 20_000)]),
    ], {1: "fusion.1", 2: "dot.2", 3: "late.3", 4: "jit_step(12)",
        5: "jit_other(7)"})
    host = _plane(2, "/host:CPU", [
        ("python", [(6, 0, 100_000), (7, 35_000, 25_000),
                    (8, 70_000, 30_000)]),
    ], {6: "bench.window", 7: "bench.plan", 8: "bench.observe"})
    return write_xspace(str(tmp_path / "t.xplane.pb"), [dev, host])


def test_busy_time_is_the_union_of_op_intervals(trace_file):
    """Overlapping ops count once; ops outside the window do not count;
    the window is the host annotation."""
    got = xtrace.reduce_file(trace_file)
    # [10, 35] + [60, 70] us inside [0, 100] us.
    assert got["busy_s"] == pytest.approx(35e-6)
    assert got["window_s"] == pytest.approx(100e-6)


def test_ops_are_named_by_their_module(trace_file):
    """Device time per op, prefixed with the module it ran in."""
    ops = dict(xtrace.reduce_file(trace_file)["device_ops"])
    assert ops["jit_step:fusion.1"] == pytest.approx(20e-6)
    assert ops["jit_step:dot.2"] == pytest.approx(10e-6)
    assert ops["jit_other:fusion.1"] == pytest.approx(10e-6)
    assert not any(k.endswith("late.3") for k in ops)


def test_idle_gaps_are_named_by_the_host(trace_file):
    """Longest gaps first, each named by the innermost host annotation
    at its midpoint."""
    gaps = xtrace.reduce_file(trace_file)["idle_gaps"]
    assert [n for n, _ in gaps] == ["bench.observe", "bench.plan", "host"]
    assert [d for _, d in gaps] == pytest.approx([30e-6, 25e-6, 10e-6])


def test_no_device_ops_reads_nothing(tmp_path):
    """A trace without device operations gives no reading at all."""
    host = _plane(2, "/host:CPU", [("python", [(1, 0, 1000)])],
                  {1: "bench.window"})
    assert xtrace.reduce_file(write_xspace(str(tmp_path / "h.xplane.pb"),
                                           [host])) is None


def test_union_and_clip():
    """Interval arithmetic the reduction rests on."""
    assert xtrace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert xtrace.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]
