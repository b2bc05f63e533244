"""The four-chip cell's entries, and the readers of its mesh metrics on
hand-built records, including runs that give them nothing to read."""

import types

import pytest

import harness

X4 = "ccsds-x4.playback"


def _run(trace=None, chips=4, compiles=0, launches=0):
    return types.SimpleNamespace(trace=trace, chips=chips, compiles=compiles, launches=launches)


def test_four_chip_cell_loads_from_benchmark_json():
    bench, cell, config, mix = harness.load_cell(X4)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ccsds-r12-x4", "playback", 4)
    assert config["mesh"] == "data=4" and config["chips"] == 4
    assert config["service"]["max_batch_blocks"] == 16384
    assert mix == harness.load_mix("playback")
    traced = {m["name"] for m in harness.metrics_of(bench, X4, True)}
    assert traced == {"mesh_busy_spread", "compiles_per_launch"}
    assert {m["name"] for m in harness.metrics_of(bench, X4, False)} == {"decoded_mbps", "setup_s"}
    for cell_name in ("ccsds.playback", "is95.frames"):  # the new metrics stay in their cell
        assert not traced & {m["name"] for m in harness.metrics_of(bench, cell_name, True)}


def test_mesh_busy_spread_reads_the_cells_chips_only():
    trace = {"window_s": 2.0, "busy_s": [0.30, 0.10, 0.20, 0.25, 0.90]}  # a fifth chip: not the cell's
    got, note = harness.read_metric("mesh_busy_spread", _run(trace))
    assert got == pytest.approx(100 * (0.30 - 0.10) / 2.0) and note is None
    even = {"window_s": 1.0, "busy_s": [0.4, 0.4, 0.4, 0.4]}
    assert harness.read_metric("mesh_busy_spread", _run(even)) == (0.0, None)


@pytest.mark.parametrize("trace", [None, {"window_s": 1.0, "busy_s": []}])
def test_mesh_busy_spread_without_a_device_trace(trace):
    assert harness.read_metric("mesh_busy_spread", _run(trace)) == (None, None)


@pytest.mark.parametrize("compiles,launches,value", [(36, 12, 3.0), (0, 40, 0.0), (1, 8, 0.125)])
def test_compiles_per_launch(compiles, launches, value):
    got, _ = harness.read_metric("compiles_per_launch", _run(compiles=compiles, launches=launches))
    assert got == pytest.approx(value)


def test_compiles_per_launch_without_launches():
    assert harness.read_metric("compiles_per_launch", _run(compiles=3)) == (None, None)


def test_shard_ms_is_self_time_per_launch():
    trace = {"spans": {"pbvd.launch": [4, 0.4], "pbvd.kernel": [4, 0.1], "pbvd.shard": [4, 0.006]}}
    got, note = harness.read_metric("shard_ms", _run(trace))
    assert got == pytest.approx(1.5) and note is None


@pytest.mark.parametrize(
    "trace",
    [
        None,  # no trace
        {"window_s": 1.0, "busy_s": [0.1]},  # a trace the harness did not reduce spans in
        {"spans": {"pbvd.shard": [2, 0.002]}},  # no launch span
        {"spans": {"pbvd.launch": [4, 0.4], "pbvd.kernel": [4, 0.1]}},  # no shard span: one chip
    ],
)
def test_shard_ms_finds_nothing_to_read(trace):
    assert harness.read_metric("shard_ms", _run(trace)) == (None, None)
