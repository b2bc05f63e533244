"""The reduction of the program's ``pbvd.*`` spans, and the readers of the
per-layer metrics built on them and on the service's counters, by hand."""

import types

import pytest

import harness
from spans import idle_attribution, span_self_times

# one host line: a dispatch holding a launch and its four children, then a
# finish holding a flush launch; another line with an ingest; times in ns
LINE = [
    ("pbvd.dispatch", 0, 100),
    ("pbvd.launch", 10, 90),
    ("pbvd.frame", 10, 40),
    ("pbvd.kernel", 40, 50),
    ("pbvd.device_wait", 50, 80),
    ("pbvd.deliver", 80, 90),
    ("pbvd.finish", 120, 200),
    ("pbvd.launch", 130, 180),
    ("pbvd.frame", 130, 150),
]
OTHER = [("pbvd.ingest", 95, 125)]


def test_self_time_subtracts_children_on_the_same_line():
    out = span_self_times([LINE, OTHER], 0, 1000)
    sec = {k: round(v[1] * 1e9) for k, v in out.items()}
    assert sec == {
        "pbvd.dispatch": 20, "pbvd.launch": 30, "pbvd.frame": 50, "pbvd.kernel": 10,
        "pbvd.device_wait": 30, "pbvd.deliver": 10, "pbvd.finish": 30, "pbvd.ingest": 30,
    }
    assert out["pbvd.launch"][0] == 2 and out["pbvd.frame"][0] == 2


def test_self_time_is_cut_to_the_window_and_counts_spans_started_in_it():
    out = span_self_times([LINE], 45, 140)
    assert out["pbvd.launch"][0] == 1  # the one at 130; the one at 10 started before
    assert round(out["pbvd.launch"][1] * 1e9) == 0  # [45, 90) and [130, 140), all children
    assert round(out["pbvd.frame"][1] * 1e9) == 10  # [130, 140)
    assert out["pbvd.frame"][0] == 1
    assert round(out["pbvd.finish"][1] * 1e9) == 10  # [120, 140) less the launch's [130, 140)


def test_idle_is_put_down_to_the_innermost_open_span():
    idle = [(0, 5), (20, 30), (45, 55), (100, 110), (190, 260)]
    free, by = idle_attribution([LINE, OTHER], idle, 0, 300)
    by = {k: round(v * 1e9) for k, v in by.items()}
    assert round(free * 1e9) == 60  # [200, 260): nothing open
    # [0, 5): the dispatch alone; [45, 55): kernel to 50, device_wait after;
    # [100, 110): the ingest (the dispatch closed); [190, 200): the finish,
    # whose launch closed at 180
    assert by == {
        "pbvd.dispatch": 5, "pbvd.frame": 10, "pbvd.kernel": 5, "pbvd.device_wait": 5,
        "pbvd.ingest": 10, "pbvd.finish": 10,
    }
    assert round((free + sum(by.values()) * 1e-9) * 1e9) == sum(b - a for a, b in idle)


def test_a_span_open_on_another_line_counts_as_open():
    free, by = idle_attribution([OTHER], [(90, 130)], 0, 200)
    assert round(free * 1e9) == 10 and round(by["pbvd.ingest"] * 1e9) == 30


def _run(counters=None, trace=None, payload_bits=0, chips=1):
    return types.SimpleNamespace(
        counters=counters, trace=trace, payload_bits=payload_bits, chips=chips
    )


COUNTS = {
    "admits": 4, "admit_wait_s": 0.2, "lanes_real": 2, "lanes_launched": 256,
    "stages_real": 384, "stages_launched": 1240, "h2d_bytes": 2480, "d2h_bytes": 4096,
}
TRACE = {
    "spans": {"pbvd.launch": [4, 0.004], "pbvd.frame": [4, 0.002], "pbvd.deliver": [4, 0.0008]},
    "idle_s": [0.8, 0.5], "idle_unattributed_s": [0.2, 0.5],
}


@pytest.mark.parametrize(
    "name,value",
    [
        ("admit_wait_ms", 50.0),
        ("pad_lane_share", 100 * (1 - 2 / 256)),
        ("padded_stage_share", 100 * (1 - 384 / 1240)),
        ("host_bytes_per_bit", (2480 + 4096) / 368),
        ("framing_ms", 0.5),
        ("deliver_ms", 0.2),
        ("idle_unattributed_share", 25.0),
    ],
)
def test_reader_on_a_hand_built_record(name, value):
    got, note = harness.read_metric(name, _run(COUNTS, TRACE, payload_bits=368))
    assert got == pytest.approx(value) and note is None


@pytest.mark.parametrize(
    "name",
    ["admit_wait_ms", "pad_lane_share", "padded_stage_share", "host_bytes_per_bit",
     "framing_ms", "deliver_ms", "idle_unattributed_share"],
)
def test_reader_finds_nothing_in_a_program_without_the_counters_or_spans(name):
    """A program that keeps no such counter or span (as before they existed)
    gives nothing to read, and no error."""
    trace = {"window_s": 1.0, "busy_s": [0.1], "kernel_s": [0.05]}
    assert harness.read_metric(name, _run({}, trace, payload_bits=368)) == (None, None)
    bare = types.SimpleNamespace(trace=None, payload_bits=368, chips=1)  # no counters field
    assert harness.read_metric(name, bare) == (None, None)


def test_unattributed_share_averages_over_the_cells_chips_only():
    got, _ = harness.read_metric("idle_unattributed_share", _run(trace=TRACE, chips=2))
    assert got == pytest.approx(100 * (0.25 + 1.0) / 2)
