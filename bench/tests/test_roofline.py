"""Work functions against hand counts, and the peak table."""

import json

import pytest

from roofline import PEAKS, block_work, least_time_s, peaks_for


def test_ccsds_block_work_by_hand():
    # T = 512 + 2*42 = 596 stages, 64 states, 4 operations per state and stage
    ops, nbytes = block_work(K=7, R=2, D=512, L=42, q=8)
    assert ops == 4 * 64 * 596 == 152_576
    # 596 stages x 2 int8 symbols in, 512 bits = 64 bytes out
    assert nbytes == 596 * 2 + 64 == 1256


def test_is95_block_work_by_hand():
    ops, nbytes = block_work(K=9, R=2, D=512, L=54, q=8)
    assert ops == 4 * 256 * 620 == 634_880
    assert nbytes == 620 * 2 + 64 == 1304


def test_a_frame_counts_only_its_own_stages():
    # a terminated 192-stage frame in a D=512, L=54 block: 192 stages of work,
    # 192 x 2 symbols in and 192 bits = 24 bytes out, not the 620-stage lane
    ops, nbytes = block_work(K=9, R=2, D=512, L=54, q=8, stream_stages=192)
    assert ops == 4 * 256 * 192 == 196_608
    assert nbytes == 192 * 2 + 24 == 408
    # a stream longer than a block's window counts the whole window
    assert block_work(7, 2, 512, 42, 8, stream_stages=10**6) == block_work(7, 2, 512, 42, 8)


def test_which_term_binds_on_v5e():
    peaks = peaks_for("TPU v5 lite")
    t, term = least_time_s(1000, block_work(7, 2, 512, 42, 8), peaks)
    assert term == "bytes" and t == pytest.approx(1000 * 1256 / 819e9)
    t, term = least_time_s(1000, block_work(9, 2, 512, 54, 8), peaks)
    assert term == "compute" and t == pytest.approx(1000 * 634_880 / 393e12)


def test_unknown_device_kind_is_refused(tmp_path):
    with pytest.raises(KeyError, match="no peaks"):
        peaks_for("TPU v9 imaginary")
    table = json.loads(PEAKS.read_text())
    assert table["source"] and table["why"]
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"devices": {}}))
    with pytest.raises(KeyError):
        peaks_for("TPU v5 lite", other)
