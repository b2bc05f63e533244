"""The trace reduction's interval arithmetic, gap naming and operation
names, by hand."""

from xplane import clip, gaps, name_gap, short_name, union


def test_union_merges_overlaps_and_touching():
    assert union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == [(0, 4), (5, 9)]
    assert union([]) == []


def test_clip_cuts_to_the_window():
    assert clip([(0, 2), (3, 8), (9, 12)], 1, 10) == [(1, 2), (3, 8), (9, 10)]
    assert clip([(0, 1)], 2, 3) == []


def test_gaps_are_the_complement():
    busy = [(2, 4), (6, 7)]
    assert gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert gaps([(0, 10)], 0, 10) == []
    # busy + idle = window
    total = sum(b - a for a, b in busy) + sum(b - a for a, b in gaps(busy, 0, 10))
    assert total == 10


def test_gap_named_by_innermost_covering_event():
    host = [("loop", 0, 100), ("launch", 10, 40), ("gather", 12, 18)]
    assert name_gap((11, 39), host) == "launch"  # gather covers under half
    assert name_gap((12, 16), host) == "gather"
    assert name_gap((50, 60), host) == "loop"
    assert name_gap((200, 300), host) == "(no host event)"


def test_short_name_drops_operands():
    hlo = ('%traceback_pallas.1 = s32[512,128]{1,0:T(8,128)S(1)} custom-call(s32[620,8,128]) '
           'custom_call_target="tpu_custom_call"')
    assert short_name(hlo) == "%traceback_pallas.1 = s32[512,128]"
