"""The interval arithmetic every trace metric rests on, on hand-made
intervals whose answers can be read off."""

from benchmark.reduce import intervals as iv


def test_union_merges_overlap_touch_and_drops_empty():
    assert iv.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]
    assert iv.total(iv.union([(0, 2), (1, 3), (5, 7)])) == 5


def test_union_keeps_a_contained_interval_inside_its_parent():
    assert iv.union([(0, 10), (2, 3), (4, 12)]) == [(0, 12)]


def test_gaps_inside_a_window_include_both_ends():
    merged = iv.union([(2, 4), (6, 7)])
    assert iv.gaps(merged, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert iv.gaps(merged, 3, 6.5) == [(4, 6)]
    assert iv.gaps([], 1, 2) == [(1, 2)]


def test_exposed_is_the_part_of_one_set_the_other_leaves_uncovered():
    collectives = [(0, 4), (10, 12)]
    compute = [(1, 2), (3, 11)]
    # 0-1 and 2-3 of the first, 11-12 of the second.
    assert iv.exposed(collectives, compute) == 3
    assert iv.exposed(collectives, []) == 6
    assert iv.exposed([], compute) == 0


def test_self_times_take_children_off_their_parent():
    events = [(0, 10, "while"), (1, 4, "fusion.1"), (4, 9, "fusion.2"), (12, 13, "copy")]
    assert sorted(iv.self_times(events)) == [
        ("copy", 1), ("fusion.1", 3), ("fusion.2", 5), ("while", 2),
    ]


def test_leaves_are_the_events_that_hold_no_other():
    events = [(0, 10, "while"), (1, 4, "a"), (4, 9, "b"), (12, 13, "c")]
    assert [e[2] for e in iv.leaves(events)] == ["a", "b", "c"]
