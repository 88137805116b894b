"""The line's exact counts come from a stated number of segments, time from
the whole window: `run.counted`, `run.op_counts`, `run.round_counts` and
`run.end_to_end` as pure functions over fabricated reports.  A program that
fits seven segments into the window where its parent fits two is compared
on the same two (PERF.md section 6, PR 30 and PR 33)."""

import json

import pytest

from benchmark import run, traffic

OPS = 3_320_000  # offered in one segment: read fires + write batches + conf ops
WANTED = {"group_rounds_per_s": "group-rounds/s", "read_p99_ms": "ms", "recover_ms": "ms",
          "setup_s": "s"}
G = 100_000


def report(dropped, outstanding, p99, mttr):
    return {"rounds": 600, "reads_issued": 3_000_000, "served_lease": 2_900_000 - outstanding,
            "served_quorum": 100_000, "dropped_fires": dropped, "read_p99": p99,
            "mttr_rounds": mttr}


FIRST_TWO = [report(139_000, 410, 19, 21.25), report(140_100, 395, 18, 21.5)]
# What a faster program goes on to run in the same window: no two alike.
LATER = [report(139_700 + 90 * i, 400 + i, 17 + i % 3, 20.0 + i) for i in range(5)]
STATED = {"counted_segments": 2}


def in_line(reports, mix):
    sel = run.counted(reports, mix)
    ops = run.op_counts(sel, OPS)
    return ops["attempted"], ops["failed_in_segments"], run.round_counts(sel, WANTED)


def test_two_segments_and_seven_with_equal_first_two_count_the_same_under_the_key():
    two, seven = in_line(FIRST_TWO, STATED), in_line(FIRST_TWO + LATER, STATED)
    assert two == seven
    attempted, failed, rounds = two
    assert attempted == 2 * OPS and failed == 139_000 + 140_100 + 410 + 395
    assert rounds == {"read_p99_rounds": 18.5, "mttr_rounds": 21.375}


def test_without_the_key_every_segment_counts_as_before():
    two, seven = in_line(FIRST_TWO, {}), in_line(FIRST_TWO + LATER, {})
    assert two == in_line(FIRST_TWO, STATED)
    assert seven[0] == 7 * OPS
    for a, b in zip(two, seven):  # attempted, failed and both medians move with the sample
        assert a != b
    assert seven[2]["read_p99_rounds"] != two[2]["read_p99_rounds"]
    assert seven[2]["mttr_rounds"] != two[2]["mttr_rounds"]
    # ... and so does the failed SHARE, by more than a pair of runs may differ.
    assert abs(seven[1] / seven[0] - two[1] / two[0]) > 1e-5


def test_fewer_segments_than_stated_counts_what_there_is():
    assert in_line(FIRST_TWO[:1], STATED) == in_line(FIRST_TWO[:1], {})
    assert run.op_counts(run.counted(FIRST_TWO[:1], STATED), OPS)["segments"] == 1
    assert run.op_counts(run.counted(FIRST_TWO + LATER, {"counted_segments": 40}), OPS)[
        "segments"] == 7


def test_time_is_the_whole_windows():
    """Seven 4 s segments and two 12.5 s ones: the rate and the wall ms per
    round follow the window, the round counts inside the latencies do not."""
    slow = run.end_to_end(FIRST_TWO, in_line(FIRST_TWO, STATED)[2], 25.0, 30.0, G, WANTED)
    fast_reports = FIRST_TWO + LATER
    fast = run.end_to_end(fast_reports, in_line(fast_reports, STATED)[2], 28.0, 30.0, G, WANTED)
    assert slow["group_rounds_per_s"][0] == G * 1200 / 25.0
    assert fast["group_rounds_per_s"][0] == G * 4200 / 28.0
    ms_slow, ms_fast = 1e3 * 25.0 / 1200, 1e3 * 28.0 / 4200
    assert slow["read_p99_ms"][0] == pytest.approx((18.5 + 1) * ms_slow, rel=1e-12)
    assert fast["read_p99_ms"][0] == pytest.approx((18.5 + 1) * ms_fast, rel=1e-12)
    assert fast["recover_ms"][0] / slow["recover_ms"][0] == pytest.approx(ms_fast / ms_slow)
    assert set(slow) == set(WANTED) and slow["setup_s"] == (30.0, "s")


def test_a_counted_segment_without_a_read_or_an_episode_stops_the_run_a_later_one_does_not():
    no_read, no_episode = report(0, 0, -1, 20.0), report(0, 0, 3, None)
    for bad, why in ((no_read, "served no read"), (no_episode, "no leaderless episode")):
        with pytest.raises(run.BenchError, match=why):
            run.round_counts(run.counted([FIRST_TWO[0], bad], STATED), WANTED)
        with pytest.raises(run.BenchError, match=why):
            run.round_counts(run.counted(FIRST_TWO + [bad], {}), WANTED)
        assert run.round_counts(run.counted(FIRST_TWO + [bad], STATED), WANTED)
    assert run.round_counts([no_read, no_episode], {"group_rounds_per_s": "x"}) == {}


@pytest.mark.parametrize("value", [0, -2, 1.5, True, "2", [2]])
def test_a_mix_whose_number_is_no_positive_integer_is_refused(bench, tmp_path, monkeypatch, value):
    mix = dict(traffic.load_mix("outage"), counted_segments=value)
    (tmp_path / "outage.json").write_text(json.dumps(mix), encoding="utf-8")
    monkeypatch.setattr(traffic, "MIX_DIR", str(tmp_path))
    with pytest.raises(run.BenchError, match="counted_segments"):
        run.find_cell(bench, "fleet-100k-r5.outage")


def test_the_mixes_that_state_it(bench):
    """The two whose healthy share of failures is not 0 state what the
    accepted tree completes in the window; the others count every segment."""
    for cell, want in {
        "fleet-100k-r5.serve": None, "fleet-1m-r3.serve": None, "fleet-100k-r5.load": None,
        "fleet-100k-r5.outage": 2, "fleet-100k-r3of5.rebalance": 2,
    }.items():
        assert run.find_cell(bench, cell)[2].get("counted_segments") == want, cell
