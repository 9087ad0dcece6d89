"""Percentile, due-time TTFT and token-timestamp window arithmetic on
hand-made records."""

import pytest

from perfbench import stats


def _rec(due, sent, times, error=None):
    return {"due": due, "sent": sent, "token_times": times, "error": error}


@pytest.mark.parametrize("values,p,want", [
    ([5], 50, 5), ([5], 99, 5),
    ([1, 2, 3, 4], 50, 2), ([1, 2, 3, 4], 75, 3), ([4, 3, 2, 1], 100, 4),
    (list(range(1, 101)), 90, 90), (list(range(1, 201)), 90, 180),
    (list(range(1, 11)), 91, 10),
])
def test_percentile_is_nearest_rank(values, p, want):
    assert stats.percentile(values, p) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_counts_from_when_the_request_was_due_not_from_the_send():
    rec = _rec(due=10.0, sent=10.4, times=[10.9, 11.0])
    assert stats.ttft_ms(rec) == pytest.approx(900.0)
    assert stats.lateness_ms(rec) == pytest.approx(400.0)


def test_tpot_is_last_minus_first_over_gaps():
    assert stats.tpot_ms(_rec(0, 0, [1.0, 1.1, 1.4])) == pytest.approx(200.0)
    assert stats.tpot_ms(_rec(0, 0, [1.0])) is None


def test_window_edges_cut_between_tokens_not_between_requests():
    recs = [_rec(0, 0, [0.5, 1.0, 1.5, 2.0]),      # straddles the opening
            _rec(0, 0, [1.9, 2.9, 3.0, 3.1]),      # straddles the close
            _rec(0, 0, [])]
    assert stats.tokens_between(recs, 1.0, 3.0) == 3 + 2
    assert stats.tokens_between(recs, 3.0, 9.0) == 2   # half-open at both ends


def test_answered_leaves_out_errors_and_empty_streams():
    recs = [_rec(0, 0, [1.0]), _rec(0, 0, [1.0], error="boom"), _rec(0, 0, [])]
    assert stats.answered(recs) == recs[:1]


def _check(errs):
    return {"abs_logprob_errs": errs, "finite": True}


@pytest.mark.parametrize("errs,routed,want", [
    ([0.02] * 17 + [0.12], False, True),          # bf16 rounding, dense
    ([0.02] * 17 + [0.9], False, False),          # one far token fails a dense model
    ([0.05] * 42 + [0.6, 1.2, 7.0] * 4, True, True),   # flipped routes, as on the chip
    ([0.05] * 35 + [0.6] * 19, True, False),      # over a third far: wrong experts
    ([0.4] * 54, True, False),                    # a lower precision everywhere
    ([0.2] * 54, True, False),                    # the median alone
    ([0.2] * 18, False, False),
])
def test_logprobs_agree(errs, routed, want):
    assert stats.logprobs_agree(_check(errs), routed) is want


def test_a_non_finite_logprob_never_agrees():
    check = _check([0.01] * 18)
    check["finite"] = False
    assert stats.logprobs_agree(check, routed=False) is False


def test_tokens_by_second_counts_whole_seconds_of_the_window():
    recs = [_rec(0, 0, [9.9, 10.0, 10.5, 11.2, 12.99, 13.0])]
    assert stats.tokens_by_second(recs, 10.0, 3.0) == [2, 1, 1]
