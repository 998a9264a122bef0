"""What each compared number sees, on made-up answers: a few far tokens
(a routing flip) pass; a shift shared by the tokens, a minority of tokens
moved, a lost or a non-finite answer fail."""

import numpy as np
import pytest

from harness.correct import FAR, compare, numbers

PATCH = 2              # 128 tokens an answer
LIMITS = {"worst_token_error": 0.04, "worst_task_bias": 0.01,
          "worst_far_token_share": 0.3}


def answers(change=None, n=6, seed=0):
    rng = np.random.default_rng(seed)
    ref = {(f, 0): rng.standard_normal((16, 32, 3)) for f in range(n)}
    got = []
    for (f, t), r in ref.items():
        y = r + 1e-3 * rng.standard_normal(r.shape)    # rounding
        got.append((f, t, change(y, r) if change else y))
    return got, ref


def tokens_of(y):
    """View of ``y`` as (token rows, token cols, p, p, c) patches."""
    return y.reshape(16 // PATCH, PATCH, 32 // PATCH, PATCH, 3) \
        .transpose(0, 2, 1, 3, 4)


def far_tokens(share):
    """Moves the first ``share`` of the tokens 0.2 of the token norm off,
    each in a direction of its own, as a routing flip does."""
    rng = np.random.default_rng(1)

    def change(y, r):
        t = tokens_of(y)                     # a view: writes go to y
        rows = t.shape[1]
        scale = np.sqrt((tokens_of(r) ** 2).sum((2, 3, 4)).mean())
        for i in range(int(share * t.shape[0] * rows)):
            d = rng.standard_normal(t.shape[2:])
            t[divmod(i, rows)] += 0.2 * scale * d / np.linalg.norm(d)
        return y
    return change


def test_sound_answers_and_a_few_flips_pass():
    got, ref = answers(far_tokens(0.1))
    v = compare(got, ref, LIMITS, PATCH)
    assert v["correct"] is True, v["checks"]
    n = numbers(got, ref, PATCH)
    assert 0.09 < n["worst_far_token_share"] < 0.11


@pytest.mark.parametrize("change, number", [
    (lambda y, r: y + 0.05 * r.std(), "worst_task_bias"),
    (far_tokens(0.4), "worst_far_token_share"),
    (lambda y, r: r * 1.1, "worst_token_error"),
])
def test_each_fault_fails_its_number(change, number):
    got, ref = answers(change)
    v = compare(got, ref, LIMITS, PATCH)
    assert v["correct"] is False
    c = v["checks"][number]
    assert c["value"] > c["limit"], v["checks"]


def test_a_lost_or_nonfinite_answer_fails():
    got, ref = answers()
    got[0] = (got[0][0], got[0][1], None)
    y = got[1][2].copy()
    y[0, 0, 0] = np.nan
    got[1] = (got[1][0], got[1][1], y)
    v = compare(got, ref, LIMITS, PATCH)
    assert v["correct"] is False
    assert v["checks"]["unanswered"]["value"] == 1
    assert v["checks"]["nonfinite"]["value"] == 1
    assert v["compared"] == len(got) - 2


def test_nothing_compared_is_not_correct():
    got, ref = answers()
    v = compare([(f, t, None) for f, t, _ in got], ref, LIMITS, PATCH)
    assert v["correct"] is False


def test_far_is_above_rounding():
    got, ref = answers()
    assert numbers(got, ref, PATCH)["worst_far_token_share"] == 0.0
    assert FAR > 10 * 1e-3
