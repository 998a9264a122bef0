"""Whether the served answers are right: each against the plain
reference's answer for the same frame and task.

An answer's image tokens are its 16x16 patches (every pixel and channel);
a token's error is the distance between the answer's patch and the
reference's, over the reference's root mean square token norm.  Three
numbers are compared, each with a limit from the configuration file:

  worst_token_error     the lower quartile of an answer's token errors,
                        largest over the answers.  A routing flip (a
                        near-tied gate that picks another expert) moves a
                        few tokens far; the quartile keeps that out, while
                        an answer computed in a lower precision, for
                        another image, or altered moves every token.
  worst_task_bias       the mean token difference (answer patch minus
                        reference patch, over the same norm) over every
                        answer of a task and every token of each, the
                        norm of it, largest over the tasks.  Rounding and
                        flips scatter in every direction and cancel over
                        hundreds of answers; an error that the weights
                        carry, such as int8 weights, is shared by every
                        answer of the task and stays.
  worst_far_token_share the share of an answer's tokens whose error
                        exceeds ``FAR``, largest over the answers.  Flips
                        reach a few of an answer's tokens; a fault that
                        reaches a minority of them, such as an expert
                        served with another's weights, reaches more.

Answers never given and answers holding a value that is not finite are
counted too, each with the limit 0.
"""

from __future__ import annotations

import numpy as np

__all__ = ["FAR", "rel_l2", "token_diffs", "answer_numbers", "numbers",
           "reference_answers", "compare"]

FAR = 0.05          # a token this far off is a flip or a fault


def rel_l2(y, ref) -> float:
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(y - ref) / max(np.linalg.norm(ref), 1e-30))


def token_diffs(y, ref, patch: int) -> np.ndarray:
    """(tokens, p*p*channels): each image token's patch of the answer
    minus the reference's, over the reference's root mean square token
    norm."""
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    if ref.ndim == 2:
        y, ref = y[..., None], ref[..., None]
    h, w, c = ref.shape

    def tokens(a):
        a = a.reshape(h // patch, patch, w // patch, patch, c)
        return a.transpose(0, 2, 1, 3, 4).reshape(-1, patch * patch * c)

    rt = tokens(ref)
    scale = max(np.sqrt((rt ** 2).sum(-1).mean()), 1e-30)
    return (tokens(y) - rt) / scale


def answer_numbers(y, ref, patch: int) -> dict:
    """One answer's readings."""
    d = token_diffs(y, ref, patch)
    err = np.linalg.norm(d, axis=-1)
    return {"p25": float(np.percentile(err, 25)),
            "p50": float(np.percentile(err, 50)),
            "mean_diff": d.mean(0),
            "far": float((err > FAR).mean()),
            "rel_l2": rel_l2(y, ref)}


def numbers(answers, reference: dict, patch: int) -> dict:
    """``answers``: [(frame, task, served answer or None)].  Every number
    that may be compared, and those only shown (``worst_rel_l2``,
    ``worst_token_p50``)."""
    per, missing, nonfinite = [], 0, 0
    by_task: dict[int, list[np.ndarray]] = {}
    for f, t, y in answers:
        if y is None:
            missing += 1
        elif not np.isfinite(y).all():
            nonfinite += 1
        else:
            per.append(answer_numbers(y, reference[(f, t)], patch))
            by_task.setdefault(t, []).append(per[-1]["mean_diff"])

    def worst(k):
        return max((a[k] for a in per), default=float("nan"))

    return {"worst_token_error": worst("p25"),
            "worst_task_bias": max((float(np.linalg.norm(np.mean(m, 0)))
                                    for m in by_task.values()),
                                   default=float("nan")),
            "worst_far_token_share": worst("far"),
            "unanswered": missing, "nonfinite": nonfinite,
            "compared": len(per),
            "worst_rel_l2": worst("rel_l2"), "worst_token_p50": worst("p50")}


def reference_answers(ref_mod, cfg: dict, weights: dict, frames,
                      pairs, precision: str = "f32", block: int = 4):
    """{(frame, task): answer} of the reference for each distinct pair,
    ``block`` frames to a call, so that it fits beside nothing else."""
    out = {}
    by_task: dict[int, list[int]] = {}
    for f, t in sorted(set(pairs)):
        by_task.setdefault(t, []).append(f)
    for t, fs in by_task.items():
        for i in range(0, len(fs), block):
            chunk = fs[i:i + block]
            # a fixed block shape: one compiled program per task
            idx = chunk + [chunk[0]] * (block - len(chunk))
            y = np.asarray(ref_mod.forward(weights, frames[idx], t, cfg,
                                           precision))
            for j, f in enumerate(chunk):
                out[(f, t)] = y[j]
    return out


def compare(answers, reference: dict, limits: dict, patch: int) -> dict:
    """The numbers, each compared with its limit from ``limits`` (the
    configuration's), ``correct`` when every one is within its limit, and
    the numbers only shown."""
    got = numbers(answers, reference, patch)
    limits = {**limits, "unanswered": 0, "nonfinite": 0}
    checks = {k: {"value": got[k], "limit": v} for k, v in limits.items()}
    # NaN (nothing compared) fails every comparison
    correct = got["compared"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    shown = {k: got[k] for k in ("worst_rel_l2", "worst_token_p50")}
    return {"correct": bool(correct), "checks": checks,
            "compared": got["compared"], "shown": shown}
