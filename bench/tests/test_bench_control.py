"""The comparison's controls at a small size on the CPU, through the same
``compare`` that a run makes: the served bfloat16 model passes; the
program's own int8-weight path and the reference computed in float8 do
not (the chip-size readings are ``bench/control.py``'s, given in
PERF.md)."""

from dataclasses import replace
from pathlib import Path

import pytest

import control as CT
from harness.correct import compare, reference_answers
from harness.model import (arch_from, load_config, load_reference,
                           make_frames, served_params)
from harness.weights import make_weights

BENCH = Path(__file__).resolve().parents[1]
TINY = BENCH / "tests" / "fixtures" / "m3vit_tiny.json"
SEED, TASKS = 2**31 + 77, (0, 1)


@pytest.fixture(scope="module")
def setting():
    cfg = load_config(TINY)
    ref_mod = load_reference(BENCH, cfg)
    frames = make_frames(cfg, 8, SEED)
    pairs = [(f, t) for f in range(len(frames)) for t in TASKS]
    w = make_weights(ref_mod.param_spec(cfg), SEED)
    ref = reference_answers(ref_mod, cfg, w, frames, pairs)
    params = served_params(arch_from(cfg), ref_mod.param_spec(cfg), SEED)
    return cfg, ref_mod, frames, pairs, w, ref, params


def verdict(cfg, answers, ref):
    return compare([(f, t, y) for (f, t), y in answers.items()], ref,
                   cfg["limits"], cfg["image"]["patch"])


def test_float8_control_fails_and_the_served_model_passes(setting):
    cfg, ref_mod, frames, pairs, w, ref, params = setting
    served = CT.served_answers(arch_from(cfg), params, frames, TASKS, 4)
    control = reference_answers(ref_mod, cfg, w, frames, pairs, "fp8")
    program, ctl = verdict(cfg, served, ref), verdict(cfg, control, ref)
    assert program["correct"] is True, program["checks"]
    assert ctl["correct"] is False, ctl["checks"]
    p = program["checks"]["worst_token_error"]["value"]
    assert ctl["checks"]["worst_token_error"]["value"] > 3 * p


def test_int8_weight_path_is_not_correct(setting):
    from repro.ops import policy_named
    from repro.quant import quantize_tree

    cfg, _, frames, _, _, ref, params = setting
    arch = replace(arch_from(cfg), policy=policy_named("xla_int8"))
    got = CT.served_answers(arch, quantize_tree(params, bits=8), frames,
                            TASKS, 4)
    v = verdict(cfg, got, ref)
    assert v["correct"] is False, v["checks"]
    bias = v["checks"]["worst_task_bias"]
    assert bias["value"] > bias["limit"]
