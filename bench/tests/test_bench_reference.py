"""The plain reference computes the served model: the same configuration,
the same weights, the same mathematics (tied here to the program's own
float32 forward at a small size on the CPU)."""

from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness.correct import rel_l2
from harness.model import (arch_from, load_config, load_reference,
                           make_frames, served_params, served_spec)
from harness.weights import make_weights, seed_words

BENCH = Path(__file__).resolve().parents[1]
TINY = BENCH / "tests" / "fixtures" / "m3vit_tiny.json"


@pytest.mark.parametrize("name", ["m3vit"])
def test_config_files_are_the_programs_configs(name):
    from repro import configs

    cfg = load_config(BENCH / "configs" / f"{name}.json")
    assert arch_from(cfg) == configs.get(name)
    ref = load_reference(BENCH, cfg)
    spec, _, _ = served_spec(arch_from(cfg))
    assert spec == ref.param_spec(cfg)


def test_capacity_is_the_programs():
    from repro.models import transformer as T

    cfg = load_config(TINY)
    ref = load_reference(BENCH, cfg)
    for experts, k in ((8, 2), (16, 4), (256, 4)):
        c = dict(cfg, moe=dict(cfg["moe"], num_experts=experts, top_k=k))
        mcfg = T.moe_config(arch_from(c))
        assert ref.capacity(c, 128) == mcfg.capacity(128)


def test_seed_words_take_large_seeds():
    assert seed_words(2**40 + 3) == (3, 256)
    with pytest.raises(ValueError):
        seed_words(-1)


def test_served_weights_equal_the_references():
    cfg = load_config(TINY)
    ref = load_reference(BENCH, cfg)
    params = served_params(arch_from(cfg), ref.param_spec(cfg), 2**33 + 1)
    w = make_weights(ref.param_spec(cfg), 2**33 + 1)
    assert params["layers"]["b1"]["moe"]["w1"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["b1"]["moe"]["w1"], np.float32),
        np.asarray(w["layers/b1/moe/w1"], np.float32))
    np.testing.assert_array_equal(np.asarray(params["patch"]["b"]),
                                  np.asarray(w["patch/b"]))
    assert float(jnp.std(w["final_norm/scale"])) > 0.05   # seeded, not 1


@pytest.mark.parametrize("task", [0, 1])
def test_reference_is_the_programs_forward_in_float32(task):
    from repro.models import vit as V
    from repro.ops import policy_named

    cfg = load_config(TINY)
    ref = load_reference(BENCH, cfg)
    arch = arch_from(cfg)
    params = served_params(arch, ref.param_spec(cfg), 11)
    frames = make_frames(cfg, 4, 11)
    a32 = replace(arch, dtype="float32", policy=policy_named("xla"))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(V.forward(p32, jnp.asarray(frames), a32,
                                    task=cfg["tasks"][task])[0])
    got = np.asarray(ref.forward(make_weights(ref.param_spec(cfg), 11),
                                 frames, task, cfg))
    assert got.shape == want.shape
    for i in range(len(frames)):
        assert rel_l2(got[i], want[i]) < 1e-5
