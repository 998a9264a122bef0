"""The tokens entry on the CPU at a small size.  A routed-expert decoder
joins the benchmark as new files (its configuration, reference and mixes
live only in ``tests/fixtures/``): a whole run past the harness's look
for a chip is correct and reports its latencies through the metric
readers there are; with the timed path broken underneath, in prefill or
in decode, ``correct`` comes out false; the reference computed in float8
fails too."""

import time
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import faults
from harness.cell import run
from harness.correct import rel_l2
from harness.entries import tokens as TK
from harness.model import load_config
from harness.weights import make_weights
from tokens_fixture import CELLS, FIXTURES, bench_with_fixture

TINY = FIXTURES / "moe_decoder_tiny.json"
SEED = 2**33 + 21
FAULT_SEEDS = (2**33 + 7, 2**33 + 1007, 2**33 + 2007)   # the limits' faults
EXPECTED = ("worst_first_gap", "worst_answer_gap", "far_position_share",
            "unanswered", "nonfinite")


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """The module's runs share their compiled programs through JAX's
    persistent cache, as a cell's runs share ``bench/run.py``'s."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update(keys[1], 0)
    jax.config.update(keys[2], 0)
    cc.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.fixture(scope="module")
def bench(tmp_path_factory) -> Path:
    return bench_with_fixture(tmp_path_factory.mktemp("tokens"))


def small_run(bench, cell, plant=None, seconds=1.0, also=(), seed=SEED):
    return run(bench, cell, seed, seconds, False, time.perf_counter(),
               require_tpu=False, plant=plant, also=also)


def test_a_token_configuration_joins_as_new_files(bench):
    """Open loop, with the prefix cache: every request is answered, the
    answers agree with the reference, and the cell reports what the
    frames' open-loop cell reports, through the same readers; the
    scheduler's readers read it too, and repeated prompts hit the
    prefix cache."""
    seen = {}
    out = small_run(bench, CELLS[0], also=("queue_wait_ms",
                                           "batch_occupancy"),
                    plant=lambda s: seen.update(
                        prefix=s.backend.prefix, warm=s.backend.prefix.hits))
    assert seen["prefix"].hits > seen["warm"]        # the window's own
    also = {k: out["metrics"].pop(k)["value"]
            for k in ("queue_wait_ms", "batch_occupancy")}
    assert also["queue_wait_ms"] >= 0 and 0 < also["batch_occupancy"] <= 100
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == 8 and out["failed"] == 0
    assert set(out["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
    m = out["metrics"]
    assert 0 < m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]
    assert list(out)[-1] == "checks"
    assert tuple(out["checks"]) == EXPECTED


def test_closed_loop_run_is_correct(bench):
    out = small_run(bench, CELLS[1])
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 8 and out["failed"] == 0


@pytest.mark.parametrize("seed", FAULT_SEEDS)
@pytest.mark.parametrize("fault", faults.TOKEN_FAULTS)
def test_broken_token_path_is_not_correct(bench, fault, seed):
    """On every seed the fault readings of the fixture's limits came
    from (float8 weights read least at 2**33 + 1007)."""
    out = small_run(bench, CELLS[1], seed=seed,
                    plant=lambda s: faults.plant(s.backend, fault))
    assert out["correct"] is False, (fault, out["checks"])
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_float8_reference_is_not_correct():
    """The control: at each position of answers that the float32
    reference itself decoded greedily, the token that the reference
    computed in float8 puts first."""
    cfg = load_config(TINY)
    ref = _fixture_reference()
    mix = TK.load_mix(FIXTURES / "tokens.tiny_closed.json")
    pool = TK.make_pool(mix, cfg["sizes"]["vocab_size"], SEED)
    w = make_weights(ref.param_spec(cfg), SEED)
    rows = _greedy_rows(ref, cfg, w, pool.prompts, 8, mix.max_len)
    with jax.default_matmul_precision("highest"):
        own = TK.gaps(ref, cfg, w, rows, mix.max_len)
        ctl = TK.gaps(ref, cfg, w, rows, mix.max_len, judge="fp8")
    limits = cfg["limits"]
    own_n = TK.numbers(own, cfg["far_gap"], 0, 0)
    assert own_n["worst_answer_gap"] == 0.0 and own_n["worst_gap"] == 0.0
    ctl_n = TK.numbers(ctl, cfg["far_gap"], 0, 0)
    assert any(ctl_n[k] > v for k, v in limits.items()), ctl_n


def _fixture_reference():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "moe_decoder_ref", FIXTURES / "moe_decoder_ref.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _greedy_rows(ref, cfg, w, prompts, n, length):
    """[(task 0, prompt, the reference's own greedy answer of n tokens)]."""
    toks = np.zeros((len(prompts), length), np.int32)
    for j, p in enumerate(prompts):
        toks[j, :len(p)] = p
    answers = [[] for _ in prompts]
    tasks = np.zeros((len(prompts),), np.int32)
    with jax.default_matmul_precision("highest"):
        for i in range(n):
            logits = np.asarray(ref.forward(w, toks, tasks, cfg))
            for j, p in enumerate(prompts):
                t = int(logits[j, len(p) - 1 + i].argmax())
                answers[j].append(t)
                toks[j, len(p) + i] = t
    return [(0, p, np.asarray(a)) for p, a in zip(prompts, answers)]


def test_token_mix_arrivals_are_every_seeds():
    mix = TK.load_mix(FIXTURES / "tokens.tiny.json")
    loads = [TK.TokenLoad(mix, s, TK.make_pool(mix, 128, s))
             for s in (1, 2**31 + 5, 2**40)]
    scheds = [ld.schedule(30.0) for ld in loads]
    times = [t for t, _, _ in scheds[0]]
    assert len(times) == round(mix.rate_per_s * 30.0)
    assert times == sorted(times) and times[0] == 0.0 and times[-1] < 30.0
    for s in scheds[1:]:
        assert [t for t, _, _ in s] == times
    assert [i for _, i, _ in scheds[0]] != [i for _, i, _ in scheds[1]]


def test_asks_repeat_prompts_and_lengths_stay_in_range():
    mix = TK.load_mix(FIXTURES / "tokens.tiny.json")
    pools = [TK.make_pool(mix, 128, s) for s in (3, 2**35 + 1)]
    for pool in pools:
        assert len(pool.prompts) == mix.prompt_pool
        lengths = [len(p) for p in pool.prompts]
        assert min(lengths) == mix.prompt_tokens.lo
        assert max(lengths) == mix.prompt_tokens.hi
        sent = [p for p, _ in pool.sends]
        for i in range(mix.prompt_pool):
            assert sent.count(i) == pool.asks[i]
            assert mix.asks_per_prompt.lo <= pool.asks[i] \
                <= mix.asks_per_prompt.hi
        assert max(pool.asks) > 1                 # prompts are asked again
        answers = [a for _, a in pool.sends]
        assert mix.answer_tokens.lo <= min(answers)
        assert max(answers) <= mix.answer_tokens.hi
        assert all(((p >= 0) & (p < 128)).all() for p in pool.prompts)
    # every seed: the same sizes, other token ids
    a, b = pools
    assert [len(p) for p in a.prompts] == [len(p) for p in b.prompts]
    assert a.sends == b.sends
    assert any((x != y).any() for x, y in zip(a.prompts, b.prompts))
    # a pass over the pool sends every ask once before any repeats
    load = TK.TokenLoad(mix, 7, a)
    items = [load.first(0)[0] for _ in range(len(a.sends))]
    assert sorted(items) == list(range(len(a.sends)))


def test_ranges_give_sizes_around_their_median():
    r = TK.Range.parse({"min": 4, "max": 64, "median": 16}, "r")
    s = r.sizes(101)
    assert list(s) == sorted(s) and s[0] == 4 and s[-1] == 64
    assert s[50] == 16
    assert list(TK.Range.parse(7, "r").sizes(3)) == [7, 7, 7]
    with pytest.raises(ValueError, match="median"):
        TK.Range.parse({"min": 8, "max": 4}, "r")


def test_file_and_program_must_agree():
    from repro import configs

    cfg = load_config(TINY)
    st, sz = cfg["structure"], cfg["sizes"]
    full = configs.get("kimi_k2_1t_a32b")
    at_full = dict(cfg, sizes=dict(
        {k: getattr(full, k) for k in sz if k != "moe"},
        moe={k: getattr(full.moe, k) for k in sz["moe"]}))
    assert TK.arch_from(at_full) == full
    assert TK.arch_from(cfg).moe.d_ff == 64      # the expert width is a size
    for key, value in (("mlp_kind", "gelu"), ("norm", "layernorm"),
                       ("rope_theta", 1e4), ("block_pattern", ["attn_mlp"])):
        with pytest.raises(ValueError, match=key):
            TK.arch_from(dict(cfg, structure=dict(st, **{key: value})))
    with pytest.raises(ValueError, match="top_k"):
        TK.arch_from(dict(cfg, structure=dict(
            st, moe=dict(st["moe"], top_k=2))))
    with pytest.raises(ValueError, match="does not state norm"):
        TK.arch_from(dict(cfg, structure={k: v for k, v in st.items()
                                          if k != "norm"}))
    with pytest.raises(ValueError, match="both"):
        TK.arch_from(dict(cfg, sizes=dict(sz, norm="rmsnorm")))


def test_reference_is_the_programs_forward_in_float32():
    """Same weights, same mathematics: the program's float32 forward
    (exact activations) and the reference agree at every position."""
    from repro.models import transformer as T
    from repro.ops import policy_named

    cfg = load_config(TINY)
    ref = _fixture_reference()
    arch = TK.arch_from(cfg)
    params = TK.served_params(arch, ref.param_spec(cfg), 11)
    a32 = replace(arch, dtype="float32", policy=policy_named("xla"))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(0, 128, (2, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(T.forward(p32, jnp.asarray(toks), a32)[0])
        got = np.asarray(ref.forward(make_weights(ref.param_spec(cfg), 11),
                                     toks, np.zeros(2, np.int32), cfg))
    assert got.shape == want.shape == (2, 24, 128)
    for i in range(2):
        assert rel_l2(got[i], want[i]) < 1e-5
