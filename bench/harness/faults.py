"""Faults planted in the timed path, to show that ``correct`` catches
them (``bench/tests``) and to read them on the chip (``bench/control.py``).
Each breaks the warm served model in place: the vision entry's faults
take its ``M3ViTServer``, the tokens entry's its ``LMBackend``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["FAULTS", "TOKEN_FAULTS", "plant"]


def stale_slot(server, slot: int = 0) -> None:
    """Paging in the last MoE layer leaves one slot stale: an expert paged
    into ``slot`` is booked as resident there, but the slot keeps the
    weights it held before, so tokens routed to that expert (about a
    quarter of them at top-4 of 16) are served by another's."""
    cache = server.paged[max(server.paged)].cache
    commit, commit_batch = cache._commit, cache._commit_batch

    def skip_write(e, s, arrays):
        write, cache._write = cache._write, (lambda slots, dev, i: slots)
        try:
            commit(e, s, arrays)
        finally:
            cache._write = write

    def stale_commit(e, s, arrays):
        (skip_write if s == slot else commit)(e, s, arrays)

    def stale_batch(batch):
        commit_batch([b for b in batch if b[1] != slot])
        for b in batch:
            if b[1] == slot:
                skip_write(*b)

    cache._commit, cache._commit_batch = stale_commit, stale_batch


def _wrap_infer(server, change) -> None:
    infer = server.infer
    state: dict = {}

    def broken(images, task):
        return change(np.array(infer(images, task)), task, state)
    server.infer = broken


def stale_answer(server) -> None:
    """Each forward returns the answers of the task's previous forward."""
    def change(y, task, state):
        prev = state.get(task)
        state[task] = y
        return y if prev is None else prev
    _wrap_infer(server, change)


def half_batch(server) -> None:
    """The batch's second half is left out: its rows repeat the first's."""
    def change(y, task, state):
        h = len(y) // 2
        y[h:] = y[:h]
        return y
    _wrap_infer(server, change)


def altered(server) -> None:
    """One answer of each forward is altered where it is produced."""
    def change(y, task, state):
        y[0] = -y[0]
        return y
    _wrap_infer(server, change)


def stale_kv(backend) -> None:
    """Decode steps leave the first layer's cache as it was: the new
    token's key and value rows never land, so later steps read the rows a
    lane held before (zeros, or an earlier request's)."""
    decode = backend.decode_step()

    def step(params, toks, state, cache_pos, task_ids):
        tok, new, counts = decode(params, toks, state, cache_pos, task_ids)
        first = jax.tree.map(lambda o, n: n.at[0].set(o[0]),
                             state["layers"], new["layers"])
        return tok, {**new, "layers": first}, counts

    backend._decode_fn = jax.jit(step, donate_argnums=(2,))


def prefill_without_experts(backend) -> None:
    """Admissions prefill with the first layer's routed experts zeroed:
    the first token, and the cache rows of the prompt above that layer,
    are computed without them (the shared expert stays)."""
    layers = backend.params["layers"]
    moe = layers["b0"]["moe"]
    broken = {**backend.params, "layers": {**layers, "b0": {
        **layers["b0"], "moe": {**moe, **{
            k: moe[k].at[0].set(0) for k in ("wg", "wu", "wd") if k in moe}}}}}
    admit, staged = backend.admit_step, backend.staged_steps

    def with_broken(fn):
        return lambda params, *args: fn(broken, *args)

    backend.admit_step = lambda task: with_broken(admit(task))
    backend.staged_steps = lambda task: tuple(
        with_broken(f) for f in staged(task))


def weights_fp8(backend) -> None:
    """Every bfloat16 weight is served rounded to float8 (e4m3)."""
    backend.params = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.dtype == jnp.bfloat16 else a, backend.params)


def altered_token(backend) -> None:
    """The first lane's token is altered where each decode step produces
    it (the next id of the vocabulary), and fed back as served."""
    decode = backend.decode_step()
    vocab = backend.cfg.vocab_size

    def step(params, toks, state, cache_pos, task_ids):
        tok, new, counts = decode(params, toks, state, cache_pos, task_ids)
        return tok.at[0].set((tok[0] + 1) % vocab), new, counts

    backend._decode_fn = jax.jit(step, donate_argnums=(2,))


FAULTS = {"stale_slot": stale_slot, "stale_answer": stale_answer,
          "half_batch": half_batch, "altered": altered,
          "stale_kv": stale_kv,
          "prefill_without_experts": prefill_without_experts,
          "weights_fp8": weights_fp8, "altered_token": altered_token}
TOKEN_FAULTS = ("stale_kv", "prefill_without_experts", "weights_fp8",
                "altered_token")


def plant(target, name: str) -> None:
    FAULTS[name](target)
