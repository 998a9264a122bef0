"""The vision entry: frames through ``launch.serve.vision_scheduler``
(``Scheduler`` -> ``VisionBackend`` -> ``M3ViTServer`` -> ``PagedMoE``),
the path of the ``m3vit`` configurations.

The model.  ``arch_from`` gives the program's ``ArchConfig`` with the
file's sizes; the served tree comes from ``models.vit.init_params``
shapes, every leaf drawn by ``harness.weights.make_weights``.

The traffic.  A frame mix (``bench/traffic/frames.*.json``) is either

  open    frames arrive on a schedule (``harness.traffic``):
          ``rate_frames_per_s`` (mean), ``burst`` frames per arrival
          (1: a steady Poisson stream), each frame asking for every task
          in ``tasks`` at the same due time;
  closed  ``clients`` clients each send one frame with one task and,
          when its answer is back, the next frame with the next task of
          ``tasks`` (client c starts at task ``c mod len(tasks)``);

plus ``slots`` (scheduler slots in all) and ``frame_pool`` (distinct
float32 frames, standard normal, drawn from the seed on the device;
frames are taken from the pool in a seeded order).

Whether the served answers are right: each against the plain reference's
answer for the same frame and task.  An answer's image tokens are its
16x16 patches (every pixel and channel); a token's error is the distance
between the answer's patch and the reference's, over the reference's
root mean square token norm.  Three numbers are compared, each with a
limit from the configuration file:

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
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from harness.correct import rel_l2, verdict
from harness.drive import run_window, step_counters
from harness.model import served_tree, tree_spec
from harness.traffic import PoolOrder, arrival_times, read_mix
from harness.weights import make_weights, seed_words

__all__ = ["Mix", "load_mix", "open_schedule", "FrameLoad", "arch_from",
           "served_spec", "served_params", "make_frames", "FAR",
           "token_diffs", "answer_numbers", "numbers", "reference_answers",
           "compare", "instrument", "WARM_ROUNDS", "Served"]

WARM_ROUNDS = 2          # full batches per task before the window
FAR = 0.05               # a token this far off is a flip or a fault


# ------------------------------------------------------------ traffic


@dataclass(frozen=True)
class Mix:
    name: str
    kind: str
    slots: int
    tasks: tuple[int, ...]
    frame_pool: int
    rate_frames_per_s: float = 0.0
    burst: int = 1
    clients: int = 0


def load_mix(path) -> Mix:
    name, raw = read_mix(path, "rate_frames_per_s")
    return Mix(name=name, kind=raw["kind"], slots=int(raw["slots"]),
               tasks=tuple(int(t) for t in raw["tasks"]),
               frame_pool=int(raw["frame_pool"]),
               rate_frames_per_s=float(raw.get("rate_frames_per_s", 0.0)),
               burst=int(raw.get("burst", 1)),
               clients=int(raw.get("clients", 0)))


def open_schedule(mix: Mix, seconds: float, seed: int):
    """[(due time in s from the window's start, frame, task)] of an open
    mix, in due order."""
    due = arrival_times(mix.rate_frames_per_s, mix.burst, seconds)
    frames = PoolOrder(mix.frame_pool, np.random.default_rng(seed))
    out = []
    for t in due:
        for _ in range(mix.burst):
            f = frames.next()
            out.extend((float(t), f, task) for task in mix.tasks)
    return out


class FrameLoad:
    """A frame mix as ``harness.drive`` offers it; a request's item is its
    frame's index in the pool."""

    def __init__(self, mix: Mix, seed: int, frames):
        from repro.serve import Request

        self._request = Request
        self.mix, self.seed, self.frames = mix, seed, frames
        self.kind, self.clients = mix.kind, mix.clients
        self._order = PoolOrder(mix.frame_pool, np.random.default_rng(seed))
        self._next_task: dict[int, int] = {}

    def schedule(self, seconds: float):
        return open_schedule(self.mix, seconds, self.seed)

    def first(self, client: int):
        tasks = self.mix.tasks
        k = client % len(tasks)
        self._next_task[client] = (k + 1) % len(tasks)
        return self._order.next(), tasks[k]

    def then(self, client: int):
        tasks = self.mix.tasks
        k = self._next_task[client]
        self._next_task[client] = (k + 1) % len(tasks)
        return self._order.next(), tasks[k]

    def request(self, rid: int, item, task: int, arrival: float):
        return self._request(rid=rid, task_id=task, prompt=self.frames[item],
                             arrival=arrival)


# ------------------------------------------------------------ model


def arch_from(cfg: dict):
    """The program's ``ArchConfig`` with the file's sizes.  Keys of the
    program's configuration that the file does not state keep the
    program's value; those it states and the program has otherwise must
    agree (``block_pattern``, ``norm``, ``mlp_kind``)."""
    from repro import configs

    base = configs.get(cfg["program_config"])
    for key in ("norm", "mlp_kind"):
        if getattr(base, key) != cfg[key]:
            raise ValueError(f"{key}: program {getattr(base, key)!r}, "
                             f"file {cfg[key]!r}")
    if tuple(base.block_pattern) != tuple(cfg["block_pattern"]):
        raise ValueError("block_pattern differs from the program's")
    m = cfg["moe"]
    moe = replace(base.moe, num_experts=m["num_experts"], top_k=m["top_k"],
                  d_ff=m["d_ff"], num_tasks=m["num_tasks"],
                  capacity_factor=m["capacity_factor"],
                  group_size=m["group_size"], renormalize=m["renormalize"])
    # the program spells the usual head size d_model / heads as 0
    hd = cfg["head_dim"]
    return replace(base, num_layers=cfg["num_layers"], d_model=cfg["d_model"],
                   num_heads=cfg["num_heads"], num_kv_heads=cfg["num_heads"],
                   head_dim=0 if hd * cfg["num_heads"] == cfg["d_model"]
                   else hd, d_ff=cfg["d_ff"],
                   dtype=cfg["dtype"], moe=moe, num_tasks=m["num_tasks"])


def served_spec(arch):
    """({path: (shape, dtype)}, flat paths, treedef) of the program's
    parameter tree, from shapes alone."""
    from repro.models import vit as V

    return tree_spec(jax.eval_shape(
        lambda: V.init_params(jax.random.PRNGKey(0), arch)))


def served_params(arch, ref_spec: dict, seed: int):
    """The program's parameter tree from ``seed``
    (``harness.model.served_tree``)."""
    from repro.models import vit as V

    return served_tree(jax.eval_shape(
        lambda: V.init_params(jax.random.PRNGKey(0), arch)), ref_spec, seed)


def make_frames(cfg: dict, pool: int, seed: int) -> np.ndarray:
    """``pool`` distinct float32 frames (H, W, 3), standard normal, made on
    the device in one call and kept on the host, where requests carry
    them."""
    im = cfg["image"]
    shape = (pool, im["height"], im["width"], im["channels"])

    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        key = jax.random.fold_in(key, 0x5EED)
        return jax.random.normal(key, shape, jnp.float32)

    return np.asarray(jax.jit(build)(*seed_words(seed)))


# ------------------------------------------------------------ correct


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
    return verdict(numbers(answers, reference, patch), limits,
                   ("worst_rel_l2", "worst_token_p50"))


# ------------------------------------------------------------ spans


class _Layer:
    """A paged MoE layer seen through a span: calls go through one,
    everything else reaches the layer itself."""

    def __init__(self, layer, call):
        self._layer = layer
        self._call = call

    def __call__(self, *args, **kwargs):
        return self._call(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._layer, name)


def instrument(sched, spans) -> None:
    """Spans around the served entry's layers: the scheduler's step is
    wrapped by the caller; here the vision backend's forward (timed), its
    stages, each paged MoE layer, the expert cache's ``ensure`` and slot
    writes, and the task heads."""
    server = getattr(sched.backend, "server", None)
    if server is None:
        return
    spans.wrap_attr(server, "infer", "infer", timed=True)
    spans.wrap_attr(server, "prefetch", "prefetch")
    for attr, name in (("_embed", "embed"), ("_dense", "dense"),
                       ("_moe_pre", "moe_pre"), ("_final", "final_norm")):
        spans.wrap_attr(server, attr, name)
    spans.wrap_items(getattr(server, "_heads", None), "head")
    paged = getattr(server, "paged", None)
    if isinstance(paged, dict):
        for i, layer in list(paged.items()):
            cache = getattr(layer, "cache", None)
            if cache is not None:
                spans.wrap_attr(cache, "ensure", "ensure")
                spans.wrap_attr(cache, "_write", "cache_write")
            paged[i] = _Layer(layer, spans.wrap(layer, "moe"))


# ------------------------------------------------------------ the entry


class Served:
    """The served stack: seeded weights and frames made on the device,
    the entry's defaults (paging at ``resident_fraction`` 0.5, the
    configuration's compute policy), ``slots`` in all."""

    def __init__(self, cfg: dict, mix: Mix, seed: int, ref_mod):
        from repro.launch.serve import vision_scheduler

        self.cfg, self.mix, self.seed, self.ref_mod = cfg, mix, seed, ref_mod
        arch = arch_from(cfg)
        self.params = served_params(arch, ref_mod.param_spec(cfg), seed)
        self.frames = make_frames(cfg, mix.frame_pool, seed)
        self.sched = vision_scheduler(arch, self.params, batch=mix.slots)

    def warm(self) -> None:
        """Full batches of every task the window sends, through the
        window's own scheduler and backend."""
        from repro.serve import Request

        sched, frames = self.sched, self.frames
        per_bucket = sched.slots_per_bucket
        for r in range(WARM_ROUNDS):
            now = sched.now()
            warm = [Request(rid=-1, task_id=t,
                            prompt=frames[(r * per_bucket + j) % len(frames)],
                            arrival=now)
                    for t in self.mix.tasks for j in range(per_bucket)]
            sched.run(warm)

    def instrument(self, spans) -> None:
        instrument(self.sched, spans)

    def drive(self, seconds: float, seed: int, step=None):
        return run_window(self.sched, FrameLoad(self.mix, seed, self.frames),
                          seconds, step=step)

    def counters(self) -> dict:
        steps, slot_steps = step_counters(self.sched)
        stats = getattr(self.sched.backend, "cache_stats", None)
        s = stats() if callable(stats) else {}
        return {"steps": steps, "slot_steps": slot_steps,
                "cache": {k: s.get(k, 0)
                          for k in ("hits", "misses", "bytes_paged")}}

    def collect(self, win):
        """[(frame, task, answer or None)] of the requests due in the
        window, and how many of them have no finite answer."""
        answers = [(f, r.task_id, None if r.t_done is None else
                    np.asarray(r.result))
                   for r, f in zip(win.requests, win.items)
                   if win.start <= r.arrival < win.end]
        failed = sum(1 for _, _, y in answers
                     if y is None or not np.isfinite(y).all())
        return answers, failed

    def release(self) -> None:
        self.sched = self.params = None

    def verdict(self, answers) -> dict:
        cfg = self.cfg
        ref_w = make_weights(self.ref_mod.param_spec(cfg), self.seed)
        reference = reference_answers(self.ref_mod, cfg, ref_w, self.frames,
                                      [(f, t) for f, t, _ in answers])
        out = compare(answers, reference, cfg["limits"],
                      cfg["image"]["patch"])
        out["reference"] = f"{len(reference)} frame-task pairs"
        return out
