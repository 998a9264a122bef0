"""The tokens entry: token prompts through the language-model serving
path, ``LMBackend`` under the continuous-batching ``Scheduler`` (one
mixed-task bucket of ``slots`` decode lanes, prompts prefilled on
admission, greedy decode with argmax fused into the jitted step), built
as the ``launch.serve --scheduler`` command line builds it.

The model.  The configuration file names the program's configuration
(``program_config``).  Its ``structure`` states fields that must equal
the program's (at least ``STRUCTURE``, and ``MOE_STRUCTURE`` of the MoE
layer: top-k); its ``sizes`` (widths, depth, expert count and width,
vocabulary) replace the program's fields (``arch_from``).
The served tree comes from ``models.transformer.init_params`` shapes,
every leaf drawn by ``harness.weights.make_weights``, and must equal the
reference's ``param_spec`` exactly.

The traffic.  A token mix (``bench/traffic/<name>.json``) is either

  open    requests arrive on a schedule (``harness.traffic``):
          ``rate_per_s`` requests a second (mean), ``burst`` at each
          arrival;
  closed  ``clients`` clients each send their next request when the
          answer to the last one is back;

plus ``slots`` (decode lanes), ``tasks`` (gating tasks; prompt i asks
for task ``tasks[i mod len(tasks)]``), ``prompt_pool`` (distinct
prompts), and three ranges, each a whole number or ``{"min", "max"}``
with an optional ``"median"`` (the geometric mean of the two if not
given): ``prompt_tokens``, ``answer_tokens`` (the tokens decoded for a
request) and ``asks_per_prompt`` (how often each distinct prompt is
sent, so that requests share prefixes).  ``prefix_cache`` (0 if not
given) is the program's prefix cache size in prompts.

Every seed offers the same work: a range gives n sizes as a log-normal's
quantiles at (i + 1/2) / n around its median, spread so that the
outermost quantile reaches the farther end, and clipped to the range.
Prompt i of the pool has the i-th prompt length and the i-th ask count
in one fixed order; the answer lengths of one pass over the pool's asks
are fixed the same way.  The seed draws the token ids and the order in
which the asks are sent; an open mix's arrival times are every seed's.

Whether the served answers are right.  The scheduler returns tokens, not
logits, so each answer is judged by where its tokens stand in the
reference's logits: after the window, the plain float32 reference
(``highest`` matmul precision) runs once over each compared request's
prompt and served answer, teacher-forced on what the server produced, in
blocks of ``REF_BLOCK`` rows.  At each answer position the *gap* is the
reference's largest logit minus its logit of the served token, over the
root mean square of the reference's logits there.  A rounding flip
between near-tied tokens reads as a small gap; a stale cache row, a
wrong position, a dropped expert or layer, or an altered token reads as
a large one.  Compared, each with a limit from the configuration file:

  worst_first_gap       the first answer token's gap (the prefill path),
                        largest over the answers;
  worst_answer_gap      an answer's median gap, largest over the answers;
  far_position_share    the share of all compared positions whose gap
                        exceeds the file's ``far_gap``;

and, with the limit 0, ``unanswered`` (requests due in the window never
answered) and ``nonfinite`` (answers holding a token id outside the
vocabulary).  A run compares ``check_requests`` answers (the file's),
drawn from the seed, the longest among them.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from harness.correct import verdict
from harness.drive import run_window, step_counters
from harness.model import served_tree
from harness.traffic import PoolOrder, arrival_times, read_mix
from harness.weights import make_weights

__all__ = ["Range", "Mix", "load_mix", "Pool", "make_pool", "TokenLoad",
           "STRUCTURE", "MOE_STRUCTURE", "arch_from", "served_params", "lm_scheduler",
           "gaps", "numbers", "Served", "REF_BLOCK", "PAD"]

# fields the file's structure has to state (it may state more)
STRUCTURE = ("block_pattern", "norm", "mlp_kind")
MOE_STRUCTURE = ("top_k",)
PAD = 16                 # the program pads prompts to a multiple of this
REF_BLOCK = 8            # reference rows to a call
WARM_TOKENS = 3          # answer tokens of a warm-up request
FIXED_ORDER_SEED = 20240711          # the one pairing of sizes to prompts


# ------------------------------------------------------------ traffic


@dataclass(frozen=True)
class Range:
    lo: int
    hi: int
    median: float

    @classmethod
    def parse(cls, raw, what: str) -> "Range":
        if isinstance(raw, int):
            return cls(raw, raw, float(raw))
        lo, hi = int(raw["min"]), int(raw["max"])
        med = float(raw.get("median", (lo * hi) ** 0.5))
        if not 1 <= lo <= med <= hi:
            raise ValueError(f"{what}: needs 1 <= min <= median <= max")
        return cls(lo, hi, med)

    def sizes(self, n: int) -> np.ndarray:
        """The n sizes every seed gets, ascending."""
        if self.lo == self.hi or n == 1:
            return np.full(n, int(round(self.median)), np.int64)
        z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                      for i in range(n)])
        sigma = max(np.log(self.hi / self.median),
                    np.log(self.median / self.lo)) / z[-1]
        v = np.round(self.median * np.exp(sigma * z))
        return np.clip(v, self.lo, self.hi).astype(np.int64)


@dataclass(frozen=True)
class Mix:
    name: str
    kind: str
    slots: int
    tasks: tuple[int, ...]
    prompt_pool: int
    prompt_tokens: Range
    answer_tokens: Range
    asks_per_prompt: Range
    rate_per_s: float = 0.0
    burst: int = 1
    clients: int = 0
    prefix_cache: int = 0

    @property
    def max_len(self) -> int:
        """Cache positions a lane holds: the longest prompt and answer,
        and room for a prefix-cache hit's padded one-token suffix."""
        need = self.prompt_tokens.hi + self.answer_tokens.hi + PAD
        return -(-need // PAD) * PAD


def load_mix(path) -> Mix:
    name, raw = read_mix(path, "rate_per_s")
    return Mix(name=name, kind=raw["kind"], slots=int(raw["slots"]),
               tasks=tuple(int(t) for t in raw["tasks"]),
               prompt_pool=int(raw["prompt_pool"]),
               prompt_tokens=Range.parse(raw["prompt_tokens"], "prompt_tokens"),
               answer_tokens=Range.parse(raw["answer_tokens"], "answer_tokens"),
               asks_per_prompt=Range.parse(raw["asks_per_prompt"],
                                           "asks_per_prompt"),
               rate_per_s=float(raw.get("rate_per_s", 0.0)),
               burst=int(raw.get("burst", 1)),
               clients=int(raw.get("clients", 0)),
               prefix_cache=int(raw.get("prefix_cache", 0)))


@dataclass
class Pool:
    prompts: list                      # int32 token ids, one array each
    asks: np.ndarray                   # times each prompt is sent
    sends: list                        # (prompt, answer tokens) per ask


def make_pool(mix: Mix, vocab: int, seed: int) -> Pool:
    """The pool's prompts, token ids drawn from ``seed``, and one pass
    over its asks."""
    fixed = np.random.default_rng(FIXED_ORDER_SEED)
    n = mix.prompt_pool
    lengths = mix.prompt_tokens.sizes(n)[fixed.permutation(n)]
    asks = mix.asks_per_prompt.sizes(n)[fixed.permutation(n)]
    prompt_of = np.repeat(np.arange(n), asks)
    answers = mix.answer_tokens.sizes(len(prompt_of))[
        fixed.permutation(len(prompt_of))]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, int(s)).astype(np.int32)
               for s in lengths]
    return Pool(prompts=prompts, asks=asks,
                sends=[(int(p), int(a)) for p, a in zip(prompt_of, answers)])


class TokenLoad:
    """A token mix as ``harness.drive`` offers it; a request's item is its
    place in the pass over the pool's asks (``Pool.sends``)."""

    def __init__(self, mix: Mix, seed: int, pool: Pool):
        from repro.serve import Request

        self._request = Request
        self.mix, self.pool = mix, pool
        self.kind, self.clients = mix.kind, mix.clients
        self._order = PoolOrder(len(pool.sends), np.random.default_rng(seed))

    def _next(self):
        i = self._order.next()
        p = self.pool.sends[i][0]
        return i, self.mix.tasks[p % len(self.mix.tasks)]

    def schedule(self, seconds: float):
        mix = self.mix
        return [(float(t),) + self._next()
                for t in arrival_times(mix.rate_per_s, mix.burst, seconds)
                for _ in range(mix.burst)]

    def first(self, client: int):
        return self._next()

    then = first

    def request(self, rid: int, item, task: int, arrival: float):
        p, answer = self.pool.sends[item]
        return self._request(rid=rid, task_id=task,
                             prompt=self.pool.prompts[p],
                             max_new_tokens=answer, arrival=arrival)


# ------------------------------------------------------------ model


def arch_from(cfg: dict):
    """The program's ``ArchConfig`` (``program_config``) with the file's
    ``sizes``, once each ``structure`` field the file states equals the
    program's.
    Both name ``ArchConfig`` fields, and ``MoESpec`` fields under
    ``"moe"``, so a field the program grows needs no edit here."""
    from repro import configs

    base = configs.get(cfg["program_config"])
    if base.embed_input != "tokens" or base.moe is None:
        raise ValueError(f"{cfg['program_config']}: not a token MoE model")
    st, sz = dict(cfg["structure"]), dict(cfg["sizes"])
    st_moe, sz_moe = st.pop("moe"), sz.pop("moe", {})
    for key in STRUCTURE:
        if key not in st:
            raise ValueError(f"the file's structure does not state {key}")
    for key in MOE_STRUCTURE:
        if key not in st_moe:
            raise ValueError(f"the file's structure does not state moe.{key}")
    for obj, fields, where in ((base, st, ""), (base.moe, st_moe, "moe.")):
        for key, want in fields.items():
            have = getattr(obj, key)
            if (tuple(want) if isinstance(want, list) else want) != have:
                raise ValueError(f"{where}{key}: program {have!r}, "
                                 f"file {want!r}")
    both = (set(st) & set(sz)) | (set(st_moe) & set(sz_moe))
    if both:
        raise ValueError(f"both structure and sizes: {sorted(both)}")
    return replace(base, moe=replace(base.moe, **sz_moe), **sz)


def _shapes(arch):
    from repro.models import transformer as T

    return jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), arch))


def served_params(arch, ref_spec: dict, seed: int):
    """The program's parameter tree from ``seed``
    (``harness.model.served_tree``)."""
    return served_tree(_shapes(arch), ref_spec, seed)


def lm_scheduler(arch, params, *, batch: int, scfg):
    """``LMBackend`` under ``Scheduler`` with ``batch`` lanes on one
    device, as the ``launch.serve`` command line builds them
    (``_serve_scheduler_lm``, without an SLO policy)."""
    from repro.serve import LMBackend, Scheduler

    backend = LMBackend(arch, params, scfg)
    return Scheduler(backend, total_slots=batch, quantum=4,
                     num_tasks=backend.num_tasks)


# ------------------------------------------------------------ correct


@jax.jit
def _gaps(logits, served):
    """(B, L): the reference's largest logit minus its logit of the
    served token, over the root mean square of its logits."""
    best = logits.max(-1)
    mine = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    rms = jnp.sqrt((logits * logits).mean(-1))
    return (best - mine) / jnp.maximum(rms, 1e-30)


def gaps(ref_mod, cfg: dict, weights: dict, rows, length: int,
         block: int = REF_BLOCK, judge: str = "served"):
    """Each row's answer gaps in the float32 reference.  ``rows``:
    [(task, prompt, served answer)]; ``length``: every block's row length
    (one compiled program).  ``judge`` names a precision of the reference
    (``fp8``) whose top token at each position is judged in place of the
    served one, on the same prompt and served tokens: the control."""
    out = []
    for i in range(0, len(rows), block):
        chunk = rows[i:i + block]
        toks = np.zeros((block, length), np.int32)
        served = np.zeros((block, length), np.int32)
        tasks = np.zeros((block,), np.int32)
        for j, (t, prompt, answer) in enumerate(chunk):
            seq = np.concatenate([prompt, answer[:-1]])
            toks[j, :len(seq)] = seq
            s0 = len(prompt)
            served[j, s0 - 1:s0 - 1 + len(answer)] = answer
            tasks[j] = t
        logits = ref_mod.forward(weights, toks, tasks, cfg)
        if judge != "served":
            served = np.asarray(ref_mod.forward(weights, toks, tasks, cfg,
                                                judge).argmax(-1), np.int32)
        g = np.asarray(_gaps(logits, jnp.asarray(served)), np.float64)
        for j, (_, prompt, answer) in enumerate(chunk):
            s0 = len(prompt)
            out.append(g[j, s0 - 1:s0 - 1 + len(answer)])
    return out


def numbers(answer_gaps: list, far: float, unanswered: int,
            nonfinite: int) -> dict:
    """Every number that may be compared, and those only shown
    (``mean_gap``, ``worst_gap``, ``positions``)."""
    nan = float("nan")
    flat = np.concatenate(answer_gaps) if answer_gaps else np.zeros(0)
    return {"worst_first_gap": max((float(g[0]) for g in answer_gaps),
                                   default=nan),
            "worst_answer_gap": max((float(np.median(g))
                                     for g in answer_gaps), default=nan),
            "far_position_share": float((flat > far).mean()) if flat.size
            else nan,
            "unanswered": unanswered, "nonfinite": nonfinite,
            "compared": len(answer_gaps),
            "mean_gap": float(flat.mean()) if flat.size else nan,
            "worst_gap": float(flat.max()) if flat.size else nan,
            "positions": int(flat.size)}


# ------------------------------------------------------------ the entry


class Served:
    """The served stack: seeded weights made on the device in the served
    type, the pool's prompts, and the scheduler with ``slots`` lanes of
    ``Mix.max_len`` positions, the program's default compute policy."""

    def __init__(self, cfg: dict, mix: Mix, seed: int, ref_mod):
        from repro.serve import ServeConfig

        self.cfg, self.mix, self.seed, self.ref_mod = cfg, mix, seed, ref_mod
        arch = arch_from(cfg)
        self.vocab = arch.vocab_size
        self.params = served_params(arch, ref_mod.param_spec(cfg), seed)
        self.pool = make_pool(mix, self.vocab, seed)
        scfg = ServeConfig(max_len=mix.max_len, prefix_cache=mix.prefix_cache)
        self.sched = lm_scheduler(arch, self.params, batch=mix.slots,
                                  scfg=scfg)

    def warm(self) -> None:
        """One prompt of every padded length the pool holds, each
        answered with ``WARM_TOKENS`` tokens (a prefill, then decode steps
        of one quantum, whose expert counts add up), through the window's
        own scheduler and backend; with a prefix cache, one of them again
        (the cache's hit path).  Their ids are drawn apart from the
        pool's, so that no window prompt finds them cached."""
        from repro.serve import Request

        rng = np.random.default_rng([self.seed, 1])
        padded = sorted({-(-len(p) // PAD) * PAD for p in self.pool.prompts})
        prompts = [rng.integers(0, self.vocab, n).astype(np.int32)
                   for n in padded]
        if self.mix.prefix_cache:
            prompts.append(prompts[0])
        for i, p in enumerate(prompts):
            now = self.sched.now()
            self.sched.run([Request(rid=-1 - i, task_id=t, prompt=p,
                                    max_new_tokens=WARM_TOKENS,
                                    arrival=now)
                            for t in sorted(set(self.mix.tasks))])

    def instrument(self, spans) -> None:
        """Timed spans around every prefill call (``prefill``: the fused
        admission, or the staged steps of a prefix-cache admission) and
        decode step (``decode``).  They time the call's dispatch: the
        step's tokens are read back by the bucket after it."""
        b = self.sched.backend
        admit, staged, decode = b.admit_step, b.staged_steps, b.decode_step
        b.admit_step = lambda task: spans.wrap(admit(task), "prefill", True)
        b.staged_steps = lambda task: tuple(
            spans.wrap(f, "prefill", True) for f in staged(task))
        b.decode_step = lambda: spans.wrap(decode(), "decode", True)

    def drive(self, seconds: float, seed: int, step=None):
        return run_window(self.sched, TokenLoad(self.mix, seed, self.pool),
                          seconds, step=step)

    def counters(self) -> dict:
        steps, slot_steps = step_counters(self.sched)
        prefix = self.sched.backend.prefix
        s = prefix.stats() if prefix is not None else {}
        return {"steps": steps, "slot_steps": slot_steps,
                "cache": {"prefix_lookups": s.get("lookups", 0),
                          "prefix_hits": s.get("hits", 0),
                          "prefix_hit_tokens": s.get("hit_tokens", 0)}}

    def collect(self, win):
        """[(task, prompt, served answer or None)] of the requests due in
        the window, and how many have no answer or one outside the
        vocabulary."""
        vocab = self.vocab
        answers = [(r.task_id, np.asarray(r.prompt, np.int32),
                    None if r.t_done is None else
                    np.asarray(r.tokens, np.int64))
                   for r in win.due()]
        failed = sum(1 for _, _, y in answers
                     if y is None or not _in_vocab(y, vocab))
        return answers, failed

    def release(self) -> None:
        self.sched = self.params = None

    def verdict(self, answers, judge: str = "served") -> dict:
        """``judge``: ``gaps``'s, the control's precision in place of the
        served tokens (``control_tokens.py``)."""
        cfg, vocab = self.cfg, self.vocab
        unanswered = sum(1 for _, _, y in answers if y is None)
        bad = sum(1 for _, _, y in answers
                  if y is not None and not _in_vocab(y, vocab))
        rows = _sample([a for a in answers
                        if a[2] is not None and _in_vocab(a[2], vocab)],
                       cfg["check_requests"], self.seed)
        w = make_weights(self.ref_mod.param_spec(cfg), self.seed)
        with jax.default_matmul_precision("highest"):
            g = gaps(self.ref_mod, cfg, w, rows, self.mix.max_len,
                     judge=judge)
        out = verdict(numbers(g, cfg["far_gap"], unanswered, bad),
                      cfg["limits"], ("mean_gap", "worst_gap", "positions"))
        out["reference"] = (f"{len(rows)} requests, "
                            f"{out['shown']['positions']} answer positions")
        return out


def _in_vocab(y, vocab: int) -> bool:
    return bool(((y >= 0) & (y < vocab)).all())


def _sample(rows: list, n: int, seed: int) -> list:
    """``n`` of ``rows`` drawn from ``seed``, the longest (prompt and
    answer) always among them."""
    if len(rows) <= n:
        return rows
    longest = max(range(len(rows)),
                  key=lambda i: len(rows[i][1]) + len(rows[i][2]))
    rest = [i for i in range(len(rows)) if i != longest]
    pick = np.random.default_rng([seed, 2]).choice(len(rest), n - 1,
                                                   replace=False)
    return [rows[longest]] + [rows[rest[i]] for i in sorted(pick)]
