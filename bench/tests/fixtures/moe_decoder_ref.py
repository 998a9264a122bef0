"""Plain float32 reference of a routed-expert decoder of the kind the
``kimi_k2_1t_a32b`` program configuration describes, written from the
configuration file alone (its ``structure`` and ``sizes``).  It imports
nothing of the program under test.

    token embedding
    L blocks:  x += attn(RMSNorm(x));  x += MoE(RMSNorm(x))
    RMSNorm -> linear LM head (logits over the vocabulary)

RMSNorm is x / sqrt(mean(x^2) + eps) times a gain.  Attention is causal
softmax(q k^T / sqrt(head_dim)) v with grouped key-value heads (query
head h reads key-value head h // (heads / kv_heads)); queries and keys
are rotated by RoPE, each head's first half paired with its second half,
at angle position / rope_theta^(2i / head_dim).  The MoE block gates
with the task's gate table: softmax over the routed experts, the top-k,
gates renormalised to sum to 1 where the file says so; each routed
expert and the shared expert is a SwiGLU, (SiLU(x W_g) * x W_u) W_d, the
shared expert added with weight 1.  No expert's queue is ever cut: the
file's ``capacity_factor`` times ``top_k`` must reach the number of
experts, which makes the served model's capacity hold every token.

Weights are the configuration's served type (bfloat16 matrices; float32
norm gains and gate tables), drawn by ``harness.weights`` from the seed
and computed on here in float32 at ``highest`` matmul precision.

``cast`` rounds every matmul operand first: the identity for the
reference, float8 for the control (``fp8``).
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["param_spec", "forward", "CASTS"]

F32_LEAVES = frozenset({"scale", "gate"})


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


CASTS = {"f32": lambda x: x, "fp8": _fp8}


def _sizes(cfg: dict) -> dict:
    """The model's numbers: the file's structure and sizes in one."""
    st, sz = cfg["structure"], cfg["sizes"]
    return {**st, **sz, "moe": {**st["moe"], **sz["moe"]},
            "norm_eps": cfg["norm_eps"]}


def param_spec(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """{path: (shape, served dtype)} of the model's parameters."""
    cfg = _sizes(cfg)
    d, v = cfg["d_model"], cfg["vocab_size"]
    h, hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    m = cfg["moe"]
    e, f, fs = m["num_experts"], m["d_ff"], m["d_ff"] * m["num_shared_experts"]
    pattern = cfg["block_pattern"]
    if pattern != ["attn_moe"]:
        raise ValueError("the reference has MoE blocks only")
    n = cfg["num_layers"]
    pre = "layers/b0"
    shapes = {
        "embed/tokens": (v, d), "final_norm/scale": (d,), "head/w": (d, v),
        f"{pre}/ln1/scale": (n, d), f"{pre}/ln2/scale": (n, d),
        f"{pre}/attn/wq": (n, d, h * hd), f"{pre}/attn/wk": (n, d, hkv * hd),
        f"{pre}/attn/wv": (n, d, hkv * hd), f"{pre}/attn/wo": (n, h * hd, d),
        f"{pre}/moe/gate": (n, m["num_tasks"], d, e),
        f"{pre}/moe/wg": (n, e, d, f), f"{pre}/moe/wu": (n, e, d, f),
        f"{pre}/moe/wd": (n, e, f, d),
    }
    if fs:
        shapes.update({f"{pre}/moe/shared_wg": (n, d, fs),
                       f"{pre}/moe/shared_wu": (n, d, fs),
                       f"{pre}/moe/shared_wd": (n, fs, d)})
    return {k: (s, "float32" if k.rsplit("/", 1)[-1] in F32_LEAVES
                else cfg["dtype"]) for k, s in shapes.items()}


def _mm(a, b, cast):
    return jnp.matmul(cast(a), cast(b), precision=jax.lax.Precision.HIGHEST)


def _rms(x, gain, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: (B, H, L, hd), position = index along L."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(w, i, x, cfg, cast):
    b, t, _ = x.shape
    h, hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]

    def heads(name, n):
        y = _mm(x, w[f"layers/b0/attn/{name}"][i], cast)
        return y.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    q = _rope(heads("wq", h), cfg["rope_theta"])
    k = _rope(heads("wk", hkv), cfg["rope_theta"])
    v = heads("wv", hkv)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    s = _mm(q, k.transpose(0, 1, 3, 2), cast) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm(jax.nn.softmax(s, axis=-1), v, cast)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, h * hd)
    return _mm(o, w["layers/b0/attn/wo"][i], cast)


def _swiglu(x, wg, wu, wd, cast):
    return _mm(jax.nn.silu(_mm(x, wg, cast)) * _mm(x, wu, cast), wd, cast)


def _moe(w, i, x, tasks, cfg, cast):
    """x: (B, T, d); tasks: (B,) the gate table of each row."""
    m = cfg["moe"]
    pre = "layers/b0/moe"
    gate_w = w[f"{pre}/gate"][i][tasks]                          # (B, d, E)
    logits = jnp.einsum("btd,bde->bte", cast(x), cast(gate_w),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, m["top_k"])              # (B, T, k)
    if m["renormalize"]:
        gate = gate / gate.sum(-1, keepdims=True)
    # every expert on every token, then each token's k picks
    g = jnp.einsum("btd,edf->betf", cast(x), cast(w[f"{pre}/wg"][i]),
                   precision=jax.lax.Precision.HIGHEST)
    u = jnp.einsum("btd,edf->betf", cast(x), cast(w[f"{pre}/wu"][i]),
                   precision=jax.lax.Precision.HIGHEST)
    out = jnp.einsum("betf,efd->betd", cast(jax.nn.silu(g) * u),
                     cast(w[f"{pre}/wd"][i]),
                     precision=jax.lax.Precision.HIGHEST)        # (B,E,T,d)
    picked = jnp.take_along_axis(
        out.transpose(0, 2, 1, 3), expert[..., None], axis=2)    # (B,T,k,d)
    y = (gate[..., None] * picked).sum(2)
    if m["num_shared_experts"]:
        y = y + _swiglu(x, w[f"{pre}/shared_wg"][i], w[f"{pre}/shared_wu"][i],
                        w[f"{pre}/shared_wd"][i], cast)
    return y


@partial(jax.jit, static_argnames=("cfg_json", "precision"))
def _forward(w, tokens, tasks, *, cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    cast = CASTS[precision]
    eps = cfg["norm_eps"]
    x = w["embed/tokens"][tokens]
    for i in range(cfg["num_layers"]):
        x = x + _attention(w, i, _rms(x, w["layers/b0/ln1/scale"][i], eps),
                           cfg, cast)
        x = x + _moe(w, i, _rms(x, w["layers/b0/ln2/scale"][i], eps), tasks,
                     cfg, cast)
    x = _rms(x, w["final_norm/scale"], eps)
    return _mm(x, w["head/w"], cast)


def forward(w: dict, tokens, tasks, cfg: dict, precision: str = "f32"):
    """Logits (B, L, vocab), float32, of every position of ``tokens``
    (B, L) int32, row b gated by task ``tasks[b]``."""
    cfg = _sizes(cfg)
    m = cfg["moe"]
    if m["capacity_factor"] * m["top_k"] < m["num_experts"]:
        raise ValueError("the reference routes without capacity: "
                         "capacity_factor * top_k must reach num_experts")
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    return _forward(w, jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(tasks, jnp.int32),
                    cfg_json=json.dumps(cfg, sort_keys=True),
                    precision=precision)
