"""Plain float32 reference of M³ViT (arXiv 2210.14793; Edge-MoE, arXiv
2305.18691, Table III), written from the published description and the
configuration file alone.  It imports nothing of the program under test.

    patchify (16x16) -> linear patch embed + learned positions
    12 blocks:  x += attn(LN(x));  x += MLP(LN(x))  (even blocks)
                                   x += MoE(LN(x))  (odd blocks)
    LN -> task head (a linear map from each token to its 16x16 patch of
          19 class logits, or of depths)

Attention is non-causal softmax(q k^T / sqrt(head_dim)) v over all tokens
of one image.  GELU is exact (erf).  LayerNorm has eps 1e-6.  The MoE
block gates with the task's own gate table (the paper's task-level
sparsity): softmax over experts, top-k, gates renormalised to sum to 1.
A routing group is one image's tokens; in each, an expert takes at most
``capacity`` tokens, first come (token-major order) first served, and the
slots beyond it contribute nothing (GShard).  The configuration states
``capacity_factor``; ``capacity`` rounds it as the served model does:
``ceil4(max(4, int(g * top_k * capacity_factor / experts) + 1))``.

Weights are the configuration's served type (bfloat16 matrices; float32
biases, norms and gate tables), drawn by ``harness.weights`` from the
seed and computed on here in float32 at ``highest`` matmul precision.

``cast`` rounds every matmul operand first: the identity for the
reference, float8 for the control (``fp8``).
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["param_spec", "capacity", "forward", "CASTS"]

F32_LEAVES = frozenset({"scale", "bias", "b", "b1", "b2", "gate"})


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


CASTS = {"f32": lambda x: x, "fp8": _fp8}


def param_spec(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """{path: (shape, served dtype)} of the model's parameters."""
    d, f, h, hd = cfg["d_model"], cfg["d_ff"], cfg["num_heads"], cfg["head_dim"]
    m = cfg["moe"]
    e, fe = m["num_experts"], m["d_ff"]
    im = cfg["image"]
    p, c = im["patch"], im["channels"]
    tokens = (im["height"] // p) * (im["width"] // p)
    pattern = cfg["block_pattern"]
    n_scan = cfg["num_layers"] // len(pattern)
    if cfg["num_layers"] % len(pattern):
        raise ValueError("the reference takes whole periods of blocks")
    shapes = {
        "patch/w": (p * p * c, d), "patch/b": (d,), "patch/pos": (tokens, d),
        "final_norm/scale": (d,), "final_norm/bias": (d,),
        "heads/semseg/w": (d, p * p * cfg["num_seg_classes"]),
        "heads/semseg/b": (p * p * cfg["num_seg_classes"],),
        "heads/depth/w": (d, p * p), "heads/depth/b": (p * p,),
    }
    for i, kind in enumerate(pattern):
        pre = f"layers/b{i}"
        for ln in ("ln1", "ln2"):
            shapes[f"{pre}/{ln}/scale"] = (n_scan, d)
            shapes[f"{pre}/{ln}/bias"] = (n_scan, d)
        for w in ("wq", "wk", "wv"):
            shapes[f"{pre}/attn/{w}"] = (n_scan, d, h * hd)
        shapes[f"{pre}/attn/wo"] = (n_scan, h * hd, d)
        if kind == "attn_mlp":
            shapes[f"{pre}/mlp/w1"] = (n_scan, d, f)
            shapes[f"{pre}/mlp/b1"] = (n_scan, f)
            shapes[f"{pre}/mlp/w2"] = (n_scan, f, d)
            shapes[f"{pre}/mlp/b2"] = (n_scan, d)
        elif kind == "attn_moe":
            shapes[f"{pre}/moe/gate"] = (n_scan, m["num_tasks"], d, e)
            shapes[f"{pre}/moe/w1"] = (n_scan, e, d, fe)
            shapes[f"{pre}/moe/b1"] = (n_scan, e, fe)
            shapes[f"{pre}/moe/w2"] = (n_scan, e, fe, d)
            shapes[f"{pre}/moe/b2"] = (n_scan, e, d)
        else:
            raise ValueError(f"the reference has no block {kind!r}")
    return {k: (v, "float32" if k.rsplit("/", 1)[-1] in F32_LEAVES
                else cfg["dtype"]) for k, v in shapes.items()}


def capacity(cfg: dict, group: int) -> int:
    m = cfg["moe"]
    c = int(group * m["top_k"] * m["capacity_factor"] / m["num_experts"]) + 1
    return max(4, -(-c // 4) * 4)


def _mm(a, b, cast):
    return jnp.matmul(cast(a), cast(b), precision=jax.lax.Precision.HIGHEST)


def _ln(x, w, pre, i, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w[pre + "/scale"][i] \
        + w[pre + "/bias"][i]


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _attention(w, pre, i, x, cfg, cast):
    b, t, _ = x.shape
    h, hd = cfg["num_heads"], cfg["head_dim"]

    def heads(name):
        y = _mm(x, w[f"{pre}/attn/{name}"][i], cast)
        return y.reshape(b, t, h, hd).transpose(0, 2, 1, 3)

    q, k, v = heads("wq"), heads("wk"), heads("wv")
    s = _mm(q, k.transpose(0, 1, 3, 2), cast) / math.sqrt(hd)
    o = _mm(jax.nn.softmax(s, axis=-1), v, cast)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, h * hd)
    return _mm(o, w[f"{pre}/attn/wo"][i], cast)


def _mlp(w, pre, i, x, cast):
    y = _gelu(_mm(x, w[pre + "/mlp/w1"][i], cast) + w[pre + "/mlp/b1"][i])
    return _mm(y, w[pre + "/mlp/w2"][i], cast) + w[pre + "/mlp/b2"][i]


def _moe(w, pre, i, x, task, cfg, cast):
    """x: (B, T, d), one routing group per image."""
    m = cfg["moe"]
    k, e = m["top_k"], m["num_experts"]
    b, t, _ = x.shape
    cap = capacity(cfg, t)
    logits = _mm(x, w[pre + "/moe/gate"][i][task], cast)        # (B, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, k)                       # (B, T, k)
    if m["renormalize"]:
        gate = gate / gate.sum(-1, keepdims=True)
    # first come, first served: an assignment's place in its expert's queue
    # counts the earlier (token, slot) pairs that chose the same expert
    onehot = jax.nn.one_hot(expert.reshape(b, t * k), e, dtype=jnp.int32)
    place = ((jnp.cumsum(onehot, axis=1) - onehot) * onehot).sum(-1)
    place = place.reshape(b, t, k)
    keep = (place < cap).astype(jnp.float32)
    # every expert on every token, then each token's k picks
    hid = _gelu(jnp.einsum("btd,edf->betf", cast(x),
                           cast(w[pre + "/moe/w1"][i]),
                           precision=jax.lax.Precision.HIGHEST)
                + w[pre + "/moe/b1"][i][None, :, None, :])
    out = jnp.einsum("betf,efd->betd", cast(hid), cast(w[pre + "/moe/w2"][i]),
                     precision=jax.lax.Precision.HIGHEST) \
        + w[pre + "/moe/b2"][i][None, :, None, :]                 # (B,E,T,d)
    picked = jnp.take_along_axis(
        out.transpose(0, 2, 1, 3), expert[..., None], axis=2)     # (B,T,k,d)
    return ((gate * keep)[..., None] * picked).sum(2)


@partial(jax.jit, static_argnames=("task", "cfg_json", "precision"))
def _forward(w, images, *, task: int, cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    cast = CASTS[precision]
    im = cfg["image"]
    p = im["patch"]
    b, hh, ww, c = images.shape
    nh, nw = hh // p, ww // p
    x = images.reshape(b, nh, p, nw, p, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, nh * nw, p * p * c)
    x = _mm(x, w["patch/w"], cast) + w["patch/b"] + w["patch/pos"]
    pattern = cfg["block_pattern"]
    eps = cfg["norm_eps"]
    for layer in range(cfg["num_layers"]):
        i, j = divmod(layer, len(pattern))
        pre = f"layers/b{j}"
        x = x + _attention(w, pre, i, _ln(x, w, pre + "/ln1", i, eps), cfg,
                           cast)
        h = _ln(x, w, pre + "/ln2", i, eps)
        if pattern[j] == "attn_mlp":
            x = x + _mlp(w, pre, i, h, cast)
        else:
            x = x + _moe(w, pre, i, h, task, cfg, cast)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    x = (x - mu) / jnp.sqrt(var + eps) * w["final_norm/scale"] \
        + w["final_norm/bias"]
    name = cfg["tasks"][task]
    y = _mm(x, w[f"heads/{name}/w"], cast) + w[f"heads/{name}/b"]
    if name == "semseg":
        ncls = cfg["num_seg_classes"]
        y = y.reshape(b, nh, nw, p, p, ncls).transpose(0, 1, 3, 2, 4, 5)
        return y.reshape(b, hh, ww, ncls)
    y = y.reshape(b, nh, nw, p, p).transpose(0, 1, 3, 2, 4)
    return y.reshape(b, hh, ww)


def forward(w: dict, images, task: int, cfg: dict, precision: str = "f32"):
    """Predictions of ``images`` (B, H, W, 3) for task ``task``: semseg
    (B, H, W, classes) logits or depth (B, H, W), float32."""
    if cfg["moe"]["group_size"] != (cfg["image"]["height"] // cfg["image"]["patch"]) \
            * (cfg["image"]["width"] // cfg["image"]["patch"]):
        raise ValueError("the reference routes one image per group")
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    return _forward(w, jnp.asarray(images, jnp.float32), task=int(task),
                    cfg_json=json.dumps(cfg, sort_keys=True),
                    precision=precision)
