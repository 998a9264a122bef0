"""Operations and bytes that the algorithm needs, from shapes alone.

A multiply-add counts 2 operations.  A MoE layer counts the top-k experts
each token is routed to, not the capacity padding of the expert buffers,
and a prediction counts one image.  Bytes are the least a program must
move through HBM: its weights and its input and output activations, each
read or written once.
"""

from __future__ import annotations

__all__ = ["tokens", "attention_flops", "mlp_flops", "moe_flops",
           "prediction_flops", "dense_block_cost", "roofline_s"]


def tokens(cfg: dict) -> int:
    im = cfg["image"]
    return (im["height"] // im["patch"]) * (im["width"] // im["patch"])


def attention_flops(cfg: dict) -> int:
    t, d = tokens(cfg), cfg["d_model"]
    hw = cfg["num_heads"] * cfg["head_dim"]
    proj = 2 * t * d * hw * 3 + 2 * t * hw * d
    mix = 2 * 2 * cfg["num_heads"] * t * t * cfg["head_dim"]
    return proj + mix


def mlp_flops(cfg: dict) -> int:
    return 2 * 2 * tokens(cfg) * cfg["d_model"] * cfg["d_ff"]


def moe_flops(cfg: dict) -> int:
    m = cfg["moe"]
    t, d = tokens(cfg), cfg["d_model"]
    gate = 2 * t * d * m["num_experts"]
    return gate + m["top_k"] * 2 * 2 * t * d * m["d_ff"]


def head_outputs(cfg: dict, task: int) -> int:
    p = cfg["image"]["patch"]
    name = cfg["tasks"][task]
    return p * p * (cfg["num_seg_classes"] if name == "semseg" else 1)


def prediction_flops(cfg: dict, task: int) -> int:
    """Operations of one image's prediction for ``task``."""
    t, d = tokens(cfg), cfg["d_model"]
    c = cfg["image"]["channels"]
    p = cfg["image"]["patch"]
    embed = 2 * t * p * p * c * d
    blocks = 0
    pattern = cfg["block_pattern"]
    for layer in range(cfg["num_layers"]):
        kind = pattern[layer % len(pattern)]
        blocks += attention_flops(cfg)
        blocks += mlp_flops(cfg) if kind == "attn_mlp" else moe_flops(cfg)
    head = 2 * t * d * head_outputs(cfg, task)
    return embed + blocks + head


def dense_block_cost(cfg: dict, batch: int, act_bytes: int = 2,
                     weight_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one dense block (attention and MLP with their
    norms and residuals) on ``batch`` images."""
    t, d, f = tokens(cfg), cfg["d_model"], cfg["d_ff"]
    hw = cfg["num_heads"] * cfg["head_dim"]
    ops = batch * (attention_flops(cfg) + mlp_flops(cfg))
    weights = (3 * d * hw + hw * d + 2 * d * f) * weight_bytes \
        + (4 * d + f + d) * 4                # norms and biases, float32
    acts = 2 * batch * t * d * act_bytes     # the block's input and output
    return ops, weights + acts


def roofline_s(ops: float, nbytes: float, peak) -> tuple[float, str]:
    """The least time the chip could take, and what bounds it."""
    t_ops = ops / peak.flops_per_s
    t_mem = nbytes / peak.hbm_bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
