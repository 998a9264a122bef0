"""The served model of a configuration file: its ``ArchConfig``, its
seeded weights in the program's own tree, and the seeded frames."""

from __future__ import annotations

import importlib.util
import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import make_weights, path_name, seed_words

__all__ = ["load_config", "load_reference", "arch_from", "served_spec",
           "served_params", "make_frames"]


def load_config(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_reference(bench_root: Path, cfg: dict):
    """The plain reference module named by the configuration file."""
    path = Path(bench_root) / "configs" / f"{cfg['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{cfg['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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

    shape_tree = jax.eval_shape(
        lambda: V.init_params(jax.random.PRNGKey(0), arch))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    spec = {path_name(p): (tuple(a.shape), str(a.dtype)) for p, a in flat}
    return spec, [path_name(p) for p, _ in flat], treedef


def served_params(arch, ref_spec: dict, seed: int):
    """The program's parameter tree, every leaf drawn by ``make_weights``
    from ``seed`` in the dtype the program serves it in.  The tree must
    hold exactly the reference's leaves, shapes and dtypes: anything else
    means the reference would compute another model."""
    spec, paths, treedef = served_spec(arch)
    if spec != ref_spec:
        diff = sorted(set(spec.items()) ^ set(ref_spec.items()))
        raise ValueError(f"served tree and reference disagree: {diff[:6]}")
    leaves = make_weights(spec, seed)
    return jax.tree_util.tree_unflatten(treedef, [leaves[p] for p in paths])


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
