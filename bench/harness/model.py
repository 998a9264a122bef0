"""What every entry's served model shares: the configuration file, its
plain reference, and the program's parameter tree filled with the seeded
weights that the reference gets too."""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

import jax

from harness.weights import make_weights, path_name

__all__ = ["load_config", "load_reference", "tree_spec", "served_tree"]


def load_config(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_reference(bench_root: Path, cfg: dict):
    """The plain reference module named by the configuration file."""
    return _module(Path(bench_root) / "configs" / f"{cfg['reference']}.py")


@functools.lru_cache(maxsize=8)
def _module(path: Path):
    """A reference loaded once a process, with its compiled programs."""
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tree_spec(shape_tree):
    """({path: (shape, dtype)}, flat paths, treedef) of a parameter tree
    of shapes (``jax.eval_shape`` of the program's ``init_params``)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shape_tree)
    spec = {path_name(p): (tuple(a.shape), str(a.dtype)) for p, a in flat}
    return spec, [path_name(p) for p, _ in flat], treedef


def served_tree(shape_tree, ref_spec: dict, seed: int):
    """The program's parameter tree, every leaf drawn by ``make_weights``
    from ``seed`` in the dtype the program serves it in.  The tree must
    hold exactly the reference's leaves, shapes and dtypes: anything else
    means the reference would compute another model."""
    spec, paths, treedef = tree_spec(shape_tree)
    if spec != ref_spec:
        diff = sorted(set(spec.items()) ^ set(ref_spec.items()))
        raise ValueError(f"served tree and reference disagree: {diff[:6]}")
    leaves = make_weights(spec, seed)
    return jax.tree_util.tree_unflatten(treedef, [leaves[p] for p in paths])
