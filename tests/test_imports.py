"""Collection smoke test: import every module under src/repro/ so a
missing-module regression fails as ONE named test per module instead of
a dozen opaque collection errors (the seed's failure mode when
``repro.dist`` was absent)."""

import importlib
import os
import pkgutil

import pytest

import repro


def _module_names():
    root = os.path.dirname(repro.__file__)
    names = ["repro"]
    for mod in pkgutil.walk_packages([root], prefix="repro."):
        names.append(mod.name)
    return sorted(names)


@pytest.mark.parametrize("name", _module_names())
def test_import(name, monkeypatch):
    # launch/dryrun sets XLA_FLAGS and JAX_PLATFORMS at import for its own
    # process; pin both so the import can't leak them into this session
    for var in ("XLA_FLAGS", "JAX_PLATFORMS"):
        monkeypatch.setenv(var, os.environ.get(var, ""))
    importlib.import_module(name)
