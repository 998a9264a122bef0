"""chip_smoke.py's phases at smoke size on the CPU.

The script's device check refuses anything but a TPU, so these tests call
the phase functions directly (kernels run in interpret mode here) and check
the device check's refusal on its own.  The four-chip mesh phase runs in a
subprocess on four forced host devices.
"""

import importlib.util
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from repro import configs
from repro.kernels import ops as kops
from repro.kernels.runtime import interpret_mode_name

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(cs):
    cfg = configs.get("m3vit", smoke=True)
    params = cs.make_params(cfg, 0)
    images, tasks = cs.make_inputs(0, 4)
    return cs, cfg, params, images, tasks, cs.CompileClock()


def test_device_check_refuses_cpu(cs):
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        cs.check_device(1)


@pytest.fixture(scope="module")
def default_phase(served):
    cs, cfg, params, images, tasks, clock = served
    return cs.phase_default(cfg, params, images, tasks, batch=4, clock=clock)


def test_default_and_kernel_phases_at_smoke_size(served, default_phase):
    cs, cfg, params, images, tasks, clock = served
    ref, base, sched = default_phase
    assert len(ref) == len(base) == len(tasks)
    cs.phase_kernels(cfg, params, images, tasks, ref, base, sched, batch=4,
                     clock=clock, mode=interpret_mode_name(None))


# Faults planted in one kernel wrapper of ``repro.kernels.ops`` each: the
# kernel phase's stage check must fail on every one.  (name: (op, wrap))
FAULTS = {
    "moe_gemm: slot 0 zeroed": (
        "moe_gemm", lambda f: lambda buf, w, gs, **kw:
        f(buf, w, gs, **kw).at[0].set(0)),
    "unified_linear: bias dropped": (
        "unified_linear", lambda f: lambda x, w, b=None, **kw:
        f(x, w, None, **kw)),
    "lut_activation: SiLU table for GELU": (
        "lut_activation", lambda f: lambda x, kind="gelu", **kw:
        f(x, "silu" if kind == "gelu" else kind, **kw)),
    "flash_attention: values shifted by one key": (
        "flash_attention", lambda f: lambda q, k, v, **kw:
        f(q, k, jnp.roll(v, 1, axis=2), **kw)),
    "fused_moe_ffn: b2 dropped": (
        "fused_moe_ffn", lambda f: lambda x, p, *a, **kw:
        f(x, {**p, "b2": jnp.zeros_like(p["b2"])}, *a, **kw)),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_stage_check_catches_planted_kernel_fault(served, default_phase,
                                                  fault, monkeypatch):
    cs, cfg, params, images, tasks, clock = served
    ref, base, sched = default_phase
    op, wrap = FAULTS[fault]
    monkeypatch.setattr(kops, op, wrap(getattr(kops, op)))
    monkeypatch.setattr(cs, "TOL", float("inf"))   # the stage check alone
    with pytest.raises(RuntimeError, match="stages over"):
        cs.phase_kernels(cfg, params, images, tasks, ref, base, sched,
                         batch=4, clock=clock,
                         mode=interpret_mode_name(None))


@pytest.mark.parametrize("report, why", [
    ({"linear": {"hits": {"pallas": 1}, "modes": {"pallas": {"compiled": 1}},
                 "fallbacks": []}}, None),
    ({"linear": {"hits": {"pallas": 1},
                 "modes": {"pallas": {"interpret": 1}}, "fallbacks": []}},
     "not compiled"),
    ({"linear": {"hits": {}, "modes": {}, "fallbacks": [
        {"requested": "pallas", "used": "xla", "reasons": ["r"],
         "count": 1}]}}, "fell back"),
    ({}, "no hit"),
])
def test_check_kernels_flags_fallbacks_and_modes(cs, report, why):
    if why is None:
        cs.check_kernels("t", report, {"linear": "pallas"}, "compiled")
    else:
        with pytest.raises(RuntimeError, match=why):
            cs.check_kernels("t", report, {"linear": "pallas"}, "compiled")


MESH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ".")
    import chip_smoke as cs
    from repro import configs

    cfg = configs.get("m3vit", smoke=True)
    params = cs.make_params(cfg, 0)
    images, tasks = cs.make_inputs(0, 4)
    cs.phase_mesh(cfg, params, images, tasks, batch=4,
                  clock=cs.CompileClock())
    print("MESH_PHASE_OK")
""")


def test_mesh_phase_on_four_host_devices():
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
                       cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    assert "expert slot stores span [4] devices" in r.stdout
    assert "MESH_PHASE_OK" in r.stdout
