"""Compression edge case: non-finite gradients must not poison the
error-feedback carry (which is re-added into every subsequent step)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import compress as C
from repro.dist import make_mesh


def test_nonfinite_grad_does_not_poison_error_state():
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    fn = jax.shard_map(
        lambda g, e: C.compressed_psum(g, e, axes=("data",)),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False)

    g_bad = {"w": jnp.asarray([jnp.inf, 1.0, jnp.nan, -2.0], jnp.float32)}
    e = C.init_error_state(g_bad)
    out_g, out_e = fn(g_bad, e)
    # corrupt values dropped, everything stays finite
    assert np.isfinite(np.asarray(out_g["w"])).all()
    assert np.isfinite(np.asarray(out_e["w"])).all()

    # the next (healthy) step recovers instead of inheriting NaN
    g_ok = {"w": jnp.asarray([0.5, 1.0, -1.0, -2.0], jnp.float32)}
    out_g, out_e = fn(g_ok, out_e)
    assert np.isfinite(np.asarray(out_g["w"])).all()
    np.testing.assert_allclose(np.asarray(out_g["w"]),
                               np.asarray(g_ok["w"]), atol=0.05)
