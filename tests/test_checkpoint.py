"""Atomic, mesh-agnostic checkpointing."""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.dist import make_mesh


def tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16), jnp.bfloat16),
                   "b": jnp.zeros((16,), jnp.float32)},
        "opt": {"step": jnp.int32(7),
                "nested": [jnp.arange(4), jnp.ones((2, 2))]},
    }


class TestRoundtrip:
    def test_save_restore_bitexact(self, tmp_path):
        t = tree()
        save(str(tmp_path), 10, t)
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
        r = restore(str(tmp_path), 10, like)
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
            assert a.dtype == b.dtype

    def test_latest_step(self, tmp_path):
        assert latest_step(str(tmp_path)) is None
        for s in (5, 20, 10):
            save(str(tmp_path), s, tree())
        assert latest_step(str(tmp_path)) == 20

    def test_shape_mismatch_raises(self, tmp_path):
        save(str(tmp_path), 1, {"w": jnp.zeros((4,))})
        with pytest.raises(ValueError):
            restore(str(tmp_path), 1, {"w": jnp.zeros((8,))})

    def test_missing_leaf_raises(self, tmp_path):
        save(str(tmp_path), 1, {"w": jnp.zeros((4,))})
        with pytest.raises(KeyError):
            restore(str(tmp_path), 1, {"w": jnp.zeros((4,)),
                                       "extra": jnp.zeros((2,))})


class TestAtomicity:
    def test_partial_write_invisible(self, tmp_path):
        """A tmp.<step> dir (crash mid-write) is never listed as a valid
        checkpoint, and a later save cleans it."""
        os.makedirs(tmp_path / "tmp.5")
        (tmp_path / "tmp.5" / "junk.npy").write_bytes(b"xx")
        assert latest_step(str(tmp_path)) is None
        save(str(tmp_path), 5, tree())
        assert latest_step(str(tmp_path)) == 5

    def test_overwrite_same_step(self, tmp_path):
        save(str(tmp_path), 3, {"w": jnp.zeros((2,))})
        save(str(tmp_path), 3, {"w": jnp.ones((2,))})
        r = restore(str(tmp_path), 3, {"w": jnp.zeros((2,))})
        np.testing.assert_array_equal(np.asarray(r["w"]), 1.0)


class TestManager:
    def test_async_save_and_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, tree(seed=s))
        mgr.wait()
        steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path)
                       if n.startswith("step_"))
        assert steps == [3, 4]

    def test_manager_latest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3)
        assert mgr.latest() is None
        mgr.save(9, tree())
        mgr.wait()
        assert mgr.latest() == 9


class TestQuantizedRoundtrip:
    """QTensor leaves round-trip checkpoints: packed values and scales are
    bit-exact, and a PagedMoE serving from the restored tree matches the
    in-memory one exactly."""

    def qtree(self):
        from repro.quant import quantize

        k = jax.random.PRNGKey(3)
        w8 = jax.random.normal(k, (24, 16), jnp.float32)
        w4 = jax.random.normal(k, (33, 8), jnp.float32)
        return {"layer": {"w": quantize(w8, 8),
                          "w4": quantize(w4, 4, group_size=8),
                          "b": jnp.zeros((16,), jnp.float32)}}

    def test_qtensor_bitexact(self, tmp_path):
        t = self.qtree()
        save(str(tmp_path), 1, t)
        r = restore(str(tmp_path), 1, t)
        for name in ("w", "w4"):
            a, b = t["layer"][name], r["layer"][name]
            np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
            np.testing.assert_array_equal(np.asarray(a.scale),
                                          np.asarray(b.scale))
            assert a.q.dtype == b.q.dtype          # int8 / packed uint8
            assert (a.bits, a.rows, a.shape) == (b.bits, b.rows, b.shape)

    def test_manifest_names_qtensor_leaves(self, tmp_path):
        import json
        import os

        save(str(tmp_path), 1, self.qtree())
        with open(os.path.join(tmp_path, "step_1", "manifest.json")) as f:
            leaves = json.load(f)["leaves"]
        assert "layer.w.q" in leaves and "layer.w.scale" in leaves
        assert leaves["layer.w.q"]["dtype"] == "int8"

    def test_async_manager_roundtrip(self, tmp_path):
        t = self.qtree()
        mgr = CheckpointManager(str(tmp_path), keep=2)
        mgr.save(7, t)
        mgr.wait()
        r = restore(str(tmp_path), 7, t)
        np.testing.assert_array_equal(np.asarray(t["layer"]["w"].q),
                                      np.asarray(r["layer"]["w"].q))

    def test_paged_moe_from_restored_checkpoint(self, tmp_path):
        from repro import ops
        from repro.core.moe import MoEConfig, init_moe
        from repro.quant import quantize_tree
        from repro.serve.expert_cache import PagedMoE

        cfg = MoEConfig(d_model=16, d_ff=24, num_experts=4, top_k=2,
                        num_tasks=2, expert_kind="gelu",
                        capacity_factor=2.0, group_size=64, impl="grouped")
        qparams = quantize_tree(
            init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32))
        save(str(tmp_path), 2, qparams)
        restored = restore(str(tmp_path), 2, qparams)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 16),
                              jnp.float32)
        with ops.use_policy(ops.policy_named("xla_int8")):
            y_mem, _ = PagedMoE(qparams, cfg, resident_fraction=0.5)(
                x, task_id=1)
            y_ckpt, _ = PagedMoE(restored, cfg, resident_fraction=0.5)(
                x, task_id=1)
        np.testing.assert_array_equal(np.asarray(y_mem), np.asarray(y_ckpt))


class TestFactoredRoundtrip:
    """FactoredTensor leaves round-trip checkpoints: basis and delta
    factors (including nested QTensor deltas, whose leaves name themselves
    ``<param>.u.q`` / ``<param>.u.scale``) are bit-exact, and a PagedMoE
    serving from the restored tree matches the in-memory one exactly."""

    def ftree(self, delta_bits=None):
        from repro.factor import factorize

        k = jax.random.PRNGKey(5)
        w = jax.random.normal(k, (4, 16, 24), jnp.float32)
        bf = jax.random.normal(k, (4, 16, 36), jnp.float32)
        return {"layer": {"w": factorize(w, "rank", rank=3,
                                         delta_bits=delta_bits),
                          "wb": factorize(bf, "butterfly"),
                          "b": jnp.zeros((24,), jnp.float32)}}

    @pytest.mark.parametrize("delta_bits", [None, 8])
    def test_factored_bitexact(self, tmp_path, delta_bits):
        from repro.quant import is_qtensor

        t = self.ftree(delta_bits)
        save(str(tmp_path), 1, t)
        r = restore(str(tmp_path), 1, t)
        for name in ("w", "wb"):
            a, b = t["layer"][name], r["layer"][name]
            assert (a.kind, a.dtype, a.shape) == (b.kind, b.dtype, b.shape)
            np.testing.assert_array_equal(np.asarray(a.basis),
                                          np.asarray(b.basis))
            for fa, fb in ((a.u, b.u), (a.v, b.v)):
                assert is_qtensor(fa) == is_qtensor(fb)
                if is_qtensor(fa):
                    np.testing.assert_array_equal(np.asarray(fa.q),
                                                  np.asarray(fb.q))
                    np.testing.assert_array_equal(np.asarray(fa.scale),
                                                  np.asarray(fb.scale))
                else:
                    np.testing.assert_array_equal(np.asarray(fa),
                                                  np.asarray(fb))

    def test_manifest_names_factored_leaves(self, tmp_path):
        import json
        import os

        save(str(tmp_path), 1, self.ftree(delta_bits=8))
        with open(os.path.join(tmp_path, "step_1", "manifest.json")) as f:
            leaves = json.load(f)["leaves"]
        assert "layer.w.basis" in leaves
        # quantized deltas nest: QTensor children of the FactoredTensor
        assert "layer.w.u.q" in leaves and "layer.w.u.scale" in leaves
        assert "layer.w.v.q" in leaves
        # fp butterfly deltas stay flat
        assert "layer.wb.u" in leaves and "layer.wb.v" in leaves
        assert leaves["layer.w.u.q"]["dtype"] == "int8"

    def test_paged_moe_from_restored_checkpoint(self, tmp_path):
        from repro import ops
        from repro.core.moe import MoEConfig, init_moe
        from repro.factor import factorize_tree
        from repro.serve.expert_cache import PagedMoE

        cfg = MoEConfig(d_model=16, d_ff=24, num_experts=4, top_k=2,
                        num_tasks=2, expert_kind="gelu",
                        capacity_factor=2.0, group_size=64, impl="grouped")
        fparams = factorize_tree(
            init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32),
            rank=4, delta_bits=8)
        save(str(tmp_path), 2, fparams)
        restored = restore(str(tmp_path), 2, fparams)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 16),
                              jnp.float32)
        with ops.use_policy(ops.policy_named("xla_factored")):
            y_mem, _ = PagedMoE(fparams, cfg, resident_fraction=0.5)(
                x, task_id=1)
            y_ckpt, _ = PagedMoE(restored, cfg, resident_fraction=0.5)(
                x, task_id=1)
        np.testing.assert_array_equal(np.asarray(y_mem), np.asarray(y_ckpt))


class TestElasticRestore:
    def test_restore_with_shardings(self, tmp_path):
        """Mesh-agnostic restore: leaves are placed onto the live mesh's
        NamedShardings (elastic rescale = restore onto a different mesh)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        t = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4)}
        save(str(tmp_path), 1, t)
        mesh = make_mesh((1,), ("data",))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        r = restore(str(tmp_path), 1, t, shardings=sh)
        assert r["w"].sharding == sh["w"]
        np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(t["w"]))
