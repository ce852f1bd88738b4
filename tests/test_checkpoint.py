"""Checkpointing: atomic commit, async writer, retention."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    Checkpointer,
    latest_step,
    restore_pytree,
    save_pytree,
)


def tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {
            "w": jnp.asarray(rng.standard_normal((16, 8)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal(8), jnp.bfloat16),
        },
        "step": jnp.asarray(7, jnp.int32),
    }


class TestSaveRestore:
    def test_roundtrip_bitwise(self, tmp_path):
        t = tree()
        save_pytree(t, str(tmp_path), 7, metadata={"loss": 1.5})
        restored, manifest = restore_pytree(t, str(tmp_path))
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert a.dtype == b.dtype
        assert manifest["step"] == 7
        assert manifest["metadata"]["loss"] == 1.5

    def test_latest_pointer_and_multiple_steps(self, tmp_path):
        t = tree()
        for s in (1, 5, 3):  # out-of-order saves; LATEST follows writes
            save_pytree(t, str(tmp_path), s)
        assert latest_step(str(tmp_path)) == 3
        _, manifest = restore_pytree(t, str(tmp_path), step=5)
        assert manifest["step"] == 5

    def test_shape_mismatch_rejected(self, tmp_path):
        save_pytree(tree(), str(tmp_path), 1)
        bad = tree()
        bad["params"]["w"] = jnp.zeros((4, 4))
        with pytest.raises(ValueError):
            restore_pytree(bad, str(tmp_path))

    def test_missing_leaf_rejected(self, tmp_path):
        save_pytree(tree(), str(tmp_path), 1)
        bigger = tree()
        bigger["params"]["extra"] = jnp.zeros(3)
        with pytest.raises(KeyError):
            restore_pytree(bigger, str(tmp_path))

    def test_no_partial_checkpoint_visible(self, tmp_path):
        # tmp dirs must never be readable as committed checkpoints
        save_pytree(tree(), str(tmp_path), 2)
        os.makedirs(tmp_path / "tmp.99.1234")  # simulated crash leftovers
        assert latest_step(str(tmp_path)) == 2
        restored, m = restore_pytree(tree(), str(tmp_path))
        assert m["step"] == 2


class TestCheckpointer:
    def test_async_save_and_gc(self, tmp_path):
        ckpt = Checkpointer(str(tmp_path), keep_last=2)
        for s in range(5):
            ckpt.save_async(tree(s), s, metadata={"loss": 5.0 - s})
        ckpt.wait()
        steps = sorted(
            int(n.split("_")[-1])
            for n in os.listdir(tmp_path)
            if n.startswith("step_")
        )
        assert steps == [3, 4]

    def test_keep_best(self, tmp_path):
        ckpt = Checkpointer(
            str(tmp_path), keep_last=1, keep_best=1, best_metric="loss"
        )
        losses = {0: 3.0, 1: 1.0, 2: 2.5}
        for s, l in losses.items():
            ckpt.save_async(tree(s), s, metadata={"loss": l})
            ckpt.wait()
        steps = {
            int(n.split("_")[-1])
            for n in os.listdir(tmp_path)
            if n.startswith("step_")
        }
        assert 1 in steps  # the best survived the GC
        assert 2 in steps  # the most recent survived

    def test_corrupt_latest_reads_as_no_checkpoint(self, tmp_path):
        save_pytree(tree(), str(tmp_path), 3)
        with open(tmp_path / "LATEST", "w") as f:
            f.write("not_a_step_name")
        assert latest_step(str(tmp_path)) is None
        with pytest.raises(FileNotFoundError):
            restore_pytree(tree(), str(tmp_path))
        # explicit step addressing still works around the corrupt pointer
        _, m = restore_pytree(tree(), str(tmp_path), step=3)
        assert m["step"] == 3

    def test_latest_pointing_at_missing_dir_raises(self, tmp_path):
        save_pytree(tree(), str(tmp_path), 1)
        with open(tmp_path / "LATEST", "w") as f:
            f.write("step_00000099")
        with pytest.raises(OSError):
            restore_pytree(tree(), str(tmp_path))

    def test_gc_reads_each_manifest_once(self, tmp_path, monkeypatch):
        ckpt = Checkpointer(
            str(tmp_path), keep_last=1, keep_best=1, best_metric="loss"
        )
        for s in range(4):
            ckpt.save_async(tree(s), s, metadata={"loss": float(s)})
        ckpt.wait()
        calls = []
        orig = Checkpointer._metric_of
        monkeypatch.setattr(
            Checkpointer,
            "_metric_of",
            lambda self, step: calls.append(step) or orig(self, step),
        )
        ckpt._gc()
        # one scoring pass: each surviving step's manifest read exactly once
        assert sorted(calls) == sorted(set(calls))

    def test_gc_tolerates_corrupt_manifest(self, tmp_path):
        ckpt = Checkpointer(
            str(tmp_path), keep_last=1, keep_best=2, best_metric="loss"
        )
        for s in range(3):
            ckpt.save_async(tree(s), s, metadata={"loss": 3.0 - s})
            ckpt.wait()
        with open(tmp_path / "step_00000001" / "manifest.json", "w") as f:
            f.write("{ torn write")
        ckpt._gc()  # unscored, not fatal
        survivors = {
            n for n in os.listdir(tmp_path) if n.startswith("step_")
        }
        assert "step_00000002" in survivors  # most recent kept regardless

    def test_writer_errors_surface_on_wait(self, tmp_path):
        ckpt = Checkpointer(str(tmp_path / "sub"), keep_last=1)
        # unpicklable leaf triggers a writer failure, surfaced on wait()
        ckpt._q.put(("save", {"bad": (lambda: 1)}, 0, None))
        with pytest.raises(BaseException):
            ckpt.wait()
