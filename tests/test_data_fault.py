"""Fault-tolerance machinery: the restart supervisor and the heartbeat."""

import time

import numpy as np
import pytest

from repro.runtime.fault import Heartbeat, read_heartbeat, supervise


class TestSupervisor:
    def test_restarts_until_success(self):
        attempts = []

        def run(start):
            attempts.append(start)
            if len(attempts) < 3:
                raise RuntimeError(f"simulated node failure {len(attempts)}")
            return 100

        report = supervise(run, max_restarts=5)
        assert report.completed_steps == 100
        assert report.restarts == 2
        assert len(report.failures) == 2

    def test_gives_up_after_max_restarts(self):
        def run(start):
            raise RuntimeError("persistent failure")

        with pytest.raises(RuntimeError, match="exceeded"):
            supervise(run, max_restarts=2)

    def test_checkpoint_resume_under_failures(self, tmp_path):
        """End-to-end: a crashing trainer driven by the supervisor finishes
        with the same result as an uninterrupted run (step-keyed inputs +
        checkpoint restart)."""
        import jax
        import jax.numpy as jnp

        from repro.checkpoint import latest_step, restore_pytree, save_pytree

        def get_batch(i):
            # step-keyed: a restart replays exactly the batches it missed
            rng = np.random.default_rng(i)
            return {"tokens": rng.integers(1, 64, size=(2, 8))}

        def make_step():
            @jax.jit
            def step(w, batch):
                x = jnp.asarray(batch["tokens"], jnp.float32)
                g = x.mean() * jnp.ones_like(w)
                return w - 0.1 * g

            return step

        def train(n_steps, crash_at=None, ckpt_dir=None):
            step = make_step()
            start = 0
            w = jnp.zeros((4,))
            if ckpt_dir and latest_step(ckpt_dir) is not None:
                restored, manifest = restore_pytree({"w": w}, ckpt_dir)
                w = restored["w"]
                start = manifest["step"]
            for i in range(start, n_steps):
                w = step(w, get_batch(i))
                if ckpt_dir:
                    save_pytree({"w": w}, ckpt_dir, i + 1)
                if crash_at is not None and i == crash_at and not getattr(
                    train, "crashed", False
                ):
                    train.crashed = True
                    raise RuntimeError("chaos monkey")
            return w

        w_clean = train(6)

        ckpt = str(tmp_path / "ck")
        result = {}

        def run(start):
            result["w"] = train(6, crash_at=3, ckpt_dir=ckpt)
            return 6

        supervise(run, max_restarts=2)
        np.testing.assert_allclose(
            np.asarray(result["w"]), np.asarray(w_clean), atol=1e-7
        )


class TestHeartbeat:
    def test_writes_liveness_file(self, tmp_path):
        path = str(tmp_path / "hb")
        hb = Heartbeat(path, interval=0.0)
        hb.beat(5)
        with open(path) as f:
            step, ts = f.read().split()
        assert int(step) == 5
        assert abs(float(ts) - time.time()) < 5

    def test_watchdog_reads_fresh_beat(self, tmp_path):
        path = str(tmp_path / "hb")
        Heartbeat(path, interval=0.0).beat(17)
        status = read_heartbeat(path, stale_after=60.0)
        assert status.step == 17
        assert status.age_s < 60.0
        assert not status.stale

    def test_watchdog_flags_stale_beat(self, tmp_path):
        path = str(tmp_path / "hb")
        with open(path, "w") as f:
            f.write(f"3 {time.time() - 100.0}\n")
        status = read_heartbeat(path, stale_after=30.0)
        assert status.step == 3
        assert status.stale

    def test_watchdog_fails_stale_on_missing_or_corrupt(self, tmp_path):
        missing = read_heartbeat(str(tmp_path / "nope"), stale_after=30.0)
        assert missing.step is None and missing.stale
        assert missing.age_s == float("inf")
        path = str(tmp_path / "hb")
        with open(path, "w") as f:
            f.write("garbage not a beat")
        corrupt = read_heartbeat(path, stale_after=30.0)
        assert corrupt.step is None and corrupt.stale
