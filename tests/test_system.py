"""End-to-end behaviour tests for the full system."""

import numpy as np
import jax.numpy as jnp

from repro.core.cahn_hilliard import (
    CahnHilliardADI,
    CHConfig,
    coarsening_metrics,
    deep_quench_ic,
)
from repro.core.metrics import fit_power_law


class TestCahnHilliardPhysics:
    """The paper's validation (Fig. 1) at reduced scale: coarsening must
    follow the Lifshitz–Slyozov t^{1/3} law within a generous band."""

    def test_coarsening_exponent(self):
        cfg = CHConfig(nx=96, ny=96, dt=2e-3, rhs_mode="fused", backend="jnp")
        solver = CahnHilliardADI(cfg)
        c0 = deep_quench_ic(96, 96, seed=0)
        mfn = coarsening_metrics(cfg)
        _, hist = solver.run(c0, 1500, save_every=150, metrics_fn=mfn)
        # discard the spinodal-decomposition transient (first third)
        t = np.array([h[0] for h in hist], dtype=float)[3:] * cfg.dt
        s = np.array([float(h[1][0]) for h in hist])[3:]
        grow = fit_power_law(t, s - 1.0)
        # s-1 ~ t^{2/3}..t^{1/3} band depending on regime; must be growing
        # with a positive, sub-linear exponent in the coarsening window
        assert 0.15 < grow < 1.6, grow

    def test_solution_phases_separate(self):
        cfg = CHConfig(nx=64, ny=64, dt=2e-3, rhs_mode="fused", backend="jnp")
        solver = CahnHilliardADI(cfg)
        c0 = deep_quench_ic(64, 64, seed=1)
        c, _ = solver.run(c0, 800)
        # after coarsening, a large fraction of the domain sits near +-1
        frac_separated = float(jnp.mean(jnp.abs(c) > 0.6))
        assert frac_separated > 0.5, frac_separated


class TestTrainLoop:
    """examples/train_lm.py path: loss decreases on real (synthetic) data."""

    def test_train_driver_smoke(self):
        from repro.launch.train import train_loop

        metrics = train_loop(
            arch="smollm-135m",
            reduced=True,
            steps=8,
            global_batch=4,
            seq_len=16,
            checkpoint_dir=None,
            log_every=4,
        )
        assert len(metrics) == 8
        assert all(np.isfinite(m["loss"]) for m in metrics)

    def test_serve_driver_smoke(self):
        from repro.launch.cells import greedy_generate as generate

        out = generate(
            arch="smollm-135m", reduced=True,
            prompt_tokens=[5, 6, 7], max_new_tokens=4,
        )
        assert len(out) == 7  # prompt + 4


class TestBenchmarkHarness:
    def test_benchmarks_importable_and_listed(self):
        import benchmarks.run as brun

        names = [b[0] for b in brun.BENCHMARKS]
        assert "stencil_sweep" in names
        assert "cahn_hilliard_step" in names


class TestCompileCache:
    """Entry points place JAX's persistent compile cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at a fixed ``<repo>/.jax_cache``."""

    def _run(self, monkeypatch, env):
        import jax

        from repro import util

        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        was = jax.config.jax_compilation_cache_dir
        try:
            return util.init_compile_cache(), jax.config.jax_compilation_cache_dir
        finally:
            jax.config.update("jax_compilation_cache_dir", was)

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        import jax

        before = jax.config.jax_compilation_cache_dir
        where, config = self._run(monkeypatch, str(tmp_path))
        assert where == str(tmp_path)
        assert config == before  # nothing set in code

    def test_default_is_the_repo_cache(self, monkeypatch):
        from pathlib import Path

        where, config = self._run(monkeypatch, None)
        repo = Path(__file__).resolve().parents[1]
        assert where == config == str(repo / ".jax_cache")
