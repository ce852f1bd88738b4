"""Create-time autotuner: cache hit/miss behaviour, key stability across
processes, force re-measurement, bit-identical tuned plans at fp64, and
corrupted-cache resilience."""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune as T
from repro.core.adi import make_adi_operator
from repro.core.cahn_hilliard import CahnHilliardADI, CHConfig, deep_quench_ic
from repro.core.stencil import stencil_create_1d_batch, stencil_create_2d


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    """A fresh, empty cache dir wired in through the env var."""
    root = tmp_path / "tune-cache"
    monkeypatch.setenv(T.ENV_VAR, str(root))
    T.reset_stats()
    return T.TuneCache(root)


def _toy_candidates():
    return [{"w": 1}, {"w": 2}]


def _toy_build(cfg):
    w = cfg["w"]

    def f(x):
        return x * w

    return jax.jit(f)


ARGS = (jnp.ones((8,)),)
KEY_KW = dict(shape=(8,), dtype=jnp.float32, bc="periodic", backend="auto")


class TestCacheHitMiss:
    def test_miss_measures_then_hit_does_not(self, cache):
        best = T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached", **KEY_KW
        )
        assert best in _toy_candidates()
        assert T.stats.cache_misses == 1
        assert T.stats.measure_runs >= 2  # both candidates timed

        runs_before = T.stats.measure_runs
        again = T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached", **KEY_KW
        )
        assert again == best
        assert T.stats.cache_hits == 1
        assert T.stats.measure_runs == runs_before  # no re-measurement

    def test_force_remeasures(self, cache):
        T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached", **KEY_KW
        )
        runs_before = T.stats.measure_runs
        T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="force", **KEY_KW
        )
        assert T.stats.measure_runs > runs_before

    def test_off_never_measures(self, cache):
        best = T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="off", **KEY_KW
        )
        assert best == _toy_candidates()[0]
        assert T.stats.measure_runs == 0

    def test_single_candidate_short_circuits(self, cache):
        best = T.autotune(
            "toy", [{"w": 7}], _toy_build, ARGS, mode="cached", **KEY_KW
        )
        assert best == {"w": 7}
        assert T.stats.measure_runs == 0

    def test_failing_candidate_is_recorded_not_hidden(self, cache):
        # an injected kernel failure at the Pallas dispatch drops that
        # candidate from the race, with its exception in the stats
        from repro.kernels import ops
        from repro.runtime import chaos

        x = jnp.ones((16, 16), jnp.float32)
        coeffs = jnp.ones((5,), jnp.float32)

        def build(cfg):
            return jax.jit(
                lambda a: ops.stencil_apply(
                    a, coeffs, left=1, right=1, top=1, bottom=1,
                    backend=cfg["backend"],
                )
            )

        cands = [{"backend": "pallas"}, {"backend": "jnp"}]
        plan = chaos.FaultPlan().add("pallas.dispatch", "backend_error", at=1)
        with chaos.injected(plan):
            best = T.autotune(
                "toy_dispatch", cands, build, (x,), mode="force",
                shape=(16, 16), dtype=jnp.float32,
            )
        assert best == {"backend": "jnp"}
        [(kernel, config, err)] = T.stats.dropped
        assert (kernel, config) == ("toy_dispatch", {"backend": "pallas"})
        assert isinstance(err, chaos.BackendError)
        T.reset_stats()
        assert T.stats.dropped == []

    def test_stale_cache_entry_not_in_candidates_is_miss(self, cache):
        key = T.tune_key("toy", extra=None, **KEY_KW)
        cache.put(key, {"w": 999})  # config no longer offered
        best = T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached", **KEY_KW
        )
        assert best in _toy_candidates()
        assert T.stats.cache_misses == 1


class TestSecondCreateIsFree:
    def test_adi_create_cached_performs_no_measurement(self, cache):
        # the acceptance case: second creation of an identical plan with
        # tune='cached' performs no measurement runs at all
        make_adi_operator(32, 32, 0.3, cyclic=True, tune="cached")
        assert T.stats.measure_runs > 0
        runs_before = T.stats.measure_runs
        op2 = make_adi_operator(32, 32, 0.3, cyclic=True, tune="cached")
        assert T.stats.measure_runs == runs_before
        assert T.stats.cache_hits >= 2  # both sweeps hit
        assert op2.x_cfg is not None and op2.y_cfg is not None

    def test_ch_solver_second_create_is_free(self, cache):
        cfg = CHConfig(nx=32, ny=32, dt=1e-3, backend="jnp", tune="cached")
        CahnHilliardADI(cfg)
        runs_before = T.stats.measure_runs
        CahnHilliardADI(cfg)
        assert T.stats.measure_runs == runs_before


class TestHostFingerprint:
    """Cross-host cache hygiene: keys carry a hardware identity, and
    REPRO_TUNE_FORCE re-measures even on a hit."""

    def test_key_contains_fingerprint(self):
        fp = T.host_fingerprint()
        assert fp  # non-empty, deterministic
        assert fp == T.host_fingerprint()
        key = T.tune_key("k", shape=(8,), dtype=jnp.float32)
        assert json.loads(key)["host"] == fp

    def test_differing_host_is_a_different_key(self, monkeypatch):
        from repro.tune import cache as C

        base = T.tune_key("k", shape=(8,), dtype=jnp.float32)
        monkeypatch.setattr(
            C, "host_fingerprint", lambda: "other-arch/96cpu/tpu/v5e"
        )
        assert T.tune_key("k", shape=(8,), dtype=jnp.float32) != base

    def test_force_env_remeasures_on_hit(self, cache, monkeypatch):
        T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached",
            **KEY_KW,
        )
        runs_before = T.stats.measure_runs
        monkeypatch.setenv(T.FORCE_ENV, "1")
        T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached",
            **KEY_KW,
        )
        assert T.stats.measure_runs > runs_before  # hit was re-measured

    def test_force_env_does_not_enable_tuning_when_off(self, cache,
                                                       monkeypatch):
        monkeypatch.setenv(T.FORCE_ENV, "1")
        T.reset_stats()
        best = T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="off", **KEY_KW
        )
        assert best == _toy_candidates()[0]
        assert T.stats.measure_runs == 0

    def test_force_env_zero_is_off(self, cache, monkeypatch):
        T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached",
            **KEY_KW,
        )
        runs_before = T.stats.measure_runs
        monkeypatch.setenv(T.FORCE_ENV, "0")
        T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached",
            **KEY_KW,
        )
        assert T.stats.measure_runs == runs_before  # plain cached hit


class TestKeyStability:
    def test_key_is_deterministic_across_processes(self, cache):
        kw = dict(
            shape=(64, 32), dtype=jnp.float64, bc="periodic", backend="auto",
            extra={"cyclic": True},
        )
        key_here = T.tune_key("adi_solve_x", **kw)
        code = (
            "import jax.numpy as jnp; from repro.tune import tune_key; "
            "print(tune_key('adi_solve_x', shape=(64, 32), "
            "dtype=jnp.float64, bc='periodic', backend='auto', "
            "extra={'cyclic': True}), end='')"
        )
        key_there = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, check=True,
        ).stdout
        assert key_here == key_there

    def test_key_discriminates(self):
        base = T.tune_key("k", shape=(8,), dtype=jnp.float32)
        assert base != T.tune_key("k2", shape=(8,), dtype=jnp.float32)
        assert base != T.tune_key("k", shape=(16,), dtype=jnp.float32)
        assert base != T.tune_key("k", shape=(8,), dtype=jnp.float64)
        assert base != T.tune_key("k", shape=(8,), dtype=jnp.float32, bc="np")


class TestBitMatch:
    def test_tuned_plans_bit_match_untuned_fp64(self, cache):
        # tuning must be result-invariant: at fp64 a tuned plan's Compute
        # is bit-identical to the untuned plan's
        rng = np.random.default_rng(0)
        data = jnp.asarray(rng.standard_normal((32, 32)))
        w = jnp.asarray(rng.standard_normal((5, 5)))
        p0 = stencil_create_2d("xy", "periodic", weights=w, backend="jnp")
        p1 = stencil_create_2d(
            "xy", "periodic", weights=w, backend="jnp",
            tune="cached", shape=(32, 32),
        )
        np.testing.assert_array_equal(p0.apply(data), p1.apply(data))

        w1 = jnp.asarray(rng.standard_normal((5,)))
        b0 = stencil_create_1d_batch("periodic", weights=w1, backend="jnp")
        b1 = stencil_create_1d_batch(
            "periodic", weights=w1, backend="jnp",
            tune="cached", shape=(32, 32),
        )
        np.testing.assert_array_equal(b0.apply(data), b1.apply(data))

    def test_tuned_ch_step_matches_untuned_fp64(self, cache):
        c0 = deep_quench_ic(32, 32, seed=1)
        base = CHConfig(nx=32, ny=32, dt=1e-3, backend="jnp")
        s0 = CahnHilliardADI(base)
        s1 = CahnHilliardADI(
            CHConfig(nx=32, ny=32, dt=1e-3, backend="jnp", tune="cached")
        )
        c1 = s0.initial_step(c0)
        a0, _ = s0.step(c1, c0)
        a1, _ = s1.step(c1, c0)
        # off-TPU the candidate space is backend-preserving (jnp), where
        # the unroll knob does not change the arithmetic: bitwise equal
        np.testing.assert_array_equal(a0, a1)

    def test_tune_needs_shape(self):
        with pytest.raises(ValueError):
            stencil_create_2d(
                "xy", "periodic", weights=jnp.ones((3, 3)), tune="cached"
            )

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            make_adi_operator(16, 16, 0.1, tune="always")
        with pytest.raises(ValueError):
            CHConfig(nx=16, ny=16, tune="sometimes").validate()


class TestStreamGeometryGrid:
    def test_tuned_streamed_solver_matches_untuned(self, cache):
        # the (width x chunk_rows) grid must be result-invariant: a tuned
        # streamed solver steps bit-identically (fp64, jnp backend) to the
        # untuned streamed solver
        n = 32
        kw = dict(
            nx=n, ny=n, dt=1e-3, rhs_mode="fused", backend="jnp",
            streams=2, max_tile_bytes=n * n * 8 // 4,
        )
        s0 = CahnHilliardADI(CHConfig(**kw))
        s1 = CahnHilliardADI(CHConfig(**kw, tune="force"))
        assert s1._streams_eff >= 1
        assert s1._chunk_rows_eff is None or n % s1._chunk_rows_eff == 0
        c0 = deep_quench_ic(n, n, seed=4)
        c1 = s0.initial_step(c0)
        a0, _ = s0.step(c1, c0)
        a1, _ = s1.step(c1, c0)
        np.testing.assert_allclose(a0, a1, atol=1e-12, rtol=1e-12)

    def test_geometry_winner_is_cached(self, cache):
        n = 32
        kw = dict(
            nx=n, ny=n, dt=1e-3, rhs_mode="fused", backend="jnp",
            streams=2, max_tile_bytes=n * n * 8 // 4, tune="cached",
        )
        CahnHilliardADI(CHConfig(**kw))
        runs_before = T.stats.measure_runs
        CahnHilliardADI(CHConfig(**kw))
        assert T.stats.measure_runs == runs_before  # second Create is free


class TestCorruptedCache:
    def test_corrupted_file_is_ignored_not_fatal(self, cache):
        key = T.tune_key("toy", extra=None, **KEY_KW)
        T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached", **KEY_KW
        )
        path = cache.path_for(key)
        assert path.exists()
        path.write_bytes(b"{ not json at all \x00\xff")
        T.reset_stats()
        again = T.autotune(
            "toy", _toy_candidates(), _toy_build, ARGS, mode="cached", **KEY_KW
        )
        assert again in _toy_candidates()
        assert T.stats.cache_misses == 1  # treated as a miss, re-measured
        # and the rewrite healed the file (winner may legitimately differ
        # between measurements of two near-identical toy candidates)
        healed = json.loads(path.read_text())
        assert healed["key"] == key and healed["best"] in _toy_candidates()

    def test_foreign_key_file_is_miss(self, cache):
        key = T.tune_key("toy", extra=None, **KEY_KW)
        path = cache.path_for(key)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({"key": "something-else", "best": {"w": 5}}))
        assert cache.get(key) is None

    def test_missing_dir_is_miss(self, tmp_path):
        c = T.TuneCache(tmp_path / "never-created")
        assert c.get("whatever") is None


class TestSpectralArbitrage:
    """fft candidates in the tuner race (PR-9): the winner round-trips
    the JSON cache — including across processes — and arbitrage stays
    confined to backend='auto'."""

    def test_fft_winner_round_trips_the_cache(self, cache):
        # deterministic winner: the fft candidate's callable is made
        # artificially cheap, so timing noise cannot flip the race
        import time

        candidates = [
            {"backend": "jnp", "unroll": 1},
            {"backend": "fft"},
        ]

        def build(cfg):
            if cfg["backend"] == "fft":
                return lambda x: x
            def slow(x):
                time.sleep(0.005)
                return x
            return slow

        kw = dict(
            shape=(64, 64), dtype=jnp.float64, bc="periodic",
            backend="auto", extra={"cyclic": True, "operator": "hyper"},
        )
        best = T.autotune(
            "adi_solve_x", candidates, build, ARGS, mode="force", **kw
        )
        assert best["backend"] == "fft"
        # a fresh cache handle on the same dir (what another process
        # sees): the fft winner must be a pure hit, not a stale miss
        T.reset_stats()
        again = T.autotune(
            "adi_solve_x", candidates, build, ARGS, mode="cached", **kw
        )
        assert again["backend"] == "fft"
        assert T.stats.measure_runs == 0 and T.stats.cache_hits == 1

    def test_fft_tuned_adi_plan_round_trips_cross_process(
        self, cache, tmp_path
    ):
        """A tuned backend='auto' ADI Create (whose race includes the
        fft candidate) lands in the cache; a second *process* pointing
        at the same cache dir re-Creates the plan with zero measurement
        runs and the identical per-sweep winners."""
        from repro import api

        op = api.create(
            "hyperdiffusion", (64, 64), mode="adi", alpha=0.2,
            tune="force", lint="off",
        )
        code = (
            "import os, json, jax\n"
            "jax.config.update('jax_enable_x64', True)\n"
            "from repro import api\n"
            "from repro import tune as T\n"
            "T.reset_stats()\n"
            "op = api.create('hyperdiffusion', (64, 64), mode='adi',"
            " alpha=0.2, tune='cached', lint='off')\n"
            "print(json.dumps({'runs': T.stats.measure_runs,"
            " 'x': op.x_cfg, 'y': op.y_cfg}), end='')\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, check=True,
        ).stdout
        got = json.loads(out)
        assert got["runs"] == 0, "cross-process Create re-measured"
        assert got["x"] == op.x_cfg and got["y"] == op.y_cfg

    def test_explicit_backend_excludes_fft_from_the_race(self, cache):
        # backend='jnp' pins the arithmetic: the candidate space must
        # not contain fft (the fp64 bit-match contract depends on it)
        from repro.core.adi import _sweep_candidates

        assert all(
            c["backend"] != "fft" for c in _sweep_candidates(32)
        )
        assert {"backend": "fft"} in _sweep_candidates(32, fft=True)

    def test_auto_stencil_plan_races_fft(self, cache):
        # the speculative symbol is attached under backend='auto' with
        # tuning on, so the race includes the spectral candidate; the
        # tuned plan keeps a symbol either way and stays correct
        from repro import api

        plan = api.create(
            "hyperdiffusion", (64, 64), tune="force", lint="off"
        )
        assert plan.symbol is not None
        assert plan.backend in ("auto", "fft", "jnp", "pallas")
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((64, 64)))
        ref = api.create("hyperdiffusion", (64, 64), backend="jnp",
                         lint="off")
        np.testing.assert_allclose(
            np.asarray(plan.apply(x)), np.asarray(ref.apply(x)),
            rtol=1e-10, atol=1e-10,
        )


# ---------------------------------------------------------------------------
# The analytic cost prior (PR-10): prune without measuring, never flip
# a winner
# ---------------------------------------------------------------------------


class TestCostPrior:
    def test_prune_keeps_the_band_and_drops_the_rest(self):
        cands = [{"w": 1}, {"w": 2}, {"w": 3}]
        scores = {1: 100.0, 2: 120.0, 3: 1000.0}
        kept, dropped = T.prune_candidates(
            cands, lambda c: scores[c["w"]]
        )
        assert kept == [{"w": 1}, {"w": 2}]  # 1.2x is inside the band
        assert dropped == [{"w": 3}]

    def test_unscorable_candidates_always_race(self):
        kept, dropped = T.prune_candidates(
            [{"w": 1}, {"w": 2}],
            lambda c: 1.0 if c["w"] == 1 else None,
        )
        assert dropped == [] and len(kept) == 2

    def test_scoring_exception_means_keep(self):
        def prior(c):
            if c["w"] == 2:
                raise RuntimeError("cannot model")
            return float(c["w"])

        kept, dropped = T.prune_candidates(
            [{"w": 1}, {"w": 2}, {"w": 30}], prior
        )
        assert {"w": 2} in kept and dropped == [{"w": 30}]

    def test_autotune_skips_measuring_dominated_candidates(self, cache):
        calls = []

        def build(cfg):
            calls.append(cfg["w"])
            return _toy_build(cfg)

        best = T.autotune(
            "toy", _toy_candidates(), build, ARGS, **KEY_KW,
            mode="force", prior=lambda c: {1: 1.0, 2: 100.0}[c["w"]],
        )
        # a prune to a single survivor returns it without any timing
        assert best == {"w": 1}
        assert calls == []
        assert T.stats.measure_runs == 0
        assert T.stats.pruned == 1

    def test_stencil_prior_prefers_direct_for_sparse_small(self):
        prior = T.stencil_prior((64, 64), taps=5, itemsize=8)
        direct = prior({"backend": "auto"})
        fft = prior({"backend": "fft"})
        assert direct < fft  # 5-tap laplacian at 64^2: direct wins
        assert prior({"backend": "mystery"}) is None

    def test_noprior_env_disables_pruning(self, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE_NOPRIOR", "1")
        assert not T.prior_enabled()
        monkeypatch.setenv("REPRO_TUNE_NOPRIOR", "0")
        assert T.prior_enabled()

    def test_plan_prior_measures_strictly_less_same_winner_fp64(
        self, cache, monkeypatch
    ):
        # the acceptance case: laplacian 64^2 backend='auto' races
        # direct vs fft.  With the prior the fft candidate is pruned
        # (strictly fewer measurements); the winner and the fp64
        # numbers must be identical either way.
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.standard_normal((64, 64)))

        monkeypatch.setenv("REPRO_TUNE_NOPRIOR", "1")
        T.reset_stats()
        from repro import api

        p_off = api.create("laplacian", (64, 64), tune="force", lint="off")
        runs_off = T.stats.measure_runs
        y_off = np.asarray(p_off.apply(x))

        monkeypatch.delenv("REPRO_TUNE_NOPRIOR")
        T.reset_stats()
        p_on = api.create("laplacian", (64, 64), tune="force", lint="off")
        runs_on = T.stats.measure_runs
        y_on = np.asarray(p_on.apply(x))

        assert runs_off >= 2, "without the prior both candidates race"
        assert runs_on < runs_off, "the prior must measure strictly less"
        assert T.stats.pruned >= 1
        assert p_on.backend == p_off.backend
        np.testing.assert_array_equal(y_on, y_off)
