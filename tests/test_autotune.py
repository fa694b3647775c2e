"""Tiling-autotuner behavior: cache round-trip, disabled fallback, tuning,
ops integration, and the serving engine's warm-at-build hook."""

import json

import jax
import pytest

from repro.kernels import autotune, ops


@pytest.fixture(autouse=True)
def _clean_state():
    autotune.disable()
    yield
    autotune.disable()


class TestCache:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "cache.json")
        key = autotune.Key(T=8, m=64, n=64, b=4, r=16)
        cache = autotune.TuningCache(path)
        assert cache.get(key) is None
        cache.put(key, (8, 32))
        cache.save()
        reloaded = autotune.TuningCache(path)
        assert reloaded.get(key) == (8, 32)

    def test_key_encoding_distinguishes_signatures(self):
        a = autotune.Key(T=8, m=64, n=64, b=4, r=16)
        variants = [
            autotune.Key(T=1, m=64, n=64, b=4, r=16),
            autotune.Key(T=8, m=64, n=64, b=4, r=16, G=2),
            autotune.Key(T=8, m=64, n=64, b=4, r=16, kind="int4"),
            autotune.Key(T=8, m=64, n=64, b=4, r=16, dtype="bfloat16"),
            autotune.Key(T=8, m=64, n=64, b=4, r=16, kind="int8", act="int8"),
        ]
        assert len({k.encode() for k in [a, *variants]}) == 6

    def test_unknown_version_and_garbage_ignored(self, tmp_path):
        p1 = tmp_path / "v999.json"
        p1.write_text(json.dumps({"version": 999, "entries": {"x": [8, 8]}}))
        assert autotune.TuningCache(str(p1)).entries == {}
        p2 = tmp_path / "garbage.json"
        p2.write_text("{not json")
        assert autotune.TuningCache(str(p2)).entries == {}
        p3 = tmp_path / "badvals.json"
        p3.write_text(json.dumps(
            {"version": autotune._VERSION,
             "entries": {"a": [8], "b": [0, 8], "c": [8, 32]}}))
        assert autotune.TuningCache(str(p3)).entries == {"c": (8, 32)}

    def test_version1_cache_migration_ignored(self, tmp_path):
        """Version-1 files predate the activation-storage key component:
        their keys would silently collide with the act="none" twins of
        W8A8/W4A8 calls, so the loader must treat them as empty and let
        re-tuning rebuild the file at the current version."""
        p = tmp_path / "v1.json"
        p.write_text(json.dumps(
            {"version": 1,
             "entries": {"T8.m64.n64.b4.r16.G1.float32.int8.cpu": [8, 32]}}))
        cache = autotune.TuningCache(str(p))
        assert cache.entries == {}
        key = autotune.Key(T=8, m=64, n=64, b=4, r=16, kind="int8")
        cache.put(key, (8, 16))
        cache.save()
        raw = json.loads(p.read_text())
        assert raw["version"] == autotune._VERSION
        assert autotune.TuningCache(str(p)).get(key) == (8, 16)

    def test_missing_file_is_empty(self, tmp_path):
        assert autotune.TuningCache(str(tmp_path / "nope.json")).entries == {}


class TestFallback:
    def test_disabled_lookup_is_none(self):
        assert not autotune.enabled()
        assert autotune.lookup(autotune.Key(T=8, m=64, n=64, b=4, r=16)) is None

    def test_disabled_tune_returns_heuristic(self):
        got = autotune.tune_blast(8, 64, 64, 4, 16)
        assert got == ops.pick_blast_blocks(8, 64, 64, 4, 16, 4, 4)

    def test_resolve_blocks_falls_back_to_heuristic(self):
        import jax.numpy as jnp
        bt, br = ops._resolve_blocks(None, None, 8, 64, 64, 4, 16,
                                     jnp.float32, 4, 1, "float")
        h = ops.pick_blast_blocks(8, 64, 64, 4, 16, 4, 4)
        assert (bt, br) == (min(h[0], 8), min(h[1], 16))

    def test_explicit_blocks_always_win(self):
        import jax.numpy as jnp
        autotune.enable()
        autotune.cache().put(
            autotune.Key(T=8, m=64, n=64, b=4, r=16,
                         backend=jax.default_backend()), (16, 64))
        assert ops._resolve_blocks(8, 8, 8, 64, 64, 4, 16,
                                   jnp.float32, 4, 1, "float") == (8, 8)


class TestTuning:
    def test_tune_caches_a_feasible_candidate(self, tmp_path):
        autotune.enable(str(tmp_path / "c.json"))
        got = autotune.tune_blast(4, 32, 32, 4, 8, reps=1)
        cands = autotune.candidates(4, 32, 32, 4, 8)
        assert got in cands
        key = autotune.Key(T=4, m=32, n=32, b=4, r=8,
                           backend=jax.default_backend())
        assert autotune.cache().get(key) == got
        # second call is a cache hit (no re-timing): identical result
        assert autotune.tune_blast(4, 32, 32, 4, 8, reps=1) == got
        autotune.save()
        assert autotune.TuningCache(str(tmp_path / "c.json")).get(key) == got

    def test_resolve_blocks_uses_tuned_entry(self):
        import jax.numpy as jnp
        autotune.enable()
        key = autotune.Key(T=6, m=32, n=32, b=4, r=8,
                           backend=jax.default_backend())
        autotune.cache().put(key, (8, 8))
        assert ops._resolve_blocks(None, None, 6, 32, 32, 4, 8,
                                   jnp.float32, 4, 1, "float") == (8, 8)

    def test_tuned_blocks_do_not_change_numerics(self, tmp_path):
        import jax.numpy as jnp
        import numpy as np
        from repro.core import blast
        from repro.kernels import ref
        params = blast.init(jax.random.PRNGKey(0), 32, 32, 4, 8)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
        want = ref.blast_matmul_ref(x, params.U, params.S, params.V)
        autotune.enable(str(tmp_path / "c.json"))
        autotune.tune_blast(4, 32, 32, 4, 8, reps=1)
        got = ops.blast_matmul(x, params.U, params.S, params.V)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)

    def test_candidates_respect_shape_caps(self):
        for bt, br in autotune.candidates(1, 256, 256, 16, 24):
            assert bt <= 8 and br <= 32       # T=1 → 8-row cap; r=24 → 32

    def test_act_tunes_under_distinct_key(self, tmp_path):
        """W8A8 calls key separately from their float-activation twins, and
        the integer-activation path refuses float factors."""
        autotune.enable(str(tmp_path / "c.json"))
        got = autotune.tune_blast(4, 32, 32, 4, 8, kind="int8", act="int8",
                                  reps=1)
        backend = jax.default_backend()
        a8 = autotune.Key(T=4, m=32, n=32, b=4, r=8, kind="int8",
                          backend=backend, act="int8")
        assert autotune.cache().get(a8) == got
        assert autotune.cache().get(
            autotune.Key(T=4, m=32, n=32, b=4, r=8, kind="int8",
                         backend=backend)) is None
        with pytest.raises(ValueError):
            autotune.tune_blast(4, 32, 32, 4, 8, kind="float", act="int8")

    @pytest.mark.parametrize("kind", ["int8", "int4"])
    def test_grouped_act_tuning_runs(self, tmp_path, kind):
        autotune.enable(str(tmp_path / "g.json"))
        got = autotune.tune_blast(4, 32, 32, 4, 8, G=2, kind=kind,
                                  act="int8", reps=1)
        key = autotune.Key(T=4, m=32, n=32, b=4, r=8, G=2, kind=kind,
                           backend=jax.default_backend(), act="int8")
        assert autotune.cache().get(key) == got


class TestEngineWarm:
    def test_engine_build_warms_cache(self, tmp_path):
        from repro import configs
        from repro.models import build_model
        from repro.serve import (AutotuneConfig, Engine, EngineConfig,
                                 MemoryConfig, Request, SchedulerConfig)

        path = str(tmp_path / "engine_cache.json")
        cfg = configs.ARCHS["smollm-135m"].reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        eng = Engine(model, params, EngineConfig(
            scheduler=SchedulerConfig(slots=2, chunk_size=4),
            memory=MemoryConfig(max_len=32),
            autotune=AutotuneConfig(enabled=True, cache_path=path)))
        entries = autotune.TuningCache(path).entries
        assert entries, "warm-at-build must persist tuned tilings"
        # decode width (B) and full-chunk width (B·chunk) both tuned
        assert any(".T2." in k or k.startswith("T2.") for k in entries)
        assert any(k.startswith("T8.") for k in entries)
        eng.submit(Request(uid=0, prompt=[1, 2, 3], max_new_tokens=2))
        done = eng.run()
        assert len(done) == 1 and len(done[0].output) == 2
