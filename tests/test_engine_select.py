"""Autotuner: winner selection, two-layer caching, server integration,
and the Pallas batch-bucketing recompile bound."""
import json

import numpy as np
import pytest

from repro import core
from repro.core import engine_select
from repro.inference.server import ForestServer

CHEAP = ("qs", "qs-bitmm", "native")


@pytest.fixture(autouse=True)
def _fresh_cache():
    engine_select.clear_cache()
    yield
    engine_select.clear_cache()


def test_choose_benchmarks_and_caches(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    c1 = engine_select.choose(small_forest, 64, engines=CHEAP,
                              cache_path=cache, repeats=1)
    assert c1.engine in CHEAP and not c1.from_cache
    assert set(c1.timings) == set(CHEAP)
    assert all(t > 0 for t in c1.timings.values())
    # winner really is the fastest measured engine
    assert c1.engine == min(c1.timings, key=c1.timings.get)

    # in-memory hit
    c2 = engine_select.choose(small_forest, 64, engines=CHEAP,
                              cache_path=cache, repeats=1)
    assert c2.from_cache and c2.engine == c1.engine

    # disk hit (fresh process simulated by clearing the memory layer)
    engine_select.clear_cache()
    with open(cache) as f:
        assert c1.key in json.load(f)
    c3 = engine_select.choose(small_forest, 64, engines=CHEAP,
                              cache_path=cache, repeats=1)
    assert c3.from_cache and c3.engine == c1.engine


def test_subset_sweep_never_answers_for_full_matrix(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    narrow = engine_select.choose(small_forest, 64, engines=("qs",),
                                  cache_path=cache, repeats=1)
    assert narrow.engine == "qs" and not narrow.from_cache
    # the qs-only entry must not satisfy a lookup for a wider engine set
    full = engine_select.choose(small_forest, 64, engines=CHEAP,
                                cache_path=cache, repeats=1)
    assert not full.from_cache and set(full.timings) == set(CHEAP)
    # ...but the wide entry answers later narrow lookups, re-deriving the
    # winner over just the requested subset
    again = engine_select.choose(small_forest, 64, engines=("qs", "native"),
                                 cache_path=cache, repeats=1)
    assert again.from_cache
    assert again.engine == min(("qs", "native"), key=full.timings.get)


def test_narrow_resweep_keeps_richer_cache_entry(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    full = engine_select.choose(small_forest, 64, engines=CHEAP,
                                cache_path=cache, repeats=1)
    # a forced qs-only re-benchmark must not clobber the CHEAP-wide entry
    engine_select.choose(small_forest, 64, engines=("qs",),
                         cache_path=cache, force=True, repeats=1)
    with open(cache) as f:
        entry = json.load(f)[full.key]
    assert set(entry["timings"]) == set(CHEAP)
    c = engine_select.choose(small_forest, 64, engines=CHEAP,
                             cache_path=cache, repeats=1)
    assert c.from_cache


def test_narrow_resweep_cannot_clobber_disk_via_memory_layer(small_forest,
                                                            tmp_path):
    """A narrow entry cached only in memory (cache_path=None) must not let
    a later forced narrow sweep erase a wider entry on disk."""
    cache = str(tmp_path / "engines.json")
    full = engine_select.choose(small_forest, 64, engines=CHEAP,
                                cache_path=cache, repeats=1)
    engine_select.clear_cache()
    engine_select.choose(small_forest, 64, engines=("qs",),
                         cache_path=None, repeats=1)   # memory-only, narrow
    engine_select.choose(small_forest, 64, engines=("qs",),
                         cache_path=cache, force=True, repeats=1)
    with open(cache) as f:
        assert set(json.load(f)[full.key]["timings"]) == set(CHEAP)


def test_partial_miss_benches_only_missing_engines(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    narrow = engine_select.choose(small_forest, 64, engines=("qs",),
                                  cache_path=cache, repeats=1)
    wider = engine_select.choose(small_forest, 64, engines=CHEAP,
                                 cache_path=cache, repeats=1)
    assert not wider.from_cache and set(wider.timings) == set(CHEAP)
    # qs was not re-benchmarked: its cached timing is reused verbatim
    assert wider.timings["qs"] == narrow.timings["qs"]


def test_partial_miss_persists_merged_union_to_disk(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    narrow = engine_select.choose(small_forest, 64, engines=("qs",),
                                  cache_path=None, repeats=1)  # memory-only
    engine_select.choose(small_forest, 64, engines=CHEAP,
                         cache_path=cache, repeats=1)
    # the memory-only qs timing reached disk along with the fresh ones
    with open(cache) as f:
        entry = json.load(f)[narrow.key]
    assert set(entry["timings"]) == set(CHEAP)


def test_memory_hit_writes_through_to_disk(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    c1 = engine_select.choose(small_forest, 64, engines=CHEAP,
                              cache_path=None, repeats=1)   # memory-only
    c2 = engine_select.choose(small_forest, 64, engines=CHEAP,
                              cache_path=cache, repeats=1)
    assert c2.from_cache
    with open(cache) as f:
        assert set(json.load(f)[c1.key]["timings"]) == set(CHEAP)


def test_overlapping_sweeps_merge_coverage(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    engine_select.choose(small_forest, 64, engines=("qs", "native"),
                         cache_path=cache, repeats=1)
    c2 = engine_select.choose(small_forest, 64, engines=("qs-bitmm",),
                              cache_path=cache, repeats=1)
    assert not c2.from_cache
    # both sweeps' timings accumulated → the union now hits the cache
    c3 = engine_select.choose(small_forest, 64, engines=CHEAP,
                              cache_path=cache, repeats=1)
    assert c3.from_cache and set(c3.timings) == set(CHEAP)


def test_env_cache_path_resolved_per_call(small_forest, tmp_path,
                                          monkeypatch):
    cache = tmp_path / "env_cache.json"
    monkeypatch.setenv("REPRO_ENGINE_CACHE", str(cache))
    c = engine_select.choose(small_forest, 64, engines=("qs",), repeats=1)
    assert cache.exists() and c.key in json.loads(cache.read_text())


def test_disk_hit_warms_memory_layer(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    c1 = engine_select.choose(small_forest, 64, engines=CHEAP,
                              cache_path=cache, repeats=1)
    engine_select.clear_cache()             # simulate a fresh process
    c2 = engine_select.choose(small_forest, 64, engines=CHEAP,
                              cache_path=cache, repeats=1)
    assert c2.from_cache
    assert engine_select._MEM_CACHE[c1.key]["timings"] == c2.timings


def test_choose_batch_bucketing(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    c1 = engine_select.choose(small_forest, 33, engines=CHEAP,
                              cache_path=cache, repeats=1)
    c2 = engine_select.choose(small_forest, 64, engines=CHEAP,
                              cache_path=cache, repeats=1)
    # 33 and 64 share the 64 bucket → one sweep, one cache entry
    assert c1.key == c2.key and c2.from_cache


def test_choice_predictor_correct(small_forest):
    from conftest import rand_X
    c = engine_select.choose(small_forest, 32, engines=CHEAP,
                             cache_path=None, repeats=1)
    X = rand_X(small_forest, B=32)
    np.testing.assert_allclose(c.predict(X),
                               small_forest.predict_oracle(X),
                               rtol=1e-4, atol=1e-5)


def test_forest_server_uses_autotuned_winner(small_forest, tmp_path):
    cache = str(tmp_path / "engines.json")
    choice = engine_select.choose(small_forest, 16, engines=CHEAP,
                                  cache_path=cache, repeats=1)
    srv = ForestServer.from_forest(small_forest, max_batch=16,
                                   engines=CHEAP, cache_path=cache)
    # the server's decision came from the cache and matches the winner
    assert srv.engine_choice is not None
    assert srv.engine_choice.from_cache
    assert srv.engine_choice.engine == choice.engine
    assert srv.predictor is srv.engine_choice.predictor

    # and the served scores are the winner's predictions
    rng = np.random.default_rng(1)
    feats = rng.normal(size=(16, small_forest.n_features))
    for i in range(16):
        srv.submit(feats[i], arrival_s=float(i) * 1e-4)
    done = srv.poll(now_s=1.0)
    assert len(done) == 16
    got = np.stack([r.result for r in done])
    np.testing.assert_allclose(got, choice.predict(feats), rtol=1e-5,
                               atol=1e-6)


def test_pallas_batch_bucketing_bounds_recompiles(small_forest):
    """Satellite regression: distinct batch sizes inside one power-of-two
    bucket must reuse one compiled kernel."""
    from repro.kernels.ops import bucket_rows, pallas_qs_predictor
    assert [bucket_rows(b, 32) for b in (1, 32, 33, 64, 65, 100, 129)] == \
        [32, 32, 64, 64, 128, 128, 256]
    pred = pallas_qs_predictor(small_forest, block_b=32, block_t=4)
    rng = np.random.default_rng(0)
    for B in (3, 17, 31, 32):          # one bucket: 32
        pred.predict(rng.normal(size=(B, small_forest.n_features)))
    assert pred.n_compiles == 1
    for B in (33, 50, 64):             # second bucket: 64
        pred.predict(rng.normal(size=(B, small_forest.n_features)))
    assert pred.n_compiles == 2
    if hasattr(pred._fn, "_cache_size"):    # actual jit cache, where exposed
        assert pred._fn._cache_size() == pred.n_compiles


# --------------------------------------------------------------------------- #
# cascade candidates: naming, winner wiring, and cache hygiene — cascade
# tags participate in cache entries like the _dev{n} key component does:
# entries from before the cascade axis existed must key-miss and re-sweep
# --------------------------------------------------------------------------- #
def _cascade_spec(threshold=0.9):
    from repro.cascade import CascadeSpec, MarginGate
    return CascadeSpec(stages=(4, 8), policy=MarginGate(threshold))


def test_cascade_candidates_swept_and_usable(small_forest):
    from repro.cascade import CascadePredictor
    spec = _cascade_spec()
    c = engine_select.choose(small_forest, 16, engines=("qs",),
                             cascade_specs=(spec,), cache_path=None,
                             repeats=1)
    assert set(c.timings) == {"qs", f"qs@{spec.tag()}"}
    assert "cascade=4/8:margin0.9" in spec.tag()
    # the winning predictor is buildable and correct either way
    from conftest import rand_X
    X = rand_X(small_forest, B=16)
    np.testing.assert_allclose(c.predict(X),
                               small_forest.predict_oracle(X),
                               rtol=1e-4, atol=1e-5)
    if "cascade" in c.engine:
        assert isinstance(c.predictor, CascadePredictor)


def test_old_cache_entries_keymiss_cascade_sweeps(small_forest, tmp_path):
    """An entry written before the cascade axis existed (plain engine
    timings only) must not answer a cascade sweep — partial miss, only
    the cascade candidates are benchmarked, coverage merges."""
    cache = str(tmp_path / "engines.json")
    plain = engine_select.choose(small_forest, 16, engines=("qs", "native"),
                                 cache_path=cache, repeats=1)
    # simulate a fresh process with only the old-format disk entry
    engine_select.clear_cache()
    spec = _cascade_spec()
    c = engine_select.choose(small_forest, 16, engines=("qs", "native"),
                             cascade_specs=(spec,), cache_path=cache,
                             repeats=1)
    assert not c.from_cache
    # the plain timings were reused verbatim, not re-benchmarked
    assert c.timings["qs"] == plain.timings["qs"]
    assert set(c.timings) == {"qs", "native", f"qs@{spec.tag()}",
                              f"native@{spec.tag()}"}
    # the widened entry now answers both shapes of request
    hit = engine_select.choose(small_forest, 16, engines=("qs", "native"),
                               cascade_specs=(spec,), cache_path=cache,
                               repeats=1)
    assert hit.from_cache
    plain_hit = engine_select.choose(small_forest, 16,
                                     engines=("qs", "native"),
                                     cache_path=cache, repeats=1)
    assert plain_hit.from_cache


def test_distinct_cascade_specs_never_alias(small_forest, tmp_path):
    """Different stages or thresholds → different candidate names: a
    sweep for one spec must not answer for another."""
    cache = str(tmp_path / "engines.json")
    engine_select.choose(small_forest, 16, engines=("qs",),
                         cascade_specs=(_cascade_spec(0.9),),
                         cache_path=cache, repeats=1)
    other = engine_select.choose(small_forest, 16, engines=("qs",),
                                 cascade_specs=(_cascade_spec(0.5),),
                                 cache_path=cache, repeats=1)
    assert not other.from_cache
    from repro.cascade import CascadeSpec, MarginGate
    stages = engine_select.choose(
        small_forest, 16, engines=("qs",),
        cascade_specs=(CascadeSpec((2, 8), MarginGate(0.9)),),
        cache_path=cache, repeats=1)
    assert not stages.from_cache


def test_cascade_specs_reject_multi_device(small_forest):
    with pytest.raises(ValueError, match="cascade"):
        engine_select.choose(small_forest, 16, engines=("qs",),
                             cascade_specs=(_cascade_spec(),),
                             n_devices=2, cache_path=None, repeats=1)


def test_forest_server_serves_cascade_winner(small_forest, tmp_path):
    """from_forest(cascade_specs=) serves whatever wins; when the winner
    is a cascade, exit fractions land in the serving stats."""
    from repro.cascade import CascadePredictor, CascadeSpec, MarginGate
    # a gate this aggressive on an 8-tree forest makes the cascade the
    # plausible winner, but the assertion holds either way
    spec = CascadeSpec(stages=(2, 8), policy=MarginGate(0.0))
    srv = ForestServer.from_forest(small_forest, max_batch=8,
                                   engines=("qs",), cascade_specs=(spec,),
                                   cache_path=str(tmp_path / "c.json"),
                                   repeats=1)
    assert srv.engine_choice.engine in {"qs", f"qs@{spec.tag()}"}
    rng = np.random.default_rng(0)
    for i in range(8):
        srv.submit(rng.normal(size=small_forest.n_features),
                   arrival_s=float(i) * 1e-4)
    srv.flush(now_s=1.0)
    s = srv.stats.summary()
    assert s["n_requests"] == 8
    if isinstance(srv.predictor, CascadePredictor):
        assert "exit_fractions" in s


# --------------------------------------------------------------------------- #
# cache-file robustness: garbage on disk must mean re-sweep, never a crash
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("garbage", [
    '{"truncated": {"timings": {"qs": 0.0',           # cut mid-write
    "not json at all",
    "[1, 2, 3]",                                      # valid JSON, wrong type
    '"just a string"',
    '{"key": "entry is not a dict"}',
    '{"key": {"engine": "qs"}}',                      # missing timings
    '{"key": {"timings": {"qs": "fast"}}}',           # non-numeric timing
    '{"key": {"timings": {}}}',                       # empty timings
    "",
], ids=["truncated", "not-json", "list", "string", "str-entry",
        "no-timings", "str-timing", "empty-timings", "empty-file"])
def test_garbage_cache_file_triggers_clean_resweep(small_forest, tmp_path,
                                                   garbage):
    cache = str(tmp_path / "engines.json")
    with open(cache, "w") as f:
        f.write(garbage)
    c = engine_select.choose(small_forest, 64, engines=("qs", "native"),
                             cache_path=cache, repeats=1)
    assert not c.from_cache and set(c.timings) == {"qs", "native"}
    # ...and the file was rewritten into a valid cache that now hits
    with open(cache) as f:
        data = json.load(f)
    assert set(data[c.key]["timings"]) == {"qs", "native"}
    engine_select.clear_cache()
    c2 = engine_select.choose(small_forest, 64, engines=("qs", "native"),
                              cache_path=cache, repeats=1)
    assert c2.from_cache and c2.engine == c.engine


def test_garbage_entries_dropped_but_valid_entries_kept(small_forest,
                                                        class_forest,
                                                        tmp_path):
    """A partially corrupt cache keeps its healthy entries: only the
    malformed ones are dropped (and purged on the next rewrite)."""
    cache = str(tmp_path / "engines.json")
    good = engine_select.choose(small_forest, 64, engines=("qs",),
                                cache_path=cache, repeats=1)
    with open(cache) as f:
        data = json.load(f)
    data["corrupt_key"] = {"timings": "nope"}
    with open(cache, "w") as f:
        json.dump(data, f)
    engine_select.clear_cache()
    # the healthy entry still answers
    hit = engine_select.choose(small_forest, 64, engines=("qs",),
                               cache_path=cache, repeats=1)
    assert hit.from_cache and hit.engine == good.engine
    # a sweep for a different forest rewrites the file without the junk
    engine_select.choose(class_forest, 64, engines=("qs",),
                         cache_path=cache, repeats=1)
    with open(cache) as f:
        rewritten = json.load(f)
    assert "corrupt_key" not in rewritten
    assert good.key in rewritten


# --------------------------------------------------------------------------- #
# candidates that cannot allocate: dropped from the sweep, never fatal
# --------------------------------------------------------------------------- #
def _oom():
    import jax
    return jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "16.66G.")


def _failing_factories(monkeypatch, failing: dict):
    """``_candidate_factories`` with the factories of ``failing`` (name →
    exception) replaced by stubs that raise it, counting their calls."""
    real = engine_select._candidate_factories
    calls = {name: 0 for name in failing}

    def patched(*a, **kw):
        facs = real(*a, **kw)
        for name, exc in failing.items():
            def stub(name=name, exc=exc):
                calls[name] += 1
                raise exc
            stub.axes, stub.group_key = facs[name].axes, facs[name].group_key
            facs[name] = stub
        return facs

    monkeypatch.setattr(engine_select, "_candidate_factories", patched)
    return calls


@pytest.mark.parametrize("exc", [_oom, MemoryError],
                         ids=["resource-exhausted", "host-memory"])
def test_candidate_that_cannot_allocate_is_dropped(small_forest, tmp_path,
                                                   monkeypatch, exc):
    from conftest import rand_X
    from repro.obs.metrics import MetricsRegistry, set_default_registry
    calls = _failing_factories(monkeypatch, {"native": exc()})
    cache = str(tmp_path / "engines.json")
    mine = MetricsRegistry()
    old = set_default_registry(mine)
    try:
        c = engine_select.choose(small_forest, 32, engines=CHEAP,
                                 cache_path=cache, repeats=1)
    finally:
        set_default_registry(old)
    snap = mine.snapshot()
    assert c.engine in ("qs", "qs-bitmm") and not c.from_cache
    assert c.timings["native"] == float("inf")
    X = rand_X(small_forest, B=32)
    np.testing.assert_allclose(c.predict(X), small_forest.predict_oracle(X),
                               rtol=1e-4, atol=1e-5)
    dropped, = snap["repro_autotune_candidates_dropped_total"]["samples"]
    assert dropped["labels"] == {"candidate": "native"}
    assert dropped["value"] == 1.0
    assert snap["repro_autotune_candidates_benched_total"]["samples"][0][
        "value"] == 3.0
    # the drop is cached: a later process neither retries nor re-sweeps
    engine_select.clear_cache()
    again = engine_select.choose(small_forest, 32, engines=CHEAP,
                                 cache_path=cache, repeats=1)
    assert again.from_cache and again.engine == c.engine
    assert calls == {"native": 1}
    with open(cache) as f:
        entry = json.load(f)[c.key]
    assert entry["timings"]["native"] == float("inf")
    assert "native" not in entry["bench_us"]


def test_other_candidate_errors_still_raise(small_forest, monkeypatch):
    _failing_factories(monkeypatch, {"native": ValueError("a real bug")})
    with pytest.raises(ValueError, match="a real bug"):
        engine_select.choose(small_forest, 32, engines=CHEAP,
                             cache_path=None, repeats=1)


def test_no_candidate_can_allocate_raises(small_forest, monkeypatch):
    _failing_factories(monkeypatch, {e: _oom() for e in CHEAP})
    with pytest.raises(MemoryError, match="no candidate"):
        engine_select.choose(small_forest, 32, engines=CHEAP,
                             cache_path=None, repeats=1)
