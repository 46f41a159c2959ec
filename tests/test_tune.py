"""The autotuning farm: search spaces, cache, tuner, kernel fallback.

Covers the ``repro.tune`` contract ends-to-end:

- **static pruning invariant** — every candidate ``search_space``
  returns passes ``validate_config`` (fuzzed over kernels × shapes), so
  an invalid config can never reach a farm worker;
- **typed validation at the kernel entry points** — a well-formed block
  that doesn't tile the shape degrades to the largest valid divisor
  (and stays numerically exact against the reference); malformed blocks
  raise :class:`KernelConfigError` — never a bare ``AssertionError``;
- **cache** — round-trip through JSON, shape bucketing (one sweep at
  1024 covers 1000; head dims stay exact), merge-on-write under
  concurrent writers (no torn files, no lost keys), ``best_config``
  default fallback and memoized hit path;
- **tuner determinism** — two same-seed ``sim://`` sweeps with the
  scripted cost model pick byte-identical winners and emit identical
  ``tune-*`` event streams;
- **a bad candidate fails its task, not its worker** —
  ``measure_candidate`` returns ``ok=False`` instead of raising;
- **numerics parity** — dispatch through a tuned (non-default) config
  matches the naive reference.
"""

from __future__ import annotations

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import Observability
from repro.sim import SimCluster
from repro.tune import (DEFAULTS, KERNELS, KernelConfigError, KernelTuner,
                        TuningCache, best_config, cache_key,
                        measure_candidate, resolve_block, resolve_config,
                        scripted_cost_us, search_space, set_cache,
                        shape_bucket, validate_config)
from repro.tune.cache import device_platform

#: the platform this process computes on, which lookups serve
HERE = device_platform()

SHAPES = {
    "flash_fwd": {"B": 1, "Sq": 1024, "Skv": 1024, "H": 8, "K": 2, "D": 64,
                  "Dv": 64},
    "flash_bwd": {"B": 1, "Sq": 512, "Skv": 512, "H": 4, "K": 4, "D": 64,
                  "Dv": 64},
    "decode": {"B": 2, "S": 2048, "H": 8, "K": 2, "D": 64, "Dv": 64},
    "mamba": {"b": 2, "s": 1024, "d": 128, "n": 16},
    "xla_flash": {"B": 1, "Sq": 1024, "Skv": 1024, "H": 8, "K": 2, "D": 64,
                  "Dv": 64},
}


@pytest.fixture(autouse=True)
def _no_ambient_cache():
    """Tests control the active cache explicitly."""
    prev = set_cache(None)
    yield
    set_cache(prev)


# ---------------- search space / static pruning ---------------------- #

@pytest.mark.parametrize("kernel", KERNELS)
def test_search_space_never_emits_invalid(kernel):
    cands, pruned = search_space(kernel, SHAPES[kernel])
    assert cands, f"{kernel}: empty space"
    assert pruned >= 0
    for cand in cands:
        validate_config(kernel, SHAPES[kernel], cand)  # must not raise


@pytest.mark.parametrize("kernel", KERNELS)
def test_search_space_fuzzed_shapes(kernel):
    rng = np.random.default_rng(7)
    for _ in range(10):
        shape = dict(SHAPES[kernel])
        for name in shape:
            if name in ("Sq", "Skv", "S", "s"):
                shape[name] = int(rng.choice([128, 192, 384, 1024, 1536]))
            elif name in ("B", "b"):
                shape[name] = int(rng.integers(1, 5))
        cands, _ = search_space(kernel, shape)
        for cand in cands:
            validate_config(kernel, shape, cand)


def test_search_space_deterministic_order():
    a, _ = search_space("xla_flash", SHAPES["xla_flash"])
    b, _ = search_space("xla_flash", SHAPES["xla_flash"])
    assert a == b


def test_resolve_block_fallback_and_typed_errors():
    assert resolve_block("block_q", 128, 100) == 64
    assert resolve_block("block_q", 128, 128) == 128
    assert resolve_block("block_q", 128, 4096) == 128
    assert resolve_block("block_q", 48, 33) == 24  # largest divisor <= 33
    for bad in (0, -4, True, False, 64.0, "64", None):
        with pytest.raises(KernelConfigError):
            resolve_block("block_q", 128, bad)


def test_resolve_config_degrades_like_dispatch():
    # the shipped mamba default block_d=256 cannot tile d=64
    eff = resolve_config("mamba", {"b": 2, "s": 1024, "d": 64, "n": 16},
                         DEFAULTS["mamba"])
    assert eff == {"chunk": 256, "block_d": 64}
    validate_config("mamba", {"b": 2, "s": 1024, "d": 64, "n": 16}, eff)


# ---------------- cache ---------------------------------------------- #

def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "tune.json")
    shape = SHAPES["xla_flash"]
    c = TuningCache(path)
    key = c.put("xla_flash", shape, "float32", "xla",
                {"q_chunk": 128, "kv_chunk": 256}, 123.4, platform=HERE,
                meta={"speedup": 2.0})
    reloaded = TuningCache(path)
    rec = reloaded.lookup("xla_flash", shape, "float32", "xla")
    assert rec["config"] == {"q_chunk": 128, "kv_chunk": 256}
    assert rec["us"] == 123.4
    assert rec["meta"]["speedup"] == 2.0
    assert key in json.load(open(path))["entries"]


def _on_platform(monkeypatch, platform):
    """Make lookups behave as in a process computing on ``platform``."""
    monkeypatch.setattr("repro.tune.cache.device_platform",
                        lambda: platform)


def test_cache_entry_serves_only_its_platform(tmp_path, monkeypatch):
    # a winner timed on the CPU must not steer dispatch on the chip
    shape = SHAPES["xla_flash"]
    c = TuningCache(str(tmp_path / "tune.json"))
    c.put("xla_flash", shape, "float32", "xla", {"q_chunk": 64}, 1.0,
          platform="cpu")
    _on_platform(monkeypatch, "tpu")
    assert c.lookup("xla_flash", shape, "float32", "xla") is None
    assert cache_key("xla_flash", shape, "float32", "xla").endswith("|tpu")
    _on_platform(monkeypatch, "cpu")
    assert c.lookup("xla_flash", shape, "float32", "xla")["platform"] == "cpu"
    assert cache_key("xla_flash", shape, "float32", "xla").endswith("|cpu")


def test_shape_bucketing():
    # sequence/batch dims bucket to the next pow2; head dims stay exact
    assert shape_bucket({"Sq": 1000, "D": 64}) == "D=64,Sq=1024"
    assert (cache_key("xla_flash", {"B": 3, "Sq": 700, "D": 64}, "float32",
                      "xla")
            == cache_key("xla_flash", {"B": 4, "Sq": 1024, "D": 64},
                         "float32", "xla"))
    assert (cache_key("xla_flash", {"Sq": 1024, "D": 64}, "float32", "xla")
            != cache_key("xla_flash", {"Sq": 1024, "D": 128}, "float32",
                         "xla"))
    assert (cache_key("xla_flash", {"Sq": 1024, "D": 64}, "float32", "xla")
            != cache_key("xla_flash", {"Sq": 1025, "D": 64}, "float32",
                         "xla"))


def test_cache_bucketed_lookup_covers_nearby_shapes(tmp_path):
    c = TuningCache(str(tmp_path / "tune.json"))
    c.put("xla_flash", {"B": 1, "Sq": 1024, "D": 64}, "float32", "xla",
          {"q_chunk": 128}, 1.0, platform=HERE)
    # a sweep at 1024 serves a 1000-token prompt (same bucket)...
    assert c.lookup("xla_flash", {"B": 1, "Sq": 1000, "D": 64}, "float32",
                    "xla") is not None
    # ...but not a 2048-token one
    assert c.lookup("xla_flash", {"B": 1, "Sq": 2048, "D": 64}, "float32",
                    "xla") is None


def test_concurrent_cache_writes_lose_nothing(tmp_path):
    path = str(tmp_path / "tune.json")
    n = 16

    def writer(i):
        # D is exact in the key (not pow2-bucketed) — 16 distinct keys
        c = TuningCache(path)
        c.put("xla_flash", {"Sq": 1024, "D": 8 * (i + 1)}, "float32", "xla",
              {"q_chunk": 64}, float(i), platform=HERE)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    doc = json.load(open(path))  # valid JSON — no torn file
    merged = TuningCache(path)
    assert len(doc["entries"]) == len(merged) == n


def test_best_config_fallback_and_memo(tmp_path):
    shape = SHAPES["xla_flash"]
    default = DEFAULTS["xla_flash"]
    # no active cache: the default comes straight back
    assert best_config("xla_flash", shape, "float32", "xla",
                       default) == default
    c = TuningCache(str(tmp_path / "tune.json"))
    set_cache(c)
    # cache miss: default, memoized
    assert best_config("xla_flash", shape, "float32", "xla",
                       default) == default
    c.put("xla_flash", shape, "float32", "xla", {"q_chunk": 64}, 1.0,
          platform=HERE)
    # generation bump invalidates the memo; partial entries merge over
    # the default
    cfg = best_config("xla_flash", shape, "float32", "xla", default)
    assert cfg == {"q_chunk": 64, "kv_chunk": default["kv_chunk"]}
    before = c.hits
    for _ in range(5):
        best_config("xla_flash", shape, "float32", "xla", default)
    assert c.hits == before + 5  # memoized hit path still counts


# ---------------- measurement: tasks fail, workers don't -------------- #

def test_measure_candidate_invalid_config_fails_softly():
    res = measure_candidate({"kernel": "xla_flash",
                             "shape": SHAPES["xla_flash"],
                             "config": {"q_chunk": 333, "kv_chunk": 128},
                             "cost_model": "scripted"})
    assert res["ok"] is False
    assert res["us"] == float("inf")
    assert "KernelConfigError" in res["error"]


def test_measure_candidate_malformed_payload_fails_softly():
    res = measure_candidate({"kernel": "no-such-kernel", "shape": {},
                             "config": {}})
    assert res["ok"] is False


def test_scripted_cost_pure_function():
    shape = SHAPES["xla_flash"]
    cfg = {"q_chunk": 128, "kv_chunk": 256}
    a = scripted_cost_us("xla_flash", shape, cfg, seed=3)
    assert a == scripted_cost_us("xla_flash", shape, cfg, seed=3)
    assert a != scripted_cost_us("xla_flash", shape, cfg, seed=4)


# ---------------- kernel entry points: typed fallback ----------------- #

def test_flash_entry_divisor_fallback_matches_reference():
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_fwd)
    from repro.kernels.flash_attention.ref import attention_naive

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, 128, 4, 32), jnp.float32)
    k = jax.random.normal(kk, (1, 128, 2, 32), jnp.float32)
    v = jax.random.normal(kv, (1, 128, 2, 32), jnp.float32)
    ref = attention_naive(q, k, v, causal=True)
    # 100 does not tile 128 — degrades to 64 instead of asserting
    out = flash_attention_fwd(q, k, v, causal=True, block_q=100, block_k=100,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_entry_typed_errors():
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_fwd)

    q = jnp.zeros((1, 128, 4, 32))
    k = v = jnp.zeros((1, 128, 2, 32))
    with pytest.raises(KernelConfigError):
        flash_attention_fwd(q, k, v, block_q=-4, block_k=64, interpret=True)
    with pytest.raises(KernelConfigError):
        flash_attention_fwd(q, k, v, block_q=True, block_k=64, interpret=True)


def test_mamba_ref_nondividing_chunk_matches_naive():
    from repro.kernels.mamba_scan.ref import mamba_scan_naive, mamba_scan_ref

    kx, kdt, ka, kb, kc = jax.random.split(jax.random.PRNGKey(1), 5)
    b, s, d, n = 1, 96, 8, 4
    x = jax.random.normal(kx, (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(kdt, (b, s, d)))
    A = -jnp.exp(jax.random.normal(ka, (d, n)) * 0.5)
    B = jax.random.normal(kb, (b, s, n))
    C = jax.random.normal(kc, (b, s, n))
    y_ref, h_ref = mamba_scan_naive(x, dt, A, B, C)
    # 64 does not tile 96 — degrades to 48; previously this silently
    # truncated the sequence (s // chunk chunks) and DROPPED the tail
    y, h = mamba_scan_ref(x, dt, A, B, C, chunk=64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-4)


def test_decode_entry_divisor_fallback():
    from repro.kernels.decode_attention.decode_attention import (
        decode_attention_fwd)

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S, H, K, D = 1, 64, 4, 2, 32
    q = jax.random.normal(kq, (B, 1, H, D), jnp.float32)
    kc = jax.random.normal(kk, (B, S, K, D), jnp.float32)
    vc = jax.random.normal(kv, (B, S, K, D), jnp.float32)
    ref = decode_attention_fwd(q, kc, vc, cache_index=S - 1, block_k=32,
                               interpret=True)
    # 48 does not tile 64 — degrades to 32 instead of asserting
    out = decode_attention_fwd(q, kc, vc, cache_index=S - 1, block_k=48,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    with pytest.raises(KernelConfigError):
        decode_attention_fwd(q, kc, vc, cache_index=S - 1, block_k=0,
                             interpret=True)


# ---------------- tuned dispatch numerics parity ---------------------- #

def test_dispatch_through_tuned_config_matches_reference(tmp_path):
    from repro.kernels import flash_attention_dispatch, mamba_scan_dispatch
    from repro.kernels.flash_attention.ref import attention_naive
    from repro.kernels.mamba_scan.ref import mamba_scan_naive

    cache = TuningCache(str(tmp_path / "tune.json"))
    set_cache(cache)

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, H, K, D = 1, 256, 4, 2, 32
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, K, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, K, D), jnp.float32)
    shape = {"B": B, "Sq": S, "Skv": S, "H": H, "K": K, "D": D, "Dv": D}
    cache.put("xla_flash", shape, "float32", "xla",
              {"q_chunk": 64, "kv_chunk": 128}, 1.0, platform=HERE)
    out = flash_attention_dispatch(q, k, v, causal=True)
    ref = attention_naive(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    assert cache.hits >= 1

    kx, kdt, ka, kb2, kc2 = jax.random.split(jax.random.PRNGKey(4), 5)
    b, s, d, n = 1, 128, 8, 4
    x = jax.random.normal(kx, (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(kdt, (b, s, d)))
    A = -jnp.exp(jax.random.normal(ka, (d, n)) * 0.5)
    Bm = jax.random.normal(kb2, (b, s, n))
    C = jax.random.normal(kc2, (b, s, n))
    cache.put("mamba", {"b": b, "s": s, "d": d, "n": n}, "float32", "xla",
              {"chunk": 32, "block_d": 8}, 1.0, platform=HERE)
    y, h = mamba_scan_dispatch(x, dt, A, Bm, C)
    y_ref, h_ref = mamba_scan_naive(x, dt, A, Bm, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-4)


# ---------------- tuner on the sim:// farm ---------------------------- #

SIM_SHAPE = {"B": 1, "Sq": 1024, "Skv": 1024, "H": 8, "K": 2, "D": 64,
             "Dv": 64}


def _sim_sweep(seed=3):
    obs = Observability()
    with SimCluster(speed_factors=[1, 1, 2, 4], seed=7, obs=obs) as cluster:
        with cluster.make_scheduler(max_batch=4) as sched:
            tuner = KernelTuner(scheduler=sched, cache=TuningCache())
            r = tuner.tune("xla_flash", SIM_SHAPE, cost_model="scripted",
                           seed=seed)
    trace = [e for e in obs.events() if str(e[1]).startswith("tune-")]
    return r, trace


def test_sim_sweep_same_seed_identical_winner_and_trace():
    r1, t1 = _sim_sweep(seed=3)
    r2, t2 = _sim_sweep(seed=3)
    assert (json.dumps(r1.summary(), sort_keys=True)
            == json.dumps(r2.summary(), sort_keys=True))
    assert t1 == t2
    assert any(str(e[1]) == "tune-winner" for e in t1)
    # the scripted model makes the winner a pure function of the seed:
    # the global argmin survives every halving round, so it must win
    cands, _ = search_space("xla_flash", SIM_SHAPE)
    names = sorted(cands[0])
    expect = min(cands, key=lambda c: (
        scripted_cost_us("xla_flash", SIM_SHAPE, c, seed=3),
        tuple(c[n] for n in names)))
    assert r1.config == expect


def test_sim_sweep_caches_winner_and_dispatch_reads_it(tmp_path,
                                                       monkeypatch):
    path = str(tmp_path / "tune.json")
    with SimCluster(speed_factors=[1, 1], seed=5) as cluster:
        with cluster.make_scheduler(max_batch=4) as sched:
            tuner = KernelTuner(scheduler=sched, cache=TuningCache(path))
            r = tuner.tune("xla_flash", SIM_SHAPE, cost_model="scripted",
                           seed=3)
    assert r.speedup > 0 and r.failed == 0
    # fresh process-equivalent: reload from disk; dispatch in a process
    # on the platform the winner was timed on (the cost model's) reads it
    reloaded = TuningCache(path)
    set_cache(reloaded)
    _on_platform(monkeypatch, "sim")
    got = best_config("xla_flash", SIM_SHAPE, "float32", "xla",
                      DEFAULTS["xla_flash"])
    assert {k: got[k] for k in r.config} == r.config


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_sim_sweep_not_served_to_a_device_lookup(platform, monkeypatch):
    # the scripted cost model times no device: its winner steers no
    # dispatch on one, whatever platform the tuning process sat on
    cache = TuningCache()
    with SimCluster(speed_factors=[1, 1], seed=5) as cluster:
        with cluster.make_scheduler(max_batch=4) as sched:
            KernelTuner(scheduler=sched, cache=cache).tune(
                "xla_flash", SIM_SHAPE, cost_model="scripted", seed=3)
    assert [k.rsplit("|", 1)[1] for k in cache.entries()] == ["sim"]
    _on_platform(monkeypatch, platform)
    assert cache.lookup("xla_flash", SIM_SHAPE, "float32", "xla") is None


def test_measured_winner_is_keyed_to_the_device_that_timed_it():
    res = measure_candidate({"kernel": "xla_flash",
                             "shape": {"B": 1, "Sq": 128, "Skv": 128, "H": 2,
                                       "K": 1, "D": 16, "Dv": 16},
                             "config": {"q_chunk": 64, "kv_chunk": 64},
                             "reps": 1})
    assert res["ok"] and res["platform"] == jax.devices()[0].platform


def test_tuner_bad_candidates_fail_tasks_not_workers():
    """Inject an always-invalid candidate list: the sweep completes and
    reports the failures instead of losing workers."""
    with SimCluster(speed_factors=[1, 1], seed=5) as cluster:
        with cluster.make_scheduler(max_batch=4) as sched:
            tuner = KernelTuner(scheduler=sched, cache=TuningCache())
            timed, platforms = tuner._measure_round(
                "xla_flash", SIM_SHAPE, "float32",
                [{"q_chunk": 333, "kv_chunk": 128},   # invalid
                 {"q_chunk": 128, "kv_chunk": 128}],  # valid
                1, 0, "scripted", False, 0)
    assert timed[0][0] == float("inf")
    assert np.isfinite(timed[1][0])
    assert platforms == {"sim"}
