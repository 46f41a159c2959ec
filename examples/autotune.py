"""Tune a kernel on the farm, persist the winner, read it back at dispatch.

The autotuner IS a farm application — the purest embarrassingly-parallel
workload there is: N independent (compile a candidate, time it, report a
number) tasks.  This example runs a successive-halving sweep over a
deterministic ``sim://`` cluster with the scripted cost model (so it
finishes in seconds and picks the same winner every run), persists the
winner to a JSON cache, and then calls the model-side dispatch, which
reads the cache with zero call-site changes.  A winner is keyed by the
platform it was timed on; the cost model's is keyed ``sim``, so dispatch
on a real device leaves it alone (a cache miss) — a sweep with
``cost_model=None`` on services that own the device is what steers it.

    PYTHONPATH=src python examples/autotune.py
"""

import json
import os
import tempfile

import jax
import jax.numpy as jnp

from repro.sim import SimCluster
from repro.tune import KernelTuner, TuningCache, configure, get_cache

SHAPE = {"B": 1, "Sq": 1024, "Skv": 1024, "H": 8, "K": 2, "D": 64, "Dv": 64}


def main():
    cache_path = os.path.join(tempfile.mkdtemp(prefix="jjpf-tune-"),
                              "tune_cache.json")

    # 1. sweep: a farm job over four virtual services of unequal speed
    with SimCluster(speed_factors=[1, 1, 2, 4], seed=7) as cluster:
        with cluster.make_scheduler(max_batch=4) as sched:
            tuner = KernelTuner(scheduler=sched,
                                cache=TuningCache(cache_path))
            r = tuner.tune("xla_flash", SHAPE, cost_model="scripted", seed=3)
        leases = len(cluster.trace)
    print(f"winner {r.config}  ({r.speedup:.2f}x over default "
          f"{r.default_config}; {r.candidates} candidates, {r.pruned} "
          f"pruned, rounds {r.rounds}, {leases} farm leases)")

    # 2. the cache is plain JSON on disk — inspectable, committable
    entry = json.load(open(cache_path))
    print(f"cache {cache_path}: {list(entry['entries'])}")

    # 3. dispatch reads the cache: a winner timed on this platform would
    #    apply with no call-site changes; the cost model's does not
    configure(cache_path)
    from repro.kernels import flash_attention_dispatch

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, 1024, 8, 64), jnp.float32)
    k = jax.random.normal(kk, (1, 1024, 2, 64), jnp.float32)
    v = jax.random.normal(kv, (1, 1024, 2, 64), jnp.float32)
    out = flash_attention_dispatch(q, k, v, causal=True)
    c = get_cache()
    print(f"dispatch on {jax.devices()[0].platform}: out {out.shape}, "
          f"cache hits={c.hits} misses={c.misses}")


if __name__ == "__main__":
    main()
