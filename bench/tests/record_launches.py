#!/usr/bin/env python3
"""Record the chip trace that ``test_spans.py`` reads.

    python3 bench/tests/record_launches.py    # on a TPU; writes data/launches/

Two in-process services on one chip serve a jitted ``generate`` (a
``prefill`` scope of chained matmuls, then a ``decode`` scan of them)
through a ``FarmExecutor`` with ``max_batch`` 4, so that two threads
launch batches on one device inside ``stack`` / ``launch`` / ``unstack``
spans.  Requests arrive in bursts with pauses between them: the chip is
queued in a burst and idle between.  Writes the profile and the farm's
``repro.obs`` events, and prints what the readers find in them.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

NAME = "launches"
OUT = os.path.join(HERE, "data", NAME)
EVENTS = os.path.join(OUT, "events.json")
READERS = ("prefill_ms.gen", "decode_step_ms.gen", "device_wait_p90_s.chat",
           "service_host_ms.chat", "idle_queued_share.chat")
PREFILL_MATMULS, DECODE_STEPS = 16, 32
BURSTS, PER_BURST, PAUSE_S = 6, 8, 0.03


def generate(w, payload):
    import jax
    import jax.numpy as jnp

    x = payload["x"].astype(jnp.bfloat16)
    with jax.named_scope("prefill"):
        for _ in range(PREFILL_MATMULS):
            x = jnp.tanh(x @ w)

    def step(x, _):
        x = jnp.tanh(x @ w)
        return x, x[0, 0]

    with jax.named_scope("decode"):
        x, firsts = jax.lax.scan(step, x, None, length=DECODE_STEPS)
    return {"generated": firsts}


def fixture_run(out_dir: str = OUT):
    """What the readers get to read of the recorded run (``RunView``'s
    fields that they use)."""
    from bench.lib import trace

    with open(os.path.join(out_dir, "events.json")) as f:
        saved = json.load(f)
    events = [_tuples(ev) for ev in saved["events"]]
    served = SimpleNamespace(events=events, t_open=saved["t_open"],
                             t_close=saved["t_close"])
    cell = SimpleNamespace(name=os.path.basename(out_dir),
                           traffic=SimpleNamespace(new_tokens=DECODE_STEPS))
    return SimpleNamespace(
        cell=cell, served=served, trace=trace.reduce(out_dir),
        trace_dir=out_dir,
        events_in_window=lambda: [ev for ev in events
                                  if served.t_open <= ev[0] <= served.t_close])


def _tuples(x):
    return tuple(_tuples(v) for v in x) if isinstance(x, list) else x


def read_all(run) -> dict:
    import importlib.util

    out = {}
    for name in READERS:
        path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod.read(run)
    return out


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import FarmExecutor, LookupService, Program, Service
    from repro.obs import Observability
    from bench.lib import spans

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("record_launches: needs a TPU", file=sys.stderr)
        return 2
    w = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048),
                          jnp.bfloat16) / 48
    program = Program(generate, name="generate", resident=w)
    lookup = LookupService()
    services = [Service(lookup, devices=[dev]) for _ in range(2)]
    rng = np.random.default_rng(0)
    payloads = [{"x": rng.standard_normal((128, 2048), np.float32)}
                for _ in range(BURSTS * PER_BURST)]
    for svc in services:
        for m in (1, 2, 4):
            jax.block_until_ready(svc.execute_batch(program, payloads[:m],
                                                    pad_to=m))
        svc.start()
    shutil.rmtree(OUT, ignore_errors=True)
    tmp = os.path.join(OUT, "tmp")
    obs = Observability()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with FarmExecutor(program, lookup=lookup, obs=obs, max_batch=4) as ex:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        t_open = time.monotonic()
        with jax.profiler.TraceAnnotation("window"):
            futs = []
            for b in range(BURSTS):
                futs += [ex.submit(p) for p in
                         payloads[b * PER_BURST:(b + 1) * PER_BURST]]
                time.sleep(PAUSE_S)
            for f in futs:
                f.result(timeout=60)
            t_close = time.monotonic()
        jax.profiler.stop_trace()
    for svc in services:
        svc.kill()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.move(path, os.path.join(OUT, f"{NAME}.xplane.pb"))
    shutil.rmtree(tmp)
    with open(EVENTS, "w") as f:
        json.dump({"t_open": t_open, "t_close": t_close,
                   "events": obs.events()}, f)
    run = fixture_run()
    prof = spans.profile_at(OUT)
    in_window = run.events_in_window()
    print(json.dumps({
        "kind": dev.device_kind,
        "bytes": os.path.getsize(os.path.join(OUT, f"{NAME}.xplane.pb")),
        "launches_in_window": sum(ev[1] == "launch" for ev in in_window),
        "spans_on_host_plane": len(prof.spans),
        "matched_by": prof.matched_by, "matched": len(prof.launched),
        "offset_s": prof.offset_s,
        "max_residual_s": max(map(abs, prof.residuals_s)),
        "whole_executions_s": run.trace.module_times("generate"),
        "readers": read_all(run)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
