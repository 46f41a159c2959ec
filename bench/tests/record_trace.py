#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py    # on a TPU; writes data/

Five calls of a jitted ``generate`` (a 2048 x 2048 bf16 matmul chain),
each inside a host span ``submit``, with a 50 ms ``wait`` span after
each, all inside the harness's ``window`` span.  Prints what the test
expects: the window's host length and the calls' host times.
"""

import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "small.xplane.pb")
CALLS, PAUSE_S = 5, 0.05


@jax.jit
def generate(x):
    for _ in range(8):
        x = jnp.tanh(x @ x)
    return x


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048), jnp.bfloat16)
    jax.block_until_ready(generate(x))
    tmp = os.path.join(HERE, "data", "tmp_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(CALLS):
            with jax.profiler.TraceAnnotation("submit"):
                jax.block_until_ready(generate(x))
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(PAUSE_S)
    window_s = time.monotonic() - t0
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, OUT)
    shutil.rmtree(tmp)
    print(json.dumps({"window_host_s": window_s, "bytes": os.path.getsize(OUT),
                      "kind": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
