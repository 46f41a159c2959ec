"""Quickstart: the paper's two-line API on a local 'cluster' — and the
three front-ends of the one dispatch engine behind it.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --transport=proc
    PYTHONPATH=src python examples/quickstart.py --transport=shm
    PYTHONPATH=src python examples/quickstart.py --transport=tcp

``--transport=inproc`` (default) stands the cluster up as objects in this
process; ``--transport=proc`` spawns one OS worker process per service
(the NoW deployment) — same client code, same two lines, the endpoint
addresses in the lookup are just ``proc://`` instead of ``inproc://``.
``--transport=shm`` is proc with payloads over a shared-memory ring (the
same-host fast path); ``--transport=tcp`` runs discovery itself over the
network (a LookupServer + self-registering workers — point other hosts'
workers at its address to grow the farm).

Every idiom below (blocking ``BasicClient``, futures ``FarmExecutor``,
shared multi-tenant ``FarmScheduler``) is an adapter over the same
``repro.farm`` scheduler core, so all of them run on either transport.

``--trace out.json`` attaches the telemetry spine (``repro.obs``) to
every farm below and exports one Chrome trace-event JSON at the end —
open it at https://ui.perfetto.dev (or chrome://tracing): one track per
service, task spans nested under leases, scheduler decisions as
instants.  A ``farm-top`` summary of the last engine prints too.
"""

import argparse

import jax.numpy as jnp

from repro.core import (BasicClient, Farm, FarmExecutor, LookupService, Pipe,
                        Program, Seq, Service)
from repro.farm import FarmScheduler

ap = argparse.ArgumentParser(description=__doc__)
ap.add_argument("--transport", choices=("inproc", "proc", "shm", "tcp"),
                default="inproc")
ap.add_argument("--trace", default=None, metavar="PATH",
                help="export a Chrome/Perfetto trace of every farm run "
                     "below to PATH")
args = ap.parse_args()

obs = None
if args.trace:
    from repro.obs import Observability

    obs = Observability()

# --- stand up a tiny cluster (normally: one Service per pod/workstation) --
pool = None
if args.transport in ("proc", "shm"):
    from repro.launch.now import NowPool

    lookup = LookupService()
    pool = NowPool(3, lookup, service_prefix="qs", transport=args.transport)
elif args.transport == "tcp":
    from repro.launch.tcp import TcpPool

    pool = TcpPool(3, service_prefix="qs")
    lookup = pool.lookup  # a RemoteLookup: discovery over the network
else:
    lookup = LookupService()
    for _ in range(3):
        Service(lookup).start()

# --- the paper's two lines ------------------------------------------------
program = Program(lambda x: x * x + 1, name="poly")
tasks = [jnp.asarray(float(i)) for i in range(16)]
output: list = []

cm = BasicClient(program, None, tasks, output, lookup=lookup, obs=obs)
cm.compute()

print("results :", [float(v) for v in output])
print("stats   :", cm.stats())

# --- skeleton composition: pipe(farm, seq) normalizes to one fused farm ---
skel = Pipe(Farm(Seq(Program(lambda x: x + 10, name="shift"))),
            Seq(Program(lambda x: x * 2, name="scale")))
out2: list = []
BasicClient(skel, None, tasks, out2, lookup=lookup, obs=obs).compute()
print("pipeline:", [float(v) for v in out2])

# --- the batched async hot path (beyond the paper) -------------------------
# max_batch    : lease up to N shape-compatible tasks per round-trip and run
#                them as ONE jax.vmap-compiled call
# max_inflight : batches kept un-materialized per service, so device compute
#                overlaps host scheduling
# adaptive_batching / target_batch_latency_s : per-service controller that
#                grows/shrinks the lease toward the latency target (slow
#                services get small leases -> sharp load balancing); on a
#                service where one task alone takes half the target or
#                more, it steers by 4x that observed one-task time instead
out3: list = []
cm3 = BasicClient(program, None, tasks, out3, lookup=lookup, obs=obs,
                  max_batch=8, max_inflight=2, adaptive_batching=True,
                  target_batch_latency_s=0.05)
cm3.compute()
print("batched :", [float(v) for v in out3])
print("batching:", cm3.stats()["batching"])

# --- front-end 2: futures (FarmExecutor over the same engine) --------------
# submit() returns a concurrent.futures.Future immediately; map() registers
# the whole batch under one repository lock acquisition
with FarmExecutor(program, lookup=lookup, max_batch=4, obs=obs) as ex:
    futs = ex.map(tasks)
    print("futures :", [float(f.result(timeout=120)) for f in futs])

# --- front-end 3: the shared multi-tenant scheduler ------------------------
# two weighted jobs time-share the same pool; the engine arbitrates by
# weighted fair share and revokes control threads to rebalance
with FarmScheduler(lookup, max_batch=4, obs=obs) as sched:
    heavy = sched.submit(program, tasks, weight=2.0)
    light = sched.submit(Program(lambda x: x + 1, name="inc"), tasks)
    heavy.wait(timeout=120)
    light.wait(timeout=120)
    print("tenants :", [float(v) for v in heavy.results_in_order()][:4], "...",
          [float(v) for v in light.results_in_order()][:4], "...")
    sched_stats = sched.stats()

if obs is not None:
    from repro.obs.export import farm_top

    obs.export_chrome_trace(args.trace)
    print(farm_top(sched_stats))
    print(f"trace   : wrote {args.trace} "
          f"({obs.stats()['events_recorded']} events) — open it at "
          f"https://ui.perfetto.dev")

if pool is not None:
    pool.shutdown()
