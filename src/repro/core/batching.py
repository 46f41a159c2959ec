"""Batched dispatch support: payload signatures, stacking, and the
adaptive per-service batch-size controller.

JJPF dispatches one task per round-trip per service (paper Algorithms 1-2).
That is the right granularity for Jini-era workstations, but on a JAX
runtime the per-dispatch overhead (host scheduling, device handoff,
result materialization) dwarfs the kernel time of a single task.  The
batched engine leases *compatible* tasks — same payload shape/dtype tree —
in groups, stacks them along a new leading axis, and runs ONE
``jax.jit(jax.vmap(fn))`` call per group.

The controller is deliberately simple: AIMD-style hill climbing toward a
per-batch latency target.  Slow services (large ``speed_factor``) converge
to small batches, fast services to large ones, which keeps the pull
scheduler's load balancing sharp — a slow node never hoards a huge lease.
A service on which one task alone already takes half the target or more
(a model's generate program, say) can never land in the band, so its
controller steers by four times the one-task time it observed instead:
such a service batches what is queued while a batch costs little more
than one task, and holds at one or two tasks where cost grows with batch
size.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------- #
# payload compatibility
# --------------------------------------------------------------------- #
def payload_signature(payload: Any) -> tuple:
    """Hashable (treedef, leaf shape/dtype) fingerprint of a payload.

    Two payloads with equal signatures can be stacked into one batch and
    share a compiled executable; this is also the shape component of the
    service compile-cache key."""
    leaves, treedef = jax.tree.flatten(payload)
    leaf_sigs = tuple(
        (tuple(getattr(leaf, "shape", ())),
         str(getattr(leaf, "dtype", type(leaf).__name__)))
        for leaf in leaves)
    return (treedef, leaf_sigs)


def stack_payloads(payloads: Sequence[Any]) -> Any:
    """Stack same-signature payload pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)


def bucket_size(n: int, max_batch: int) -> int:
    """Round a lease size up to the next power-of-two bucket (capped at
    ``max_batch``).  Padding tail batches to a bucket bounds the number of
    distinct batch shapes — and therefore XLA compiles — at
    ``log2(max_batch) + 2`` instead of one per ragged tail size."""
    b = 1
    while b < n:
        b *= 2
    return n if b > max_batch else b


def pow2_floor(x: float) -> int:
    """Largest power of two ≤ x (and ≥ 1) — batch sizes live on the
    power-of-two lattice so the compile-cache bucketing stays bounded."""
    b = 1
    while b * 2 <= x:
        b *= 2
    return b


def pad_stacked(stacked: Any, n: int, m: int) -> Any:
    """Pad a stacked batch of ``n`` tasks up to ``m`` rows by repeating the
    last row (pure per-row programs never see their neighbours, so the
    padding rows are computed and discarded)."""
    if m <= n:
        return stacked
    return jax.tree.map(
        lambda a: jnp.concatenate([a, jnp.repeat(a[-1:], m - n, axis=0)]),
        stacked)


def unstack_results(result: Any, n: int) -> list:
    """Split a batched result pytree back into per-task results.

    Slicing is itself an async JAX op, so this does not force the batch to
    materialize — callers can keep the batch in flight and
    ``jax.block_until_ready`` later."""
    return [jax.tree.map(lambda a: a[i], result) for i in range(n)]


# --------------------------------------------------------------------- #
# adaptive batch sizing
# --------------------------------------------------------------------- #
class AdaptiveBatchController:
    """Per-service batch-size hill climber, weighted by observed throughput.

    Doubles the batch while a batch completes in under half the latency
    target, halves it when a batch overruns the target, holds inside the
    [target/2, target] band.  Because the band spans exactly a factor of
    two, a monotone latency(batch) curve cannot oscillate: if latency(b)
    < target/2 then latency(2b) <= 2*latency(b) < target for any
    sub-linear-overhead service, so growth lands in (or below) the band.

    Heterogeneity-aware extensions:

    - **Throughput-weighted growth.**  The controller keeps a tasks/second
      EWMA; on a growth step it jumps straight to the power-of-two floor
      of ``throughput_ewma × target_latency_s`` (never below the plain
      doubling), so a fast service reaches its steady-state batch in O(1)
      growth steps instead of O(log max_batch) — which matters on short
      streams, where the slow climb is pure lost efficiency.  The jump
      only fires on under-half-target batches, where ideal ≥ 2×current,
      so the band-hold stability argument above is untouched.
    - **Speed-factor capping.**  ``max_batch`` here is per service: the
      control thread derives it from the descriptor's advertised
      ``speed_factor`` (``max_batch / speed_factor``, power-of-two floor),
      so a node known to be k× slower can never hoard a full-size lease
      near the end of a stream.

    Second regime: the target taken from the observed one-task time.
    ``t1_s`` is the least elapsed time of any one-task batch (a lone task
    runs unpadded whatever the lease size, so it measures one task's cost
    exactly).  Once two one-task batches are recorded (the first may hold
    its compile) and ``t1_s`` is at least half of ``target_latency_s``, no
    batch can land in the band and halving is a no-op, so the band is set
    by ``4 × t1_s`` instead: the batch grows while a full batch costs
    under ``2 × t1_s`` and halves when one costs over ``4 × t1_s``.  A
    service whose cost is flat in batch size climbs to its ceiling; one
    whose cost is linear in batch size holds at one or two tasks.  The
    band's upper edge leaves room for one batch of a neighbour service on
    the same device ahead of this one, since the elapsed time includes
    that wait.  The throughput jump still aims at ``target_latency_s``;
    with no batch faster than ``t1_s``, at least half of it, the jump
    never goes past plain doubling.  A service whose one task fits under half the
    target never leaves the first regime (``t1_s`` only falls), so
    ``target_latency_s`` keeps its meaning there.
    """

    def __init__(self, *, min_batch: int = 1, max_batch: int = 64,
                 initial: int | None = None,
                 target_latency_s: float = 0.05):
        if min_batch < 1 or max_batch < min_batch:
            raise ValueError(f"bad batch bounds [{min_batch}, {max_batch}]")
        self.min_batch = min_batch
        self.max_batch = max_batch
        self.target_latency_s = target_latency_s
        self.batch = min(max(initial or min_batch, min_batch), max_batch)
        self.last_latency_s: float | None = None
        self.throughput_ewma: float | None = None  # tasks / second
        self.batches_recorded = 0
        self.t1_s: float | None = None  # least time of a one-task batch
        self.one_task_batches = 0

    def next_batch(self) -> int:
        return self.batch

    def record(self, n_tasks: int, elapsed_s: float) -> None:
        """Feed back one completed batch (size actually leased, wall time
        from dispatch to materialized results)."""
        if n_tasks <= 0:
            return
        self.batches_recorded += 1
        self.last_latency_s = elapsed_s
        tput = n_tasks / max(elapsed_s, 1e-9)
        self.throughput_ewma = (tput if self.throughput_ewma is None
                                else 0.7 * self.throughput_ewma + 0.3 * tput)
        if n_tasks == 1:
            self.one_task_batches += 1
            self.t1_s = (elapsed_s if self.t1_s is None
                         else min(self.t1_s, elapsed_s))
        # only steer from full-size batches; a tail batch of 2 tasks says
        # nothing about how a full lease would behave
        if n_tasks < self.batch:
            return
        target = self.steer_target_s
        if elapsed_s < 0.5 * target:
            grown = self.batch * 2
            suggestion = self._throughput_suggestion()
            if suggestion is not None:
                grown = max(grown, suggestion)
            self.batch = min(grown, self.max_batch)
        elif elapsed_s > target:
            self.batch = max(self.batch // 2, self.min_batch)

    @property
    def steer_target_s(self) -> float:
        """The latency the band is set by: ``target_latency_s``, or four
        times ``t1_s`` once one task alone reaches half of it."""
        if (self.one_task_batches >= 2
                and self.t1_s >= 0.5 * self.target_latency_s):
            return 4.0 * self.t1_s
        return self.target_latency_s

    def _throughput_suggestion(self) -> int | None:
        """Batch size the observed throughput says would land exactly on
        the latency target (power-of-two floor); None until the EWMA has
        seen enough batches to trust."""
        if self.throughput_ewma is None or self.batches_recorded < 3:
            return None
        ideal = self.throughput_ewma * self.target_latency_s
        if ideal < 1.0:
            return None
        return max(self.min_batch, min(pow2_floor(ideal), self.max_batch))

    def stats(self) -> dict:
        return {
            "batch": self.batch,
            "max_batch": self.max_batch,
            "last_latency_s": self.last_latency_s,
            "throughput_ewma": self.throughput_ewma,
            "batches_recorded": self.batches_recorded,
            "t1_s": self.t1_s,
            "steer_target_s": self.steer_target_s,
        }


def speed_capped_max_batch(max_batch: int, speed_factor: float) -> int:
    """Per-service lease ceiling from the descriptor's advertised speed
    factor: a service k× slower than baseline is capped at the power-of-
    two floor of ``max_batch / k``, so pull scheduling stays sharp on
    heterogeneous clusters (the paper's NoW case) — a slow node holding a
    full-size lease at end-of-stream is the one way a pull farm goes
    idle.  ``speed_factor ≤ 1`` (baseline or faster) keeps the full
    ceiling."""
    if speed_factor <= 1.0 or max_batch <= 1:
        return max_batch
    return max(1, min(max_batch, pow2_floor(max_batch / speed_factor)))
