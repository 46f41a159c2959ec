"""One tuning task = compile + time one candidate config on a service.

:func:`measure_candidate` is the farm *program body* (a ``jit=False``
host-side :class:`~repro.core.skeletons.Program`): the payload is a plain
dict (wire-friendly) naming the kernel, shape, dtype, candidate config
and rep count; the result is a dict with the measured microseconds.

Two measurement modes:

* **real** — build seeded inputs (independent PRNG keys per tensor),
  jit-compile the kernel at the candidate tiling, warm up, then take the
  best-of-``reps`` wall time.  Used on ``inproc://``/``proc://`` farms
  where the worker owns real hardware.
* **scripted** (``payload["cost_model"] == "scripted"``) — a smooth
  analytic cost (work term + per-tile overhead + imbalance penalties)
  plus hash-seeded noise, a pure function of (kernel, shape, config,
  seed).  This is what makes tuning **deterministic under** ``sim://``:
  the number a candidate reports does not depend on which virtual
  service ran it, when, or how many times the lease bounced — so
  same-seed sweeps pick byte-identical winners, which the autotune
  benchmark gates.

A candidate that fails validation or crashes in compile/run returns
``{"ok": False, "us": inf}`` — the *task* fails, ranked last; the worker
lives on to time the next candidate.
"""

from __future__ import annotations

import hashlib
import math
import time

import numpy as np

from .space import KernelConfigError, validate_config

_INF = float("inf")
#: the platform a scripted-cost result is keyed to: a cost model times
#: no device, so its winners must never steer dispatch on a real one
SIM_PLATFORM = "sim"


# --------------------------------------------------------------------- #
# scripted cost model (sim:// determinism)
# --------------------------------------------------------------------- #
def _hash_noise(seed: int, kernel: str, config: dict, scale: float) -> float:
    """Deterministic multiplicative noise in [1-scale, 1+scale]."""
    blob = f"{seed}|{kernel}|{sorted(config.items())}".encode()
    h = int.from_bytes(hashlib.blake2b(blob, digest_size=8).digest(), "big")
    return 1.0 + scale * (2.0 * (h / 2**64) - 1.0)


def scripted_cost_us(kernel: str, shape: dict, config: dict,
                     seed: int = 0, noise: float = 0.03) -> float:
    """Analytic candidate cost in µs: total work spread over tiles, plus
    a fixed overhead per tile dispatch and a penalty for tiles far from
    the MXU-friendly 128 sweet spot.  Smooth with a unique interior
    optimum, so successive halving has a meaningful gradient to follow
    and same-seed runs converge on one winner."""
    def tile_pen(b: int) -> float:
        # quadratic-in-log distance from 128
        return 1.0 + 0.08 * (math.log2(b / 128.0)) ** 2

    if kernel in ("flash_fwd", "flash_bwd", "xla_flash"):
        sq = int(shape["Sq"]); skv = int(shape["Skv"])
        d = int(shape.get("D", 64)); h = int(shape.get("H", 8))
        b = int(shape.get("B", 1))
        if kernel == "xla_flash":
            bq, bk = config["q_chunk"], config["kv_chunk"]
        else:
            bq, bk = config["block_q"], config["block_k"]
        ntiles = (sq // bq) * (skv // bk)
        work = b * h * sq * skv * d * (3.0 if kernel == "flash_bwd" else 1.0)
        us = work * 1e-5 * tile_pen(bq) * tile_pen(bk) + ntiles * 2.0
    elif kernel == "decode":
        s = int(shape["S"]); d = int(shape.get("D", 64))
        h = int(shape.get("H", 8)); b = int(shape.get("B", 1))
        bk = config["block_k"]
        us = b * h * s * d * 1e-5 * tile_pen(bk) + (s // bk) * 2.0
    elif kernel == "mamba":
        s = int(shape["s"]); d = int(shape["d"]); n = int(shape["n"])
        b = int(shape.get("b", 1))
        c = config["chunk"]; bd = config.get("block_d", 256)
        us = (b * s * d * n * 2e-5 * tile_pen(bd)
              + (s // c) * 3.0 + c * 0.05)
    else:
        raise KernelConfigError(f"unknown kernel {kernel!r}")
    return us * _hash_noise(seed, kernel, config, noise)


# --------------------------------------------------------------------- #
# real measurement
# --------------------------------------------------------------------- #
def make_inputs(kernel: str, shape: dict, dtype: str, seed: int):
    """Seeded inputs with an independent stream per tensor (correlated
    q == k == v inflates attention scores and skews timings)."""
    rng = np.random.default_rng(seed)

    def draw(*dims):
        return rng.standard_normal(dims).astype(dtype)

    if kernel in ("flash_fwd", "flash_bwd", "xla_flash"):
        b, sq, skv = int(shape["B"]), int(shape["Sq"]), int(shape["Skv"])
        h, k = int(shape["H"]), int(shape["K"])
        d = int(shape["D"]); dv = int(shape.get("Dv", d))
        return (draw(b, sq, h, d), draw(b, skv, k, d), draw(b, skv, k, dv))
    if kernel == "decode":
        b, s = int(shape["B"]), int(shape["S"])
        h, k, d = int(shape["H"]), int(shape["K"]), int(shape["D"])
        q = draw(b, 1, h, d)
        return (q, draw(b, s, k, d), draw(b, s, k, d), s - 1)
    if kernel == "mamba":
        b, s, d, n = (int(shape["b"]), int(shape["s"]), int(shape["d"]),
                      int(shape["n"]))
        x = draw(b, s, d)
        dt = np.logaddexp(0.0, rng.standard_normal((b, s, d))).astype(dtype)
        a = -np.exp(rng.standard_normal((d, n)) * 0.5).astype(dtype)
        return (x, dt, a, draw(b, s, n), draw(b, s, n))
    raise KernelConfigError(f"unknown kernel {kernel!r}")


def build_fn(kernel: str, config: dict, *, interpret: bool = False):
    """The jitted callable for one candidate (imports deferred — workers
    only pay for the kernel family they measure)."""
    import jax

    if kernel == "xla_flash":
        from repro.kernels.flash_attention.xla import flash_attention_xla

        qc, kc = config["q_chunk"], config["kv_chunk"]
        return jax.jit(lambda q, k, v: flash_attention_xla(
            q, k, v, True, None, qc, kc))
    if kernel == "flash_fwd":
        from repro.kernels.flash_attention.flash_attention import \
            flash_attention_fwd

        bq, bk = config["block_q"], config["block_k"]
        return jax.jit(lambda q, k, v: flash_attention_fwd(
            q, k, v, causal=True, block_q=bq, block_k=bk,
            interpret=interpret))
    if kernel == "flash_bwd":
        from repro.kernels.flash_attention.ops import flash_attention

        bq, bk = config["block_q"], config["block_k"]

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=bq,
                                   block_k=bk, interpret=interpret).sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    if kernel == "decode":
        from repro.kernels.decode_attention.decode_attention import \
            decode_attention_fwd

        bk = config["block_k"]
        return jax.jit(lambda q, kc_, vc_, ci: decode_attention_fwd(
            q, kc_, vc_, cache_index=ci, block_k=bk, interpret=interpret))
    if kernel == "mamba":
        from repro.kernels.mamba_scan.ref import mamba_scan_ref

        c = config["chunk"]
        return jax.jit(lambda x, dt, a, b_, c_: mamba_scan_ref(
            x, dt, a, b_, c_, chunk=c)[0])
    raise KernelConfigError(f"unknown kernel {kernel!r}")


def _time_fn(fn, args, *, reps: int, warmup: int = 1) -> tuple[float, str]:
    """Best-of-``reps`` microseconds, and the platform of the device the
    timed call ran on."""
    import jax

    jax.block_until_ready(fn(*args))  # compile
    for _ in range(max(0, warmup - 1)):
        jax.block_until_ready(fn(*args))
    best = _INF
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    (device, *_) = jax.tree.leaves(out)[0].devices()
    return best * 1e6, device.platform


def measure_candidate(payload: dict) -> dict:
    """The farm task body.  Payload keys: ``kernel``, ``shape``,
    ``dtype``, ``config``, ``reps``, ``seed``, optional ``cost_model``
    ("scripted") and ``interpret``.  A timed result names the
    ``platform`` it was timed on (:data:`SIM_PLATFORM` for the cost
    model).  Never raises for a bad candidate — returns ``ok=False`` with
    infinite cost instead."""
    kernel = payload["kernel"]
    shape = payload["shape"]
    config = payload["config"]
    seed = int(payload.get("seed", 0))
    try:
        validate_config(kernel, shape, config)
        if payload.get("cost_model") == "scripted":
            us = scripted_cost_us(kernel, shape, config, seed=seed)
            platform = SIM_PLATFORM
        else:
            fn = build_fn(kernel, config,
                          interpret=bool(payload.get("interpret", False)))
            args = make_inputs(kernel, shape, payload.get("dtype", "float32"),
                               seed)
            us, platform = _time_fn(fn, args,
                                    reps=int(payload.get("reps", 3)))
        return {"ok": True, "us": float(us), "config": config,
                "platform": platform}
    except Exception as e:  # a bad candidate fails the TASK, not the worker
        return {"ok": False, "us": _INF, "config": config,
                "error": f"{type(e).__name__}: {e}"}
