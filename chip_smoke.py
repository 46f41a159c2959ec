#!/usr/bin/env python3
"""Smoke run on the TPU: the farm's generation path and the Pallas kernels.

    python chip_smoke.py [--seed N]   # one chip
    python chip_smoke.py --chips 4    # four services, one per chip

One chip: full-width qwen3-1.7b (random weights from ``--seed``) served
through an in-process farm, two ``Service``s on one ``LookupService``,
32 requests of 128 prompt tokens and 32 new tokens, 8 per task; the
farm's tokens must equal a direct ``jax.jit`` call of the same generate
function on the same params and device.  Then each Pallas kernel at the
widths of the configuration that runs it (qwen3-1.7b attention at
S=2048 in bf16, a falcon-mamba-7b slice of the selective scan), compiled
for the chip (``tpu_custom_call`` in its text, never interpreted) and
compared with its ``ref.py`` oracle (f32) output by output.  Beside it
runs a lower-precision control, the same computation kept in bf16; the
tolerance must sit between the kernel's error and the control's, so a
kernel that lost precision the same way fails.

``--chips 4``: only the farm across chips — four services, one per
device, against a one-service run on device 0; every service must
execute tasks, and results must come back from every chip.

One line per phase; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Exits non-zero without that line if a phase fails or JAX finds no TPU.
One process, no children: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen3_1p7b"
PROMPT_LEN, NEW_TOKENS, BATCH_PER_TASK = 128, 32, 8
# qwen3-1.7b attention (configs/qwen3_1p7b.py) at S=2048
B, S, H, K, D = 1, 2048, 16, 8, 128
# falcon-mamba-7b: its state dim, a 1024-channel slice of d_inner=8192
MB, MS, MD, MN = 1, 2048, 1024, 16
# per output, max |kernel - ref| / max |ref|: flash (out), flash
# gradients (dq, dk, dv), decode (out), scan (y, h).  Each sits between
# the kernel's reading and the bf16 control's on one v5e at seed 0
# (PERF.md, PR 11); for attention that band is narrow, since rounding
# the output to bf16 dominates both.
TOL = {"flash_fwd": (4e-3,), "flash_fwd_bwd": (5e-3, 6.5e-3, 4.7e-3),
       "decode": (6e-3,), "mamba_scan": (1e-5, 1e-5)}


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


# ------------------------------- serving ------------------------------ #
def build_generate(seed: int, n_requests: int):
    """(program, params, prompts, ServeConfig) for full-width qwen3-1.7b."""
    import repro.configs as cfgs
    from repro.models import build
    from repro.runtime.serve_loop import ServeConfig, make_generate_program

    cfg = cfgs.get(ARCH)
    api = build(cfg)
    params = jax.jit(api.init)(jax.random.PRNGKey(seed))
    sc = ServeConfig(max_new_tokens=NEW_TOKENS, prompt_len=PROMPT_LEN,
                     batch_per_task=BATCH_PER_TASK)
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n_requests, PROMPT_LEN), dtype=np.int32)
    return api, make_generate_program(api, sc, params), params, prompts, sc


def start_farm(devices):
    from repro.core import LookupService, Service

    lookup = LookupService()
    services = [Service(lookup, devices=[d]) for d in devices]
    for s in services:
        s.start()
    return lookup, services


def timed_serve(program, prompts, sc, lookup):
    from repro.runtime.serve_loop import generated_tokens, serve_requests

    t0 = time.perf_counter()
    results, stats = serve_requests(program, prompts, sc, lookup=lookup,
                                    timeout=900.0)
    tokens = generated_tokens(results)
    return results, tokens, time.perf_counter() - t0, stats


def phase_serve(seed: int) -> None:
    dev = jax.devices()[0]
    api, program, params, prompts, sc = build_generate(seed, 32)
    cfg = api.cfg

    # the reference: the same generate function, jitted directly
    t0 = time.perf_counter()
    direct = jax.jit(program.fn).lower(
        params, {"tokens": prompts[:BATCH_PER_TASK]}).compile()
    compile_s = time.perf_counter() - t0
    want = np.concatenate([
        np.asarray(direct(params, {"tokens": prompts[i:i + BATCH_PER_TASK]})
                   ["generated"])
        for i in range(0, len(prompts), BATCH_PER_TASK)])
    logits, _ = jax.jit(api.prefill, static_argnames="seq_budget")(
        params, {"tokens": prompts[:BATCH_PER_TASK]},
        seq_budget=PROMPT_LEN + NEW_TOKENS)
    if not bool(jnp.isfinite(logits).all()):
        raise AssertionError("prefill logits are not finite")
    if want.shape != (len(prompts), NEW_TOKENS) or not (
            (want >= 0) & (want < cfg.vocab_size)).all():
        raise AssertionError(f"reference tokens malformed: {want.shape}")

    lookup, services = start_farm([dev, dev])
    _, first, first_s, _ = timed_serve(program, prompts, sc, lookup)
    _, got, warm_s, stats = timed_serve(program, prompts, sc, lookup)
    for name, toks in (("first", first), ("warm", got)):
        if not np.array_equal(toks, want):
            bad = int((toks != want).sum())
            raise AssertionError(f"farm tokens ({name} call) differ from "
                                 f"the direct jit call in {bad} places")
    report("serve", arch=cfg.name, n_layers=cfg.n_layers,
           d_model=cfg.d_model, requests=len(prompts),
           prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS,
           batch_per_task=BATCH_PER_TASK, services=len(services),
           tasks_per_service=[s.tasks_executed for s in services],
           speculative_issues=stats["speculative_issues"],
           tokens_match_direct_jit=True, compile_s=compile_s,
           first_call_s=first_s, warm_call_s=warm_s,
           tokens_per_s=got.size / warm_s, device_kind=dev.device_kind,
           peak_bytes_in_use=peak_bytes(dev))


def phase_farm_across_chips(seed: int) -> None:
    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, JAX sees "
                             f"{len(devices)}")
    api, program, params, prompts, sc = build_generate(seed, 64)
    lookup1, (alone,) = start_farm(devices[:1])
    _, want, one_s, _ = timed_serve(program, prompts, sc, lookup1)
    alone.kill()

    # each chip compiles its own executable; warm them all first, or the
    # first chip to finish compiling drains the queue
    t0 = time.perf_counter()
    for d in devices:
        jax.block_until_ready(program.prepare([d])(
            {"tokens": prompts[:BATCH_PER_TASK]}))
    warmup_s = time.perf_counter() - t0
    lookup4, services = start_farm(devices)
    results, got, first_s, _ = timed_serve(program, prompts, sc, lookup4)
    _, got2, warm_s, _ = timed_serve(program, prompts, sc, lookup4)
    if not (np.array_equal(got, want) and np.array_equal(got2, want)):
        raise AssertionError("tokens from four chips differ from the "
                             "one-service run on device 0")
    executed = [s.tasks_executed for s in services]
    if min(executed) == 0:
        raise AssertionError(f"a service executed no task: {executed}")
    homes = [r["generated"].devices() for r in results]
    if any(len(h) != 1 for h in homes):
        raise AssertionError(f"a result spans devices: {homes}")
    on = sorted(d.id for (d,) in homes)
    if set(on) != {d.id for d in devices}:
        raise AssertionError(f"results came back from devices {on}, not "
                             f"from every chip")
    report("farm_4_chips", arch=api.cfg.name, requests=len(prompts),
           services=len(services), tasks_per_service=executed,
           result_device_ids=on, tokens_match_one_chip=True,
           one_service_s=one_s, warmup_s=warmup_s, first_call_s=first_s,
           warm_call_s=warm_s,
           tokens_per_s=got2.size / warm_s,
           device_kind=devices[0].device_kind,
           peak_bytes_in_use=[peak_bytes(d) for d in devices])


# ------------------------------- kernels ------------------------------ #
def rel_errors(name: str, got, want) -> list[float]:
    """max |got - want| / max |want|, one figure per output leaf."""
    errs = []
    for o, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        o, w = np.asarray(o, np.float32), np.asarray(w, np.float32)
        if o.shape != w.shape or not np.isfinite(o).all():
            raise AssertionError(f"{name}: shape {o.shape} vs {w.shape} or "
                                 f"non-finite output")
        errs.append(float(np.abs(o - w).max() / np.abs(w).max()))
    return errs


def check_kernel(name: str, fn, ref, control, args) -> None:
    """Compile ``fn`` for the chip, require a Mosaic kernel in it, run it
    and compare each output with ``ref`` (f32, highest matmul precision).
    ``control`` computes the same in bf16: each output must be within its
    entry of ``TOL[name]`` and the control's outside it."""
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    if n_kernels == 0:
        raise AssertionError(f"{name}: no tpu_custom_call in the compiled "
                             f"program — the kernel was not lowered")
    out = jax.block_until_ready(compiled(*args))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(*args)
    errs = rel_errors(name, out, want)
    control_errs = rel_errors(f"{name} control", jax.jit(control)(*args),
                              want)
    tol = TOL[name]
    report(name, compile_s=compile_s, tpu_custom_calls=n_kernels,
           rel_err=errs, control_rel_err=control_errs, tol=tol,
           device_kind=dev.device_kind, peak_bytes_in_use=peak_bytes(dev))
    if any(e > t for e, t in zip(errs, tol, strict=True)):
        raise AssertionError(f"{name}: relative errors {errs} exceed {tol}")
    if any(e <= t for e, t in zip(control_errs, tol, strict=True)):
        raise AssertionError(f"{name}: the bf16 control's errors "
                             f"{control_errs} are not all above {tol}: the "
                             f"tolerance cannot tell it from the kernel")


def _normal(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def attention_bf16(q, k, v, visible, *, block_q=128, block_k=128):
    """The control: the flash recurrence with its running state (max,
    denominator, output accumulator) and scores kept in bf16 — what the
    kernel would compute with bf16 scratch.  Its gradient keeps dq, dk
    and dv in bf16 across blocks likewise.  ``visible(q_pos, k_pos)``
    marks the keys a query sees."""
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g, bq, bk = h // kh, min(block_q, sq), min(block_k, skv)

    def rnd(x):
        # round to bf16 here, even where the compiler would keep more
        # (XLA may carry fused bf16 intermediates in f32)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    bf16, lo = jnp.bfloat16, jnp.finfo(jnp.bfloat16).min
    qb = q.reshape(b, sq // bq, bq, kh, g, d).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(b, skv // bk, bk, kh, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, skv // bk, bk, kh, -1).transpose(1, 0, 3, 2, 4)

    def q_block(_, qi_i):
        qi, i = qi_i
        q_pos = i * bq + jnp.arange(bq)

        def k_block(state, kvj):
            m, l, acc = state
            kj, vj, j = kvj
            s = rnd(jnp.einsum("bkgqd,bksd->bkgqs", qi, kj,
                               preferred_element_type=bf16) * bf16(d ** -0.5))
            s = jnp.where(visible(q_pos[:, None], j * bk + jnp.arange(bk)),
                          s, lo)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p, alpha = rnd(jnp.exp(s - m_new)), rnd(jnp.exp(m - m_new))
            l = rnd(alpha * l + p.sum(-1, keepdims=True))
            acc = rnd(alpha * acc + jnp.einsum("bkgqs,bksv->bkgqv", p, vj,
                                               preferred_element_type=bf16))
            return (m_new, l, acc), None

        init = (jnp.full(qi.shape[:-1] + (1,), lo, bf16),
                jnp.zeros(qi.shape[:-1] + (1,), bf16),
                jnp.zeros(qi.shape[:-1] + (vb.shape[-1],), bf16))
        (_, l, acc), _ = jax.lax.scan(k_block, init,
                                      (kb, vb, jnp.arange(skv // bk)))
        return None, acc / l

    _, out = jax.lax.scan(q_block, None, (qb, jnp.arange(sq // bq)))
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, -1)


def _causal(q_pos, k_pos):
    return k_pos <= q_pos


def phase_flash_fwd(seed: int) -> None:
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_naive

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (_normal(ks[0], (B, S, H, D)), _normal(ks[1], (B, S, K, D)),
               _normal(ks[2], (B, S, K, D)))
    check_kernel("flash_fwd",
                 lambda q, k, v: flash_attention(q, k, v, causal=True),
                 lambda q, k, v: attention_naive(*_f32(q, k, v)),
                 lambda q, k, v: attention_bf16(q, k, v, _causal),
                 (q, k, v))


def phase_flash_fwd_bwd(seed: int) -> None:
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_naive

    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    q, k, v = (_normal(ks[0], (B, S, H, D)), _normal(ks[1], (B, S, K, D)),
               _normal(ks[2], (B, S, K, D)))
    co = _normal(ks[3], (B, S, H, D), jnp.float32)

    def grads(attn):
        def loss(q, k, v, co):
            return (attn(q, k, v).astype(jnp.float32) * co).sum()
        return jax.grad(loss, argnums=(0, 1, 2))

    kernel = grads(lambda q, k, v: flash_attention(q, k, v, causal=True))
    naive = grads(lambda q, k, v: attention_naive(q, k, v))
    control = grads(lambda q, k, v: attention_bf16(q, k, v, _causal))
    check_kernel("flash_fwd_bwd", kernel,
                 lambda q, k, v, co: naive(*_f32(q, k, v), co),
                 control, (q, k, v, co))


def phase_decode(seed: int) -> None:
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref

    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 3)
    q = _normal(ks[0], (BATCH_PER_TASK, 1, H, D))
    kc, vc = (_normal(ks[1], (BATCH_PER_TASK, S, K, D)),
              _normal(ks[2], (BATCH_PER_TASK, S, K, D)))
    idx = jnp.int32(S * 3 // 4)  # blocks past it are skipped
    check_kernel("decode",
                 lambda q, k, v, i: decode_attention(q, k, v, cache_index=i),
                 lambda q, k, v, i: decode_attention_ref(*_f32(q, k, v),
                                                         cache_index=i),
                 lambda q, k, v, i: attention_bf16(
                     q, k, v, lambda _, k_pos: k_pos <= i, block_k=512),
                 (q, kc, vc, idx))


def mamba_scan_bf16_state(x, dt, A, Bm, Cm):
    """The control: the sequential scan with its state kept in bf16."""
    x, dt, A, Bm, Cm = _f32(x, dt, A, Bm, Cm)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (h * jnp.exp(dtt[..., None] * A)
             + (dtt * xt)[..., None] * bt[:, None, :]).astype(jnp.bfloat16)
        return h, jnp.einsum("bdn,bn->bd", h.astype(jnp.float32), ct)

    h0 = jnp.zeros((x.shape[0], x.shape[2], A.shape[-1]), jnp.bfloat16)
    h, ys = jax.lax.scan(step, h0, tuple(t.transpose(1, 0, 2)
                                         for t in (x, dt, Bm, Cm)))
    return ys.transpose(1, 0, 2), h


def phase_mamba_scan(seed: int) -> None:
    from repro.kernels.mamba_scan.ops import mamba_scan
    from repro.kernels.mamba_scan.ref import mamba_scan_ref

    ks = jax.random.split(jax.random.PRNGKey(seed + 3), 5)
    x = _normal(ks[0], (MB, MS, MD))
    dt = jax.nn.softplus(_normal(ks[1], (MB, MS, MD), jnp.float32)
                         ).astype(jnp.bfloat16)
    A = -jnp.exp(0.5 * _normal(ks[2], (MD, MN), jnp.float32))
    Bm, Cm = _normal(ks[3], (MB, MS, MN)), _normal(ks[4], (MB, MS, MN))
    check_kernel("mamba_scan", mamba_scan, mamba_scan_ref,
                 mamba_scan_bf16_state, (x, dt, A, Bm, Cm))


# --------------------------------- main ------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX's devices are "
              f"{devices[0].platform} ({len(devices)}); this script runs "
              f"only on a TPU chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} TPU devices", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import configure_compile_cache
    from repro.tune.cache import set_cache

    configure_compile_cache()
    set_cache(None)  # hand-picked kernel configs, whatever the environment

    if args.chips == 4:
        phases = [phase_farm_across_chips]
    else:
        phases = [phase_serve, phase_flash_fwd, phase_flash_fwd_bwd,
                  phase_decode, phase_mamba_scan]
    failed = []
    for phase in phases:
        try:
            phase(args.seed)
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            failed.append(phase.__name__)
            print(f"chip_smoke: {phase.__name__} FAILED", file=sys.stderr,
                  flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
