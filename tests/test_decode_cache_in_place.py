"""Decode writes each new token into the carried cache stack in place.

The layer scan of a decode step carries the stacked caches and writes one
token's row a layer (or, for mamba, the layer's state), so the compiled
decode loop must neither copy a whole cache stack nor write a whole
``(B, S, ...)`` layer slice.  Checked on the compiled generate program's
HLO text for every cache type; the bf16 programs that the chip runs are
checked the same way in ``test_tpu_compile.py``, compiled for a v5e.

These compiles keep float32: XLA's CPU backend computes a bf16
dynamic-update-slice in float32 over the whole buffer, which copies the
stack whatever the program does.  It also fuses the shift of mamba's conv
window into the window's write and then copies the (small) window stack,
and it duplicates the row write of a leading dense layer's one-layer stack
(``lead``, at a fixed index, then read whole) into the stack's write and
the layer's read, with a copy of the stack between them.  The v5e compile
writes both in place, and is held to it.
"""

import math
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
from repro.models import build
from repro.runtime.serve_loop import ServeConfig, make_generate_program
from repro.utils.hlo import _parse_computations

B, PROMPT, NEW = 8, 448, 64  # a 512-position cache

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(\w+)\[([0-9,]*)\]"
                    r"(?:\{([0-9,]*))?\S*\s+([\w\-]+)\(([^)]*)\)")
_CALLEE = re.compile(r"(?:body|condition|calls|to_apply)=(%[\w.\-]+)")
_DECODE_WHILE = re.compile(r"\swhile\(.*\sbody=(%[\w.\-]+).*"
                           r"op_name=\"[^\"]*/decode/while\"")


class Instr(NamedTuple):
    name: str
    dtype: str
    dims: tuple
    layout: tuple  # minor to major; () where the text gives none
    op: str
    operands: list
    dims_of: dict  # dims of its computation's instructions, by name


def _ints(s):
    return tuple(int(d) for d in (s or "").split(",") if d)


def decode_loop_instructions(hlo_text: str) -> list[Instr]:
    """Every array-valued instruction that the decode scan's while loop
    runs, fused computations included."""
    _, comps = _parse_computations(hlo_text)
    bodies = [m.group(1) for lines in comps.values() for line in lines
              for m in [_DECODE_WHILE.search(line)] if m]
    assert len(bodies) == 1, bodies
    seen, todo, out = set(), bodies, []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        dims_of = {}
        for line in comps[name]:
            todo += _CALLEE.findall(line)
            m = _INSTR.match(line)
            if m:
                dims_of[m.group(1)] = _ints(m.group(3))
                out.append(Instr(m.group(1), m.group(2), _ints(m.group(3)),
                                 _ints(m.group(4)), m.group(5),
                                 [o.strip() for o in m.group(6).split(",")],
                                 dims_of))
    return out


def cache_traffic_faults(hlo_text: str, caches, *, copied=(),
                         seq_second_minor=False) -> list[str]:
    """Instructions of the decode loop that copy a whole cache stack (but
    those named in ``copied``) or write a whole layer slice of a cache with
    a sequence axis; with ``seq_second_minor``, also the stacks of such
    caches laid out with another axis than the sequence next to the minor
    one."""
    leaves = [(jax.tree_util.keystr(path), a.shape) for path, a in
              jax.tree_util.tree_leaves_with_path(caches)]
    stacks = {shape for key, shape in leaves
              if not any(k in key for k in copied)}
    seq_stacks = {shape for key, shape in leaves if "mamba" not in key}
    seq_slice = min((math.prod(shape[1:]) for shape in seq_stacks),
                    default=None)
    faults = []
    for i in decode_loop_instructions(hlo_text):
        what = f"{i.name} {i.dtype}{list(i.dims)}{{{i.layout}}}"
        if i.op == "copy" and i.dims in stacks:
            faults.append(f"copies a whole stack: {what}")
        if i.op == "dynamic-update-slice" and seq_slice is not None:
            upd = i.dims_of.get(i.operands[1], ())
            if math.prod(upd) >= seq_slice:
                faults.append(f"writes a whole layer slice {list(upd)}: {what}")
        if (seq_second_minor and i.dims in seq_stacks
                and i.layout[:2] != (len(i.dims) - 1, 2)):
            faults.append(f"sequence axis not second-minor: {what}")
    return faults


def generate_program(arch: str, dtype: str):
    """The served generate program of ``arch``, reduced to two pattern
    repeats (four layers for a one-block pattern), weights as shapes."""
    cfg = cfgs.reduced(cfgs.get(arch))
    cfg = cfg.replace(n_layers=max(4, cfg.n_layers), param_dtype=dtype,
                      compute_dtype=dtype)
    api = build(cfg)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    sc = ServeConfig(max_new_tokens=NEW, prompt_len=PROMPT, batch_per_task=B)
    caches = jax.eval_shape(lambda: api.make_caches(B, PROMPT + NEW))
    return make_generate_program(api, sc, params), params, caches


# one per cache type: GQA with qk-norm, MHA, MLA latent, pure SSM state,
# the 1:7 attention/mamba hybrid with MoE, plain GQA, a VLM backbone, MLA
# with a leading dense layer's cache outside the layer scan
ARCHS = ["qwen3_1p7b", "minicpm_2b", "minicpm3_4b", "falcon_mamba_7b",
         "jamba_1p5_large_398b", "llama3p2_1b", "phi3_vision_4p2b",
         "deepseek_v2_lite_ep4"]
# the stacks the CPU compile may copy (see the module docstring): mamba's
# conv windows, and in the one arch with a leading dense layer, its stack
COPIED = {"deepseek_v2_lite_ep4": ("lead",)}


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_loop_writes_cache_in_place(arch):
    program, params, caches = generate_program(arch, "float32")
    payload = {"tokens": jax.ShapeDtypeStruct((B, PROMPT), jnp.int32)}
    text = jax.jit(program.fn).lower(params, payload).compile().as_text()
    assert cache_traffic_faults(text, caches,
                                copied=COPIED.get(arch, ("conv",))) == []


@pytest.mark.parametrize("batched", ["operand", "update", "both", "start"])
def test_update_slice_under_vmap(batched):
    """``update_slice`` keeps ``lax.dynamic_update_slice``'s result under
    vmap, whichever arguments are batched, and stays a plain
    dynamic-update-slice where the start is shared."""
    from repro.models.attention import update_slice

    key = jax.random.PRNGKey(3)
    operand = jax.random.normal(key, (3, 4, 6, 5))
    update = jax.random.normal(jax.random.fold_in(key, 1), (3, 1, 2, 5))
    start = jnp.array([2, 1, 4])  # per member; the last one is clamped
    op_ax = 0 if batched in ("operand", "both", "start") else None
    up_ax = 0 if batched in ("update", "both", "start") else None
    st_ax = 0 if batched == "start" else None
    args = (operand if op_ax == 0 else operand[0],
            update if up_ax == 0 else update[0],
            start if st_ax == 0 else start[0])

    def ours(o, u, s):
        return update_slice(o, u, s, 1, 0)

    def lax_dus(o, u, s):
        return jax.lax.dynamic_update_slice(o, u, (s, 1, 0))

    axes = (op_ax, up_ax, st_ax)
    got = jax.vmap(ours, in_axes=axes)(*args)
    np.testing.assert_array_equal(got, jax.vmap(lax_dus, in_axes=axes)(*args))
    text = jax.jit(jax.vmap(ours, in_axes=axes)).lower(*args).as_text()
    assert ("scatter" in text) == (batched == "start")
