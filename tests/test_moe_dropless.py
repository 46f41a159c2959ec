"""The dropless expert layer over one chip's share of the experts
(``MoEConfig.dropless``), on seeded random weights at a small size: the
shares add up to the uncut layer, the layer matches a per-token loop
whatever the routing, and the gates are kept or renormalised as the
config says."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.common import ModelConfig, MoEConfig
from repro.models.layers import apply_dense_mlp
from repro.models.moe import apply_moe, init_moe

E, K, FF, SHARED = 8, 3, 16, 24


def _cfg(**moe):
    kw = dict(n_experts=E, top_k=K, d_ff_expert=FF, dense_residual=True,
              dense_residual_ff=SHARED, renormalize=False, dropless=True)
    kw.update(moe)
    return ModelConfig(name="tiny-dropless", family="moe", n_layers=1,
                       d_model=32, n_heads=4, n_kv_heads=4, d_ff=48,
                       vocab_size=64, moe=MoEConfig(**kw),
                       param_dtype="float32", compute_dtype="float32")


def _params(cfg, seed=0):
    return init_moe(jax.random.PRNGKey(seed), cfg)


def _share(p, offset, held):
    return dict(p, experts=jax.tree_util.tree_map(
        lambda a: a[offset:offset + held], p["experts"]))


def _x(seed=1, shape=(2, 5, 32)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _per_token(p, x, cfg):
    """Each token on its own: softmax over every expert, its top-k, each
    held expert's SwiGLU times its gate, plus the shared SwiGLU."""
    m = cfg.moe
    xs = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    ex = {k: np.asarray(v, np.float64) for k, v in p["experts"].items()}
    sh = {k: np.asarray(v, np.float64) for k, v in p["residual"].items()}
    router = np.asarray(p["router"], np.float64)

    def swiglu(h, wg, wi, wo):
        g = h @ wg
        return (g / (1 + np.exp(-g)) * (h @ wi)) @ wo

    out = []
    for h in xs:
        z = h @ router
        probs = np.exp(z - z.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:m.top_k]
        gates = probs[top]
        if m.renormalize:
            gates = gates / gates.sum()
        y = swiglu(h, sh["wg"], sh["wi"], sh["wo"])
        for e, g in zip(top, gates):
            local = e - m.held_offset
            if 0 <= local < m.held:
                y = y + g * swiglu(h, ex["wg"][local], ex["wi"][local],
                                   ex["wo"][local])
        out.append(y)
    return np.stack(out).reshape(x.shape)


def test_four_shares_add_up_to_the_uncut_layer():
    """Every chip computes its held experts' part and the shared experts
    alike: the four shares' outputs, less three copies of the shared
    branch, are the layer that holds all experts."""
    full_cfg = _cfg()
    p = _params(full_cfg)
    x = _x()
    whole, _ = apply_moe(p, x, full_cfg)
    held = E // 4
    parts = []
    for offset in range(0, E, held):
        cfg = _cfg(n_held=held, held_offset=offset)
        out, _ = apply_moe(_share(p, offset, held), x, cfg)
        parts.append(out)
    shared = apply_dense_mlp(p["residual"], x, full_cfg.replace(d_ff=SHARED))
    np.testing.assert_allclose(sum(parts) - 3 * shared, whole, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("skew", [False, True], ids=["random", "one_expert"])
@pytest.mark.parametrize("offset", [0, 2])
def test_dropless_matches_a_per_token_loop(skew, offset):
    """With every token's first choice on one held expert (where a
    capacity-factor layer would drop most of them) as with random
    routing, each token gets exactly its own experts' sum."""
    cfg = _cfg(n_held=4, held_offset=offset)
    p = _params(cfg)
    x = _x(shape=(3, 16, 32))
    if skew:  # positive inputs and one dominant router column
        x = jnp.abs(x) + 0.5
        p = dict(p, router=p["router"].at[:, offset + 1].set(3.0))
    out, _ = apply_moe(p, x, cfg)
    np.testing.assert_allclose(out, _per_token(p, x, cfg), rtol=1e-4,
                               atol=1e-5)
    if skew:
        top = jax.lax.top_k(jax.nn.softmax(
            x.reshape(-1, 32) @ p["router"]), K)[1]
        assert bool(jnp.all(top[:, 0] == offset + 1))


@pytest.mark.parametrize("renormalize", [False, True])
def test_gates_are_renormalised_only_when_the_config_says(renormalize):
    cfg = _cfg(n_held=E, renormalize=renormalize)
    p = _params(cfg)
    x = _x(seed=3)
    out, _ = apply_moe(p, x, cfg)
    np.testing.assert_allclose(out, _per_token(p, x, cfg), rtol=1e-4,
                               atol=1e-5)
    other = dataclasses.replace(cfg.moe, renormalize=not renormalize)
    flipped, _ = apply_moe(p, x, dataclasses.replace(cfg, moe=other))
    assert not np.allclose(out, flipped, rtol=1e-3, atol=1e-4)


def test_only_the_dropless_layer_holds_a_share():
    with pytest.raises(ValueError, match="dropless"):
        MoEConfig(n_experts=E, top_k=K, n_held=2)


def test_dropless_layer_trains():
    """The grouped matmul has gradients: one step's loss falls."""
    cfg = _cfg(n_held=4, held_offset=4)
    p = _params(cfg)
    x = _x(seed=5)
    target = _x(seed=6)

    def loss(p):
        out, aux = apply_moe(p, x, cfg)
        return jnp.mean((out - target) ** 2) + aux

    l0, g = jax.value_and_grad(loss)(p)
    assert float(jnp.abs(g["experts"]["wi"]).sum()) > 0
    p1 = jax.tree_util.tree_map(lambda a, b: a - 0.05 * b, p, g)
    assert float(loss(p1)) < float(l0)
