"""Fine-grained Mixture-of-Experts (DeepSeekMoE family).

Shared experts (always-on) + routed experts with top-k gating. Two dispatch
implementations, selectable via ``MoEConfig.dispatch``:

* ``"einsum"`` — GShard-style capacity-factor dispatch with one-hot
  (group, token, expert, slot) combine tensors; the faithful TPU-era baseline.
* ``"scatter"`` — slot-index scatter/gather dispatch, which avoids the
  one-hot einsum FLOPs (beyond-paper optimization; see EXPERIMENTS.md §Perf).

Expert weights carry a leading E axis sharded on the ``model`` mesh axis, so
expert-parallel all-to-alls emerge from the SPMD partitioner.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding.act import constrain, out_sharding


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    D, E, F = cfg.d_model, m.num_experts, m.d_expert
    ks = jax.random.split(key, 4)
    p = {
        "router": L.dense_init(ks[0], (D, E), (0,), jnp.float32),
        "wi": L.dense_init(ks[1], (E, D, 2 * F), (1,), dtype),
        "wo": L.dense_init(ks[2], (E, F, D), (1,), dtype),
    }
    if m.num_shared:
        p["shared"] = L.init_mlp(ks[3], D, m.num_shared * F, "swiglu", dtype)
    return p


def _route(m, xg, router):
    """Top-k routing. xg: (G, S, D) -> gate weights and indices (G, S, k)."""
    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, m.top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return probs, topv, topi


def _aux_loss(m, probs, topi):
    """Switch-style load-balancing loss (per group, then averaged)."""
    E = m.num_experts
    me = probs.mean(axis=(0, 1))                              # (E,)
    disp = jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32)
    ce = disp.mean(axis=(0, 1))
    return E * jnp.sum(me * ce)


def _positions(m, topi, S, no_drop=False):
    """GShard slot assignment: choice j gets slots after choices < j.
    Returns (pos (G,S,k) slot-in-expert, keep (G,S,k) bool)."""
    E = m.num_experts
    C = _capacity(m, S, no_drop)
    pos_list, keep_list = [], []
    counts = 0
    for j in range(m.top_k):
        mj = jax.nn.one_hot(topi[..., j], E, dtype=jnp.int32)   # (G,S,E)
        cum = jnp.cumsum(mj, axis=1) - mj + counts
        pj = jnp.sum(cum * mj, axis=-1)                          # (G,S)
        keep_list.append(pj < C)
        pos_list.append(pj)
        counts = counts + jnp.sum(mj, axis=1, keepdims=True)     # (G,1,E)
    return jnp.stack(pos_list, -1), jnp.stack(keep_list, -1)


def _capacity(m, S: int, no_drop: bool = False) -> int:
    if no_drop:
        return S        # worst case: every token routes to the same expert
    return max(1, int(S * m.top_k / m.num_experts * m.capacity_factor))


def _experts(p, xe):
    """xe: (G, E, C, D) -> (G, E, C, D) through per-expert SwiGLU."""
    h = jnp.einsum("gecd,edf->gecf", xe, p["wi"])
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    return jnp.einsum("gecf,efd->gecd", h, p["wo"])


def _dispatch_einsum(p, m, xg, topv, topi, no_drop=False):
    G, S, D = xg.shape
    E, C = m.num_experts, _capacity(m, S, no_drop)
    pos, keep = _positions(m, topi, S, no_drop)
    y = jnp.zeros_like(xg)
    dispatch = jnp.zeros((G, S, E, C), xg.dtype)
    combine = jnp.zeros((G, S, E, C), jnp.float32)
    for j in range(m.top_k):
        oh = (jax.nn.one_hot(topi[..., j], E, dtype=xg.dtype)[..., None]
              * jax.nn.one_hot(pos[..., j], C, dtype=xg.dtype)[..., None, :])
        oh = oh * keep[..., j, None, None].astype(xg.dtype)
        dispatch = dispatch + oh
        combine = combine + oh.astype(jnp.float32) * topv[..., j, None, None]
    # s is contracted and may be sharded on both sides: name the output's
    # sharding, which jax otherwise refuses to guess
    xe = jnp.einsum("gsec,gsd->gecd", dispatch, xg,
                    out_sharding=out_sharding((G, E, C, D), "batch",
                                              "model", None, None))
    xe = constrain(xe, "batch", "model", None, None)
    ye = _experts(p, xe)
    ye = constrain(ye, "batch", "model", None, None)
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(xg.dtype), ye)
    return constrain(y, "batch", None, None)


def _dispatch_scatter(p, m, xg, topv, topi, no_drop=False):
    G, S, D = xg.shape
    E, C = m.num_experts, _capacity(m, S, no_drop)
    pos, keep = _positions(m, topi, S, no_drop)
    slot = topi * C + jnp.minimum(pos, C - 1)                 # (G,S,k)
    w = topv * keep.astype(jnp.float32)

    def one_group(xs, slots, keeps):
        buf = jnp.zeros((E * C, D), xs.dtype)
        for j in range(m.top_k):
            buf = buf.at[slots[:, j]].add(
                xs * keeps[:, j, None].astype(xs.dtype), mode="drop")
        return buf

    xe = jax.vmap(one_group)(xg, slot, keep)                  # (G, E*C, D)
    ye = _experts(p, xe.reshape(G, E, C, D)).reshape(G, E * C, D)

    def gather_group(ys, slots, ws):
        out = 0.0
        for j in range(m.top_k):
            out = out + ys[slots[:, j]] * ws[:, j, None].astype(ys.dtype)
        return out

    return jax.vmap(gather_group)(ye, slot, w)


def apply_moe(p, cfg: ModelConfig, x, *, no_drop: bool = False):
    """x: (B, S, D) -> (y, aux_loss). Routed top-k + shared experts.
    no_drop=True (decode/serving): capacity covers the worst case so no
    token is ever dropped."""
    # NOTE(§Perf B2, refuted): splitting decode tokens into one group per
    # batch shard was hypothesized to preserve batch sharding through the
    # dispatch; measured 8x WORSE collectives (per-group all-to-alls
    # between the data-sharded G axis and model-sharded E axis). Single
    # global group retained for decode.
    m = cfg.moe
    B, S, D = x.shape
    gs = min(m.group_size, B * S)
    if (B * S) % gs != 0:        # odd token counts: one group of everything
        gs = B * S
    G = B * S // gs
    xg = constrain(x.reshape(G, gs, D), "batch", None, None)
    probs, topv, topi = _route(m, xg, p["router"])
    if m.dispatch == "scatter":
        y = _dispatch_scatter(p, m, xg, topv, topi, no_drop)
    else:
        y = _dispatch_einsum(p, m, xg, topv, topi, no_drop)
    y = y.reshape(B, S, D)
    if m.num_shared:
        y = y + L.apply_mlp(p["shared"], x, "swiglu")
    return y, m.router_aux_weight * _aux_loss(m, probs, topi)
