"""Attention mixers: GQA (qk-norm / local-global / softcap) and MLA.

Full-sequence paths route through ``repro.kernels.flash_attention.ops`` (Pallas
on TPU, bounded-memory XLA elsewhere). Decode paths operate on a KV cache via
``jax.lax.dynamic_update_slice``; MLA decode uses the matrix-absorption trick
on the compressed latent cache.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as attn_ops
from repro.models import layers as L

GLOBAL_WINDOW = 1 << 30  # "no window" sentinel large enough for any seq


# ------------------------------------------------------------------ GQA

def init_gqa(key, cfg: ModelConfig, dtype):
    a = cfg.attn
    D, N, K, H = cfg.d_model, a.num_heads, a.num_kv_heads, a.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], (D, N, H), (0,), dtype),
        "wk": L.dense_init(ks[1], (D, K, H), (0,), dtype),
        "wv": L.dense_init(ks[2], (D, K, H), (0,), dtype),
        "wo": L.dense_init(ks[3], (N, H, D), (0, 1), dtype),
    }
    if a.qk_norm:
        p["q_norm"] = L.init_rms(H)
        p["k_norm"] = L.init_rms(H)
    return p


def _project_qkv(p, cfg, x, positions):
    a = cfg.attn
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    if a.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = L.rope(q, positions, a.rope_theta)
    k = L.rope(k, positions, a.rope_theta)
    return q, k, v


def apply_gqa(p, cfg: ModelConfig, x, positions, *, causal=True, window=None,
              return_kv: bool = False):
    """Full-sequence GQA. x: (B, S, D). window: None | int | traced scalar
    (per-layer local/global selection inside a scan)."""
    a = cfg.attn
    q, k, v = _project_qkv(p, cfg, x, positions)
    o = attn_ops.attention(q, k, v, causal=causal, window=window,
                           softcap=a.attn_softcap)
    out = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    if return_kv:
        return out, (k, v)
    return out


def apply_gqa_decode(p, cfg: ModelConfig, x, kc, vc, pos, *, window=None):
    """One decode step. x: (B, 1, D); kc/vc: (B, Smax, K, H); pos: scalar.
    Returns (out (B,1,D), new kc, new vc)."""
    a = cfg.attn
    q, k, v = _project_qkv(p, cfg, x, pos[None] if jnp.ndim(pos) == 0
                           else pos)
    kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, pos, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, pos, 0, 0))
    o = attn_ops.attention(q, kc, vc, causal=True, window=window,
                           softcap=a.attn_softcap, q_offset=pos,
                           length=pos + 1)
    out = jnp.einsum("bsnh,nhd->bsd", o, p["wo"])
    return out, kc, vc


# ------------------------------------------------------------------ MLA

def init_mla(key, cfg: ModelConfig, dtype):
    m, a = cfg.mla, cfg.attn
    D, N = cfg.d_model, a.num_heads
    qh = m.nope_head_dim + m.rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": L.dense_init(ks[0], (D, m.q_lora_rank), (0,), dtype),
        "q_norm": L.init_rms(m.q_lora_rank),
        "wq_b": L.dense_init(ks[1], (m.q_lora_rank, N, qh), (0,), dtype),
        "wkv_a": L.dense_init(ks[2], (D, m.kv_lora_rank + m.rope_head_dim),
                              (0,), dtype),
        "kv_norm": L.init_rms(m.kv_lora_rank),
        "wk_b": L.dense_init(ks[3], (m.kv_lora_rank, N, m.nope_head_dim),
                             (0,), dtype),
        "wv_b": L.dense_init(ks[4], (m.kv_lora_rank, N, m.v_head_dim),
                             (0,), dtype),
        "wo": L.dense_init(ks[5], (N, m.v_head_dim, D), (0, 1), dtype),
    }


def _mla_q(p, cfg, x, positions):
    m = cfg.mla
    cq = L.rms_norm(jnp.einsum("bsd,dl->bsl", x, p["wq_a"]), p["q_norm"],
                    cfg.norm_eps)
    q = jnp.einsum("bsl,lnh->bsnh", cq, p["wq_b"])
    q_nope = q[..., :m.nope_head_dim]
    q_rope = L.rope(q[..., m.nope_head_dim:], positions, cfg.attn.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(p, cfg, x, positions):
    m = cfg.mla
    kv = jnp.einsum("bsd,dl->bsl", x, p["wkv_a"])
    ckv = L.rms_norm(kv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kv[..., None, m.kv_lora_rank:]           # (B, S, 1, rope_hd)
    k_rope = L.rope(k_rope, positions, cfg.attn.rope_theta)[:, :, 0]
    return ckv, k_rope


def apply_mla(p, cfg: ModelConfig, x, positions, *, return_kv: bool = False):
    """Full-sequence MLA (expanded path). x: (B, S, D)."""
    m, a = cfg.mla, cfg.attn
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    ckv, k_rope = _mla_kv_latent(p, cfg, x, positions)
    k_nope = jnp.einsum("bsl,lnh->bsnh", ckv, p["wk_b"])
    v = jnp.einsum("bsl,lnh->bsnh", ckv, p["wv_b"])
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None],
                                  k_nope.shape[:3] + (m.rope_head_dim,))], -1)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    # pad v to q/k head_dim for the shared attention op, then slice back
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                     (0, q.shape[-1] - v.shape[-1])))
    o = attn_ops.attention(q, k, vp, causal=True, scale=scale)
    o = o[..., :m.v_head_dim]
    out = jnp.einsum("bsnv,nvd->bsd", o, p["wo"])
    if return_kv:
        return out, (ckv, k_rope)
    return out


def apply_mla_decode(p, cfg: ModelConfig, x, ckv_c, krope_c, pos):
    """Matrix-absorbed MLA decode. x: (B, 1, D); ckv_c: (B, Smax, kv_lora);
    krope_c: (B, Smax, rope_hd). Returns (out, new ckv_c, new krope_c)."""
    m = cfg.mla
    posv = pos[None] if jnp.ndim(pos) == 0 else pos
    q_nope, q_rope = _mla_q(p, cfg, x, posv)           # (B,1,N,·)
    ckv, k_rope = _mla_kv_latent(p, cfg, x, posv)      # (B,1,lora),(B,1,rope)
    # absorb W_UK into q: (B,1,N,lora)
    q_eff = jnp.einsum("bqnh,lnh->bqnl", q_nope, p["wk_b"])
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    from repro.sharding.act import current_mesh
    mesh = current_mesh()
    if cfg.flash_decode and mesh is not None:
        ctx, ckv_c, krope_c = _mla_flash_decode(
            mesh, q_eff, q_rope, ckv, k_rope, ckv_c, krope_c, pos, scale)
    else:
        ckv_c = jax.lax.dynamic_update_slice(ckv_c, ckv.astype(ckv_c.dtype),
                                             (0, pos, 0))
        krope_c = jax.lax.dynamic_update_slice(
            krope_c, k_rope.astype(krope_c.dtype), (0, pos, 0))
        s = (jnp.einsum("bqnl,bsl->bnqs", q_eff.astype(jnp.float32),
                        ckv_c.astype(jnp.float32))
             + jnp.einsum("bqnr,bsr->bnqs", q_rope.astype(jnp.float32),
                          krope_c.astype(jnp.float32)))
        s = s * scale
        mask = (jnp.arange(ckv_c.shape[1]) <= pos)[None, None, None]
        s = jnp.where(mask, s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bnqs,bsl->bqnl", w, ckv_c.astype(jnp.float32))
    o = jnp.einsum("bqnl,lnv->bqnv", ctx.astype(x.dtype), p["wv_b"])
    out = jnp.einsum("bqnv,nvd->bqd", o, p["wo"])
    return out, ckv_c, krope_c


def _mla_flash_decode(mesh, q_eff, q_rope, ckv_new, krope_new, ckv_c,
                      krope_c, pos, scale):
    """Flash-decode over a sequence-sharded MLA latent cache (shard_map
    across the `model` axis). Each shard computes partial softmax stats on
    its S/tp slice; combination psums only (B, N) stats and the (B, N, R)
    context — collectives shrink from full-score psums to per-head stats.

    Sharding: ckv_c/krope_c are P(batch, 'model', None); q/new-kv entries
    replicated across 'model'.
    """
    from jax.sharding import PartitionSpec as P
    ba = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    S_local = ckv_c.shape[1] // mesh.shape["model"]

    def shard_fn(q_eff, q_rope, ckv_new, krope_new, ckv_c, krope_c, pos):
        idx = jax.lax.axis_index("model")
        start = idx * S_local
        lpos = pos - start
        in_range = (lpos >= 0) & (lpos < S_local)
        cl = jnp.clip(lpos, 0, S_local - 1)
        cur_ckv = jax.lax.dynamic_slice(
            ckv_c, (0, cl, 0), (ckv_c.shape[0], 1, ckv_c.shape[2]))
        cur_kr = jax.lax.dynamic_slice(
            krope_c, (0, cl, 0), (krope_c.shape[0], 1, krope_c.shape[2]))
        ckv_c = jax.lax.dynamic_update_slice(
            ckv_c, jnp.where(in_range, ckv_new.astype(ckv_c.dtype),
                             cur_ckv), (0, cl, 0))
        krope_c = jax.lax.dynamic_update_slice(
            krope_c, jnp.where(in_range, krope_new.astype(krope_c.dtype),
                               cur_kr), (0, cl, 0))
        s = (jnp.einsum("bqnl,bsl->bnqs", q_eff.astype(jnp.float32),
                        ckv_c.astype(jnp.float32))
             + jnp.einsum("bqnr,bsr->bnqs", q_rope.astype(jnp.float32),
                          krope_c.astype(jnp.float32))) * scale
        kpos = start + jnp.arange(S_local)
        s = jnp.where((kpos <= pos)[None, None, None], s, -1e30)
        mx = s.max(axis=-1)                          # (B,N,1)
        w = jnp.exp(s - mx[..., None])
        l = w.sum(axis=-1)                           # (B,N,1)
        ctx = jnp.einsum("bnqs,bsl->bqnl", w, ckv_c.astype(jnp.float32))
        # combine across shards: logsumexp-weighted psums of small stats
        gmx = jax.lax.pmax(mx, "model")
        corr = jnp.exp(mx - gmx)
        gl = jax.lax.psum(l * corr, "model")
        gctx = jax.lax.psum(ctx * corr.transpose(0, 2, 1)[..., None],
                            "model")
        ctx = gctx / jnp.maximum(gl, 1e-30).transpose(0, 2, 1)[..., None]
        return ctx, ckv_c, krope_c

    in_specs = (P(ba, None, None, None), P(ba, None, None, None),
                P(ba, None, None), P(ba, None, None),
                P(ba, "model", None), P(ba, "model", None), P())
    out_specs = (P(ba, None, None, None), P(ba, "model", None),
                 P(ba, "model", None))
    mapped = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return mapped(q_eff, q_rope, ckv_new, krope_new, ckv_c, krope_c, pos)
