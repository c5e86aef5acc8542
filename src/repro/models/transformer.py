"""LM transformer family covering the five assigned architectures.

One implementation, config-selected variants:
* GQA attention with optional QKV bias (qwen2.5-14b, internlm2-20b)
* 5:1 local(sliding-window):global interleave + QK-norm + pre/post norms
  (gemma3-12b) — scanned as super-blocks of (ratio local + 1 global) layers
  so the local layers can keep window-sized KV caches
* MLA (multi-head latent attention, deepseek-v2): latent KV cache
  (kv_lora+rope per token) with weight-absorbed decode
* MoE FFN (deepseek-v2: 2 shared + 160 routed top-6, first layer dense;
  granite: 32 experts top-8) — expert-parallel dispatch inside shard_map,
  capacity-based scatter (sort-free ranking via cummax), psum combine

Systems features: scan-over-layers (compile-time O(1) in depth), configurable
remat, gradient accumulation microbatching, FSDP+TP logical sharding
annotations, bf16 activations with fp32 softmax/norm/loss.

Params are pytrees of ``(array, logical_axes)`` pairs split via
``dist.split_params``; shapes are documented inline.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..dist.sharding import ShardingPolicy
from .common import (apply_rope, attend, causal_mask, rmsnorm, rope_freqs,
                     softmax_xent, swiglu)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    qkv_bias: bool = False
    act: str = "silu"
    rope_theta: float = 1e4
    rope_theta_local: float = 1e4
    norm_eps: float = 1e-6
    embed_scale: bool = False          # gemma: x *= sqrt(d_model)
    qk_norm: bool = False
    post_norm: bool = False            # gemma3 post-attn/post-ffn RMSNorm
    attn_scale: Optional[float] = None
    # local:global interleave (gemma3): ratio local layers then 1 global
    local_global_ratio: int = 0
    local_window: int = 1024
    # MLA (deepseek-v2)
    attn_type: str = "gqa"             # 'gqa' | 'mla'
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # MoE
    moe: bool = False
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    first_dense_layers: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.001
    # systems
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: str = "full"                # 'none' | 'full' | 'dots'
    grad_accum: int = 1
    fsdp: bool = True
    attn_chunk: int = 1024             # KV block for online-softmax attention
    loss_chunk: int = 0                # >0: blockwise vocab loss (S chunks)
    opt_state_dtype: Any = jnp.float32  # bf16: Gopher-style moment storage
    # pad head counts up to a multiple (TP divisibility) — heads beyond the
    # architectural count are masked out of the attention output, so the math
    # stays exactly the configured architecture. 0 = off (§Perf baseline).
    pad_heads_multiple: int = 0

    # -- derived --------------------------------------------------------------
    def _pad(self, n: int) -> int:
        m = self.pad_heads_multiple
        return n if not m else ((n + m - 1) // m) * m

    @property
    def n_heads_p(self) -> int:
        return self._pad(self.n_heads)

    @property
    def n_kv_heads_p(self) -> int:
        return self._pad(self.n_kv_heads)

    def kv_map(self) -> np.ndarray:
        """q head → kv head index (real heads keep the real GQA grouping;
        padded q heads point at padded kv heads)."""
        group = self.n_heads // self.n_kv_heads
        m = np.arange(self.n_heads_p) // group
        extra_kv = self.n_kv_heads_p - self.n_kv_heads
        dead = np.arange(self.n_heads_p) >= self.n_heads
        if extra_kv > 0:
            m = np.where(
                dead,
                self.n_kv_heads + (np.arange(self.n_heads_p)
                                   - self.n_heads) % extra_kv,
                np.minimum(m, self.n_kv_heads - 1))
        else:
            m = np.minimum(m, self.n_kv_heads - 1)
        return m.astype(np.int32)

    def head_mask(self) -> np.ndarray:
        return (np.arange(self.n_heads_p) < self.n_heads)

    def kv_map_cache(self) -> np.ndarray:
        """q head → UNPADDED kv index (decode caches store only the real
        kv heads; dead/padded q heads map to 0 and are masked out)."""
        group = self.n_heads // self.n_kv_heads
        m = np.arange(self.n_heads_p) // group
        return np.where(np.arange(self.n_heads_p) < self.n_heads,
                        np.minimum(m, self.n_kv_heads - 1), 0
                        ).astype(np.int32)

    @property
    def qk_head_dim(self) -> int:
        if self.attn_type == "mla":
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim

    @property
    def o_head_dim(self) -> int:
        return self.v_head_dim if self.attn_type == "mla" else self.head_dim

    @property
    def n_blocks(self) -> int:
        if self.local_global_ratio:
            assert self.n_layers % (self.local_global_ratio + 1) == 0
            return self.n_layers // (self.local_global_ratio + 1)
        return self.n_layers

    def num_params(self) -> int:
        p, _ = init_abstract(self)
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(p))

    def num_active_params(self) -> int:
        """Params touched per token (MoE: top_k of routed experts)."""
        total = self.num_params()
        if not self.moe:
            return total
        per_expert = (2 * self.d_model * self.d_ff_expert
                      + self.d_ff_expert * self.d_model)
        n_moe_layers = self.n_layers - self.first_dense_layers
        inactive = (self.n_experts - self.top_k) * per_expert * n_moe_layers
        return total - inactive


# =============================================================================
# Parameter construction
# =============================================================================

def _pair(arr, logical):
    return (arr, tuple(logical))


def _split_rng(rng, n):
    return jax.random.split(rng, n) if rng is not None else [None] * n


def _dense_init(rng, shape, logical, dtype, scale=None):
    if rng is None:  # abstract mode — no allocation (dry-run path)
        return _pair(jax.ShapeDtypeStruct(shape, dtype), logical)
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[-2] if
                                                          len(shape) > 1
                                                          else shape[-1])
    return _pair((scale * jax.random.normal(rng, shape)).astype(dtype),
                 logical)


def _zeros_init(rng, shape, logical, dtype):
    if rng is None:
        return _pair(jax.ShapeDtypeStruct(shape, dtype), logical)
    return _pair(jnp.zeros(shape, dtype), logical)


def _attn_params(cfg: TransformerConfig, rng, lead: tuple[int, ...],
                 lead_logical: tuple[Optional[str], ...]):
    """Attention params with ``lead`` stacking dims (layer stacking)."""
    d, H, Hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    ks = _split_rng(rng, 8)
    dt = cfg.param_dtype
    ll = lead_logical
    if cfg.attn_type == "mla":
        qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
        rope = cfg.qk_rope_head_dim
        nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
        p = {
            "wq_a": _dense_init(ks[0], lead + (d, qr),
                                ll + ("embed", None), dt),
            "q_norm": _zeros_init(rng, lead + (qr,), ll + (None,), dt),
            "wq_b": _dense_init(ks[1], lead + (qr, H, nope + rope),
                                ll + (None, "q_heads", None), dt),
            "wkv_a": _dense_init(ks[2], lead + (d, kvr + rope),
                                 ll + ("embed", None), dt),
            "kv_norm": _zeros_init(rng, lead + (kvr,), ll + (None,), dt),
            "wkv_b": _dense_init(ks[3], lead + (kvr, H, nope + vd),
                                 ll + (None, "q_heads", None), dt),
            "wo": _dense_init(ks[4], lead + (H, vd, d),
                              ll + ("q_heads", None, "embed"), dt,
                              scale=1.0 / np.sqrt(H * vd)),
        }
        return p
    dh = cfg.head_dim
    H, Hkv = cfg.n_heads_p, cfg.n_kv_heads_p
    p = {
        "wq": _dense_init(ks[0], lead + (d, H, dh),
                          ll + ("embed", "q_heads", None), dt),
        "wk": _dense_init(ks[1], lead + (d, Hkv, dh),
                          ll + ("embed", "kv_heads", None), dt),
        "wv": _dense_init(ks[2], lead + (d, Hkv, dh),
                          ll + ("embed", "kv_heads", None), dt),
        "wo": _dense_init(ks[3], lead + (H, dh, d),
                          ll + ("q_heads", None, "embed"), dt,
                          scale=1.0 / np.sqrt(H * dh)),
    }
    if cfg.qkv_bias:
        p["bq"] = _zeros_init(rng, lead + (H, dh),
                              ll + ("q_heads", None), dt)
        p["bk"] = _zeros_init(rng, lead + (Hkv, dh),
                              ll + ("kv_heads", None), dt)
        p["bv"] = _zeros_init(rng, lead + (Hkv, dh),
                              ll + ("kv_heads", None), dt)
    if cfg.qk_norm:
        p["qn"] = _zeros_init(rng, lead + (dh,), ll + (None,), dt)
        p["kn"] = _zeros_init(rng, lead + (dh,), ll + (None,), dt)
    return p


def _dense_mlp_params(cfg, rng, lead, ll, d_ff=None):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    dt = cfg.param_dtype
    ks = _split_rng(rng, 3)
    return {
        "wg": _dense_init(ks[0], lead + (d, ff), ll + ("embed", "mlp"), dt),
        "wu": _dense_init(ks[1], lead + (d, ff), ll + ("embed", "mlp"), dt),
        "wd": _dense_init(ks[2], lead + (ff, d), ll + ("mlp", "embed"), dt),
    }


def _moe_params(cfg, rng, lead, ll):
    d, E, ffe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    dt = cfg.param_dtype
    ks = _split_rng(rng, 5)
    p = {
        "router": _dense_init(ks[0], lead + (d, E), ll + ("embed", None),
                              jnp.float32),
        "we_g": _dense_init(ks[1], lead + (E, d, ffe),
                            ll + ("experts", "moe_mlp", None), dt),
        "we_u": _dense_init(ks[2], lead + (E, d, ffe),
                            ll + ("experts", "moe_mlp", None), dt),
        "we_d": _dense_init(ks[3], lead + (E, ffe, d),
                            ll + ("experts", None, "moe_mlp"), dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = _dense_mlp_params(
            cfg, ks[4], lead, ll, d_ff=cfg.n_shared_experts * ffe)
    return p


def _norm(cfg, lead, ll, rng=None):
    return _zeros_init(rng, lead + (cfg.d_model,), ll + (None,),
                       cfg.param_dtype)


def _layer_params(cfg: TransformerConfig, rng, lead, ll, moe: bool):
    k1, k2 = _split_rng(rng, 2)
    p = {
        "ln1": _norm(cfg, lead, ll, rng),
        "ln2": _norm(cfg, lead, ll, rng),
        "attn": _attn_params(cfg, k1, lead, ll),
        "mlp": (_moe_params(cfg, k2, lead, ll) if moe
                else _dense_mlp_params(cfg, k2, lead, ll)),
    }
    if cfg.post_norm:
        p["ln1_post"] = _norm(cfg, lead, ll, rng)
        p["ln2_post"] = _norm(cfg, lead, ll, rng)
    return p


def init_transformer(cfg: TransformerConfig, rng):
    """Returns (params, logical) pytrees."""
    from ..dist.sharding import split_params
    ks = _split_rng(rng, 6)
    dt = cfg.param_dtype
    tree: dict = {
        "embed": _dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                             ("vocab", "embed"), dt, scale=0.02),
        "unembed": _dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                               ("embed", "vocab"), dt),
        "final_norm": _norm(cfg, (), (), rng),
    }
    if cfg.local_global_ratio:
        nb, r = cfg.n_blocks, cfg.local_global_ratio
        tree["blocks_local"] = _layer_params(
            cfg, ks[2], (nb, r), (None, None), moe=False)
        tree["blocks_global"] = _layer_params(
            cfg, ks[3], (nb,), (None,), moe=cfg.moe)
    else:
        n_main = cfg.n_layers - cfg.first_dense_layers
        if cfg.first_dense_layers:
            tree["dense_layers"] = _layer_params(
                cfg, ks[4], (cfg.first_dense_layers,), (None,), moe=False)
        tree["blocks"] = _layer_params(
            cfg, ks[2], (n_main,), (None,), moe=cfg.moe)
    return split_params(tree)


def init_abstract(cfg: TransformerConfig):
    """Shape-only init (no allocation) — used by the dry-run and num_params."""
    return init_transformer(cfg, None)


# =============================================================================
# Forward
# =============================================================================

def _maybe_sc(x, spec: Optional[P], mesh: Optional[Mesh]):
    if mesh is None or spec is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def _gqa_attention(cfg: TransformerConfig, p, x, positions, window=None,
                   cache=None, cache_pos=None, theta=None):
    """Full-sequence GQA attention (train/prefill): causal (+optional
    sliding window) positional masking, KV-chunked online softmax."""
    dt = cfg.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.qk_norm:
        q = rmsnorm(q, p["qn"], cfg.norm_eps)
        k = rmsnorm(k, p["kn"], cfg.norm_eps)
    theta = theta if theta is not None else cfg.rope_theta
    cos, sin = rope_freqs(cfg.head_dim, theta, positions)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    new_cache = None
    if cache is not None:
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, cache_pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, cache_pos, 0, 0))
        new_cache = {"k": kc, "v": vc}
        k, v = kc, vc
    scale = cfg.attn_scale or 1.0 / np.sqrt(cfg.head_dim)
    kv_map = cfg.kv_map() if cfg.pad_heads_multiple else None
    out = attend(q, k, v, scale=scale, kv_map=kv_map, q_pos=positions,
                 k_pos=positions, window=window, chunk=cfg.attn_chunk)
    if cfg.pad_heads_multiple:
        out = out * jnp.asarray(cfg.head_mask(), dt)[None, None, :, None]
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return out, new_cache


def _mla_attention(cfg: TransformerConfig, p, x, positions, mask=None,
                   cache=None, cache_pos=None, absorb=False):
    """MLA. cache: dict(ckv (B,S,kvr), krope (B,S,rope)). ``absorb``=True is
    the decode path: scores/values computed against the latent cache."""
    dt = cfg.dtype
    b, s, d = x.shape
    H = cfg.n_heads
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    kvr = cfg.kv_lora_rank
    # --- queries ---
    q_lat = jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(dt))
    q_lat = rmsnorm(q_lat, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"].astype(dt))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    cos, sin = rope_freqs(rope, cfg.rope_theta, positions)
    q_rope = apply_rope(q_rope, cos[None, :, None, :], sin[None, :, None, :])
    # --- latent kv ---
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt))
    ckv, k_rope = kv[..., :kvr], kv[..., kvr:]
    ckv = rmsnorm(ckv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], cos[None, :, None, :],
                        sin[None, :, None, :])[:, :, 0, :]
    if cache is not None:
        ckv_c = jax.lax.dynamic_update_slice(cache["ckv"], ckv,
                                             (0, cache_pos, 0))
        kr_c = jax.lax.dynamic_update_slice(cache["krope"], k_rope,
                                            (0, cache_pos, 0))
        cache = {"ckv": ckv_c, "krope": kr_c}
        ckv_all, krope_all = ckv_c, kr_c
    else:
        ckv_all, krope_all = ckv, k_rope
    scale = cfg.attn_scale or 1.0 / np.sqrt(nope + rope)
    wkv_b = p["wkv_b"].astype(dt)           # (kvr, H, nope+vd)
    wk_b, wv_b = wkv_b[..., :nope], wkv_b[..., nope:]
    if absorb:
        # decode: fold wk_b into q, attend in latent space (the MLA trick)
        q_lat2 = jnp.einsum("bshn,rhn->bshr", q_nope, wk_b)
        scores = (jnp.einsum("bshr,btr->bhst", q_lat2, ckv_all,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshr,btr->bhst", q_rope, krope_all,
                               preferred_element_type=jnp.float32)) * scale
        scores = jnp.where(mask[None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        o_lat = jnp.einsum("bhst,btr->bshr", w, ckv_all)
        out = jnp.einsum("bshr,rhv->bshv", o_lat, wv_b)
    else:
        # train/prefill: expand k/v per head
        k_nope = jnp.einsum("btr,rhn->bthn", ckv_all, wk_b)
        v = jnp.einsum("btr,rhv->bthv", ckv_all, wv_b)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope_all[:, :, None, :],
                                      k_nope.shape[:3] + (rope,))], axis=-1)
        qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = attend(qfull, k, v, mask, scale=scale, q_pos=positions,
                     k_pos=positions, chunk=cfg.attn_chunk)
    out = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(dt))
    return out, cache


# --- FFN ---------------------------------------------------------------------

def _dense_ffn(cfg, p, x):
    dt = cfg.dtype
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(dt))
    return jnp.einsum("bsf,fd->bsd", swiglu(g, u, cfg.act),
                      p["wd"].astype(dt))


def _moe_dispatch_local(cfg: TransformerConfig, x, router_w, we_g, we_u,
                        we_d, e_start, n_model_shards):
    """Capacity-based top-k dispatch over the experts local to this shard.

    x: (T, d). we_*: (E_loc, ...). Returns (y (T,d), aux_loss scalar).
    """
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    E_loc = we_g.shape[0]
    C = int(np.ceil(T * k / E * cfg.capacity_factor))
    C = max(8, ((C + 7) // 8) * 8)
    dt = cfg.dtype

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)               # (T,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch-style): E * Σ_e density_e · mean_prob_e
    density = jnp.zeros((E,)).at[idx.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(density * probs.mean(0))

    e_flat = idx.reshape(-1)                            # (T*k,)
    n = T * k
    # rank of each assignment within its expert (stable, sort-based)
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    start_idx = jax.lax.cummax(jnp.where(is_start, pos, 0))
    rank_sorted = pos - start_idx
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)

    e_loc = e_flat - e_start
    ok = (e_loc >= 0) & (e_loc < E_loc) & (rank < C)
    dest = jnp.where(ok, e_loc * C + rank, E_loc * C)   # sentinel row
    x_rep = jnp.repeat(x, k, axis=0)                    # (T*k, d)
    buf = jnp.zeros((E_loc * C + 1, d), dt).at[dest].add(x_rep.astype(dt))
    buf = buf[:E_loc * C].reshape(E_loc, C, d)

    g = jnp.einsum("ecd,edf->ecf", buf, we_g.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, we_u.astype(dt))
    h = jnp.einsum("ecf,efd->ecd", swiglu(g, u, cfg.act), we_d.astype(dt))

    h_flat = jnp.concatenate(
        [h.reshape(E_loc * C, d), jnp.zeros((1, d), dt)], axis=0)
    vals = (h_flat[dest] * gates.reshape(-1)[:, None].astype(dt)
            * ok[:, None].astype(dt))
    tok = jnp.arange(n, dtype=jnp.int32) // k
    y = jnp.zeros((T, d), dt).at[tok].add(vals)
    return y, aux


def _moe_ffn(cfg: TransformerConfig, p, x, mesh: Optional[Mesh],
             policy: Optional[ShardingPolicy]):
    """MoE FFN: shared experts (dense TP path) + routed experts (EP path)."""
    dt = cfg.dtype
    y_shared = (_dense_ffn(cfg, p["shared"], x)
                if cfg.n_shared_experts else 0.0)
    router_w = p["router"]
    we_g, we_u, we_d = p["we_g"], p["we_u"], p["we_d"]

    if mesh is None or "model" not in mesh.axis_names \
            or mesh.shape["model"] == 1:
        xf = x.reshape(-1, cfg.d_model)
        y, aux = _moe_dispatch_local(cfg, xf, router_w, we_g, we_u, we_d,
                                     e_start=0, n_model_shards=1)
        return y.reshape(x.shape).astype(dt) + y_shared, aux

    batch_axes = policy.data_axes if policy else ("data",)
    n_model = mesh.shape["model"]

    def block(xb, rw, wg, wu, wd):
        shard = jax.lax.axis_index("model")
        E_loc = wg.shape[0]
        xf = xb.reshape(-1, cfg.d_model)
        y, aux = _moe_dispatch_local(cfg, xf, rw, wg, wu, wd,
                                     e_start=shard * E_loc,
                                     n_model_shards=n_model)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, "model")
        return y.reshape(xb.shape), aux

    y, aux = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(batch_axes), P(), P("model"), P("model"), P("model")),
        out_specs=(P(batch_axes), P()),
        check_vma=False,
    )(x, router_w, we_g, we_u, we_d)
    return y.astype(dt) + y_shared, aux


# --- Layer -------------------------------------------------------------------

def _layer(cfg: TransformerConfig, p, x, positions, window=None, *,
           moe: bool, theta: float, cache=None, cache_pos=None,
           absorb=False, mesh=None, policy=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.attn_type == "mla":
        attn_out, new_cache = _mla_attention(cfg, p["attn"], h, positions,
                                             None, cache, cache_pos, absorb)
    else:
        attn_out, new_cache = _gqa_attention(cfg, p["attn"], h, positions,
                                             window, cache, cache_pos,
                                             theta)
    if cfg.post_norm:
        attn_out = rmsnorm(attn_out, p["ln1_post"], cfg.norm_eps)
    x = x + attn_out
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if moe:
        ffn_out, aux = _moe_ffn(cfg, p["mlp"], h, mesh, policy)
    else:
        ffn_out, aux = _dense_ffn(cfg, p["mlp"], h), jnp.float32(0.0)
    if cfg.post_norm:
        ffn_out = rmsnorm(ffn_out, p["ln2_post"], cfg.norm_eps)
    return x + ffn_out, new_cache, aux


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _slice_tree(tree, i):
    return jax.tree.map(lambda a: a[i] if hasattr(a, "shape") else a, tree)


def _params_only(tree):
    """Strip logical names if present (params already split → identity)."""
    return tree


# =============================================================================
# Full-sequence forward (train / prefill)
# =============================================================================

def forward(cfg: TransformerConfig, params, tokens, *, mesh=None,
            policy=None, return_cache=False, cache_len=None,
            return_hidden=False):
    """tokens (B,S) int32 → logits (B,S,V) [+ cache dict].

    ``return_hidden=True`` returns the final-norm hidden states instead of
    logits — the chunked-vocab-loss path fuses unembedding into the loss so
    the (B,S,V) tensor is never materialized."""
    b, s = tokens.shape
    dt = cfg.dtype
    x = params["embed"].astype(dt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dt)
    positions = jnp.arange(s)
    batch_axes = policy.data_axes if policy else None
    if batch_axes:
        x = _maybe_sc(x, P(batch_axes), mesh)

    caches = {} if return_cache else None
    cl = cache_len or s

    def pad_cache(arr):  # (B,s,...) -> (B,cl,...)
        if cl == s:
            return arr
        pad = [(0, 0)] * arr.ndim
        pad[1] = (0, cl - s)
        return jnp.pad(arr, pad)

    aux_total = jnp.float32(0.0)

    if cfg.local_global_ratio:
        pl_, pg = params["blocks_local"], params["blocks_global"]

        def block_step(carry, blk):
            x, aux = carry
            bp_local, bp_global = blk

            def inner(xc, lp):
                y, c, a = _layer(cfg, lp, xc[0], positions,
                                 cfg.local_window, moe=False,
                                 theta=cfg.rope_theta_local,
                                 mesh=mesh, policy=policy)
                return (y, xc[1] + a), c
            (x, aux), local_caches = jax.lax.scan(
                _remat(cfg, inner), (x, aux), bp_local)
            x, gcache, a = _layer(cfg, bp_global, x, positions, None,
                                  moe=cfg.moe, theta=cfg.rope_theta,
                                  mesh=mesh, policy=policy)
            return (x, aux + a), (local_caches, gcache)

        (x, aux_total), _ = jax.lax.scan(
            block_step, (x, aux_total), (pl_, pg))
        if return_cache:
            # re-run is avoided: caches from scan ys — recompute cheaply here
            # by a dedicated prefill that materializes k/v (see prefill()).
            raise NotImplementedError("use prefill() for cached forward")
    else:
        if cfg.first_dense_layers:
            def dense_step(carry, lp):
                x, aux = carry
                y, c, a = _layer(cfg, lp, x, positions, None, moe=False,
                                 theta=cfg.rope_theta, mesh=mesh,
                                 policy=policy)
                return (y, aux + a), None
            (x, aux_total), _ = jax.lax.scan(
                _remat(cfg, dense_step), (x, aux_total),
                params["dense_layers"])

        def step(carry, lp):
            x, aux = carry
            y, c, a = _layer(cfg, lp, x, positions, None, moe=cfg.moe,
                             theta=cfg.rope_theta, mesh=mesh, policy=policy)
            return (y, aux + a), None
        (x, aux_total), _ = jax.lax.scan(
            _remat(cfg, step), (x, aux_total), params["blocks"])

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return (x, aux_total)
    logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"].astype(dt))
    if batch_axes:
        logits = _maybe_sc(logits, P(batch_axes, None, "model"), mesh)
    return (logits, aux_total)


# =============================================================================
# KV caches, prefill, decode
# =============================================================================

def _cache_entry(cfg: TransformerConfig, lead, B, S, *, abstract,
                 seq_shard=False, seq_tp=False):
    """One layer-stack cache. Logical: batch on B; S goes to the data axes
    for single-sequence long-context decode (seq_shard), or to the 'model'
    axis (seq_tp) — used by MLA, whose latent cache has no head dim to
    shard (attention over the S-sharded latent psums partial softmax)."""
    b_l = None if seq_shard else "batch"
    s_l = "batch" if seq_shard else ("kv_seq" if seq_tp else None)
    if cfg.attn_type == "mla":
        shapes = {
            "ckv": (lead + (B, S, cfg.kv_lora_rank),
                    (None,) * len(lead) + (b_l, s_l, None)),
            "krope": (lead + (B, S, cfg.qk_rope_head_dim),
                      (None,) * len(lead) + (b_l, s_l, None)),
        }
    else:
        kv = lead + (B, S, cfg.n_kv_heads, cfg.head_dim)  # unpadded
        lg = (None,) * len(lead) + (b_l, s_l, "kv_heads", None)
        shapes = {"k": (kv, lg), "v": (kv, lg)}
    mk = (jax.ShapeDtypeStruct if abstract
          else (lambda s, d: jnp.zeros(s, d)))
    vals = {k: mk(sh, cfg.dtype) for k, (sh, _) in shapes.items()}
    logical = {k: lg for k, (_, lg) in shapes.items()}
    return vals, logical


def init_cache(cfg: TransformerConfig, batch: int, s_max: int, *,
               abstract: bool = False, seq_shard: bool = False,
               seq_tp: bool = False):
    """Returns (cache, logical). Layout mirrors the param layer stacks."""
    vals: dict = {}
    logical: dict = {}
    if cfg.local_global_ratio:
        nb, r = cfg.n_blocks, cfg.local_global_ratio
        w = min(cfg.local_window, s_max)
        vals["local"], logical["local"] = _cache_entry(
            cfg, (nb, r), batch, w, abstract=abstract)
        vals["global"], logical["global"] = _cache_entry(
            cfg, (nb,), batch, s_max, abstract=abstract,
            seq_shard=seq_shard, seq_tp=seq_tp)
    else:
        if cfg.first_dense_layers:
            vals["dense"], logical["dense"] = _cache_entry(
                cfg, (cfg.first_dense_layers,), batch, s_max,
                abstract=abstract, seq_shard=seq_shard, seq_tp=seq_tp)
        n_main = cfg.n_layers - cfg.first_dense_layers
        vals["blocks"], logical["blocks"] = _cache_entry(
            cfg, (n_main,), batch, s_max, abstract=abstract,
            seq_shard=seq_shard, seq_tp=seq_tp)
    return vals, logical


def _decode_mask(cache_pos, s_max):
    """(1, s_max) mask for standard decode: positions ≤ cache_pos."""
    k_pos = jnp.arange(s_max)
    return (k_pos <= cache_pos)[None, :]


def _ring_mask_and_slotpos(cache_pos, window):
    """Positions stored in each ring slot + validity mask for local decode."""
    j = jnp.arange(window)
    slot_pos = cache_pos - jnp.mod(cache_pos - j, window)
    return (slot_pos >= 0)[None, :], slot_pos


def _decode_layer_gqa(cfg, p, x, cache, cache_pos, theta, window=None):
    """One-token GQA decode for one layer; ring-buffer update when window."""
    dt = cfg.dtype
    b = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.qk_norm:
        q = rmsnorm(q, p["qn"], cfg.norm_eps)
        k = rmsnorm(k, p["kn"], cfg.norm_eps)
    pos = cache_pos[None] if jnp.ndim(cache_pos) == 0 else cache_pos
    cos, sin = rope_freqs(cfg.head_dim, theta, pos)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    k = k[:, :, :cfg.n_kv_heads]   # cache stores unpadded kv heads
    v = v[:, :, :cfg.n_kv_heads]
    if window is not None:
        slot = jnp.mod(cache_pos, window)
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        mask, _ = _ring_mask_and_slotpos(cache_pos, window)
    else:
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, cache_pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, cache_pos, 0, 0))
        mask = _decode_mask(cache_pos, kc.shape[1])
    scale = cfg.attn_scale or 1.0 / np.sqrt(cfg.head_dim)
    kv_map = cfg.kv_map_cache() if cfg.pad_heads_multiple else None
    out = attend(q, kc, vc, mask, scale=scale, kv_map=kv_map)
    if cfg.pad_heads_multiple:
        out = out * jnp.asarray(cfg.head_mask(), dt)[None, None, :, None]
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return out, {"k": kc, "v": vc}


def decode_step(cfg: TransformerConfig, params, cache, tokens, cache_pos, *,
                mesh=None, policy=None):
    """One-token decode. tokens (B,1) int32, cache_pos scalar int32.

    Returns (logits (B,1,V), new_cache). MLA uses the weight-absorbed latent
    path; gemma local layers use ring-buffer window caches.
    """
    dt = cfg.dtype
    x = params["embed"].astype(dt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dt)
    pos_vec = cache_pos[None]
    aux = jnp.float32(0.0)

    def attn_layer(p, x, lcache, *, window, theta):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if cfg.attn_type == "mla":
            mask = _decode_mask(cache_pos, lcache["ckv"].shape[1])
            a, nc = _mla_attention(cfg, p["attn"], h, pos_vec, mask,
                                   cache=lcache, cache_pos=cache_pos,
                                   absorb=True)
        else:
            a, nc = _decode_layer_gqa(cfg, p["attn"], h, lcache, cache_pos,
                                      theta, window=window)
        if cfg.post_norm:
            a = rmsnorm(a, p["ln1_post"], cfg.norm_eps)
        x = x + a
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe and "router" in p["mlp"]:
            f, _ = _moe_ffn(cfg, p["mlp"], h, mesh, policy)
        else:
            f = _dense_ffn(cfg, p["mlp"], h)
        if cfg.post_norm:
            f = rmsnorm(f, p["ln2_post"], cfg.norm_eps)
        return x + f, nc

    new_cache: dict = {}
    if cfg.local_global_ratio:
        w = cache["local"]["k"].shape[3]

        def block_step(x, blk):
            pl_, pg, cl, cg = blk

            def inner(xc, lp_lc):
                lp, lc = lp_lc
                y, nc = attn_layer(lp, xc, lc, window=w,
                                   theta=cfg.rope_theta_local)
                return y, nc
            x, ncl = jax.lax.scan(inner, x, (pl_, cl))
            x, ncg = attn_layer(pg, x, cg, window=None, theta=cfg.rope_theta)
            return x, (ncl, ncg)

        x, (ncl, ncg) = jax.lax.scan(
            block_step, x,
            (params["blocks_local"], params["blocks_global"],
             cache["local"], cache["global"]))
        new_cache = {"local": ncl, "global": ncg}
    else:
        if cfg.first_dense_layers:
            def dstep(x, lp_lc):
                lp, lc = lp_lc
                y, nc = attn_layer(lp, x, lc, window=None,
                                   theta=cfg.rope_theta)
                return y, nc
            x, ncd = jax.lax.scan(dstep, x,
                                  (params["dense_layers"], cache["dense"]))
            new_cache["dense"] = ncd

        def step(x, lp_lc):
            lp, lc = lp_lc
            y, nc = attn_layer(lp, x, lc, window=None, theta=cfg.rope_theta)
            return y, nc
        x, ncb = jax.lax.scan(step, x, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = ncb

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"].astype(dt))
    return logits, new_cache


def _constrain_cache(entry, cfg, mesh, policy):
    """Pin per-layer cache slices to the decode layout inside the prefill
    scan (sharding does not propagate into scan ys on its own): batch over
    data axes, cache sequence dim over 'model' (split-KV decode)."""
    if mesh is None or policy is None:
        return entry
    from jax.sharding import NamedSharding

    def pin(a):
        if a.ndim >= 3 and a.shape[1] > 2048:      # (B, S, ...) long dim
            spec = P(policy.data_axes, "model")
        else:
            spec = P(policy.data_axes)
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, spec))
    return jax.tree.map(pin, entry)


def prefill(cfg: TransformerConfig, params, tokens, s_max: int, *,
            mesh=None, policy=None, seq_shard: bool = False,
            logits_last_only: bool = True):
    """Full-sequence forward that also materializes decode caches.

    ``logits_last_only`` returns only the final position's logits (what a
    serving prefill needs) — avoids materializing the (B,S,V) tensor."""
    b, s = tokens.shape
    dt = cfg.dtype
    x = params["embed"].astype(dt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dt)
    positions = jnp.arange(s)
    if policy is not None:
        x = _maybe_sc(x, P(policy.data_axes), mesh)  # pin batch sharding

    def pad_s(arr):  # (B, s, ...) -> (B, s_max, ...)
        if s_max == s:
            return arr
        pad = [(0, 0)] * arr.ndim
        pad[1] = (0, s_max - s)
        return jnp.pad(arr, pad)

    def run_layer(p, x, *, moe, theta, window=None):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if cfg.attn_type == "mla":
            a, _ = _mla_attention(cfg, p["attn"], h, positions)
            kv = jnp.einsum("bsd,dr->bsr", h, p["attn"]["wkv_a"].astype(dt))
            ckv = rmsnorm(kv[..., :cfg.kv_lora_rank], p["attn"]["kv_norm"],
                          cfg.norm_eps)
            cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.rope_theta,
                                  positions)
            krope = apply_rope(kv[:, :, None, cfg.kv_lora_rank:],
                               cos[None, :, None, :],
                               sin[None, :, None, :])[:, :, 0, :]
            lcache = _constrain_cache(
                {"ckv": pad_s(ckv), "krope": pad_s(krope)}, cfg, mesh,
                policy)
        else:
            k = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"].astype(dt))
            if cfg.qkv_bias:
                k = k + p["attn"]["bk"].astype(dt)
                v = v + p["attn"]["bv"].astype(dt)
            if cfg.qk_norm:
                k = rmsnorm(k, p["attn"]["kn"], cfg.norm_eps)
            cos, sin = rope_freqs(cfg.head_dim, theta, positions)
            k = apply_rope(k, cos[None, :, None, :], sin[None, :, None, :])
            k = k[:, :, :cfg.n_kv_heads]   # cache stores unpadded kv
            v = v[:, :, :cfg.n_kv_heads]
            a, _ = _gqa_attention(cfg, p["attn"], h, positions, window,
                                  theta=theta)
            if window is not None:
                w = min(window, s_max)  # ring cache size (see init_cache)
                kk = k[:, -w:] if s >= w else jnp.pad(
                    k, ((0, 0), (0, w - s)) + ((0, 0),) * (k.ndim - 2))
                vv = v[:, -w:] if s >= w else jnp.pad(
                    v, ((0, 0), (0, w - s)) + ((0, 0),) * (v.ndim - 2))
                if s >= w:
                    # place position p at ring slot p % w
                    slots = jnp.mod(jnp.arange(s - w, s), w)
                    kk = jnp.zeros_like(kk).at[:, slots].set(kk)
                    vv = jnp.zeros_like(vv).at[:, slots].set(vv)
                lcache = {"k": kk, "v": vv}
            else:
                lcache = _constrain_cache({"k": pad_s(k), "v": pad_s(v)},
                                          cfg, mesh, policy)
        if cfg.post_norm:
            a = rmsnorm(a, p["ln1_post"], cfg.norm_eps)
        x = x + a
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if moe and "router" in p["mlp"]:
            f, _ = _moe_ffn(cfg, p["mlp"], h, mesh, policy)
        else:
            f = _dense_ffn(cfg, p["mlp"], h)
        if cfg.post_norm:
            f = rmsnorm(f, p["ln2_post"], cfg.norm_eps)
        return x + f, lcache

    cache: dict = {}
    if cfg.local_global_ratio:
        w = min(cfg.local_window, s_max)

        def block_step(x, blk):
            bp_local, bp_global = blk

            def inner(xc, lp):
                y, lc = run_layer(lp, xc, moe=False,
                                  theta=cfg.rope_theta_local,
                                  window=cfg.local_window)
                return y, lc
            x, lcs = jax.lax.scan(inner, x, bp_local)
            x, gc = run_layer(bp_global, x, moe=cfg.moe,
                              theta=cfg.rope_theta)
            return x, (lcs, gc)
        x, (lcs, gcs) = jax.lax.scan(
            block_step, x, (params["blocks_local"], params["blocks_global"]))
        cache = {"local": lcs, "global": gcs}
    else:
        if cfg.first_dense_layers:
            def dstep(x, lp):
                y, lc = run_layer(lp, x, moe=False, theta=cfg.rope_theta)
                return y, lc
            x, dcs = jax.lax.scan(dstep, x, params["dense_layers"])
            cache["dense"] = dcs

        def step(x, lp):
            y, lc = run_layer(lp, x, moe=cfg.moe, theta=cfg.rope_theta)
            return y, lc
        x, bcs = jax.lax.scan(step, x, params["blocks"])
        cache["blocks"] = bcs

    if logits_last_only:
        x = x[:, -1:]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"].astype(dt))
    return logits, cache


# =============================================================================
# Training step
# =============================================================================

def make_train_step(cfg: TransformerConfig, optimizer, *, mesh=None,
                    policy=None):
    """Builds train_step(state, batch) -> (state, metrics).

    batch = {'tokens': (B, S) int32}; next-token loss; optional gradient
    accumulation over cfg.grad_accum microbatches (activation memory ÷ k).
    """

    def loss_fn(params, tokens):
        if cfg.loss_chunk:
            # fuse unembedding into a blockwise loss: never materialize the
            # (B, S, V) logits (vocab 262k × 32k tokens would dominate HBM)
            x, aux = forward(cfg, params, tokens, mesh=mesh, policy=policy,
                             return_hidden=True)
            b, s, d = x.shape
            labels = jnp.concatenate(
                [tokens[:, 1:], jnp.full((b, 1), -100, tokens.dtype)], 1)
            cs = cfg.loss_chunk
            pad = (-s) % cs
            if pad:
                x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                labels = jnp.pad(labels, ((0, 0), (0, pad)),
                                 constant_values=-100)
            nb = x.shape[1] // cs
            xb = x.reshape(b, nb, cs, d).transpose(1, 0, 2, 3)
            lb = labels.reshape(b, nb, cs).transpose(1, 0, 2)
            unemb = params["unembed"].astype(cfg.dtype)

            def blk(carry, inp):
                tot, cnt = carry
                xc, lc = inp
                logits = jnp.einsum("bsd,dv->bsv", xc, unemb)
                lg = logits.astype(jnp.float32)
                mask = lc >= 0
                safe = jnp.where(mask, lc, 0)
                logz = jax.nn.logsumexp(lg, axis=-1)
                gold = jnp.take_along_axis(lg, safe[..., None],
                                           axis=-1)[..., 0]
                tot = tot + ((logz - gold) * mask).sum()
                cnt = cnt + mask.sum()
                return (tot, cnt), None
            (tot, cnt), _ = jax.lax.scan(
                jax.checkpoint(blk), (jnp.float32(0), jnp.int32(0)),
                (xb, lb))
            loss = tot / jnp.maximum(cnt, 1)
        else:
            logits, aux = forward(cfg, params, tokens, mesh=mesh,
                                  policy=policy)
            loss = softmax_xent(logits[:, :-1], tokens[:, 1:])
        return loss + cfg.router_aux_coef * aux, (loss, aux)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        params, opt_state, step = (state["params"], state["opt"],
                                   state["step"])
        tokens = batch["tokens"]
        k = cfg.grad_accum
        if k > 1:
            b = tokens.shape[0]
            mbs = tokens.reshape(k, b // k, -1)

            def acc(carry, mb):
                g_acc, l_acc, a_acc = carry
                (_, (loss, aux)), g = grad_fn(params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + loss, a_acc + aux), None

            g0 = jax.tree.map(jnp.zeros_like, params)
            (grads, loss, aux), _ = jax.lax.scan(
                acc, (g0, jnp.float32(0), jnp.float32(0)), mbs)
            grads = jax.tree.map(lambda g: g / k, grads)
            loss, aux = loss / k, aux / k
        else:
            (_, (loss, aux)), grads = grad_fn(params, tokens)
        params, opt_state = optimizer.update(params, grads, opt_state)
        new_state = {"params": params, "opt": opt_state, "step": step + 1}
        return new_state, {"loss": loss, "aux_loss": aux}

    return train_step
