"""Per-family blocks: param specs + forward + single-token decode.

Spec axes are literal mesh axes: "model" (TP/EP), "fsdp" (resolved to the
innermost data axis when the config enables FSDP), or None.  Builders are
divisibility-aware: e.g. attention picks heads-TP when n_heads % tp == 0
(Megatron GQA with replicated KV when kv doesn't divide), else head_dim-TP,
else replicated.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.context import MeshCtx

from . import layers
from .config import ModelConfig
from .params import Spec

# ---------------------------------------------------------------- helpers


def _padded_heads(cfg: ModelConfig, ctx: MeshCtx) -> int:
    tp = ctx.tp_size
    h = cfg.n_heads
    if cfg.pad_heads and tp > 1 and h % tp != 0:
        return -(-h // tp) * tp
    return h


def _attn_layout(cfg: ModelConfig, ctx: MeshCtx):
    tp = ctx.tp_size
    hp, kv, hd = _padded_heads(cfg, ctx), cfg.n_kv_heads, cfg.head_dim_
    if hp % tp == 0 and kv % tp == 0:
        return "model", "model", None, None
    if hp % tp == 0:
        return "model", None, None, None          # KV replicated (GQA-TP)
    if hd % tp == 0:
        return None, None, "model", "model"       # head_dim TP
    return None, None, None, None


def _kv_index(cfg: ModelConfig, ctx: MeshCtx):
    """Padded-q-head -> kv-head mapping (GQA groups preserved for the real
    heads; padded heads borrow group 0 — their wo rows learn from scratch)."""
    import numpy as np
    h, kv = cfg.n_heads, cfg.n_kv_heads
    hp = _padded_heads(cfg, ctx)
    group = max(h // kv, 1)
    return np.asarray([min(j, h - 1) // group for j in range(hp)], np.int32)


def _mlp_axis(d_ff: int, ctx: MeshCtx) -> Optional[str]:
    return "model" if d_ff % ctx.tp_size == 0 else None


# ---------------------------------------------------------------- attention


def attention_spec(cfg: ModelConfig, ctx: MeshCtx, *, cross: bool = False) -> Dict:
    d, kv, hd = cfg.d_model, cfg.n_kv_heads, cfg.head_dim_
    hp = _padded_heads(cfg, ctx)
    qh, kvh, qd, kvd = _attn_layout(cfg, ctx)
    spec = {
        "wq": Spec((d, hp, hd), ("fsdp", qh, qd)),
        "wk": Spec((d, kv, hd), ("fsdp", kvh, kvd)),
        "wv": Spec((d, kv, hd), ("fsdp", kvh, kvd)),
        "wo": Spec((hp, hd, d), (qh, qd, "fsdp")),
    }
    if cfg.qkv_bias and not cross:
        spec["bq"] = Spec((hp, hd), (qh, qd), init="zeros")
        spec["bk"] = Spec((kv, hd), (kvh, kvd), init="zeros")
        spec["bv"] = Spec((kv, hd), (kvh, kvd), init="zeros")
    if cross:
        spec["gate"] = Spec((), (), init="zeros")   # gated cross-attn (VLM)
    return spec


def _qkv(p: Dict, x: jax.Array, kv_src: jax.Array, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return q, k, v


def attention_apply(
    p: Dict, x: jax.Array, cfg: ModelConfig, ctx: MeshCtx, *,
    causal: bool = True,
    window: Optional[int] = None,
    kv_src: Optional[jax.Array] = None,     # cross-attention source
    use_rope: bool = True,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    cross = kv_src is not None
    src = kv_src if cross else x
    q, k, v = _qkv(p, x, src, cfg)
    if use_rope and not cross:
        pos = positions if positions is not None else jnp.arange(x.shape[1])
        q = layers.rope(q, pos, cfg.rope_theta)
        k = layers.rope(k, pos, cfg.rope_theta)
    idx = jnp.asarray(_kv_index(cfg, ctx))
    ke, ve = jnp.take(k, idx, axis=2), jnp.take(v, idx, axis=2)
    out = layers.flash_attention(
        q, ke, ve, causal=causal and not cross, window=window,
        chunk=cfg.attn_chunk)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if cross:
        y = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype) * y
    return y


def attention_prefill(p, x, cfg, ctx, *, window=None, cache_len=None):
    """Forward + return the KV cache (window-clipped, with decode headroom)."""
    s = x.shape[1]
    pos = jnp.arange(s)
    q, k, v = _qkv(p, x, x, cfg)
    q = layers.rope(q, pos, cfg.rope_theta)
    k = layers.rope(k, pos, cfg.rope_theta)
    idx = jnp.asarray(_kv_index(cfg, ctx))
    out = layers.flash_attention(q, jnp.take(k, idx, axis=2),
                                 jnp.take(v, idx, axis=2),
                                 causal=True, window=window,
                                 chunk=cfg.attn_chunk)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if window:
        # ring buffer of exactly `window` slots: token t lives at t % window
        keep = min(window, s)
        slots = jnp.arange(s - keep, s) % window
        shape = (k.shape[0], window) + k.shape[2:]
        ck = jnp.zeros(shape, k.dtype).at[:, slots].set(k[:, -keep:])
        cv = jnp.zeros(shape, v.dtype).at[:, slots].set(v[:, -keep:])
    else:
        cache_len = cache_len or s + 128
        pad = ((0, 0), (0, cache_len - s), (0, 0), (0, 0))
        ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
    return y, {"k": ck, "v": cv}


def attention_decode(p, x, cache: Dict, pos: jax.Array, cfg: ModelConfig,
                     ctx: MeshCtx, *, window: Optional[int] = None,
                     cross: bool = False):
    """x: (B, 1, D).  cache: {"k","v"} (B, S, KV, hd).  pos: tokens so far."""
    if cross:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        if "bq" in p:
            q = q + p["bq"].astype(x.dtype)
        idx = jnp.asarray(_kv_index(cfg, ctx))
        out = layers.decode_attention(q, jnp.take(cache["k"], idx, axis=2),
                                      jnp.take(cache["v"], idx, axis=2),
                                      cache["k"].shape[1])
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        y = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype) * y
        return y, cache
    q, k, v = _qkv(p, x, x, cfg)
    pos_b = jnp.broadcast_to(pos, (x.shape[0], 1))
    q = layers.rope(q, pos_b, cfg.rope_theta)
    k = layers.rope(k, pos_b, cfg.rope_theta)
    s = cache["k"].shape[1]
    slot = (pos % s if window else jnp.minimum(pos, s - 1)).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    # write the new KV at the ring-buffer slot
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (zero, slot, zero, zero))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (zero, slot, zero, zero))
    idx = jnp.asarray(_kv_index(cfg, ctx))
    out = layers.decode_attention(q, jnp.take(ck, idx, axis=2),
                                  jnp.take(cv, idx, axis=2),
                                  jnp.minimum(pos + 1, s),
                                  window=None)  # ring buffer already clips
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------- dense MLP


def mlp_spec(cfg: ModelConfig, ctx: MeshCtx, d_ff: Optional[int] = None) -> Dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ax = _mlp_axis(f, ctx)
    spec = {"wi": Spec((d, f), ("fsdp", ax)), "wo": Spec((f, d), (ax, "fsdp"))}
    if cfg.act == "silu":
        spec["wg"] = Spec((d, f), ("fsdp", ax))
    return spec


def mlp_apply(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    pc = {k: v.astype(x.dtype) for k, v in p.items()}
    return layers.mlp(pc, x, cfg.act)


# ---------------------------------------------------------------- MoE


def moe_spec(cfg: ModelConfig, ctx: MeshCtx) -> Dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ep = e % ctx.tp_size == 0
    if ep:
        ax = ("model", "fsdp", None)
    else:
        ax = (None, "fsdp", "model")
    spec = {
        "router": Spec((d, e), (None, None), scale=0.02 / math.sqrt(d)),
        "wi": Spec((e, d, f), ax),
        "wg": Spec((e, d, f), ax),
        "wo": Spec((e, f, d), (ax[0], ax[2], ax[1])),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        spec["shared"] = mlp_spec(cfg, ctx, d_ff=fs)
    return spec


def _moe_local(x: jax.Array, p: Dict, cfg: ModelConfig, n_local: int,
               exp_offset: jax.Array, capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Token dispatch + expert compute on one shard.

    x: (T, D) local tokens; weights already local (n_local experts).
    Returns (out (T, D) — partial, caller psums over the expert/TP axis —
    and the load-balance aux loss).
    """
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)
    topv, topi = jax.lax.top_k(probs, k)                          # (T, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    # load-balance aux (Switch-style): E * Σ_e frac_tokens_e * frac_prob_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    flat_e = topi.reshape(-1)                                     # (T*k,)
    order = jnp.argsort(flat_e)
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])

    def slots_for(e_loc):
        eg = e_loc + exp_offset
        idx = jnp.take(order, starts[eg] + jnp.arange(capacity, dtype=jnp.int32),
                       mode="fill", fill_value=t * k)
        valid = jnp.arange(capacity) < counts[eg]
        return jnp.where(valid, idx, t * k), valid

    idxs, valids = jax.vmap(slots_for)(jnp.arange(n_local))       # (E_l, C)
    tok = jnp.where(valids, idxs // k, t)                         # sentinel t
    gate = jnp.take(topv.reshape(-1), idxs, mode="fill",
                    fill_value=0.0) * valids                      # (E_l, C)

    xg = jnp.take(x, tok, axis=0, mode="fill", fill_value=0.0)    # (E_l, C, D)
    wi, wg, wo = (p["wi"].astype(x.dtype), p["wg"].astype(x.dtype),
                  p["wo"].astype(x.dtype))
    hidden = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, wi))
    hidden = hidden * jnp.einsum("ecd,edf->ecf", xg, wg)
    ye = jnp.einsum("ecf,efd->ecd", hidden, wo)                   # (E_l, C, D)
    ye = ye * gate[..., None].astype(ye.dtype)

    out = jnp.zeros((t + 1, d), ye.dtype).at[tok.reshape(-1)].add(
        ye.reshape(-1, d), mode="drop")
    return out[:t], aux


def moe_apply(p: Dict, x: jax.Array, cfg: ModelConfig,
              ctx: MeshCtx) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).  EP over tp axis via shard_map when a
    mesh is present; identical math single-device otherwise."""
    b, s, d = x.shape
    e = cfg.n_experts
    ep = e % ctx.tp_size == 0 and ctx.tp_size > 1
    xf = x.reshape(b * s, d)

    if ctx.mesh is None:
        cap = int(b * s * cfg.top_k / e * cfg.capacity_factor) + 1
        out, aux = _moe_local(xf, p, cfg, e, jnp.int32(0), cap)
    else:
        from jax.sharding import PartitionSpec as P

        dp = ctx.dp_axes
        dp_ok = (b * s) % ctx.dp_size == 0
        t_loc = b * s // ctx.dp_size if dp_ok else b * s
        tok_spec = P(dp, None) if dp_ok else P(None, None)
        cap = int(t_loc * cfg.top_k / e * cfg.capacity_factor) + 1
        cap = -(-cap // 8) * 8
        n_local = e // ctx.tp_size if ep else e
        fa = ctx.fsdp_axis
        if ep:
            w_spec = P("model", fa, None)
            wo_spec = P("model", None, fa)
        else:
            w_spec = P(None, fa, "model")
            wo_spec = P(None, "model", fa)

        # NOTE (§Perf kimi iteration 2, refuted): emitting the expert combine
        # as psum_scatter into a (dp, model)-sharded token stream tripled the
        # all-reduce volume — GSPMD re-gathers the scattered output to feed
        # the replicated shared-expert branch and the residual add.  A full
        # psum with GSPMD left to fuse the downstream reshard is cheaper.
        use_rs = False

        def shard_fn(xl, router, wi, wg, wo):
            if fa is not None:  # FSDP: gather weight shards for this layer
                wi = jax.lax.all_gather(wi, fa, axis=1, tiled=True)
                wg = jax.lax.all_gather(wg, fa, axis=1, tiled=True)
                wo = jax.lax.all_gather(wo, fa, axis=2, tiled=True)
            off = (jax.lax.axis_index("model") * n_local) if ep else jnp.int32(0)
            pl = {"router": router, "wi": wi, "wg": wg, "wo": wo}
            out, aux = _moe_local(xl, pl, cfg, n_local, off, cap)
            if use_rs:
                out = jax.lax.psum_scatter(out, "model", scatter_dimension=0,
                                           tiled=True)
            else:
                out = jax.lax.psum(out, "model")
            if dp_ok:
                aux = jax.lax.pmean(aux, dp)
            return out, aux

        out_spec = (P((*dp, "model") if dp_ok else None, None) if use_rs
                    else tok_spec)
        out, aux = jax.shard_map(
            shard_fn, mesh=ctx.mesh,
            in_specs=(tok_spec, P(None, None), w_spec, w_spec, wo_spec),
            out_specs=(out_spec, P()),
            check_vma=False,
        )(xf, p["router"], p["wi"], p["wg"], p["wo"])

    out = out.reshape(b, s, d).astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + mlp_apply(p["shared"], x, cfg)
    return out, aux


# ---------------------------------------------------------------- Mamba-1


def mamba_spec(cfg: ModelConfig, ctx: MeshCtx) -> Dict:
    d, di, n, r = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    ax = "model" if di % ctx.tp_size == 0 else None
    return {
        "wx": Spec((d, di), ("fsdp", ax)),
        "wz": Spec((d, di), ("fsdp", ax)),
        "conv_w": Spec((di, cfg.d_conv), (ax, None)),
        "conv_b": Spec((di,), (ax,), init="zeros"),
        "x_proj": Spec((di, r + 2 * n), (ax, None)),
        "dt_proj": Spec((r, di), (None, ax)),
        "dt_bias": Spec((di,), (ax,), init="dt_bias"),
        "a_log": Spec((di, n), (ax, None), init="mamba_a"),
        "d_skip": Spec((di,), (ax,), init="ones"),
        "out_proj": Spec((di, d), (ax, "fsdp")),
    }


def _mamba_core(p, xc, cfg, h0):
    """xc: post-conv activations (B, S, di).  Returns (y, h_last)."""
    n, r = cfg.ssm_state, cfg.dt_rank_
    proj = xc @ p["x_proj"].astype(xc.dtype)                      # (B,S,r+2N)
    dt_r, b_mat, c_mat = jnp.split(proj, [r, r + n], axis=-1)
    dt = jax.nn.softplus(
        dt_r.astype(jnp.float32) @ p["dt_proj"].astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))                       # (B,S,di)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                  # (di,N)
    cd = xc.dtype                                                  # bf16 path
    a_bar = jnp.exp(dt[..., None] * a).astype(cd)                 # (B,S,di,N)
    bx = (dt[..., None].astype(cd) * b_mat[:, :, None, :].astype(cd)
          * xc[..., None])
    hs, h_last = layers.chunked_linear_recurrence(a_bar, bx, h0,
                                                  cfg.scan_chunk,
                                                  compute_dtype=cd)
    y = jnp.einsum("bsdn,bsn->bsd", hs, c_mat,
                   preferred_element_type=jnp.float32)
    y = y + p["d_skip"].astype(jnp.float32) * xc.astype(jnp.float32)
    return y.astype(xc.dtype), h_last


def mamba_apply(p: Dict, x: jax.Array, cfg: ModelConfig, ctx: MeshCtx) -> jax.Array:
    b = x.shape[0]
    di, n = cfg.d_inner, cfg.ssm_state
    xz = x @ p["wx"].astype(x.dtype)
    z = x @ p["wz"].astype(x.dtype)
    xc, _ = layers.causal_conv1d(xz, p["conv_w"].astype(x.dtype))
    xc = jax.nn.silu(xc + p["conv_b"].astype(x.dtype))
    h0 = jnp.zeros((b, di, n), jnp.float32)
    y, _ = _mamba_core(p, xc, cfg, h0)
    y = y * jax.nn.silu(z)
    return y @ p["out_proj"].astype(x.dtype)


def mamba_init_cache(cfg: ModelConfig, batch: int, dtype) -> Dict:
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), dtype),
        "h": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
    }


def mamba_decode(p: Dict, x: jax.Array, cache: Dict, cfg: ModelConfig,
                 ctx: MeshCtx) -> Tuple[jax.Array, Dict]:
    """Single-token recurrent step.  x: (B, 1, D)."""
    n, r = cfg.ssm_state, cfg.dt_rank_
    xz = x @ p["wx"].astype(x.dtype)
    z = x @ p["wz"].astype(x.dtype)
    xc, conv_state = layers.causal_conv1d(xz, p["conv_w"].astype(x.dtype),
                                          cache["conv"])
    xc = jax.nn.silu(xc + p["conv_b"].astype(x.dtype))
    proj = xc @ p["x_proj"].astype(x.dtype)
    dt_r, b_mat, c_mat = jnp.split(proj, [r, r + n], axis=-1)
    dt = jax.nn.softplus(
        dt_r.astype(jnp.float32) @ p["dt_proj"].astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    a_bar = jnp.exp(dt[:, 0, :, None] * a)                        # (B,di,N)
    bx = (dt[:, 0, :, None] * b_mat[:, 0, None, :].astype(jnp.float32)
          * xc[:, 0, :, None].astype(jnp.float32))
    h = a_bar * cache["h"] + bx
    y = jnp.einsum("bdn,bn->bd", h, c_mat[:, 0].astype(jnp.float32))
    y = y + p["d_skip"].astype(jnp.float32) * xc[:, 0].astype(jnp.float32)
    y = (y.astype(x.dtype) * jax.nn.silu(z[:, 0]))[:, None, :]
    out = y @ p["out_proj"].astype(x.dtype)
    return out, {"conv": conv_state, "h": h}


# ---------------------------------------------------------------- RG-LRU


def rglru_spec(cfg: ModelConfig, ctx: MeshCtx) -> Dict:
    d, w = cfg.d_model, cfg.lru_width_
    ax = "model" if w % ctx.tp_size == 0 else None
    return {
        "wx": Spec((d, w), ("fsdp", ax)),
        "wy": Spec((d, w), ("fsdp", ax)),        # gate branch
        "conv_w": Spec((w, cfg.d_conv), (ax, None)),
        "conv_b": Spec((w,), (ax,), init="zeros"),
        "w_input": Spec((w, w), (None, ax)),
        "b_input": Spec((w,), (ax,), init="zeros"),
        "w_rec": Spec((w, w), (None, ax)),
        "b_rec": Spec((w,), (ax,), init="zeros"),
        "lam": Spec((w,), (ax,), init="rglru_a"),
        "out_proj": Spec((w, d), (ax, "fsdp")),
    }


_RGLRU_C = 8.0


def _rglru_gates(p, xc):
    xf = xc.astype(jnp.float32)
    i_gate = jax.nn.sigmoid(xf @ p["w_input"].astype(jnp.float32)
                            + p["b_input"].astype(jnp.float32))
    r_gate = jax.nn.sigmoid(xf @ p["w_rec"].astype(jnp.float32)
                            + p["b_rec"].astype(jnp.float32))
    log_a = -_RGLRU_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r_gate
    a = jnp.exp(log_a)
    gated_x = xf * i_gate
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * gated_x
    return a, b


def rglru_apply(p: Dict, x: jax.Array, cfg: ModelConfig, ctx: MeshCtx) -> jax.Array:
    b_sz, w = x.shape[0], cfg.lru_width_
    xz = x @ p["wx"].astype(x.dtype)
    gate = x @ p["wy"].astype(x.dtype)
    xc, _ = layers.causal_conv1d(xz, p["conv_w"].astype(x.dtype))
    xc = xc + p["conv_b"].astype(x.dtype)
    a, b = _rglru_gates(p, xc)
    hs, _ = layers.chunked_linear_recurrence(
        a, b, jnp.zeros((b_sz, w), jnp.float32), cfg.scan_chunk)
    y = hs.astype(x.dtype) * jax.nn.gelu(gate)
    return y @ p["out_proj"].astype(x.dtype)


def rglru_init_cache(cfg: ModelConfig, batch: int, dtype) -> Dict:
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.lru_width_), dtype),
        "h": jnp.zeros((batch, cfg.lru_width_), jnp.float32),
    }


def rglru_decode(p: Dict, x: jax.Array, cache: Dict, cfg: ModelConfig,
                 ctx: MeshCtx) -> Tuple[jax.Array, Dict]:
    xz = x @ p["wx"].astype(x.dtype)
    gate = x @ p["wy"].astype(x.dtype)
    xc, conv_state = layers.causal_conv1d(xz, p["conv_w"].astype(x.dtype),
                                          cache["conv"])
    xc = xc + p["conv_b"].astype(x.dtype)
    a, b = _rglru_gates(p, xc)                    # (B,1,W)
    h = a[:, 0] * cache["h"] + b[:, 0]
    y = h[:, None, :].astype(x.dtype) * jax.nn.gelu(gate)
    return y @ p["out_proj"].astype(x.dtype), {"conv": conv_state, "h": h}


# ---------------------------------------------------------------- norms


def norm_spec(cfg: ModelConfig) -> Dict:
    return {"scale": Spec((cfg.d_model,), (None,), init="zeros")}


def norm_apply(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    return layers.rms_norm(x, p["scale"], cfg.norm_eps)
