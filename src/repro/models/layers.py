"""Core transformer layers: RMSNorm, RoPE, GQA attention, SwiGLU.

Pure-function style: ``init_*`` build parameter pytrees (dict of arrays),
``apply_*`` are jit-friendly.  Attention is computed blockwise over KV
chunks with an online softmax (flash-attention structure in pure JAX +
lax.scan), so 32k-token prefill never materializes an (S, S) score matrix.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from .config import ModelConfig

KV_CHUNK = 1024          # flash-attention KV block length
Q_CHUNK = 4096           # flash-attention Q block length (long prefill)


def _dt(cfg: ModelConfig, kind: str):
    s = cfg.param_dtype if kind == "param" else cfg.compute_dtype
    return jnp.dtype(s)


# ---------------------------------------------------------------------------
# activation-sharding constraints (GSPMD guidance; no-ops without a mesh)
# ---------------------------------------------------------------------------
def _mesh_axes() -> dict:
    m = jax.sharding.get_abstract_mesh()
    if not m.axis_names:
        return {}
    return {a: m.shape[a] for a in m.axis_names}


def _dp_axes(axes: dict):
    names = tuple(a for a in ("pod", "data") if a in axes)
    if not names:
        return None
    return names if len(names) > 1 else names[0]


def constrain_tokens(x: jax.Array) -> jax.Array:
    """Residual-stream constraint: batch over dp axes, rest replicated
    (Megatron-style activation layout).  Pins the backward pass too —
    without it GSPMD reshards f32 cotangents through all-gathers."""
    axes = _mesh_axes()
    dp = _dp_axes(axes)
    if dp is None or x.ndim < 2:
        return x
    batch = x.shape[0]
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= axes[a]
    if batch % dp_size:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, P(dp, *([None] * (x.ndim - 1))))


def constrain_heads(x: jax.Array, *, shard_heads: bool = True) -> jax.Array:
    """(B, S, H, hd) constraint: batch over dp, heads over model when the
    head count divides the model axis (else leave GSPMD free).

    ``shard_heads=False`` pins a head-replicated layout: used for repeated
    GQA K/V, which are produced replicated (kv_heads < TP) — GSPMD then
    *slices* them locally for the head-sharded score einsum instead of
    all-gathering a head-sharded constraint target."""
    axes = _mesh_axes()
    dp = _dp_axes(axes)
    msize = axes.get("model", 1)
    if dp is None or x.ndim != 4:
        return x
    batch, _s, h, _hd = x.shape
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= axes[a]
    if batch % dp_size:
        return x
    from jax.sharding import PartitionSpec as P
    if shard_heads and msize > 1 and h % msize == 0:
        return jax.lax.with_sharding_constraint(x, P(dp, None, "model", None))
    return jax.lax.with_sharding_constraint(x, P(dp, None, None, None))


def dense_init(key, in_dim: int, out_dim: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_cv(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def _rmsnorm_fwd(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv32 = jax.lax.rsqrt(var + eps)
    inv = inv32.astype(x.dtype)
    return x * inv * scale.astype(x.dtype), (x, inv, scale)


def _rmsnorm_bwd(eps, res, dy):
    """Hand-written backward, activation-dtype throughout: autodiff of the
    f32-upcast variance path otherwise produces f32 (B, S, D) cotangents
    whose TP collectives double in size (the dominant collective in the
    baseline §Perf profile).  Only the per-row reductions accumulate f32.
    dx = s*inv*dy - x * inv^3 * mean(dy * s * x)  ;  ds = sum(dy * x*inv)
    """
    x, inv, scale = res
    d = x.shape[-1]
    dy = dy.astype(x.dtype)   # downcast f32 cotangents arriving from loss
    s = scale.astype(x.dtype)
    dy_s = dy * s                                           # bf16
    # per-row scalar: mean(dy*s*x) in f32 (small tensor)
    m = jnp.sum((dy_s * x).astype(jnp.float32), axis=-1,
                keepdims=True) / d
    inv32 = inv.astype(jnp.float32)
    coef = (inv32 * inv32 * inv32 * m).astype(x.dtype)      # (B,S,1)
    dx = dy_s * inv - x * coef
    dscale = jnp.sum((dy * x * inv).astype(jnp.float32),
                     axis=tuple(range(x.ndim - 1)))
    return dx, dscale.astype(scale.dtype)


_rmsnorm_cv.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with f32 variance reduction and a custom bf16 backward."""
    return _rmsnorm_cv(x, p["scale"], eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32.

    cos/sin are computed in f32 (the precision that matters) and cast; the
    rotations run at the activation dtype so no (B, S, H, hd) f32 tensor
    (or f32 cotangent) exists."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(ang).astype(x.dtype)[:, :, None, :]
    sin = jnp.sin(ang).astype(x.dtype)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig) -> dict:
    dt = _dt(cfg, "param")
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, dt),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dt),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dt),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dt)
        p["k_norm"] = init_rmsnorm(hd, dt)
    return p


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.repeat(x, n_rep, axis=2)


# ---------------------------------------------------------------------------
# Fused flash attention (the Pallas-kernel path; see DESIGN.md §Perf)
# ---------------------------------------------------------------------------
# On TPU the region lowers to kernels/flash_attention.py (score tiles stay
# in VMEM -> HBM traffic is Q+K+V+O only).  The jnp implementation below is
# the same math (the kernel's reference lowering) and is what the dry-run
# traces; the jaxpr cost walker recognizes the ``fused_*`` jit boundaries
# and counts boundary bytes only (flops counted fully).
def _fused_flash_fwd_impl(q, k, v, q_pos, kv_pos, *, window: int,
                          softcap: float):
    """-> (out (B,Sq,H,hd), lse (B,H,Sq))."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    chunk = min(KV_CHUNK, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)),
                         constant_values=jnp.iinfo(jnp.int32).max // 2)
    kc = jnp.moveaxis(k.reshape(b, n_chunks, chunk, h, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, chunk, h, hd), 1, 0)
    pc = jnp.moveaxis(kv_pos.reshape(b, n_chunks, chunk), 1, 0)
    # operands stay at activation dtype; MXU accumulates f32 exactly —
    # no f32 copies of q/k/v exist (their f32 cotangents were the largest
    # collectives in the baseline profile)

    def step(carry, inp):
        m_run, l_run, acc = carry
        k_i, v_i, p_i = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_i,
                       preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        keep = q_pos[:, None, :, None] >= p_i[:, None, None, :]
        if window > 0:
            keep &= (q_pos[:, None, :, None] - p_i[:, None, None, :]
                     < window)
        s = jnp.where(keep, s, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(keep, p, 0.0)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_i.dtype), v_i,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    lse = m + jnp.log(jnp.maximum(l, 1e-20))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype), lse


@partial(jax.jit, static_argnames=("window", "softcap"))
def fused_flash_fwd(q, k, v, q_pos, kv_pos, *, window: int, softcap: float):
    return _fused_flash_fwd_impl(q, k, v, q_pos, kv_pos, window=window,
                                 softcap=softcap)


def _fused_flash_bwd_impl(q, k, v, q_pos, kv_pos, out, lse, dout, *,
                          window: int, softcap: float):
    """Recompute-based flash backward, chunked over KV."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    chunk = min(KV_CHUNK, sk)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)),
                         constant_values=jnp.iinfo(jnp.int32).max // 2)
    kc = jnp.moveaxis(k.reshape(b, n_chunks, chunk, h, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, chunk, h, hd), 1, 0)
    pc = jnp.moveaxis(kv_pos.reshape(b, n_chunks, chunk), 1, 0)
    cdt = q.dtype
    do = jnp.einsum("bqhd->bhqd", dout).astype(cdt)
    delta = jnp.sum((dout * out).astype(jnp.float32), axis=-1)  # (B,Sq,H)
    delta = jnp.einsum("bqh->bhq", delta)                       # (B,H,Sq)

    def step(dq_acc, inp):
        k_i, v_i, p_i = inp
        s_raw = jnp.einsum("bqhd,bkhd->bhqk", q, k_i,
                           preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            t = jnp.tanh(s_raw / softcap)
            s = softcap * t
        else:
            s = s_raw
        keep = q_pos[:, None, :, None] >= p_i[:, None, None, :]
        if window > 0:
            keep &= (q_pos[:, None, :, None] - p_i[:, None, None, :]
                     < window)
        p = jnp.where(keep, jnp.exp(s - lse[..., None]), 0.0)
        p16 = p.astype(cdt)
        dv_i = jnp.einsum("bhqk,bhqd->bkhd", p16, do,
                          preferred_element_type=jnp.float32).astype(cdt)
        dp = jnp.einsum("bhqd,bkhd->bhqk", do, v_i,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        if softcap > 0.0:
            ds = ds * (1.0 - t * t)
        ds16 = ds.astype(cdt)
        dq_acc = dq_acc + jnp.einsum(
            "bhqk,bkhd->bqhd", ds16, k_i,
            preferred_element_type=jnp.float32) * scale
        dk_i = (jnp.einsum("bhqk,bqhd->bkhd", ds16, q,
                           preferred_element_type=jnp.float32)
                * scale).astype(cdt)
        return dq_acc, (dk_i, dv_i)

    dq0 = jnp.zeros((b, sq, h, hd), jnp.float32)
    dq, (dk_c, dv_c) = jax.lax.scan(step, dq0, (kc, vc, pc))
    dk = jnp.moveaxis(dk_c, 0, 1).reshape(b, n_chunks * chunk, h, hd)
    dv = jnp.moveaxis(dv_c, 0, 1).reshape(b, n_chunks * chunk, h, hd)
    if pad:
        dk, dv = dk[:, :sk], dv[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@partial(jax.jit, static_argnames=("window", "softcap"))
def fused_flash_bwd(q, k, v, q_pos, kv_pos, out, lse, dout, *,
                    window: int, softcap: float):
    return _fused_flash_bwd_impl(q, k, v, q_pos, kv_pos, out, lse, dout,
                                 window=window, softcap=softcap)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def fused_attention(window: int, softcap: float, q, k, v, q_pos, kv_pos):
    out, _ = fused_flash_fwd(q, k, v, q_pos, kv_pos, window=window,
                             softcap=softcap)
    return out


def _fa_fwd(window, softcap, q, k, v, q_pos, kv_pos):
    out, lse = fused_flash_fwd(q, k, v, q_pos, kv_pos, window=window,
                               softcap=softcap)
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _fa_bwd(window, softcap, res, dout):
    q, k, v, q_pos, kv_pos, out, lse = res
    dq, dk, dv = fused_flash_bwd(q, k, v, q_pos, kv_pos, out, lse, dout,
                                 window=window, softcap=softcap)
    import numpy as _np
    zp = _np.zeros(q_pos.shape, dtype=jax.dtypes.float0)
    zk = _np.zeros(kv_pos.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, zp, zk


fused_attention.defvjp(_fa_fwd, _fa_bwd)


def _flash_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                  q_pos: jax.Array, kv_pos: jax.Array, *,
                  sliding_window: int = 0, softcap: float = 0.0,
                  extra_mask: jax.Array | None = None,
                  fused: bool = False) -> jax.Array:
    """Online-softmax attention, Q-chunked then KV-chunked (flash structure).

    Long prefill (Sq > Q_CHUNK) scans over Q blocks so the per-step score
    tile is (B, H, Q_CHUNK, KV_CHUNK) regardless of sequence length.
    ``fused=True`` routes through the fused_attention region (the Pallas
    flash kernel on TPU); extra_mask falls back to the unfused path.
    """
    if fused and extra_mask is None:
        return fused_attention(sliding_window, softcap, q, k, v,
                               q_pos, kv_pos)
    sq = q.shape[1]
    if sq > Q_CHUNK and sq % Q_CHUNK == 0:
        nq = sq // Q_CHUNK
        qc = jnp.moveaxis(q.reshape(q.shape[0], nq, Q_CHUNK, *q.shape[2:]),
                          1, 0)
        pc = jnp.moveaxis(q_pos.reshape(q_pos.shape[0], nq, Q_CHUNK), 1, 0)
        if extra_mask is not None:
            mc = jnp.moveaxis(extra_mask.reshape(
                extra_mask.shape[0], nq, Q_CHUNK, extra_mask.shape[-1]), 1, 0)

            def qstep(_, inp):
                qi, pi, mi = inp
                return None, _flash_attend_inner(
                    qi, k, v, pi, kv_pos, sliding_window=sliding_window,
                    softcap=softcap, extra_mask=mi)

            _, outs = jax.lax.scan(qstep, None, (qc, pc, mc))
        else:
            def qstep(_, inp):
                qi, pi = inp
                return None, _flash_attend_inner(
                    qi, k, v, pi, kv_pos, sliding_window=sliding_window,
                    softcap=softcap, extra_mask=None)

            _, outs = jax.lax.scan(qstep, None, (qc, pc))
        return jnp.moveaxis(outs, 0, 1).reshape(q.shape)
    return _flash_attend_inner(q, k, v, q_pos, kv_pos,
                               sliding_window=sliding_window,
                               softcap=softcap, extra_mask=extra_mask)


def _flash_attend_inner(q: jax.Array, k: jax.Array, v: jax.Array,
                        q_pos: jax.Array, kv_pos: jax.Array, *,
                        sliding_window: int = 0, softcap: float = 0.0,
                        extra_mask: jax.Array | None = None) -> jax.Array:
    """Online-softmax attention over KV chunks.

    q: (B, Sq, H, hd); k/v: (B, Sk, H, hd) (kv already head-repeated);
    q_pos: (B, Sq), kv_pos: (B, Sk).  Causal by position comparison, so it
    works for train (Sq == Sk), prefill and decode (Sq == 1) alike.
    extra_mask: optional (B, Sq, Sk) additive-keep boolean mask
    (True = attend), e.g. PuD-composed document masks.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    chunk = min(KV_CHUNK, sk)
    n_chunks = sk // chunk if sk % chunk == 0 else -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)),
                         constant_values=jnp.iinfo(jnp.int32).max)
        if extra_mask is not None:
            extra_mask = jnp.pad(extra_mask, ((0, 0), (0, 0), (0, pad)))
    kc = k.reshape(b, n_chunks, chunk, h, hd)
    vc = v.reshape(b, n_chunks, chunk, h, hd)
    pc = kv_pos.reshape(b, n_chunks, chunk)
    mc = (extra_mask.reshape(b, sq, n_chunks, chunk)
          if extra_mask is not None else None)

    qf = q.astype(jnp.float32)

    def step(carry, inp):
        m_run, l_run, acc = carry
        if mc is None:
            k_i, v_i, p_i = inp
        else:
            k_i, v_i, p_i, em_i = inp
        # scores: (B, H, Sq, chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_i.astype(jnp.float32)) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        keep = q_pos[:, None, :, None] >= p_i[:, None, None, :]
        if sliding_window > 0:
            keep &= (q_pos[:, None, :, None] - p_i[:, None, None, :]
                     < sliding_window)
        if mc is not None:
            keep &= em_i[:, None, :, :]
        s = jnp.where(keep, s, -jnp.inf)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(keep, p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m_run), m_run - m_safe,
                                 -jnp.inf))
        corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_i.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    xs = (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
          jnp.moveaxis(pc, 1, 0))
    if mc is not None:
        xs = xs + (jnp.moveaxis(mc, 2, 0),)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), xs)
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def apply_attention(p: dict, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array, *,
                    kv_cache: dict | None = None,
                    extra_mask: jax.Array | None = None,
                    ) -> tuple[jax.Array, dict | None]:
    """x: (B, S, D).  kv_cache (decode): {"k","v": (B, S_max, KV, hd),
    "length": ()} — returns updated cache."""
    b, s, d = x.shape
    hd = cfg.hd
    cdt = _dt(cfg, "compute")
    xq = (x @ p["wq"].astype(cdt)).reshape(b, s, cfg.n_heads, hd)
    xk = (x @ p["wk"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, hd)
    xv = (x @ p["wv"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, hd)
    xq = constrain_heads(xq)
    if cfg.qk_norm:
        xq = rmsnorm(p["q_norm"], xq, cfg.norm_eps)
        xk = rmsnorm(p["k_norm"], xk, cfg.norm_eps)
    xq = apply_rope(xq, positions, cfg.rope_theta)
    xk = apply_rope(xk, positions, cfg.rope_theta)

    n_rep = cfg.n_heads // cfg.n_kv_heads
    if kv_cache is None:
        k = constrain_heads(_repeat_kv(xk, n_rep), shard_heads=False)
        v = constrain_heads(_repeat_kv(xv, n_rep), shard_heads=False)
        out = _flash_attend(xq, k, v, positions, positions,
                            sliding_window=cfg.sliding_window,
                            softcap=cfg.attn_logit_softcap,
                            extra_mask=extra_mask,
                            fused=cfg.fused_attention)
        out = constrain_heads(out)
        new_cache = None
    else:
        # decode (s == 1): per-batch ring-buffer write at position % s_max
        # (the ring only wraps for sliding-window caches, s_max == window);
        # prefill-into-cache (s > 1): fresh slot, write the block at 0.
        s_max = kv_cache["k"].shape[1]
        if s == 1:
            idx = positions[:, 0].astype(jnp.int32) % s_max
            bar = jnp.arange(b)
            k_all = kv_cache["k"].at[bar, idx].set(
                xk[:, 0].astype(kv_cache["k"].dtype))
            v_all = kv_cache["v"].at[bar, idx].set(
                xv[:, 0].astype(kv_cache["v"].dtype))
            pos_all = kv_cache["pos"].at[bar, idx].set(
                positions[:, 0].astype(jnp.int32))
        else:
            k_all = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["k"], xk.astype(kv_cache["k"].dtype), 0, axis=1)
            v_all = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["v"], xv.astype(kv_cache["v"].dtype), 0, axis=1)
            pos_all = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["pos"], positions.astype(jnp.int32), 0, axis=1)
        kv_pos = pos_all
        k = _repeat_kv(k_all.astype(cdt), n_rep)
        v = _repeat_kv(v_all.astype(cdt), n_rep)
        out = _flash_attend(xq, k, v, positions, kv_pos,
                            sliding_window=cfg.sliding_window,
                            softcap=cfg.attn_logit_softcap,
                            fused=cfg.fused_attention)
        new_cache = {"k": k_all, "v": v_all, "pos": pos_all}
    out = out.reshape(b, s, cfg.n_heads * hd)
    return out @ p["wo"].astype(cdt), new_cache


#: position sentinel for unwritten/invalid cache slots — never passes the
#: causal check (q_pos >= kv_pos), so stale slots are invisible.
POS_SENTINEL = jnp.iinfo(jnp.int32).max // 2


def init_kv_cache(cfg: ModelConfig, batch: int, s_max: int,
                  dtype=jnp.bfloat16) -> dict:
    return {
        "k": jnp.zeros((batch, s_max, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, s_max, cfg.n_kv_heads, cfg.hd), dtype),
        "pos": jnp.full((batch, s_max), POS_SENTINEL, jnp.int32),
    }


# ---------------------------------------------------------------------------
# Cross-attention (VLM): queries from text stream, K/V from image embeddings
# ---------------------------------------------------------------------------
def init_cross_attention(key, cfg: ModelConfig) -> dict:
    return init_attention(key, cfg)


def apply_cross_attention(p: dict, cfg: ModelConfig, x: jax.Array,
                          image_embeds: jax.Array) -> jax.Array:
    """x: (B, S, D); image_embeds: (B, T_img, D) (stub frontend output)."""
    b, s, d = x.shape
    t = image_embeds.shape[1]
    hd = cfg.hd
    cdt = _dt(cfg, "compute")
    xq = (x @ p["wq"].astype(cdt)).reshape(b, s, cfg.n_heads, hd)
    xk = (image_embeds.astype(cdt) @ p["wk"].astype(cdt)).reshape(
        b, t, cfg.n_kv_heads, hd)
    xv = (image_embeds.astype(cdt) @ p["wv"].astype(cdt)).reshape(
        b, t, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        xq = rmsnorm(p["q_norm"], xq, cfg.norm_eps)
        xk = rmsnorm(p["k_norm"], xk, cfg.norm_eps)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k = _repeat_kv(xk, n_rep)
    v = _repeat_kv(xv, n_rep)
    # non-causal: every text token sees every image token
    qpos = jnp.ones((b, s), jnp.int32)
    kpos = jnp.zeros((b, t), jnp.int32)
    out = _flash_attend(xq, k, v, qpos, kpos, fused=cfg.fused_attention)
    return out.reshape(b, s, cfg.n_heads * hd) @ p["wo"].astype(cdt)


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    dt = _dt(cfg, "param")
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], cfg.d_model, d_ff, dt),
        "w_up": dense_init(ks[1], cfg.d_model, d_ff, dt),
        "w_down": dense_init(ks[2], d_ff, cfg.d_model, dt),
    }


def apply_mlp(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    cdt = _dt(cfg, "compute")
    g = jax.nn.silu(x @ p["w_gate"].astype(cdt))
    u = x @ p["w_up"].astype(cdt)
    return (g * u) @ p["w_down"].astype(cdt)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def init_embedding(key, cfg: ModelConfig) -> dict:
    dt = _dt(cfg, "param")
    p = {"table": (jax.random.normal(key, (cfg.vocab, cfg.d_model),
                                     jnp.float32) * 0.02).astype(dt)}
    return p


def embed(p: dict, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    cdt = _dt(cfg, "compute")
    # pin BOTH the gather input and output layouts: for tied tables GSPMD
    # otherwise propagates the unembed contraction's d_model sharding back
    # into the gather and (indivisible vocab, e.g. granite's 49155) emits
    # invalid HLO ("slice dim size greater than dynamic slice dimension")
    table = p["table"].astype(cdt)
    axes = _mesh_axes()
    msize = axes.get("model", 1)
    if axes and msize > 1:
        from jax.sharding import PartitionSpec as P
        vspec = "model" if table.shape[0] % msize == 0 else None
        table = jax.lax.with_sharding_constraint(table, P(vspec, None))
    return constrain_tokens(table[tokens])


def unembed(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """-> logits (B, S, V) in float32 (f32 MXU accumulation over bf16
    operands: no f32 copy of the residual stream; its cotangent stays at
    the activation dtype)."""
    return jnp.einsum("bsd,vd->bsv", x, p["table"].astype(x.dtype),
                      preferred_element_type=jnp.float32)
