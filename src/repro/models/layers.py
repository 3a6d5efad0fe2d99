"""Shared model layers: norms, RoPE, memory-efficient attention, FFN, MoE.

Attention is implemented flash-style in pure JAX — a double scan over query
and key/value chunks with an online-softmax accumulator — so prefill at 32k
(and beyond) compiles with bounded live memory instead of an S^2 score
tensor.  Local (sliding-window) attention gathers only the banded KV chunks
per query chunk, making it sub-quadratic end-to-end (RecurrentGemma blocks).

The MoE layer uses the static-capacity sort-based dispatch (MaxText-style
"dropping" implementation): tokens are argsorted by expert, gathered into an
[E, C, d] buffer, run through a batched per-expert SwiGLU, and combined with
their gate weights.  Compiled FLOPs therefore track *active* (top-k) params,
matching 6·N_active·D roofline accounting.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np



def rms_norm(x, scale, eps: float = 1e-6):
    """Variance reduction in f32; the elementwise apply stays in the input
    dtype, so no full-width f32 [B,S,d] tensor crosses HBM (§Perf-C5)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * (1.0 + scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float):
    """x [..., S, H, hd] (or [..., H, hd] with scalar positions)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs    # [..., S, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                              # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash attention with a custom VJP (tiled backward, p recomputed on-chip)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_cv(q, k, v, q_chunk: int = 1024, kv_chunk: int = 1024):
    """Causal GQA attention with the FlashAttention-2 style backward: the
    [Cq, Ck] probability tiles are recomputed inside the backward scan from
    (q, k, v, m, l) instead of being stashed — nothing O(S^2) ever crosses
    HBM (§Perf-C8).  q [B,S,H,hd]; k,v [B,S,Hkv,hd]."""
    out, _, _ = _flash_fwd_impl(q, k, v, q_chunk, kv_chunk)
    return out


def _flash_fwd_impl(q, k, v, q_chunk, kv_chunk):
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    Cq, Ck = min(q_chunk, S), min(kv_chunk, S)
    nq, nk = S // Cq, S // Ck
    scale = 1.0 / np.sqrt(hd)
    qs = q.reshape(B, nq, Cq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, Ck, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, Ck, Hkv, hd).transpose(1, 0, 2, 3, 4)
    q_idx = jnp.arange(Cq)
    k_idx = jnp.arange(Ck)

    def one_q(qi, q_i):
        m0 = jnp.full((B, Cq, Hkv, G), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Cq, Hkv, G), jnp.float32)
        o0 = jnp.zeros((B, Cq, Hkv, G, hd), jnp.float32)

        def kv_step(carry, kj):
            m, l, o = carry
            k_j, v_j, j = kj
            s = jnp.einsum("bqhgd,bchd->bqhgc", q_i.astype(jnp.float32),
                           k_j.astype(jnp.float32)) * scale
            mask = (qi * Cq + q_idx)[:, None] >= (j * Ck + k_idx)[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(mask[None, :, None, None, :],
                          jnp.exp(s - m_safe[..., None]), 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bqhgc,bchd->bqhgd", p, v_j.astype(jnp.float32))
            return (m_new, l_new, o_new), None

        (m, l, o), _ = jax.lax.scan(kv_step, (m0, l0, o0),
                                    (ks, vs, jnp.arange(nk)))
        out = (o / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
        return out, m, l

    outs, ms, ls = jax.lax.map(lambda a: one_q(*a), (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)
    return out, ms, ls                      # ms/ls [nq, B, Cq, Hkv, G]


def _flash_cv_fwd(q, k, v, q_chunk, kv_chunk):
    out, ms, ls = _flash_fwd_impl(q, k, v, q_chunk, kv_chunk)
    return out, (q, k, v, out, ms, ls)


def _flash_cv_bwd(q_chunk, kv_chunk, res, dout):
    q, k, v, out, ms, ls = res
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    Cq, Ck = min(q_chunk, S), min(kv_chunk, S)
    nq, nk = S // Cq, S // Ck
    scale = 1.0 / np.sqrt(hd)
    qs = q.reshape(B, nq, Cq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, Ck, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, Ck, Hkv, hd).transpose(1, 0, 2, 3, 4)
    dos = dout.reshape(B, nq, Cq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    os_ = out.reshape(B, nq, Cq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    q_idx = jnp.arange(Cq)
    k_idx = jnp.arange(Ck)

    def one_q(carry, xs):
        dk_acc, dv_acc = carry              # [nk, B, Ck, Hkv, hd] f32
        qi, q_i, do_i, o_i, m_i, l_i = xs
        do_f = do_i.astype(jnp.float32)
        # D = rowsum(dout * out)  [B,Cq,Hkv,G]
        D = jnp.einsum("bqhgd,bqhgd->bqhg", do_f, o_i.astype(jnp.float32))
        l_safe = jnp.maximum(l_i, 1e-30)

        def kv_step(inner, kj):
            dq_i, dk_acc, dv_acc = inner
            k_j, v_j, j = kj
            s = jnp.einsum("bqhgd,bchd->bqhgc", q_i.astype(jnp.float32),
                           k_j.astype(jnp.float32)) * scale
            mask = (qi * Cq + q_idx)[:, None] >= (j * Ck + k_idx)[None, :]
            m_safe = jnp.where(jnp.isfinite(m_i), m_i, 0.0)
            p = jnp.where(mask[None, :, None, None, :],
                          jnp.exp(s - m_safe[..., None]), 0.0) / \
                l_safe[..., None]                                  # [B,q,h,g,c]
            dv_j = jnp.einsum("bqhgc,bqhgd->bchd", p, do_f)
            dp = jnp.einsum("bqhgd,bchd->bqhgc", do_f,
                            v_j.astype(jnp.float32))
            ds = p * (dp - D[..., None]) * scale
            dq_i = dq_i + jnp.einsum("bqhgc,bchd->bqhgd", ds,
                                     k_j.astype(jnp.float32))
            dk_j = jnp.einsum("bqhgc,bqhgd->bchd", ds,
                              q_i.astype(jnp.float32))
            dk_acc = dk_acc.at[j].add(dk_j)
            dv_acc = dv_acc.at[j].add(dv_j)
            return (dq_i, dk_acc, dv_acc), None

        dq0 = jnp.zeros((B, Cq, Hkv, G, hd), jnp.float32)
        (dq_i, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc), (ks, vs, jnp.arange(nk)))
        return (dk_acc, dv_acc), dq_i

    dk0 = jnp.zeros((nk, B, Ck, Hkv, hd), jnp.float32)
    dv0 = jnp.zeros((nk, B, Ck, Hkv, hd), jnp.float32)
    (dk_acc, dv_acc), dqs = jax.lax.scan(
        one_q, (dk0, dv0), (jnp.arange(nq), qs, dos, os_, ms, ls))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd).astype(q.dtype)
    dk = dk_acc.transpose(1, 0, 2, 3, 4).reshape(B, S, Hkv, hd).astype(k.dtype)
    dv = dv_acc.transpose(1, 0, 2, 3, 4).reshape(B, S, Hkv, hd).astype(v.dtype)
    return dq, dk, dv


flash_attention_cv.defvjp(_flash_cv_fwd, _flash_cv_bwd)


# ---------------------------------------------------------------------------
# Flash-style attention (double-chunk scan, online softmax)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True,
                    q_chunk: int = 512, kv_chunk: int = 512,
                    window: Optional[int] = None,
                    causal_skip: bool = False,
                    remat_qchunk: bool = False):
    """q [B,S,H,hd]; k,v [B,S,Hkv,hd] (GQA: H = Hkv * G).  Returns [B,S,H,hd].

    ``causal_skip``: bound the inner KV loop at each query chunk's causal
    horizon (a dynamic fori_loop bound) — removes the ~2x wasted FLOPs of the
    masked upper triangle.  NOTE: not reverse-mode differentiable (dynamic
    fori_loop bound) — inference paths only; §Perf-C2 documents the failed
    training attempt.

    ``remat_qchunk``: wrap each query chunk in jax.checkpoint so backward
    recomputes the [Cq, Ck] probability tiles instead of stashing the full
    O(S^2) f32 score tensor per layer (§Perf-C4).
    """
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    Cq = min(q_chunk, S)
    Ck = min(kv_chunk, S)
    assert S % Cq == 0 and S % Ck == 0, (S, Cq, Ck)
    nq, nk = S // Cq, S // Ck
    scale = 1.0 / np.sqrt(hd)

    qs = q.reshape(B, nq, Cq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, Ck, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, Ck, Hkv, hd).transpose(1, 0, 2, 3, 4)

    q_idx = jnp.arange(Cq)
    k_idx = jnp.arange(Ck)

    def one_q_chunk(qi, q_i):
        # online-softmax state
        m0 = jnp.full((B, Cq, Hkv, G), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Cq, Hkv, G), jnp.float32)
        o0 = jnp.zeros((B, Cq, Hkv, G, hd), jnp.float32)

        def kv_step(carry, kj):
            m, l, o = carry
            k_j, v_j, j = kj
            s = jnp.einsum("bqhgd,bchd->bqhgc", q_i.astype(jnp.float32),
                           k_j.astype(jnp.float32)) * scale
            gq = qi * Cq + q_idx                       # global positions
            gk = j * Ck + k_idx
            mask = jnp.ones((Cq, Ck), bool)
            if causal:
                mask &= gq[:, None] >= gk[None, :]
            if window is not None:
                mask &= gq[:, None] - gk[None, :] < window
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (m_new = -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, :, None, None, :], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bqhgc,bchd->bqhgd", p, v_j.astype(jnp.float32))
            return (m_new, l_new, o_new), None

        if causal_skip and causal and Cq == Ck:
            # dynamic horizon: only kv chunks j <= qi contribute
            def body(j, carry):
                carry, _ = kv_step(carry, (ks[j], vs[j], j))
                return carry
            m, l, o = jax.lax.fori_loop(0, qi + 1, body, (m0, l0, o0))
        else:
            (m, l, o), _ = jax.lax.scan(
                kv_step, (m0, l0, o0),
                (ks, vs, jnp.arange(nk)))
        out = o / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)

    chunk_fn = one_q_chunk
    if remat_qchunk:
        chunk_fn = jax.checkpoint(one_q_chunk)
    outs = jax.lax.map(lambda args: chunk_fn(*args),
                       (jnp.arange(nq), qs))           # [nq, B, Cq, Hkv, G, hd]
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)
    return out


def local_attention(q, k, v, *, window: int, q_chunk: int = 512):
    """Banded sliding-window causal attention: each query chunk attends to a
    dynamic slice of [window + Cq] keys — compiled FLOPs are O(S * window),
    not O(S^2)."""
    B, S, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    Cq = min(q_chunk, S)
    assert S % Cq == 0
    nq = S // Cq
    Wk = min(window + Cq, S)        # keys visible to one q chunk
    scale = 1.0 / np.sqrt(hd)

    qs = q.reshape(B, nq, Cq, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def one_q_chunk(qi, q_i):
        start = jnp.clip(qi * Cq + Cq - Wk, 0, S - Wk)
        k_w = jax.lax.dynamic_slice(k, (0, start, 0, 0), (B, Wk, Hkv, hd))
        v_w = jax.lax.dynamic_slice(v, (0, start, 0, 0), (B, Wk, Hkv, hd))
        s = jnp.einsum("bqhgd,bchd->bqhgc", q_i.astype(jnp.float32),
                       k_w.astype(jnp.float32)) * scale
        gq = qi * Cq + jnp.arange(Cq)
        gk = start + jnp.arange(Wk)
        mask = (gq[:, None] >= gk[None, :]) & (gq[:, None] - gk[None, :] < window)
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bqhgc,bchd->bqhgd", p, v_w.astype(jnp.float32))
        return out.astype(q.dtype)

    outs = jax.lax.map(lambda args: one_q_chunk(*args), (jnp.arange(nq), qs))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)


def decode_attention(q, k_cache, v_cache, pos, *, window: Optional[int] = None):
    """One-token attention over a padded cache.

    q [B,H,hd]; caches [B,Smax,Hkv,hd]; pos scalar int32 (#valid positions
    BEFORE this token; the new token's kv must already be written at pos).
    """
    B, H, hd = q.shape
    Hkv = k_cache.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(B, Hkv, G, hd)
    s = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    idx = jnp.arange(k_cache.shape[1])
    mask = idx <= pos
    if window is not None:
        mask &= idx > pos - window
    s = jnp.where(mask[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# FFN (SwiGLU) and MoE
# ---------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def moe_ffn_tp(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               capacity_factor: float = 1.25, axis: str = "model"):
    """Expert-parallel MoE dispatch over the ``axis`` mesh dimension
    (§Perf-B): activations are replicated over ``axis`` (the TP axis), the
    expert weights are sharded [E/axis_size, d, f] per rank; each rank
    compacts ONLY the tokens routed to its local experts (the paper's
    MapReduceMP "emit to owner" step — here the owner already holds the
    data, so dispatch is comm-free), runs its experts, and the per-rank
    partial outputs are summed with one psum (the combine).

    Per-MoE-layer comm: ONE all-reduce of [N, d] — versus the global
    sort-based path whose sharded sort/gather makes GSPMD replicate
    [N*k, d] buffers per device.  Must be called inside shard_map with
    ``axis`` in scope; x [N, d] local tokens, expert weights local shards.
    """
    N, d = x.shape
    E_loc = w_gate.shape[0]
    n_ranks = jax.lax.axis_size(axis)
    E = E_loc * n_ranks
    rank = jax.lax.axis_index(axis)
    e_lo = rank * E_loc

    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, top_e = jax.lax.top_k(probs, top_k)                # [N, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    frac = jnp.zeros(E, jnp.float32).at[top_e.reshape(-1)].add(1.0) / (N * top_k)
    aux = E * jnp.sum(frac * probs.mean(0))

    # local compaction: (token, k) pairs whose expert lives on this rank
    eflat = top_e.reshape(-1)                                     # [N*k]
    local = (eflat >= e_lo) & (eflat < e_lo + E_loc)
    le = jnp.where(local, eflat - e_lo, E_loc)                    # E_loc = drop
    order = jnp.argsort(le)                                       # locals first
    sorted_e = jnp.take(le, order)
    C = int(np.ceil(N * top_k / E * capacity_factor))
    grp = jnp.searchsorted(sorted_e, jnp.arange(E_loc + 1, dtype=sorted_e.dtype))
    pos = jnp.arange(N * top_k, dtype=jnp.int32) - grp[
        jnp.clip(sorted_e, 0, E_loc)].astype(jnp.int32)
    keep = (sorted_e < E_loc) & (pos < C)
    slot = jnp.where(keep, sorted_e.astype(jnp.int32) * C + pos, E_loc * C)
    token_of = (order // top_k).astype(jnp.int32)

    xg = jnp.zeros((E_loc * C, d), x.dtype).at[slot].set(
        jnp.take(x, token_of, axis=0), mode="drop").reshape(E_loc, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", xg, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(E_loc * C, d)

    y_sorted = jnp.take(ye, jnp.clip(slot, 0, E_loc * C - 1), axis=0)
    gates_sorted = jnp.take(gate_vals.reshape(-1), order)
    w = jnp.where(keep, gates_sorted, 0.0).astype(jnp.float32)
    y_partial = jnp.zeros((N, d), jnp.float32).at[token_of].add(
        y_sorted.astype(jnp.float32) * w[:, None])
    y = jax.lax.psum(y_partial, axis)           # the combine (one all-reduce)
    return y.astype(x.dtype), aux


def make_tp_moe_fn(mesh, dp_spec, cfg):
    """Build the shard_map wrapper installing moe_ffn_tp as the routed-FFN
    implementation (forward's ``moe_fn`` hook).  Shared experts stay on the
    dense pjit path (transformer._apply_ffn)."""
    from jax.sharding import PartitionSpec as P
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def inner(x_l, router, wg, wu, wd):
        B, S, d = x_l.shape
        y, aux = moe_ffn_tp(x_l.reshape(B * S, d), router, wg, wu, wd,
                            top_k=cfg.top_k,
                            capacity_factor=cfg.capacity_factor)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(B, S, d), aux

    xspec = P(dp_spec, None, None)
    espec = P("model", None, None)
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(xspec, P(), espec, espec, espec),
        out_specs=(xspec, P()),
        check_vma=False)

    def moe_fn(p, x):
        return fn(x, p["router"], p["e_gate"], p["e_up"], p["e_down"])
    return moe_fn


def moe_ffn(x, router_w, w_gate, w_up, w_down, *, top_k: int,
            capacity_factor: float = 1.25):
    """Sort-based static-capacity MoE dispatch.

    x [N, d]; router_w [d, E]; expert weights [E, d, ff] / [E, ff, d].
    Returns ([N, d] output, aux load-balancing loss).
    """
    N, d = x.shape
    E = router_w.shape[1]
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # [N, E]
    gate_vals, top_e = jax.lax.top_k(probs, top_k)              # [N, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)                 # renormalize

    # switch-style aux loss: E * sum_e (frac_tokens_e * mean_prob_e)
    frac = jnp.zeros(E, jnp.float32).at[top_e.reshape(-1)].add(1.0) / (N * top_k)
    aux = E * jnp.sum(frac * probs.mean(0))

    C = int(np.ceil(N * top_k / E * capacity_factor))
    eflat = top_e.reshape(-1)                                   # [N*k]
    order = jnp.argsort(eflat)                                  # group by expert
    sorted_e = jnp.take(eflat, order)
    grp_start = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=sorted_e.dtype))
    pos_in_e = jnp.arange(N * top_k, dtype=jnp.int32) - grp_start[
        jnp.clip(sorted_e, 0, E - 1)].astype(jnp.int32)
    keep = pos_in_e < C
    slot = jnp.where(keep, sorted_e.astype(jnp.int32) * C + pos_in_e, E * C)
    token_of = (order // top_k).astype(jnp.int32)

    xg = jnp.zeros((E * C, d), x.dtype).at[slot].set(
        jnp.take(x, token_of, axis=0), mode="drop").reshape(E, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", xg, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(E * C, d)

    # combine: gather each (token, k) result and weight by its gate
    y_sorted = jnp.take(ye, jnp.clip(slot, 0, E * C - 1), axis=0)
    gates_sorted = jnp.take(gate_vals.reshape(-1), order)
    w = jnp.where(keep, gates_sorted, 0.0).astype(jnp.float32)
    y = jnp.zeros((N, d), jnp.float32).at[token_of].add(
        y_sorted.astype(jnp.float32) * w[:, None])
    return y.astype(x.dtype), aux
