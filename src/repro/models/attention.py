"""Attention for the zoo: chunked-flash (training/prefill) and decode paths.

* `flash_attention` — online-softmax over KV chunks inside a q-chunk scan;
  never materializes an (Sq, Skv) score tensor (required for 32k prefill).
  Supports GQA (q heads grouped onto kv heads), causal masking, and sliding
  windows. For windowed attention the KV range per q chunk is statically
  bounded (dynamic_slice of width window+q_chunk) → linear-time SWA/local
  attention for mixtral/recurrentgemma.
* `decode_attention` — single-token attention against a (B, S, Hk, D) cache.
* `decode_attention_seqsharded` — flash-decoding style shard_map: the KV
  cache is sharded along SEQUENCE over the `model` mesh axis (works for any
  kv-head count incl. MQA kv=1), each chip computes a partial softmax over
  its slice, partials merge with an LSE psum (DESIGN.md §5).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.common import mesh_vma, varying

NEG_INF = -1e30


def _mask_bias(qpos, kpos, window: Optional[int], kv_limit: Optional[int] = None):
    """(..., q, k) additive bias: causal + optional sliding window.

    `qpos` may carry leading batch dims — chunked prefill hands per-row
    absolute positions (B, q) and gets a (B, q, k) bias back."""
    qp = qpos[..., :, None]
    ok = kpos <= qp
    if window is not None:
        ok &= kpos > (qp - window)
    if kv_limit is not None:
        ok &= kpos < kv_limit
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _attend_chunk(q, k, v, bias, scale):
    """q: (B,qc,Hk,G,D) k/v: (B,kc,Hk,D) bias: (qc,kc) or batched
    (B,qc,kc) → partial (o,m,l)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    if getattr(bias, "ndim", 0) == 3:      # per-row bias → (B,1,1,qc,kc)
        bias = bias[:, None, None]
    s = s * scale + bias
    m = jnp.max(s, axis=-1)                       # (B,Hk,G,q)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                       # (B,Hk,G,q)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def _causal_flash_packed(q5, k4, v4, scale, chunk):
    """Causal flash over ONLY the lower-triangular (iq, ik≤iq) chunk pairs.

    The masked-full scan computes nq·nk block products and masks half away —
    2× wasted FLOPs *and* probs traffic. Here a flat scan walks the
    nq(nq+1)/2 valid pairs (statically enumerated, so the HLO while has a
    known trip count); only diagonal blocks apply the causal mask. Running
    (o, m, l) carry resets at each row start; normalized row outputs are
    emitted at row ends and gathered afterwards.
    """
    b, nq, qc, hk, g, _ = q5.shape
    nk = k4.shape[1]
    d = v4.shape[-1]
    assert nq == nk and k4.shape[2] == qc

    pairs = [(iq, ik) for iq in range(nq) for ik in range(iq + 1)]
    t_iq = jnp.asarray([p[0] for p in pairs], jnp.int32)
    t_ik = jnp.asarray([p[1] for p in pairs], jnp.int32)
    row_start = jnp.asarray([p[0] == p[1] == 0 or p[1] == 0 for p in pairs])
    row_end = jnp.asarray([p[0] == p[1] for p in pairs])
    end_idx = jnp.asarray([i for i, p in enumerate(pairs) if p[0] == p[1]],
                          jnp.int32)

    pos = jnp.arange(qc)
    diag_bias = jnp.where(pos[:, None] >= pos[None, :], 0.0, NEG_INF).astype(
        jnp.float32)

    def body(carry, xs):
        o_acc, m_acc, l_acc = carry
        iq, ik, start, end = xs
        # fresh row → reset the running softmax state
        o_acc = jnp.where(start, 0.0, o_acc)
        m_acc = jnp.where(start, NEG_INF, m_acc)
        l_acc = jnp.where(start, 0.0, l_acc)
        qcb = jax.lax.dynamic_index_in_dim(q5, iq, axis=1, keepdims=False)
        kcb = jax.lax.dynamic_index_in_dim(k4, ik, axis=1, keepdims=False)
        vcb = jax.lax.dynamic_index_in_dim(v4, ik, axis=1, keepdims=False)
        bias = jnp.where(iq == ik, diag_bias, 0.0)  # off-diag fully valid
        o, m, l = _attend_chunk(qcb, kcb, vcb, bias, scale)
        m_new = jnp.maximum(m_acc, m)
        r_old = jnp.exp(m_acc - m_new)
        r_new = jnp.exp(m - m_new)
        o_acc = o_acc * r_old[..., None] + o * r_new[..., None]
        l_acc = l_acc * r_old + l * r_new
        out = jnp.where(end, o_acc / jnp.maximum(l_acc, 1e-30)[..., None], 0.0)
        return (o_acc, m_acc * 0 + m_new, l_acc), out.astype(q5.dtype)

    o0 = jnp.zeros((b, hk, g, qc, d), jnp.float32)
    m0 = jnp.full((b, hk, g, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hk, g, qc), jnp.float32)
    # inside shard_map the carry varies over the q/k/v axes: seed it so
    vma = mesh_vma(q5, k4, v4)
    carry0 = tuple(varying(c, vma) for c in (o0, m0, l0))
    _, outs = jax.lax.scan(body, carry0, (t_iq, t_ik, row_start, row_end))
    o = outs[end_idx]  # (nq, B, hk, g, qc, D)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * qc, hk * g, d)
    return o


def flash_attention(
    q: jax.Array,            # (B, Sq, Hq, D)
    k: jax.Array,            # (B, Skv, Hk, D)
    v: jax.Array,            # (B, Skv, Hk, Dv) — Dv may differ from D
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    q_offset=0,              # absolute position of q[0] (prefill continuation):
                             # static int, traced scalar, or (B,) per-row array
    packed: bool = True,     # pair-packed causal scan (skips masked blocks)
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, skv, hk, _ = k.shape
    dv = v.shape[-1]
    g = hq // hk
    scale = 1.0 / math.sqrt(d)
    # only a STATIC offset can drive the banded dynamic-slice window path or
    # the packed lower-triangular scan; traced/per-row offsets take the
    # general kv-scan with the window folded into the additive bias
    off_static = isinstance(q_offset, int)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    # pad to chunk multiples (padded q rows discarded; padded kv masked out)
    sq_real, skv_real = sq, skv
    pq, pk = (-sq) % q_chunk, (-skv) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        sq += pq
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        skv += pk
    kv_limit = skv_real if pk else None
    nq = sq // q_chunk
    q5 = q.reshape(b, nq, q_chunk, hk, g, d)

    if window is not None and off_static:
        # static KV band per q chunk: [q_start - window + 1, q_start + q_chunk)
        band = window + q_chunk

        def per_q(iq, qc):
            q_start = iq * q_chunk + q_offset
            lo = jnp.clip(q_start - window + 1, 0, skv - band) if skv >= band else 0
            kc = jax.lax.dynamic_slice_in_dim(k, lo, min(band, skv), axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, lo, min(band, skv), axis=1)
            qpos = q_start + jnp.arange(q_chunk)
            kpos = lo + jnp.arange(min(band, skv))
            bias = _mask_bias(qpos, kpos, window, kv_limit)
            o, m, l = _attend_chunk(qc, kc, vc, bias, scale)
            return o / jnp.maximum(l, 1e-30)[..., None]

        def scan_body(_, xs):
            iq, qc = xs
            return None, per_q(iq, qc)

        _, o = jax.lax.scan(scan_body, None, (jnp.arange(nq), q5.swapaxes(0, 1)))
        o = o.swapaxes(0, 1)  # (B, nq, Hk, G, qc, Dv)
        o = o.transpose(0, 1, 4, 2, 3, 5).reshape(b, sq, hq, dv)
        return o[:, :sq_real].astype(q.dtype)

    nk = skv // kv_chunk
    k4 = k.reshape(b, nk, kv_chunk, hk, d)
    v4 = v.reshape(b, nk, kv_chunk, hk, dv)

    if (
        causal
        and packed
        and off_static
        and q_offset == 0
        and sq == skv
        and q_chunk == kv_chunk
        and pq == 0
        and pk == 0
    ):
        return _causal_flash_packed(q5, k4, v4, scale, q_chunk)

    def per_q(iq, qc):
        # (qc,) for scalar offsets, (B, qc) for per-row offsets — _mask_bias
        # and _attend_chunk broadcast either shape
        qpos = jnp.asarray(q_offset)[..., None] + iq * q_chunk + jnp.arange(q_chunk)

        def kv_body(carry, xs):
            ik, kc, vc = xs
            o_acc, m_acc, l_acc = carry
            kpos = ik * kv_chunk + jnp.arange(kv_chunk)
            if causal or kv_limit is not None:
                bias = _mask_bias(qpos, kpos, window, kv_limit)
                if not causal:
                    bias = _mask_bias(jnp.full((q_chunk,), skv), kpos, None, kv_limit)
            else:
                bias = jnp.float32(0.0)
            o, m, l = _attend_chunk(qc, kc, vc, bias, scale)
            m_new = jnp.maximum(m_acc, m)
            r_old = jnp.exp(m_acc - m_new)
            r_new = jnp.exp(m - m_new)
            o_acc = o_acc * r_old[..., None] + o * r_new[..., None]
            l_acc = l_acc * r_old + l * r_new
            return (o_acc, m_new, l_acc), None

        o0 = jnp.zeros((b, hk, g, q_chunk, dv), jnp.float32)
        m0 = jnp.full((b, hk, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hk, g, q_chunk), jnp.float32)
        (o, m, l), _ = jax.lax.scan(
            kv_body,
            tuple(varying(c, mesh_vma(qc, k4, v4)) for c in (o0, m0, l0)),
            (jnp.arange(nk), k4.swapaxes(0, 1), v4.swapaxes(0, 1))
        )
        return o / jnp.maximum(l, 1e-30)[..., None]

    def scan_body(_, xs):
        iq, qc = xs
        return None, per_q(iq, qc)

    _, o = jax.lax.scan(scan_body, None, (jnp.arange(nq), q5.swapaxes(0, 1)))
    o = o.swapaxes(0, 1)
    o = o.transpose(0, 1, 4, 2, 3, 5).reshape(b, sq, hq, dv)
    return o[:, :sq_real].astype(q.dtype)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _slot_positions(slots, lb, ring, cache_len):
    """Token position held by each cache slot, per batch row → (B, S_loc).

    Linear cache: slot s holds token s. Ring cache (sliding window W): the
    newest token is p = lb-1; slot s holds t = p - ((p - s) mod W); negative
    → slot never written.
    """
    if not ring:
        return jnp.broadcast_to(slots[None, :], (lb.shape[0], slots.shape[0]))
    p = (lb - 1)[:, None]
    return p - jnp.mod(p - slots[None, :], cache_len)


def _decode_partial(q4, k_loc, v_loc, lb, window, slots, scale, ring, cache_len):
    kpos = _slot_positions(slots, lb, ring, cache_len)   # (B, S_loc)
    scores = jnp.einsum("bhgd,bshd->bhgs", q4, k_loc,
                        preferred_element_type=jnp.float32) * scale
    valid = (kpos < lb[:, None]) & (kpos >= 0)
    if window is not None:
        valid &= kpos >= (lb[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_loc.dtype), v_loc,
                   preferred_element_type=jnp.float32)
    return o, m, l


def decode_attention(
    q: jax.Array,        # (B, Hq, D) — one new token per sequence
    k_cache: jax.Array,  # (B, S, Hk, D)
    v_cache: jax.Array,  # (B, S, Hk, D)
    length,              # scalar or (B,): number of valid token positions
    *,
    window: Optional[int] = None,
    ring: bool = False,  # cache is a sliding-window ring buffer
) -> jax.Array:
    b, hq, d = q.shape
    _, s, hk, _ = k_cache.shape
    g = hq // hk
    scale = 1.0 / math.sqrt(d)
    q4 = q.reshape(b, hk, g, d)
    length = jnp.asarray(length)
    lb = length if length.ndim else jnp.broadcast_to(length, (b,))
    o, m, l = _decode_partial(
        q4, k_cache, v_cache, lb, window, jnp.arange(s), scale, ring, s
    )
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, hq, d).astype(q.dtype)


def latent_decode_attention(
    q_lat: jax.Array,    # (B, H, C) — queries absorbed into the latent space
    q_rope: jax.Array,   # (B, H, R) — roped query part
    latent: jax.Array,   # (B, S, C + R) — cached latents, then roped keys
    length,              # scalar: number of valid token positions
    scale: float,
) -> jax.Array:
    """Latent attention of one new token per sequence over a latent cache:
    scores q_lat·c + q_rope·k_rope, softmax over the valid positions, and
    the weighted sum of the latents c. Returns (B, H, C) f32."""
    c = q_lat.shape[-1]
    s = (jnp.einsum("bhc,bsc->bhs", q_lat, latent[..., :c],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", q_rope, latent[..., c:],
                      preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(latent.shape[1]) < length
    s = jnp.where(valid[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p, latent[..., :c],
                      preferred_element_type=jnp.float32)


def _local_cache_update(cache_loc, new_val, slot, offset, s_loc):
    """Write (B,1,Hk,D) new_val at global slot `slot` iff it lands in this
    shard's [offset, offset+s_loc) slice — local slice/select/update only
    (a pjit-level DUS at a traced index makes GSPMD rewrite the whole cache
    per layer; this keeps it O(token) instead of O(cache))."""
    loc = slot - offset
    in_range = (loc >= 0) & (loc < s_loc)
    locc = jnp.clip(loc, 0, s_loc - 1)
    old = jax.lax.dynamic_slice_in_dim(cache_loc, locc, 1, axis=1)
    val = jnp.where(in_range, new_val.astype(cache_loc.dtype), old)
    return jax.lax.dynamic_update_slice_in_dim(cache_loc, val, locc, axis=1)


def decode_attention_seqsharded(
    q: jax.Array,        # (B, Hq, D) replicated over `axis`
    k_new: jax.Array,    # (B, 1, Hk, D) — this step's key (pre-roped)
    v_new: jax.Array,
    k_cache: jax.Array,  # (B, S, Hk, D) sharded on S over `axis`
    v_cache: jax.Array,
    length,              # scalar/(B,): tokens valid AFTER this update
    *,
    mesh,
    batch_axes=("data",),
    axis: str = "model",
    window: Optional[int] = None,
    ring: bool = False,
):
    """Flash-decoding: local cache update + partial softmax per KV slice,
    merged with an LSE psum. Returns (out, k_cache, v_cache)."""
    b, hq, d = q.shape
    _, s, hk, _ = k_cache.shape
    g = hq // hk
    scale = 1.0 / math.sqrt(d)
    nshard = mesh.shape[axis]
    s_loc = s // nshard

    def local(qc, knc, vnc, kc, vc, lb):
        idx = jax.lax.axis_index(axis)
        off = idx * s_loc
        pos = lb[0] - 1                      # uniform decode position
        slot = jnp.mod(pos, s) if ring else pos
        kc = _local_cache_update(kc, knc, slot, off, s_loc)
        vc = _local_cache_update(vc, vnc, slot, off, s_loc)
        slots = off + jnp.arange(s_loc)
        q4 = qc.reshape(qc.shape[0], hk, g, d)
        o, m, l = _decode_partial(q4, kc, vc, lb, window, slots, scale,
                                  ring, s)
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis)
        o_g = jax.lax.psum(o * corr[..., None], axis)
        out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.reshape(qc.shape[0], hq, d).astype(qc.dtype), kc, vc

    cspec = P(batch_axes, axis, None, None)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None, None),
            P(batch_axes, None, None, None),
            P(batch_axes, None, None, None),
            cspec,
            cspec,
            P(batch_axes),
        ),
        out_specs=(P(batch_axes, None, None), cspec, cspec),
    )
    length = jnp.asarray(length)
    lb = length if length.ndim else jnp.broadcast_to(length, (b,))
    return fn(q, k_new, v_new, k_cache, v_cache, lb)
