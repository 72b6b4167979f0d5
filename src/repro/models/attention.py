"""GQA attention: qk-norm (qwen3), QKV bias (qwen2), sliding window
(mixtral), bidirectional (whisper encoder), cross-attention (whisper
decoder), and KV-cache decode.

Train/prefill path computes scores blockwise-naturally via einsum (XLA/TPU
fuses the softmax); the decode path updates a ``(B, S_max, K, hd)`` cache
at position ``pos`` via dynamic_update_slice.  For ``long_500k`` the cache
is sequence-sharded over the "data" mesh axis and GSPMD turns the softmax
reductions into cross-device collectives (ring-attention-like; see
DESIGN.md §6).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.models import layers

NEG_INF = -1e9


def init_attn(key, cfg, *, cross: bool = False):
    d, hd = cfg.d_model, cfg.d_head
    h, k = cfg.n_heads, cfg.n_kv
    keys = jax.random.split(key, 6)
    p = {
        "norm": jnp.ones((d,), jnp.float32),
        "wq": layers.he_init(keys[0], (d, h * hd)),
        "wk": layers.he_init(keys[1], (d, k * hd)),
        "wv": layers.he_init(keys[2], (d, k * hd)),
        "wo": layers.he_init(keys[3], (h * hd, d), scale=1.0 / max(1, cfg.n_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), jnp.float32)
        p["bk"] = jnp.zeros((k * hd,), jnp.float32)
        p["bv"] = jnp.zeros((k * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    if cross:
        p["norm_kv"] = jnp.ones((d,), jnp.float32)
    return p


def _project_qkv(p, cfg, xq, xkv):
    h, k, hd = cfg.n_heads, cfg.n_kv, cfg.d_head
    dt = xq.dtype
    q = jnp.einsum("bsd,de->bse", xq, p["wq"].astype(dt))
    kk = jnp.einsum("bsd,de->bse", xkv, p["wk"].astype(dt))
    v = jnp.einsum("bsd,de->bse", xkv, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        kk = kk + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(*q.shape[:2], h, hd)
    kk = kk.reshape(*kk.shape[:2], k, hd)
    v = v.reshape(*v.shape[:2], k, hd)
    if "q_norm" in p:
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
        kk = layers.rms_norm(kk, p["k_norm"], cfg.norm_eps)
    from repro.sharding import rules

    if rules.opt_sharding_enabled():
        q = rules.constrain(q, "B", None, "model", None)
    return q, kk, v


def _sdpa(q, k, v, mask, n_rep: int):
    """q (B,Sq,H,hd), k/v (B,Sk,K,hd), mask (B|1,Sq,Sk) bool (True=keep)."""
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    qg = q.reshape(b, sq, kv, n_rep, hd)
    scores = jnp.einsum(
        "bqkrh,bskh->bkrqs", qg, k, preferred_element_type=jnp.float32
    ) / (hd**0.5)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrqs,bskh->bqkrh", w, v)
    return out.reshape(b, sq, h, hd)


# memory threshold: use the chunked online-softmax path beyond this length
CHUNK_THRESHOLD = 2048
Q_BLOCK = 512
KV_BLOCK = 1024
# opt mode (§Perf iteration 2): larger blocks amortize per-block-pair carry
# traffic; probabilities stored bf16 (f32 m/l accumulators) halve the
# dominant elementwise HBM traffic of the attention loops
OPT_Q_BLOCK = 1024
OPT_KV_BLOCK = 2048


def _sdpa_chunked(
    q, k, v, n_rep: int, *, causal: bool, window: int = 0, kv_len: int = 0
):
    """Flash-style blockwise attention: O(S·block) memory instead of O(S²).

    Outer lax.scan over query blocks, inner scan over kv blocks with an
    online (m, l, acc) softmax.  Causal/window masks are applied per block
    pair from absolute positions; fully-masked kv blocks still execute
    (static shapes) but contribute exp(-inf)=0.

    Heads are kept FLAT (GQA handled by repeating the kv block, which is
    cheap at block granularity) so the head axis stays shardable over
    "model"; with REPRO_OPT_SHARDING the explicit constraints below stop
    GSPMD from replicating the score computation across the model axis —
    the 16x redundancy found in the baseline dry-run (EXPERIMENTS §Perf).
    """
    from repro.sharding import rules

    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    opt = rules.opt_sharding_enabled()
    qb = min(OPT_Q_BLOCK if opt else Q_BLOCK, sq)
    kb = min(OPT_KV_BLOCK if opt else KV_BLOCK, sk)
    while sq % qb:
        qb //= 2
    while sk % kb:
        kb //= 2
    nq, nk = sq // qb, sk // kb
    scale = 1.0 / (hd**0.5)

    qg = q.reshape(b, nq, qb, h, hd).transpose(1, 0, 2, 3, 4)
    kg = k.reshape(b, nk, kb, kv, hd)
    vg = v.reshape(b, nk, kb, kv, hd)
    if opt:
        qg = rules.constrain(qg, None, "B", None, "model", None)

    def q_step(_, qblk_and_idx):
        qblk, qi = qblk_and_idx  # (B,qb,H,hd), ()
        q_pos = qi * qb + jnp.arange(qb)

        def kv_step(carry, ki):
            m_run, l_run, acc = carry
            kblk = jax.lax.dynamic_index_in_dim(kg, ki, 1, keepdims=False)
            vblk = jax.lax.dynamic_index_in_dim(vg, ki, 1, keepdims=False)
            # GQA: expand kv heads to H at block granularity (kb x H x hd)
            kr = jnp.repeat(kblk, n_rep, axis=2)
            vr = jnp.repeat(vblk, n_rep, axis=2)
            if opt:
                kr = rules.constrain(kr, "B", None, "model", None)
                vr = rules.constrain(vr, "B", None, "model", None)
            k_pos = ki * kb + jnp.arange(kb)
            s = (
                jnp.einsum(
                    "bqhd,bkhd->bhqk", qblk, kr,
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            mask = jnp.ones((qb, kb), bool)
            if causal:
                mask = mask & (k_pos[None, :] <= q_pos[:, None])
            if window > 0:
                mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
            if kv_len:  # kv padded to a block multiple (cross-attention)
                mask = mask & (k_pos[None, :] < kv_len)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            if opt:
                # store probabilities bf16 (m/l stay f32): halves the
                # dominant elementwise traffic; f32 accumulation in the dot
                p = p.astype(jnp.bfloat16)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(vr.dtype), vr,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, h, qb), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, qb), jnp.float32)
        a0 = jnp.zeros((b, h, qb, hd), jnp.float32)
        if opt:
            m0 = rules.constrain(m0, "B", "model", None)
            l0 = rules.constrain(l0, "B", "model", None)
            a0 = rules.constrain(a0, "B", "model", None, None)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nk)
        )
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        # (B,H,qb,hd) -> (B,qb,H,hd)
        out = out.transpose(0, 2, 1, 3)
        return None, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_step, None, (qg, jnp.arange(nq)))
    # (nq, B, qb, H, hd) -> (B, Sq, H, hd)
    return blocks.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def attend_full(
    p,
    cfg,
    x,
    positions,
    *,
    causal: bool = True,
    window: int = 0,
    return_kv: bool = False,
):
    """Train / prefill self-attention over the whole sequence."""
    xn = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    q, k, v = _project_qkv(p, cfg, xn, xn)
    if cfg.rope_theta > 0:
        cos, sin = layers.rope_cos_sin(positions, cfg.d_head, cfg.rope_theta)
        q = layers.apply_rope(q, cos, sin)
        k = layers.apply_rope(k, cos, sin)
    s = x.shape[1]
    n_rep = cfg.n_heads // cfg.n_kv
    if s > CHUNK_THRESHOLD:
        out = _sdpa_chunked(q, k, v, n_rep, causal=causal, window=window)
    else:
        i = jnp.arange(s)[:, None]
        j = jnp.arange(s)[None, :]
        mask = jnp.ones((s, s), bool) if not causal else (j <= i)
        if window > 0:
            mask = mask & (j > i - window)
        out = _sdpa(q, k, v, mask[None], n_rep)
    flat = out.reshape(*out.shape[:2], -1)
    y = x + jnp.einsum("bse,ed->bsd", flat, p["wo"].astype(x.dtype))
    if return_kv:
        return y, (k, v)
    return y


def init_cache(cfg, batch: int, max_seq: int, dtype=layers.COMPUTE_DTYPE):
    kv, hd = cfg.n_kv, cfg.d_head
    return {
        "k": jnp.zeros((batch, max_seq, kv, hd), dtype),
        "v": jnp.zeros((batch, max_seq, kv, hd), dtype),
    }


def _cache_update(cache, k_new, v_new, pos):
    """Write one token's K/V at ``pos``.

    With REPRO_OPT_SHARDING and a sequence-sharded cache, the write runs
    as a shard_map with shard-LOCAL index arithmetic: a plain
    dynamic_update_slice at a dynamic index makes GSPMD all-gather the
    whole cache per layer (measured 17 GB/layer on qwen2-72b decode_32k,
    §Perf iteration 4), and a one-hot masked select gets canonicalized
    right back into the same DUS.  shard_map is the only representation
    GSPMD cannot "simplify" away: each seq shard checks whether ``pos``
    falls in its range and applies a local DUS or a no-op.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding import rules

    b, s_max = cache["k"].shape[0], cache["k"].shape[1]
    seq_axes = rules.decode_seq_axes(b, s_max)
    if seq_axes and rules._ACTIVE_MESH:
        mesh = rules._ACTIVE_MESH[0]
        d_ax = rules.batch_axes(mesh)
        bat = (
            (d_ax if len(d_ax) > 1 else d_ax[0])
            if b % int(np.prod([mesh.shape[a] for a in d_ax])) == 0
            else None
        )
        cspec = P(bat, seq_axes if len(seq_axes) > 1 else seq_axes[0])
        nspec = P(bat, None)

        def local(ck, cv, kn, vn, p):
            # flat shard index along the sharded seq axes
            idx = jnp.int32(0)
            for a in seq_axes:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
            s_loc = ck.shape[1]
            local_pos = p - idx * s_loc
            in_range = (local_pos >= 0) & (local_pos < s_loc)
            lp = jnp.clip(local_pos, 0, s_loc - 1)
            ku = jax.lax.dynamic_update_slice(
                ck, kn.astype(ck.dtype), (0, lp, 0, 0)
            )
            vu = jax.lax.dynamic_update_slice(
                cv, vn.astype(cv.dtype), (0, lp, 0, 0)
            )
            return (
                jnp.where(in_range, ku, ck),
                jnp.where(in_range, vu, cv),
            )

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(cspec, cspec, nspec, nspec, P()),
            out_specs=(cspec, cspec),
            check_vma=False,
        )(cache["k"], cache["v"], k_new, v_new, pos)

    k = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype), (0, pos, 0, 0)
    )
    v = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype), (0, pos, 0, 0)
    )
    return k, v


def attend_decode(p, cfg, x, cache, pos, *, window: int = 0):
    """One-token decode: update cache at ``pos``, attend over the prefix.

    x (B,1,D); pos () int32 — current write index (same for the batch).
    """
    xn = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    q, k_new, v_new = _project_qkv(p, cfg, xn, xn)
    if cfg.rope_theta > 0:
        posv = jnp.full((x.shape[0], 1), pos, jnp.int32)
        cos, sin = layers.rope_cos_sin(posv, cfg.d_head, cfg.rope_theta)
        q = layers.apply_rope(q, cos, sin)
        k_new = layers.apply_rope(k_new, cos, sin)
    k, v = _cache_update(cache, k_new, v_new, pos)
    s_max = k.shape[1]
    j = jnp.arange(s_max)[None, :]
    mask = j <= pos
    if window > 0:
        mask = mask & (j > pos - window)
    out = _sdpa(q, k.astype(q.dtype), v.astype(q.dtype), mask[:, None, :], cfg.n_heads // cfg.n_kv)
    flat = out.reshape(*out.shape[:2], -1)
    y = jnp.einsum("bse,ed->bsd", flat, p["wo"].astype(x.dtype))
    return x + y, {"k": k, "v": v}


def attend_cross(p, cfg, x, kv_cache):
    """Cross-attention against precomputed encoder K/V (whisper decoder)."""
    xn = layers.rms_norm(x, p["norm"], cfg.norm_eps)
    dt = x.dtype
    h, hd = cfg.n_heads, cfg.d_head
    q = jnp.einsum("bsd,de->bse", xn, p["wq"].astype(dt)).reshape(
        *x.shape[:2], h, hd
    )
    k, v = kv_cache["k"].astype(dt), kv_cache["v"].astype(dt)
    n_rep = cfg.n_heads // cfg.n_kv
    if x.shape[1] > CHUNK_THRESHOLD:
        # pad kv length to a block multiple; padded keys are masked by l=0?
        # -> simpler: pad and give them NEG_INF via an explicit length mask
        sk = k.shape[1]
        pad = (-sk) % KV_BLOCK
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        out = _sdpa_chunked(
            q, k, v, n_rep, causal=False, window=0, kv_len=sk
        )
    else:
        mask = jnp.ones((x.shape[1], k.shape[1]), bool)
        out = _sdpa(q, k, v, mask[None], n_rep)
    flat = out.reshape(*out.shape[:2], -1)
    return x + jnp.einsum("bse,ed->bsd", flat, p["wo"].astype(dt))


def encode_cross_kv(p, cfg, enc_out):
    """Precompute cross K/V from encoder output (paper-free plumbing)."""
    xn = layers.rms_norm(enc_out, p["norm_kv"], cfg.norm_eps)
    dt = enc_out.dtype
    kv, hd = cfg.n_kv, cfg.d_head
    k = jnp.einsum("bsd,de->bse", xn, p["wk"].astype(dt)).reshape(
        *enc_out.shape[:2], kv, hd
    )
    v = jnp.einsum("bsd,de->bse", xn, p["wv"].astype(dt)).reshape(
        *enc_out.shape[:2], kv, hd
    )
    return {"k": k, "v": v}
