"""Decode-time state: KV caches (global + local ring) and recurrent states.

Ring caches keep only ``window`` slots for sliding-window layers — this is
what makes recurrentgemma's long_500k cell O(1) memory per token: its global
state is the RG-LRU hidden + a 2048-slot ring, never a 524288-token buffer.

Slot/position conventions (L = #tokens written so far, per sample):
  * global cache: slot j holds absolute position j; valid iff j < L.
  * ring cache (W slots): slot j holds the largest position p < L with
    p ≡ j (mod W); valid iff 0 <= p (i.e. once anything was written there).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import compat

from repro.models.param import ParamDef


def kv_cache_defs(cfg, batch: int, max_seq: int, *, window: int = 0) -> dict:
    size = min(window, max_seq) if window else max_seq
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    dims = ("batch", "cache_seq", "kv_heads", "head_dim")
    return {
        "k": ParamDef((batch, size, hkv, dh), dims, dt, "zeros"),
        "v": ParamDef((batch, size, hkv, dh), dims, dt, "zeros"),
    }


def rglru_cache_defs(cfg, batch: int) -> dict:
    w = cfg.lru_width or cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    return {
        "conv": ParamDef((batch, cfg.conv_width - 1, w), ("batch", "conv", "lru_width"), dt, "zeros"),
        "h": ParamDef((batch, w), ("batch", "lru_width"), jnp.float32, "zeros"),
    }


def rwkv_cache_defs(cfg, batch: int) -> dict:
    d = cfg.d_model
    hd = cfg.rwkv_head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "shift": ParamDef((batch, d), ("batch", "d_model"), dt, "zeros"),
        "wkv": ParamDef((batch, d // hd, hd, hd), ("batch", "rwkv_heads", "head_dim", "head_dim2"),
                        jnp.float32, "zeros"),
        "cm_shift": ParamDef((batch, d), ("batch", "d_model"), dt, "zeros"),
    }


def slot_positions(lengths, cache_size: int, window: int = 0):
    """Absolute positions + validity per cache slot. lengths: (B,) tokens
    written so far (AFTER the current decode token's write uses L+1)."""
    j = jnp.arange(cache_size)[None, :]                    # (1, S)
    L = lengths[:, None]
    if window:
        w = cache_size  # ring buffers are allocated at exactly min(window, S)
        pos = (L - 1) - jnp.remainder(L - 1 - j, w)
        valid = (pos >= 0) & (L > 0)
    else:
        pos = jnp.broadcast_to(j, (lengths.shape[0], cache_size))
        valid = j < L
    return pos, valid


def write_token(buf, new, lengths, window: int = 0, shard=None):
    """Write one token's k/v into the cache. buf: (B, S, H, D); new: (B, 1, H, D);
    lengths: (B,) tokens already present (write position).

    With ``shard=(mesh, dp_axes)`` and a cache whose seq dim is sharded over
    'model', the write runs under shard_map so each rank performs a purely
    local dynamic-update-slice (only the slot's owner writes). Letting the
    SPMD partitioner handle the batched scatter instead materializes a full
    f32 copy of the cache stack per step — the difference between a decode
    step fitting HBM or not on the 33B/16B archs.
    """
    size = buf.shape[1]
    idx = jnp.remainder(lengths, size) if window else jnp.clip(lengths, 0, size - 1)

    def upd(b, n, i):
        return jax.lax.dynamic_update_slice_in_dim(b, n.astype(b.dtype), i, axis=0)

    if shard is None:
        return jax.vmap(upd)(buf, new, idx)

    from jax.sharding import PartitionSpec as P
    mesh, dp_axes = shard
    msize = mesh.shape.get("model", 1)
    B = buf.shape[0]
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    ndp = 1
    for a in dp:
        ndp *= mesh.shape[a]
    if msize <= 1 or size % msize != 0:
        return jax.vmap(upd)(buf, new, idx)
    bspec = (dp if len(dp) > 1 else dp[0]) if (dp and B % ndp == 0) else None
    s_loc = size // msize

    def local(buf_l, new_l, idx_l):
        off = jax.lax.axis_index("model") * s_loc

        def upd_local(b, n, i):
            li = i - off
            ok = (li >= 0) & (li < s_loc)
            lc = jnp.clip(li, 0, s_loc - 1)
            cur = jax.lax.dynamic_slice_in_dim(b, lc, 1, 0)
            val = jnp.where(ok, n.astype(b.dtype), cur)
            return jax.lax.dynamic_update_slice_in_dim(b, val, lc, 0)

        return jax.vmap(upd_local)(buf_l, new_l, idx_l)

    fn = compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, "model", None, None), P(bspec, None, None, None),
                  P(bspec)),
        out_specs=P(bspec, "model", None, None))
    return fn(buf, new, idx)


def fill_from_prefill(kv, cache_size: int, window: int = 0):
    """Build a cache buffer from prefill-computed k or v: (B, S, H, D)."""
    B, S = kv.shape[:2]
    if window:
        w = cache_size
        if S >= w:
            last = kv[:, S - w:]
            return jnp.roll(last, shift=S % w, axis=1)
        return jnp.pad(kv, ((0, 0), (0, w - S), (0, 0), (0, 0)))
    if S >= cache_size:
        return kv[:, :cache_size]
    return jnp.pad(kv, ((0, 0), (0, cache_size - S), (0, 0), (0, 0)))
