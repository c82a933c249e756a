"""Model assembly: decoder LM over all six assigned families.

Families:
  dense / vlm / audio : L x [GQA attention + MLP]        (vlm/audio = stubs
                        providing prefix/frame embeddings per the assignment)
  moe                 : L x [GQA attention + MoE]
  ssm                 : L x [Mamba-1]                     (attention-free)
  hybrid              : L x [Mamba-2] + one *shared* attention+MLP block
                        applied every ``shared_attn_every`` layers (Zamba2)

Homogeneous layer stacks are parameter-stacked and executed with
``lax.scan`` (+ optional ``jax.checkpoint`` remat) so the HLO stays compact
for the 95-layer dry-run cells.  Decode is a single-token step against
explicit caches (KV ring-buffers for sliding-window attention, conv+state
carries for SSM).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import ssm
from repro.models.attention import (chunk_attention, decode_attention,
                                    flash_attention)
from repro.models.flash_vjp import flash_attention_trainable
from repro.models.layers import (dense_init, embed_apply, embed_init,
                                 mlp_apply, mlp_init, rmsnorm, rmsnorm_init,
                                 unembed_apply)
from repro.models.moe import moe_apply, moe_init
from repro.models.numerics import matmul
from repro.parallel.sharding import (constrain_layer_params, shard,
                                     tensor_size)


def _pad_vocab(v: int, multiple: int = 256) -> int:
    return ((v + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# Attention transformer block (dense / moe / vlm / audio; zamba shared block)
# ---------------------------------------------------------------------------
def attn_block_init(key, cfg: ArchConfig, dtype):
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 8)
    p = {
        "ln1": rmsnorm_init(d, dtype),
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype),
        "ln2": rmsnorm_init(d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    if cfg.family == "moe":
        p["moe"] = moe_init(ks[4], d, n_experts=cfg.n_experts,
                            moe_d_ff=cfg.moe_d_ff,
                            n_shared=cfg.n_shared_experts, dtype=dtype)
    else:
        p["mlp"] = mlp_init(ks[4], d, cfg.d_ff, cfg.mlp_act, dtype)
    return p


def _qkv(p, h, cfg, positions, policy):
    from repro.models.layers import apply_rope
    B, S, _ = h.shape
    q = matmul(h, p["wq"], policy)
    k = matmul(h, p["wk"], policy)
    v = matmul(h, p["wv"], policy)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_style)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_style)
    return q, k, v


def _ffn(p, h2, cfg, policy):
    if cfg.family == "moe":
        from repro.parallel.sharding import active
        ctx = active()
        if ctx is not None and ctx.mesh.shape.get("data", 1) > 1:
            from repro.models.moe_sharded import moe_apply_distributed
            return moe_apply_distributed(
                p["moe"], h2, top_k=cfg.experts_per_token,
                capacity_factor=cfg.capacity_factor, ctx=ctx)
        return moe_apply(p["moe"], h2, top_k=cfg.experts_per_token,
                         capacity_factor=cfg.capacity_factor)
    return mlp_apply(p["mlp"], h2, cfg.mlp_act, policy), {"aux_loss": 0.0}


def attn_block_apply(p, x, positions, cfg: ArchConfig, *, policy=None,
                     collect_kv: bool = False, triangle_skip: bool = False):
    B, S, _ = x.shape
    h = rmsnorm(p["ln1"], x)
    q, k, v = _qkv(p, h, cfg, positions, policy)
    # TP over heads: when Hkv doesn't divide the model axis but Hq does,
    # repeat KV to full heads so attention compute/memory shards 16-way
    # (the kv-repeat is free on TPU relative to replicating whole scores).
    ts = tensor_size()
    ka, va = k, v
    if ts > 1 and cfg.n_kv_heads % ts and cfg.n_heads % ts == 0:
        g = cfg.n_heads // cfg.n_kv_heads
        ka = jnp.repeat(k, g, axis=2)
        va = jnp.repeat(v, g, axis=2)
    q = shard(q, "batch", None, "tensor", None)
    ka = shard(ka, "batch", None, "tensor", None)
    va = shard(va, "batch", None, "tensor", None)
    if triangle_skip:
        attn = flash_attention(q, ka, va, causal=True, window=cfg.window,
                               triangle_skip=True)
    else:
        attn = flash_attention_trainable(q, ka, va, causal=True,
                                         window=cfg.window)
    x = x + matmul(attn.reshape(B, S, -1), p["wo"], policy)
    h2 = rmsnorm(p["ln2"], x)
    ff, aux = _ffn(p, h2, cfg, policy)
    out = x + ff
    # Megatron-SP: residual stream sequence-sharded over the model axis
    # between blocks (psum -> reduce-scatter; remat carries shard 16x).
    out = shard(out, "batch", "tensor", None)
    if collect_kv:
        cdt = jnp.dtype(cfg.kv_cache_dtype or cfg.dtype)
        return out, aux, (k.astype(cdt), v.astype(cdt))
    return out, aux


def _write_rows(cache, rows, index, pos, writes):
    """Write ``rows[b]`` (B,Hkv,D) at ``cache[index, b, pos[b]]`` for the
    lanes where ``writes[b]``; every other lane's row is read and written
    back as it was (a position past the end clamps to the last row in
    both).  One ``dynamic_update_slice`` per lane, not a scatter: XLA
    updates it in place in the cache's stored layout (positions minor),
    where a scatter has the whole cache relaid out, and padded, around the
    scan."""
    for b in range(rows.shape[0]):
        at = (index, b, pos[b], 0, 0)
        old = lax.dynamic_slice(cache, at, (1, 1, 1) + rows.shape[1:])
        new = rows[b].astype(cache.dtype)[None, None, None]
        cache = lax.dynamic_update_slice(cache, jnp.where(writes[b], new, old),
                                         at)
    return cache


def attn_block_decode(p, x, k_all, v_all, index, cache_len, cfg: ArchConfig,
                      *, ring: bool = False, policy=None, active=None):
    """x: (B,1,d); k_all/v_all: (N,B,Smax,Hkv,D) stacked caches, of which
    this block reads and writes entry ``index`` in place; cache_len: ()
    or (B,) count before this token; active: (B,) bool or None (every
    lane).  A lane that is not active writes its old K/V row back, so its
    cache bits stay verbatim.
    Returns (out, new_k_all, new_v_all)."""
    B = x.shape[0]
    Smax = k_all.shape[2]
    h = rmsnorm(p["ln1"], x)
    cl = jnp.asarray(cache_len)
    per_batch = cl.ndim == 1  # continuous batching: each slot has its own len
    positions = (cl if per_batch else jnp.full((B,), cl))[:, None]
    q, k, v = _qkv(p, h, cfg, positions, policy)
    write_idx = jnp.broadcast_to((cl % Smax) if ring else cl, (B,))
    writes = write_idx < Smax  # a linear cache drops writes past its end
    if active is not None:
        writes = writes & active
    k_all = _write_rows(k_all, k[:, 0], index, write_idx, writes)
    v_all = _write_rows(v_all, v[:, 0], index, write_idx, writes)
    valid = jnp.minimum(cl + 1, Smax)
    # window semantics: a ring cache IS the window (attention is permutation
    # invariant over KV), so no extra window mask is needed when ring=True.
    attn = decode_attention(q, k_all[index], v_all[index], valid,
                            window=0 if ring else cfg.window)
    x = x + matmul(attn.reshape(B, 1, -1), p["wo"], policy)
    h2 = rmsnorm(p["ln2"], x)
    ff, _ = _ffn(p, h2, cfg, policy)
    return x + ff, k_all, v_all


def _chunk_attn_block(p, x, k_cache, v_cache, offsets, chunk_lens, positions,
                      cfg: ArchConfig, *, ring: bool, policy=None):
    """Chunk-resumable attention block over gathered per-lane cache lanes.

    x: (M,Cb,d) chunk activations; k_cache/v_cache: (M,smax,Hkv,D) this
    lane's cache; offsets/chunk_lens: (M,) tokens already prefilled / valid
    tokens in this chunk; positions: (M,Cb) absolute positions.  Computes
    the chunk's K/V, attends against gathered history + fresh chunk (the
    exact column set the monolithic prefill sees for these queries), and
    writes only the *valid* chunk K/V back — pad columns must never land in
    the cache (on a ring they could wrap onto live history).  Returns
    (out, new_k, new_v)."""
    M, Cb, _ = x.shape
    smax = k_cache.shape[1]
    h = rmsnorm(p["ln1"], x)
    q, k, v = _qkv(p, h, cfg, positions, policy)
    j = jnp.arange(Cb)
    valid_new = j[None, :] < chunk_lens[:, None]  # (M, Cb)
    i = jnp.arange(smax)
    if ring:
        # history ascending by absolute position: slot p % smax holds
        # position p, the ring holds at most the last smax positions
        hist_pos = offsets[:, None] - smax + i[None, :]  # (M, smax)
        hist_slot = hist_pos % smax
        k_hist = jnp.take_along_axis(k_cache,
                                     hist_slot[..., None, None], axis=1)
        v_hist = jnp.take_along_axis(v_cache,
                                     hist_slot[..., None, None], axis=1)
        hist_valid = hist_pos >= 0
    else:
        hist_pos = jnp.broadcast_to(i[None, :], (M, smax))
        k_hist, v_hist = k_cache, v_cache
        hist_valid = hist_pos < offsets[:, None]
    k_all = jnp.concatenate([k_hist.astype(k.dtype), k], axis=1)
    v_all = jnp.concatenate([v_hist.astype(v.dtype), v], axis=1)
    k_pos = jnp.concatenate([hist_pos, positions], axis=1)
    k_valid = jnp.concatenate([hist_valid, valid_new], axis=1)
    attn = chunk_attention(q, k_all, v_all, positions, k_pos, k_valid,
                           window=cfg.window)
    write_pos = (positions % smax) if ring else positions
    write_idx = jnp.where(valid_new, write_pos, smax)  # invalid -> dropped
    bi = jnp.arange(M)[:, None]
    new_k = k_cache.at[bi, write_idx].set(k.astype(k_cache.dtype),
                                          mode="drop")
    new_v = v_cache.at[bi, write_idx].set(v.astype(v_cache.dtype),
                                          mode="drop")
    x = x + matmul(attn.reshape(M, Cb, -1), p["wo"], policy)
    h2 = rmsnorm(p["ln2"], x)
    ff, _ = _ffn(p, h2, cfg, policy)
    out = shard(x + ff, "batch", "tensor", None)
    return out, new_k, new_v


# ---------------------------------------------------------------------------
# SSM block (norm + mamba)
# ---------------------------------------------------------------------------
def ssm_block_init(key, cfg: ArchConfig, dtype):
    d = cfg.d_model
    p = {"ln": rmsnorm_init(d, dtype)}
    if cfg.ssm_version == 1:
        p["mamba"] = ssm.mamba1_init(key, d, d_state=cfg.ssm_state,
                                     expand=cfg.ssm_expand, conv=cfg.ssm_conv,
                                     dtype=dtype)
    else:
        p["mamba"] = ssm.mamba2_init(key, d, d_state=cfg.ssm_state,
                                     expand=cfg.ssm_expand, conv=cfg.ssm_conv,
                                     head_dim=cfg.ssm_head_dim, dtype=dtype)
    return p


def ssm_block_apply(p, x, cfg: ArchConfig, state=None, return_state=False):
    h = rmsnorm(p["ln"], x)
    kw = dict(state=state, return_state=return_state,
              chunk=getattr(cfg, "ssm_scan_chunk", 64))
    if cfg.ssm_version == 1:
        out = ssm.mamba1_apply(p["mamba"], h, d_state=cfg.ssm_state, **kw)
    else:
        out = ssm.mamba2_apply(p["mamba"], h, d_state=cfg.ssm_state,
                               head_dim=cfg.ssm_head_dim, **kw)
    if return_state:
        y, new_state = out
        return shard(x + y, "batch", "tensor", None), new_state
    return shard(x + out, "batch", "tensor", None)


# ---------------------------------------------------------------------------
# The LM
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DecodeCache:
    """Pytree container for decode state (registered below)."""

    data: Dict
    length: jnp.ndarray  # scalar int32

    def tree_flatten(self):
        return (self.data, self.length), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    DecodeCache, lambda c: c.tree_flatten(),
    lambda aux, ch: DecodeCache(*ch))


class LM:
    """Functional decoder LM for one ArchConfig."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.vocab_padded = _pad_vocab(cfg.vocab_size)
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------- init ----
    def init(self, key) -> Dict:
        cfg, dtype = self.cfg, self.dtype
        ks = jax.random.split(key, 4)
        params: Dict = {
            "embed": embed_init(ks[0], self.vocab_padded, cfg.d_model, dtype),
            "final_norm": rmsnorm_init(cfg.d_model, dtype),
        }
        if cfg.family in ("dense", "moe", "vlm", "audio"):
            keys = jax.random.split(ks[1], cfg.n_layers)
            params["layers"] = jax.vmap(
                lambda k: attn_block_init(k, cfg, dtype))(keys)
        elif cfg.family == "ssm":
            keys = jax.random.split(ks[1], cfg.n_layers)
            params["layers"] = jax.vmap(
                lambda k: ssm_block_init(k, cfg, dtype))(keys)
        elif cfg.family == "hybrid":
            keys = jax.random.split(ks[1], cfg.n_layers)
            params["layers"] = jax.vmap(
                lambda k: ssm_block_init(k, cfg, dtype))(keys)
            params["shared_attn"] = attn_block_init(ks[2], cfg, dtype)
        else:
            raise ValueError(cfg.family)
        return params

    # ------------------------------------------------------- segments ------
    def _segments(self):
        """Hybrid: [(start, end, apply_shared_after), ...]."""
        cfg = self.cfg
        if cfg.family != "hybrid":
            return [(0, cfg.n_layers, False)]
        every = cfg.shared_attn_every
        segs = []
        start = 0
        while start < cfg.n_layers:
            end = min(start + every, cfg.n_layers)
            segs.append((start, end, end - start == every))
            start = end
        return segs

    @property
    def n_shared_applications(self) -> int:
        return sum(1 for _, _, s in self._segments() if s)

    # ------------------------------------------------------- forward -------
    def _embed_inputs(self, params, tokens, prefix_embeds, frame_embeds):
        cfg = self.cfg
        if frame_embeds is not None:
            x = frame_embeds.astype(self.dtype)
        else:
            x = embed_apply(params["embed"], tokens)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(self.dtype), x], axis=1)
        return shard(x, "batch", None, None)

    def apply(self, params, tokens=None, *, prefix_embeds=None,
              frame_embeds=None, policy=None, collect_kv: bool = False,
              triangle_skip: bool = False, logits_last_only: bool = False,
              last_index=None):
        """Full-sequence forward. Returns (logits, aux, kv or None).

        logits_last_only: unembed only the final position (prefill path —
        avoids materializing (B,S,V) f32 logits for 32k prompts).
        last_index: (B,) per-sample position to unembed instead of the last
        one (bucket-padded batched prefill: each sample's true final
        position)."""
        cfg = self.cfg
        x = self._embed_inputs(params, tokens, prefix_embeds, frame_embeds)
        B, S, _ = x.shape
        positions = jnp.arange(S)[None, :]
        aux_total = 0.0
        kv_out = []

        if cfg.family in ("dense", "moe", "vlm", "audio"):
            x, aux_total, kv = self._attn_stack(
                params["layers"], x, positions, policy, collect_kv,
                triangle_skip)
            if collect_kv:
                kv_out.append(kv)
        elif cfg.family == "ssm":
            x = self._ssm_stack(params["layers"], x)
        else:  # hybrid
            for (s, e, shared) in self._segments():
                seg = jax.tree.map(lambda a: a[s:e], params["layers"])
                x = self._ssm_stack(seg, x)
                if shared:
                    out = attn_block_apply(
                        params["shared_attn"], x, positions, cfg,
                        policy=policy, collect_kv=collect_kv,
                        triangle_skip=triangle_skip)
                    if collect_kv:
                        x, aux, kv = out
                        kv_out.append((kv[0][None], kv[1][None]))
                    else:
                        x, aux = out

        x = rmsnorm(params["final_norm"], x)
        if last_index is not None:
            x = x[jnp.arange(x.shape[0]), last_index][:, None]
        elif logits_last_only:
            x = x[:, -1:]
        logits = unembed_apply(params["embed"], x, policy)
        logits = shard(logits, "batch", None, "tensor")
        if collect_kv:
            if cfg.family == "hybrid" and kv_out:
                kv_out = (jnp.concatenate([k for k, _ in kv_out], 0),
                          jnp.concatenate([v for _, v in kv_out], 0))
            elif kv_out:
                kv_out = kv_out[0]
            return logits, aux_total, kv_out
        return logits, aux_total

    def _attn_stack(self, layers, x, positions, policy, collect_kv,
                    triangle_skip):
        cfg = self.cfg

        def body(carry, lp):
            x, aux = carry
            lp = constrain_layer_params(lp, cfg.n_experts)
            if collect_kv:
                y, a, kv = attn_block_apply(lp, x, positions, cfg,
                                            policy=policy, collect_kv=True,
                                            triangle_skip=triangle_skip)
                return (y, aux + a["aux_loss"]), kv
            y, a = attn_block_apply(lp, x, positions, cfg, policy=policy,
                                    triangle_skip=triangle_skip)
            return (y, aux + a["aux_loss"]), None

        fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux), kv = lax.scan(fn, (x, jnp.float32(0.0)), layers)
        return x, aux, kv

    def _ssm_stack(self, layers, x):
        cfg = self.cfg

        def body(x, lp):
            lp = constrain_layer_params(lp, cfg.n_experts)
            return ssm_block_apply(lp, x, cfg), None

        fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = lax.scan(fn, x, layers)
        return x

    # --------------------------------------------------------- loss --------
    def loss_fn(self, params, batch, *, policy=None):
        """batch: tokens/labels (+prefix_embeds | frame_embeds).
        labels < 0 are masked."""
        cfg = self.cfg
        logits, aux = self.apply(
            params, batch.get("tokens"),
            prefix_embeds=batch.get("prefix_embeds"),
            frame_embeds=batch.get("frame_embeds"), policy=policy)
        labels = batch["labels"]
        # vlm prefix positions produce logits we do not score
        if logits.shape[1] != labels.shape[1]:
            logits = logits[:, logits.shape[1] - labels.shape[1]:]
        mask = (labels >= 0).astype(jnp.float32)
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = -(ll * mask).sum() / denom
        # z-loss stabilizer (production training trick)
        zl = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2
                      * mask) * 1e-4
        loss = ce + zl + 0.01 * aux
        return loss, {"ce": ce, "z_loss": zl, "aux_loss": aux,
                      "tokens": denom}

    # -------------------------------------------------------- caches -------
    @property
    def cache_dtype(self):
        return jnp.dtype(self.cfg.kv_cache_dtype or self.cfg.dtype)

    def init_cache(self, batch: int, max_len: int) -> DecodeCache:
        cfg, dtype = self.cfg, self.cache_dtype
        data: Dict = {}
        if cfg.family in ("dense", "moe", "vlm", "audio"):
            smax = min(max_len, cfg.window) if cfg.window else max_len
            shp = (cfg.n_layers, batch, smax, cfg.n_kv_heads, cfg.head_dim)
            data["k"] = jnp.zeros(shp, dtype)
            data["v"] = jnp.zeros(shp, dtype)
        if cfg.family in ("ssm", "hybrid"):
            conv_s, h_s = ssm.mamba_state_shapes(cfg, batch)
            L = cfg.n_layers
            data["conv"] = jnp.zeros((L,) + conv_s.shape, conv_s.dtype)
            data["h"] = jnp.zeros((L,) + h_s.shape, h_s.dtype)
        if cfg.family == "hybrid":
            napp = self.n_shared_applications
            shp = (napp, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            data["k"] = jnp.zeros(shp, self.cache_dtype)
            data["v"] = jnp.zeros(shp, self.cache_dtype)
        return DecodeCache(data, jnp.int32(0))

    def cache_at_length(self, cache: DecodeCache, length) -> DecodeCache:
        return DecodeCache(cache.data, jnp.int32(length))

    # -------------------------------------------------------- decode -------
    def decode_step(self, params, cache: DecodeCache, tokens, *, policy=None,
                    active=None):
        """tokens: (B,1) -> (logits (B,1,V), new cache).

        Weights, SSM state and K/V are read where they lie in their stacks
        and written back row by row, so a fused multi-token scan carries
        the cache in place.  active: (B,) bool or None (every lane); a lane
        that is not active changes nothing: its K/V row is written back as
        it was and its conv/h keep their old value inside each layer's own
        update.  The cache length still advances for every lane
        (``decode_scan`` freezes it)."""
        cfg = self.cfg
        x = embed_apply(params["embed"], tokens)
        x = shard(x, "batch", None, None)
        clen = cache.length
        data = dict(cache.data)

        if cfg.family in ("dense", "moe", "vlm", "audio"):
            ring = bool(cfg.window)

            def body(carry, inp):
                x, k, v = carry
                lp, i = inp
                y, k, v = attn_block_decode(lp, x, k, v, i, clen, cfg,
                                            ring=ring, policy=policy,
                                            active=active)
                return (y, k, v), None

            (x, data["k"], data["v"]), _ = lax.scan(
                body, (x, data["k"], data["v"]),
                (params["layers"], jnp.arange(cfg.n_layers)))
        else:  # ssm, hybrid: Mamba layers, the shared block between segments
            def lanes(new, old):
                if active is None:
                    return new
                return jnp.where(
                    active.reshape(active.shape + (1,) * (new.ndim - 1)),
                    new, old)

            def mamba_layer(carry, i):
                x, conv, h = carry
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                y, (c2, h2) = ssm_block_apply(lp, x, cfg,
                                              state=(conv[i], h[i]),
                                              return_state=True)
                return (y, conv.at[i].set(lanes(c2, conv[i])),
                        h.at[i].set(lanes(h2, h[i]))), None

            conv, h = data["conv"], data["h"]
            app = 0
            for (s, e, shared) in self._segments():
                (x, conv, h), _ = lax.scan(mamba_layer, (x, conv, h),
                                           jnp.arange(s, e))
                if shared:
                    x, data["k"], data["v"] = attn_block_decode(
                        params["shared_attn"], x, data["k"], data["v"], app,
                        clen, cfg, ring=False, policy=policy, active=active)
                    app += 1
            data["conv"], data["h"] = conv, h

        x = rmsnorm(params["final_norm"], x)
        logits = unembed_apply(params["embed"], x, policy)
        logits = shard(logits, "batch", None, "tensor")
        return logits, DecodeCache(data, clen + 1)

    # -------------------------------------------------------- prefill ------
    def prefill(self, params, tokens=None, *, prefix_embeds=None,
                frame_embeds=None, max_len: Optional[int] = None,
                policy=None):
        """Run the full prompt, build a decode cache. Returns
        (last_logits (B,V), cache)."""
        cfg = self.cfg
        out = self.apply(params, tokens, prefix_embeds=prefix_embeds,
                         frame_embeds=frame_embeds, policy=policy,
                         collect_kv=cfg.family != "ssm",
                         logits_last_only=True)
        if cfg.family == "ssm":
            (logits, _), kv = out, None
        else:
            logits, _, kv = out
        if tokens is not None:
            B, S = tokens.shape
        else:
            B, S = frame_embeds.shape[:2]
        if prefix_embeds is not None:
            S += prefix_embeds.shape[1]
        max_len = max_len or S
        cache = self.init_cache(B, max_len)
        data = dict(cache.data)
        if cfg.family != "ssm" and kv:
            k, v = kv  # (L_or_apps, B, S, Hkv, D)
            smax = data["k"].shape[2]
            cdt = self.cache_dtype
            if smax >= S:
                # pad to max_len in one shot (no zero-buffer + copy)
                pad = [(0, 0), (0, 0), (0, smax - S), (0, 0), (0, 0)]
                data["k"] = jnp.pad(k.astype(cdt), pad)
                data["v"] = jnp.pad(v.astype(cdt), pad)
            else:  # sliding window: keep the tail, ring-aligned so that
                # position p sits at slot p % smax (decode writes there).
                shift = S % smax
                data["k"] = jnp.roll(k[:, :, S - smax:].astype(cdt),
                                     shift, axis=2)
                data["v"] = jnp.roll(v[:, :, S - smax:].astype(cdt),
                                     shift, axis=2)
        if cfg.family in ("ssm", "hybrid"):
            data["conv"], data["h"] = self._prefill_ssm_states(
                params, tokens, prefix_embeds, frame_embeds)
        return logits[:, -1], DecodeCache(data, jnp.int32(S))

    def prefill_batched(self, params, tokens, true_lens, *, policy=None):
        """Bucket-padded batched prefill for the serving engine.

        tokens: (M, Lb) int32 right-padded to one bucket length; true_lens:
        (M,) actual prompt lengths.  Returns
        ``(last_logits (M, V), kv or None, ssm_states or None)`` — raw
        per-layer KV (L_or_apps, M, Lb, Hkv, D) and (conv, h) states for the
        caller to scatter into a batched decode cache.

        Right-padding is exact for causal-attention families: a pad token
        can never enter a valid position's context, so the logits at
        ``true_lens - 1`` are the unpadded logits bit for bit.  SSM/hybrid
        state carries run *through* pads, so those families must be called
        with exact lengths (all ``true_lens == Lb``) — the engine's
        bucketer degenerates to exact-length batching for them.
        """
        cfg = self.cfg
        true_lens = jnp.asarray(true_lens, jnp.int32)
        collect = cfg.family != "ssm"
        out = self.apply(params, tokens, policy=policy, collect_kv=collect,
                         last_index=true_lens - 1)
        if collect:
            logits, _, kv = out
            if not kv:  # hybrid with no shared-attention segment collects []
                kv = None
        else:
            (logits, _), kv = out, None
        states = None
        if cfg.family in ("ssm", "hybrid"):
            states = self._prefill_ssm_states(params, tokens, None, None)
        return logits[:, 0], kv, states

    def prefill_chunk(self, params, cache: DecodeCache, tokens, offsets,
                      chunk_lens, slot_ids, *, policy=None):
        """One chunk of a chunk-resumable prefill over M lanes of a batched
        decode cache (``cache.length`` must be per-slot ``(B,)``).

        tokens: (M, Cb) int32 right-padded chunk tokens; offsets: (M,)
        tokens already prefilled per lane (0 = fresh lane: SSM states are
        zeroed); chunk_lens: (M,) valid tokens in this chunk; slot_ids:
        (M,) cache lanes (out-of-range = pad lane, dropped by every
        scatter).  Returns ``(last_logits (M, V), new_cache)`` — logits at
        each lane's final valid chunk position (the first output token
        when the chunk completes its prompt) and the cache with KV/conv/h
        written at the offsets and lane lengths advanced to
        ``offsets + chunk_lens``.

        Bitwise contract (vs monolithic ``prefill``/``prefill_batched``):
        attention families may pad chunks to buckets (pad columns are
        exact-zero additive identities); SSM/hybrid chunks must be exact
        length (``chunk_lens == Cb``: the conv carry is taken from the raw
        chunk tail) and every non-final chunk boundary must land on a
        multiple of ``cfg.ssm_scan_chunk`` (the internal scan's carry
        points).  History is read back from the cache, so the cache dtype
        must equal the compute dtype (``kv_cache_dtype`` unset)."""
        cfg = self.cfg
        x = embed_apply(params["embed"], tokens)
        x = shard(x, "batch", None, None)
        M, Cb = tokens.shape
        offsets = jnp.asarray(offsets, jnp.int32)
        chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
        slot_ids = jnp.asarray(slot_ids, jnp.int32)
        positions = offsets[:, None] + jnp.arange(Cb)[None, :]  # (M, Cb)
        data = dict(cache.data)
        ring = bool(cfg.window) and cfg.family != "hybrid"

        def attn_body(shared):
            def body(x, inp):
                lp, kc, vc = inp
                lp = constrain_layer_params(lp, cfg.n_experts)
                y, kc2, vc2 = _chunk_attn_block(
                    lp, x, kc, vc, offsets, chunk_lens, positions, cfg,
                    ring=ring and not shared, policy=policy)
                return y, (kc2, vc2)
            return body

        def ssm_body(x, inp):
            lp, conv, h = inp
            lp = constrain_layer_params(lp, cfg.n_experts)
            y, (c2, h2) = ssm_block_apply(lp, x, cfg, state=(conv, h),
                                          return_state=True)
            return y, (c2, h2)

        if cfg.family in ("dense", "moe", "vlm", "audio"):
            # gather this batch's lanes (OOB pad lanes clamp -> garbage
            # lanes whose scatters drop), scan the stack, scatter back
            k_lanes = data["k"][:, slot_ids]  # (L, M, smax, Hkv, D)
            v_lanes = data["v"][:, slot_ids]
            x, (k2, v2) = lax.scan(attn_body(False), x,
                                   (params["layers"], k_lanes, v_lanes))
            data["k"] = data["k"].at[:, slot_ids].set(k2, mode="drop")
            data["v"] = data["v"].at[:, slot_ids].set(v2, mode="drop")
        else:
            fresh = (offsets == 0)
            conv_lanes = data["conv"][:, slot_ids]
            h_lanes = data["h"][:, slot_ids]
            # a fresh lane inherits the previous occupant's state: zero it
            # (zeros == the state=None start of the monolithic prefill)
            conv_lanes = jnp.where(fresh.reshape(1, -1, 1, 1), 0.0,
                                   conv_lanes)
            h_lanes = jnp.where(
                fresh.reshape((1, -1) + (1,) * (h_lanes.ndim - 2)), 0.0,
                h_lanes)
            if cfg.family == "ssm":
                x, (c2, h2) = lax.scan(
                    ssm_body, x, (params["layers"], conv_lanes, h_lanes))
            else:  # hybrid: ssm segments + the shared attention block
                new_conv, new_h = [], []
                app_idx = 0
                for (s, e, shared) in self._segments():
                    seg = jax.tree.map(lambda a: a[s:e], params["layers"])
                    x, (c2, h2) = lax.scan(
                        ssm_body, x, (seg, conv_lanes[s:e], h_lanes[s:e]))
                    new_conv.append(c2)
                    new_h.append(h2)
                    if shared:
                        k_lane = data["k"][app_idx][slot_ids]
                        v_lane = data["v"][app_idx][slot_ids]
                        x, k2, v2 = _chunk_attn_block(
                            params["shared_attn"], x, k_lane, v_lane,
                            offsets, chunk_lens, positions, cfg,
                            ring=False, policy=policy)
                        data["k"] = data["k"].at[app_idx, slot_ids].set(
                            k2, mode="drop")
                        data["v"] = data["v"].at[app_idx, slot_ids].set(
                            v2, mode="drop")
                        app_idx += 1
                c2 = jnp.concatenate(new_conv, 0)
                h2 = jnp.concatenate(new_h, 0)
            data["conv"] = data["conv"].at[:, slot_ids].set(c2, mode="drop")
            data["h"] = data["h"].at[:, slot_ids].set(h2, mode="drop")

        length = cache.length.at[slot_ids].set(offsets + chunk_lens,
                                               mode="drop")
        x = rmsnorm(params["final_norm"], x)
        x = x[jnp.arange(M), chunk_lens - 1][:, None]
        logits = unembed_apply(params["embed"], x, policy)
        logits = shard(logits, "batch", None, "tensor")
        return logits[:, 0], DecodeCache(data, length)

    def prefill_chunked(self, params, tokens, chunk_size: int, *,
                        max_len: Optional[int] = None, policy=None):
        """Monolithic-prefill equivalent built from ``prefill_chunk`` steps
        (the parity-test entry point and the reference for the serving
        scheduler).  tokens: (B, S) exact (no pads).  Returns
        ``(last_logits (B, V), cache)`` with per-lane lengths — bitwise
        equal to ``prefill`` for any chunk_size obeying the family's
        boundary contract (see ``prefill_chunk``)."""
        B, S = tokens.shape
        max_len = max_len or S
        base = self.init_cache(B, max_len)
        cache = DecodeCache(base.data, jnp.zeros(B, jnp.int32))
        slot_ids = jnp.arange(B, dtype=jnp.int32)
        last = None
        for off in range(0, S, chunk_size):
            clen = min(chunk_size, S - off)
            last, cache = self.prefill_chunk(
                params, cache, tokens[:, off:off + clen],
                jnp.full((B,), off, jnp.int32),
                jnp.full((B,), clen, jnp.int32), slot_ids, policy=policy)
        return last, cache

    def decode_scan(self, params, cache: DecodeCache, tok, active, budget,
                    n_steps: int, *, pad_id: int = 0, policy=None,
                    stop_tokens: tuple = ()):
        """Fused greedy multi-token decode: ``n_steps`` decode_step + argmax
        iterations in one ``lax.scan`` — a single host dispatch decodes up
        to ``n_steps`` tokens for every live slot.

        cache.length must be per-slot (B,); tok: (B, 1) next token per slot;
        active: (B,) bool gates which lanes sample/advance; budget: (B,)
        int32 remaining tokens per slot.  Inactive lanes still ride the
        batched step (wasted lanes, the continuous-batching deal), but
        nothing they compute lands: they are frozen at the write, not by a
        select over the whole cache.  ``decode_step`` writes an inactive
        lane's K/V row back as it was and keeps its conv/h inside each
        layer's update, and its length/token/budget are frozen.  So an
        inactive lane's cache bits stay verbatim: a lane mid chunked
        prefill keeps the partial KV, conv and h its chunks wrote, ring
        caches keep their history whole, and the next chunk resumes from
        exactly that state.  Lanes deactivate *on device* when their
        budget hits zero — or, with ``stop_tokens`` (a static tuple of
        EOS-class token ids), when they sample a stop token: the stop token
        itself is still emitted (and counted against the budget), then the
        lane freezes inside the same dispatch, so no post-EOS tokens are
        ever decoded or charged.  Returns
        ``(cache, tok, active, budget, toks (n, B), emitted (n, B))`` where
        ``emitted[t, b]`` marks lane b having sampled ``toks[t, b]`` at
        scan step t.
        """
        stop_tokens = tuple(int(s) for s in stop_tokens)

        def body(carry, _):
            cache, tok, active, budget = carry
            logits, stepped = self.decode_step(params, cache, tok,
                                               policy=policy, active=active)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            emit = jnp.where(active, nxt, jnp.int32(pad_id))
            budget = budget - active.astype(budget.dtype)
            length = jnp.where(active, stepped.length, cache.length)
            new_tok = jnp.where(active[:, None], nxt[:, None], tok)
            new_active = active & (budget > 0)
            if stop_tokens:
                stopped = jnp.zeros_like(active)
                for s in stop_tokens:
                    stopped = stopped | (nxt == jnp.int32(s))
                new_active = new_active & ~(active & stopped)
            return (DecodeCache(stepped.data, length), new_tok, new_active,
                    budget), (emit, active)

        (cache, tok, active, budget), (toks, emitted) = lax.scan(
            body, (cache, tok, active, budget), None, length=n_steps)
        return cache, tok, active, budget, toks, emitted

    def _prefill_ssm_states(self, params, tokens, prefix_embeds,
                            frame_embeds):
        """Stateful stack forward (scan-based, one layer's working set live)
        harvesting the per-layer conv/h decode states."""
        cfg = self.cfg
        x = self._embed_inputs(params, tokens, prefix_embeds, frame_embeds)
        positions = jnp.arange(x.shape[1])[None, :]
        layers = params["layers"]

        def body(x, lp):
            lp = constrain_layer_params(lp, cfg.n_experts)
            y, st = ssm_block_apply(lp, x, cfg, state=None, return_state=True)
            return y, st

        if cfg.family == "ssm":
            _, (convs, hs) = lax.scan(body, x, layers)
            return convs, hs
        convs_l, hs_l = [], []
        for (s, e, shared) in self._segments():
            seg = jax.tree.map(lambda a: a[s:e], layers)
            x, (conv, h) = lax.scan(body, x, seg)
            convs_l.append(conv)
            hs_l.append(h)
            if shared:
                x, _ = attn_block_apply(params["shared_attn"], x, positions,
                                        cfg)
        return jnp.concatenate(convs_l, 0), jnp.concatenate(hs_l, 0)
