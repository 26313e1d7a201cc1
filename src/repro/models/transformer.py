"""Stack builder: composes blocks into layer-scanned segments.

A model is a list of SEGMENTS. Each segment is (kind, n) with parameters
stacked along a leading layer axis, executed with jax.lax.scan (+ optional
per-layer remat). Heterogeneous architectures (hybrid Griffin pattern,
DeepSeek's dense-first-layer) are expressed as multiple segments; the
hybrid pattern itself becomes one "group" segment whose body runs the
pattern (rec, rec, attn) so the scan stays homogeneous.

Segment kinds:
  attn       self-attention + dense MLP           (dense, vlm, enc w/ causal=False)
  attn_moe   self-attention + MoE FFN             (olmoe, deepseek routed layers)
  ssm        Mamba-2 SSD block (no FFN)           (mamba2)
  group      Griffin pattern: rec, rec, local-attn each + MLP (recurrentgemma)
  rec        single RG-LRU block + MLP            (hybrid remainder layers)
  dec        self-attn + cross-attn + MLP         (audio decoder)

Decode caches mirror the segment list; each segment's cache is stacked along
the same leading axis and scanned together with its params.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.layers import mlp, mlp_init, rmsnorm, rmsnorm_init
from repro.sharding.ctx import constrain


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str
    n: int


def plan_segments(cfg: ModelConfig, role: str = "decoder") -> tuple[Segment, ...]:
    """Derive the segment plan for a config. role: decoder | encoder."""
    if role == "encoder":
        return (Segment("attn", cfg.n_enc_layers),)
    if cfg.is_encdec:
        return (Segment("dec", cfg.n_layers),)
    if cfg.is_hybrid:
        plen = len(cfg.block_pattern)
        n_groups, rem = divmod(cfg.n_layers, plen)
        segs = []
        if n_groups:
            segs.append(Segment("group", n_groups))
        for i in range(rem):  # trailing partial pattern, one segment per layer
            kind = cfg.block_pattern[i]
            segs.append(Segment("rec" if kind == "rec" else "attn", 1))
        return tuple(segs)
    if cfg.is_ssm:
        return (Segment("ssm", cfg.n_layers),)
    if cfg.is_moe:
        segs = []
        if cfg.first_k_dense:
            segs.append(Segment("attn", cfg.first_k_dense))
        segs.append(Segment("attn_moe", cfg.n_layers - cfg.first_k_dense))
        return tuple(segs)
    return (Segment("attn", cfg.n_layers),)


# ----------------------------------------------------------------- init
def _block_init(kind: str, key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    if kind == "attn":
        return {
            "ln1": rmsnorm_init(cfg.d_model),
            "attn": A.attn_init(ks[0], cfg),
            "ln2": rmsnorm_init(cfg.d_model),
            "mlp": mlp_init(ks[1], cfg),
        }
    if kind == "attn_moe":
        return {
            "ln1": rmsnorm_init(cfg.d_model),
            "attn": A.attn_init(ks[0], cfg),
            "ln2": rmsnorm_init(cfg.d_model),
            "moe": MOE.moe_init(ks[1], cfg),
        }
    if kind == "ssm":
        return {"ln1": rmsnorm_init(cfg.d_model), "ssm": S.ssm_init(ks[0], cfg)}
    if kind == "rec":
        return {
            "ln1": rmsnorm_init(cfg.d_model),
            "rec": R.rglru_init(ks[0], cfg),
            "ln2": rmsnorm_init(cfg.d_model),
            "mlp": mlp_init(ks[1], cfg),
        }
    if kind == "group":
        out = {}
        for i, bk in enumerate(cfg.block_pattern):
            sub = "rec" if bk == "rec" else "attn"
            out[f"b{i}_{sub}"] = _block_init(sub, ks[i], cfg)
        return out
    if kind == "dec":
        return {
            "ln1": rmsnorm_init(cfg.d_model),
            "attn": A.attn_init(ks[0], cfg),
            "lnx": rmsnorm_init(cfg.d_model),
            "xattn": A.attn_init(ks[1], cfg, cross=True),
            "ln2": rmsnorm_init(cfg.d_model),
            "mlp": mlp_init(ks[2], cfg),
        }
    raise ValueError(kind)


def stack_init(key, cfg: ModelConfig, role: str = "decoder"):
    """Returns list of stacked per-segment param pytrees."""
    segs = plan_segments(cfg, role)
    out = []
    for si, seg in enumerate(segs):
        keys = jax.random.split(jax.random.fold_in(key, si), seg.n)
        out.append(jax.vmap(lambda k, kind=seg.kind: _block_init(kind, k, cfg))(keys))
    return out


# --------------------------------------------------------------- full pass
def _ffn(p, h, cfg: ModelConfig):
    if "moe" in p:
        y, aux = MOE.moe_ffn(p["moe"], rmsnorm(p["ln2"], h, cfg.norm_eps), cfg)
        return h + y, aux
    y = mlp(p["mlp"], rmsnorm(p["ln2"], h, cfg.norm_eps), cfg.act)
    return h + y, None


def _window_for(kind: str, cfg: ModelConfig, shape_window: Optional[int]) -> Optional[int]:
    """Effective attention window: hybrids always use their local window;
    dense archs use shape_window (set for long_500k's sliding variant)."""
    if cfg.is_hybrid:
        return cfg.local_window
    return shape_window


def _block_forward(kind, p, h, cfg: ModelConfig, *, causal, window, prefix_len, enc_out):
    aux = None
    if kind in ("attn", "attn_moe"):
        a = A.attn_forward(
            p["attn"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg,
            causal=causal, window=window, prefix_len=prefix_len,
        )
        h, aux = _ffn(p, h + a, cfg)
    elif kind == "ssm":
        h = h + S.ssm_forward(p["ssm"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg)
    elif kind == "rec":
        a = R.rglru_forward(p["rec"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg)
        h, aux = _ffn(p, h + a, cfg)
    elif kind == "group":
        auxes = []
        for i, bk in enumerate(cfg.block_pattern):
            sub = "rec" if bk == "rec" else "attn"
            h, a2 = _group_sub_forward(
                sub, p[f"b{i}_{sub}"], h, cfg, causal=causal,
                window=cfg.local_window, prefix_len=prefix_len,
            )
            if a2 is not None:
                auxes.append(a2)
        aux = auxes[0] if auxes else None
    elif kind == "dec":
        a = A.attn_forward(
            p["attn"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg,
            causal=True, window=window,
        )
        h = h + a
        x = A.attn_forward(p["xattn"], rmsnorm(p["lnx"], h, cfg.norm_eps), cfg, kv_x=enc_out)
        h, aux = _ffn(p, h + x, cfg)
    else:
        raise ValueError(kind)
    return constrain(h), aux


def _group_sub_forward(sub, p, h, cfg, *, causal, window, prefix_len):
    if sub == "rec":
        a = R.rglru_forward(p["rec"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg)
    else:
        a = A.attn_forward(
            p["attn"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg,
            causal=causal, window=window, prefix_len=prefix_len,
        )
    return _ffn(p, h + a, cfg)


def forward_hidden(
    stack,
    h: jax.Array,
    cfg: ModelConfig,
    *,
    role: str = "decoder",
    enc_out: Optional[jax.Array] = None,
    prefix_len: int = 0,
    shape_window: Optional[int] = None,
) -> tuple[jax.Array, dict]:
    """Run the full stack over (B, S, D). Returns (h, aux_losses)."""
    segs = plan_segments(cfg, role)
    causal = role != "encoder"
    aux_acc = {"lb_loss": 0.0, "z_loss": 0.0, "drop_frac": 0.0}
    n_moe = 0
    for seg, params in zip(segs, stack, strict=True):
        window = _window_for(seg.kind, cfg, shape_window)

        def body(carry, p, kind=seg.kind, window=window):
            hh, acc = carry
            hh, aux = _block_forward(
                kind, p, hh, cfg, causal=causal, window=window,
                prefix_len=prefix_len, enc_out=enc_out,
            )
            if aux is not None:
                acc = {
                    "lb_loss": acc["lb_loss"] + aux["lb_loss"],
                    "z_loss": acc["z_loss"] + aux["z_loss"],
                    "drop_frac": acc["drop_frac"] + aux["drop_frac"],
                }
            return (hh, acc), None

        if cfg.remat:
            if cfg.remat_policy == "dots":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                )
            else:
                body = jax.checkpoint(body)
        (h, aux_acc), _ = jax.lax.scan(body, (h, aux_acc), params)
        if seg.kind in ("attn_moe",) or (seg.kind == "group" and cfg.is_moe):
            n_moe += seg.n
    if n_moe:
        aux_acc = {k: v / n_moe for k, v in aux_acc.items()}
    return h, aux_acc


# ------------------------------------------------------------------ prefill
def _block_prefill(kind, p, h, cfg: ModelConfig, *, cache_len, window, prefix_len,
                   enc_out, seq_lens=None):
    """Returns (h, cache) for one layer."""
    if kind in ("attn", "attn_moe"):
        a, kv = A.attn_prefill(
            p["attn"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg, cache_len,
            window=window, prefix_len=prefix_len, seq_lens=seq_lens,
        )
        h, _ = _ffn(p, h + a, cfg)
        return constrain(h), kv
    if seq_lens is not None:
        # recurrent state (ssm/rec/group) integrates every position — pads
        # would leak; enc-dec carries cross state. Gated upstream
        # (ragged_prefill_supported); fail loudly if reached anyway.
        raise ValueError(f"ragged prefill is not supported for {kind!r} blocks")
    if kind == "ssm":
        y, st = S.ssm_forward_with_state(p["ssm"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg)
        return constrain(h + y), st
    if kind == "rec":
        a, st = R.rglru_forward_with_state(p["rec"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg)
        h, _ = _ffn(p, h + a, cfg)
        return constrain(h), st
    if kind == "group":
        caches = {}
        for i, bk in enumerate(cfg.block_pattern):
            sub = "rec" if bk == "rec" else "attn"
            pp = p[f"b{i}_{sub}"]
            if sub == "rec":
                a, st = R.rglru_forward_with_state(
                    pp["rec"], rmsnorm(pp["ln1"], h, cfg.norm_eps), cfg
                )
                caches[f"b{i}"] = st
            else:
                a, st = A.attn_prefill(
                    pp["attn"], rmsnorm(pp["ln1"], h, cfg.norm_eps), cfg,
                    min(cache_len, cfg.local_window), window=cfg.local_window,
                )
                caches[f"b{i}"] = st
            h, _ = _ffn(pp, h + a, cfg)
        return constrain(h), caches
    if kind == "dec":
        a, kv = A.attn_prefill(
            p["attn"], rmsnorm(p["ln1"], h, cfg.norm_eps), cfg, cache_len, window=window
        )
        h = h + a
        x = A.attn_forward(p["xattn"], rmsnorm(p["lnx"], h, cfg.norm_eps), cfg, kv_x=enc_out)
        xc = A.cross_attn_cache(p["xattn"], enc_out)
        h, _ = _ffn(p, h + x, cfg)
        return constrain(h), {"self": kv, "cross": xc}
    raise ValueError(kind)


def prefill_hidden(stack, h, cfg: ModelConfig, *, cache_len, enc_out=None,
                   prefix_len: int = 0, shape_window: Optional[int] = None,
                   seq_lens: Optional[jax.Array] = None):
    """Full-prompt pass building decode caches. Returns (h, caches)."""
    segs = plan_segments(cfg, "decoder")
    caches = []
    for seg, params in zip(segs, stack, strict=True):
        window = _window_for(seg.kind, cfg, shape_window)

        def body(hh, p, kind=seg.kind, window=window):
            hh, cache = _block_prefill(
                kind, p, hh, cfg, cache_len=cache_len, window=window,
                prefix_len=prefix_len, enc_out=enc_out, seq_lens=seq_lens,
            )
            return hh, cache

        h, seg_cache = jax.lax.scan(body, h, params)
        caches.append(seg_cache)
    return h, caches


# ------------------------------------------------------------------- decode
def _block_decode(kind, p, h, cache, pos, cfg: ModelConfig, *, window):
    if kind in ("attn", "attn_moe"):
        with jax.named_scope("attn"):
            a, cache = A.attn_decode(
                p["attn"], rmsnorm(p["ln1"], h, cfg.norm_eps), cache, pos, cfg,
                window=window,
            )
        with jax.named_scope("ffn"):
            h, _ = _ffn(p, h + a, cfg)
        return h, cache
    if kind == "ssm":
        y, cache = S.ssm_decode(p["ssm"], rmsnorm(p["ln1"], h, cfg.norm_eps), cache, cfg)
        return h + y, cache
    if kind == "rec":
        a, cache = R.rglru_decode(p["rec"], rmsnorm(p["ln1"], h, cfg.norm_eps), cache, cfg)
        h, _ = _ffn(p, h + a, cfg)
        return h, cache
    if kind == "group":
        new = {}
        for i, bk in enumerate(cfg.block_pattern):
            sub = "rec" if bk == "rec" else "attn"
            pp = p[f"b{i}_{sub}"]
            if sub == "rec":
                a, st = R.rglru_decode(pp["rec"], rmsnorm(pp["ln1"], h, cfg.norm_eps), cache[f"b{i}"], cfg)
            else:
                a, st = A.attn_decode(
                    pp["attn"], rmsnorm(pp["ln1"], h, cfg.norm_eps), cache[f"b{i}"],
                    pos, cfg, window=cfg.local_window,
                )
            new[f"b{i}"] = st
            h, _ = _ffn(pp, h + a, cfg)
        return h, new
    if kind == "dec":
        a, kv = A.attn_decode(
            p["attn"], rmsnorm(p["ln1"], h, cfg.norm_eps), cache["self"], pos, cfg, window=window
        )
        h = h + a
        x = A.cross_attn_decode(p["xattn"], rmsnorm(p["lnx"], h, cfg.norm_eps), cache["cross"], cfg)
        h, _ = _ffn(p, h + x, cfg)
        return h, {"self": kv, "cross": cache["cross"]}
    raise ValueError(kind)


def ragged_prefill_supported(cfg: ModelConfig) -> bool:
    """Ragged (length-aware) prefill covers pure dense-attention stacks.

    Recurrent blocks (ssm/rec) integrate state through every position, so
    trailing pads would alter real rows; MoE FFN blocks couple tokens
    through capacity assignment (position_in_expert is a cumsum over the
    whole token block), so the padded-bucket size leaks into routing —
    neither can be bit-identical across bucket sizes. Dense attention + MLP
    stacks are per-position outside the causally-masked attention, which is
    exactly the property ragged bucketing relies on.
    """
    if cfg.is_encdec or cfg.arch_type in ("vlm", "audio"):
        return False
    return all(s.kind == "attn" for s in plan_segments(cfg, "decoder"))


def chunked_prefill_supported(cfg: ModelConfig) -> bool:
    """Chunked (continuous-batching) prefill covers the ragged-prefill archs.

    Lossy cache precisions (quantized or legacy cast) no longer disqualify a
    config: the in-flight prompt's K/V is carried in a native-dtype staging
    buffer (DESIGN.md §14) and attended there, so chunk N re-reads chunk
    N-1's rows exactly as the one-shot prefill would — the rounded copy in
    the cache/pool is written at the same time but only read after the
    prompt phase. MoE is excluded for the ragged reason squared: capacity
    assignment is a cumsum over the token block, so chunk boundaries would
    change routing.
    """
    return ragged_prefill_supported(cfg)


def _chunk_rows(valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(rows, n_rows): the rows with ``valid > 0`` in ascending order,
    padded with B, and their count. The order changes nothing: each row
    writes only its own pages, cache row and staging row."""
    B = valid.shape[0]
    on = valid > 0
    return (jnp.nonzero(on, size=B, fill_value=B)[0].astype(jnp.int32),
            jnp.sum(on, dtype=jnp.int32))


def _chunk_layer_rows(params, l, h, cache, rows, n_rows, attend,
                      cfg: ModelConfig):
    """Layer ``l``'s chunk attention + FFN for the rows that carry a chunk,
    one row per iteration: ``rows[:n_rows]`` (engine rows; the tail pads
    are never visited). ``n_rows`` is a device value, so one program serves
    every count of chunk rows; rows that carry no chunk do no work and
    keep their ``h`` (their logits are discarded by the caller).

    Each iteration reads the layer's weights once, so k chunk rows cost k
    weight reads and k one-row attentions — near a ``[1, C]`` pass for the
    usual one or two rows; at k = B the cost model puts it near the padded
    ``[B, C]`` pass (an estimate, not measured).
    The weights are sliced from the segment's stacked ``params`` inside
    the loop body at an index the barrier ties to the iteration: XLA then
    fuses the slice into the matmuls, where a loop-invariant slice is
    hoisted out of the loop and copied whole (on a v5e at granite-3-2b's
    widths, ~14 ms of weight copies per mixed dispatch).
    ``attend(x, cache, r, p)`` -> (a, cache) is the layer's attention for
    engine row r on x (1, C, D); ``cache`` is carried and updated in place.
    """
    def body(i, carry):
        hh, c = carry
        r = rows[i]
        li, _ = jax.lax.optimization_barrier((l, i))
        p = jax.tree.map(lambda w: w[li], params)
        x = jax.lax.dynamic_slice_in_dim(hh, r, 1)
        with jax.named_scope("attn"):
            a, c = attend(rmsnorm(p["ln1"], x, cfg.norm_eps), c, r, p)
        with jax.named_scope("ffn"):
            x, _ = _ffn(p, x + a, cfg)
        return jax.lax.dynamic_update_slice_in_dim(hh, x, r, 0), c

    return jax.lax.fori_loop(0, n_rows, body, (h, cache))


def _take_row(tree, r):
    return jax.tree.map(lambda x: jax.lax.dynamic_slice_in_dim(x, r, 1), tree)


def _put_row(tree, one, r):
    return jax.tree.map(
        lambda x, y: jax.lax.dynamic_update_slice_in_dim(x, y, r, 0), tree, one)


def chunk_hidden(stack, h, caches, pos0, valid, reset, cfg: ModelConfig, *,
                 shape_window: Optional[int] = None):
    """One prompt-chunk pass over (B, C, D); mirrors ``prefill_hidden``'s
    per-layer op order (attn -> residual -> FFN -> constrain) with
    ``attn_chunk`` writing K/V at per-row offsets. Only the rows with
    ``valid > 0`` compute (``_chunk_layer_rows``). Returns (h, caches)."""
    segs = plan_segments(cfg, "decoder")
    rows, n_rows = _chunk_rows(valid)
    new_caches = []
    for seg, params, cache in zip(segs, stack, caches, strict=True):
        if seg.kind != "attn":
            raise ValueError(f"chunked prefill is not supported for {seg.kind!r} blocks")
        window = _window_for(seg.kind, cfg, shape_window)

        def body(hh, lc, params=params, window=window):
            l, c = lc

            def attend(x, c, r, p):
                a, one = A.attn_chunk(
                    p["attn"], x, _take_row(c, r), pos0[r][None],
                    valid[r][None], cfg, window=window, reset=reset[r][None],
                )
                return a, _put_row(c, one, r)

            hh, c = _chunk_layer_rows(params, l, hh, c, rows, n_rows, attend,
                                      cfg)
            return constrain(hh), c

        h, seg_cache = jax.lax.scan(body, h, (jnp.arange(seg.n), cache))
        new_caches.append(seg_cache)
    return h, new_caches


def chunk_hidden_paged(stack, h, pools, block_table, pos0, valid,
                       cfg: ModelConfig, base=None):
    """``chunk_hidden`` against the shared page pools (one block table for
    the whole stack, like ``decode_hidden_paged``). ``base`` (B,) is the
    per-row prefix-cache hit length — under a lossy precision it splits
    attention between pool pages [0, base) and the native staging buffer,
    whose row r is the engine row's.

    Each segment's stacked pool rides in the layer scan's carry and in the
    row loop's, and every leaf (pages, scales, staging) is written and read
    at (layer, row or page, offset) in place: no layer's slab is sliced out
    or written back (DESIGN.md §6)."""
    segs = plan_segments(cfg, "decoder")
    rows, n_rows = _chunk_rows(valid)
    if base is None:
        base = jnp.zeros_like(valid)
    new_pools = []
    for seg, params, pool in zip(segs, stack, pools, strict=True):
        if seg.kind != "attn":
            raise ValueError(f"chunked prefill is not supported for {seg.kind!r} blocks")

        def body(carry, l, params=params):
            hh, pool = carry

            def attend(x, pool, r, p):
                return A.attn_chunk_paged(
                    p["attn"], x, pool, block_table[r][None], pos0[r][None],
                    valid[r][None], cfg, base=base[r][None], layer=l,
                    stage_row=r[None],
                )

            hh, pool = _chunk_layer_rows(params, l, hh, pool, rows, n_rows,
                                         attend, cfg)
            return (constrain(hh), pool), None

        (h, pool), _ = jax.lax.scan(body, (h, pool), jnp.arange(seg.n))
        new_pools.append(pool)
    return h, new_pools


def paged_segments_supported(cfg: ModelConfig) -> bool:
    """Paged decode covers pure-attention stacks (dense + MoE FFN blocks).

    SSM/RG-LRU segments carry recurrent state, not a KV cache — nothing to
    page — and enc-dec carries cross-attention state; those archs stay on
    the dense engine.
    """
    if cfg.is_encdec or cfg.arch_type in ("vlm", "audio"):
        return False
    return all(s.kind in ("attn", "attn_moe") for s in plan_segments(cfg, "decoder"))


def paged_pools_init(cfg: ModelConfig, num_pages: int, page_size: int,
                     native_pages=None, stage_rows: int = 0,
                     stage_len: int = 0):
    """Per-segment page pools, leaves stacked on the layer axis like every
    other cache: list of PagedKVPool with k/v (n, native_pages, page_size,
    KVH, hd) and, under a quantized precision, qk/qv + scales for physical
    ids >= native_pages (DESIGN.md §14). All layers of one segment share
    page indexing (one block table per request serves the whole stack)."""
    if not paged_segments_supported(cfg):
        raise ValueError(
            f"paged decode requires an all-attention stack; {cfg.name} has "
            f"segments {[s.kind for s in plan_segments(cfg, 'decoder')]}"
        )
    pools = []
    for seg in plan_segments(cfg, "decoder"):
        one = A.paged_pool_init(num_pages, page_size, cfg,
                                native_pages=native_pages,
                                stage_rows=stage_rows, stage_len=stage_len)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (seg.n, *x.shape)).copy(), one)
        pools.append(stacked)
    return pools


def decode_hidden_paged(stack, h, pools, block_table, pos, cfg: ModelConfig):
    """One-token pass over the paged pools. h: (B, D).

    Mirrors ``decode_hidden`` (same scan structure, same residual/FFN op
    order) with ``attn_decode_paged`` in place of ``attn_decode``, so the
    two paths are bit-identical on shared-length workloads. The block table
    is shared by every layer. Each segment's stacked pool rides in the
    scan's carry beside ``h``; layer l writes and gathers its K/V at
    (l, page, offset) in place, so no step slices, rewrites or copies the
    whole pool (DESIGN.md §6).
    """
    segs = plan_segments(cfg, "decoder")
    new_pools = []
    for seg, params, pool in zip(segs, stack, pools, strict=True):
        assert seg.kind in ("attn", "attn_moe"), seg.kind

        def body(carry, pl):
            hh, pool = carry
            p, l = pl
            with jax.named_scope("attn"):
                a, pool = A.attn_decode_paged(
                    p["attn"], rmsnorm(p["ln1"], hh, cfg.norm_eps), pool,
                    block_table, pos, cfg, layer=l,
                )
            with jax.named_scope("ffn"):
                hh, _ = _ffn(p, hh + a, cfg)
            return (hh, pool), None

        (h, pool), _ = jax.lax.scan(body, (h, pool),
                                    (params, jnp.arange(seg.n)))
        new_pools.append(pool)
    return h, new_pools


def decode_hidden(stack, h, caches, pos, cfg: ModelConfig, *, shape_window=None):
    """One-token pass. h: (B, D). Returns (h, new_caches)."""
    segs = plan_segments(cfg, "decoder")
    new_caches = []
    for seg, params, cache in zip(segs, stack, caches, strict=True):
        window = _window_for(seg.kind, cfg, shape_window)

        def body(hh, pc, kind=seg.kind, window=window):
            p, c = pc
            hh, c = _block_decode(kind, p, hh, c, pos, cfg, window=window)
            return hh, c

        h, seg_cache = jax.lax.scan(body, h, (params, cache))
        new_caches.append(seg_cache)
    return h, new_caches
