"""Jit'd dispatch wrappers over the Pallas kernels.

Every entry point auto-selects interpret mode off-TPU so the same call
sites run on CPU (tests, this container) and TPU (production) unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .ref import attention_ref, xmv_batched_ref, xmv_ref
from .xmv_block_sparse import RowPanelPack, from_tiles, \
    pack_graph_row_panels, pack_row_panels, to_tiles, xmv_gram_tile, \
    xmv_row_panel, xmv_row_panel_batched
from .xmv_dense import dense_row_panels, xmv_dense, xmv_dense_batched

__all__ = [
    "xmv_dense", "xmv_dense_batched", "RowPanelPack", "pack_row_panels",
    "pack_graph_row_panels", "xmv_row_panel", "xmv_row_panel_batched",
    "xmv_gram_tile", "stack_row_panel_packs", "take_row_panel_pack",
    "row_panel_packs_for_batch", "flash_attention", "attention_ref",
    "xmv_ref", "xmv_batched_ref", "to_tiles", "from_tiles",
    "dense_row_panels",
]


def take_row_panel_pack(pack: RowPanelPack, indices) -> RowPanelPack:
    """Gather a stacked RowPanelPack along its leading pair/graph axis
    (``indices`` int array) — the segmented-PCG pair-retirement remap
    and the Gram-tile -> per-pair pack expansion (core/mgk.py)."""
    idx = jnp.asarray(indices)
    return RowPanelPack(*(jnp.take(f, idx, axis=0) for f in pack))


def stack_row_panel_packs(packs: list[RowPanelPack]) -> RowPanelPack:
    """Stack per-pair RowPanelPacks (same bucket => same shapes) to
    [B, ...]."""
    return RowPanelPack(*(jnp.stack(f) for f in zip(*packs)))


def row_panel_packs_for_batch(batch, tile: int = 8,
                              pack_dtype=None) -> RowPanelPack:
    """Host-side: octile-decompose every graph of a GraphBatch into
    row-panel packs stacked to shared shapes (slot counts padded to the
    bucket max). ``pack_dtype=jnp.bfloat16`` streams the value buffers
    at half the HBM bytes per matvec (f32 in-kernel accumulation,
    DESIGN.md §9.4)."""
    import numpy as np
    from repro.core.octile import octile_decompose
    n = batch.adjacency.shape[1]
    if n % tile:
        raise ValueError(
            f"batch padded to {n}, not a multiple of tile={tile}; pad the"
            f" bucket to a multiple of the tile edge")
    osets = [octile_decompose(np.asarray(batch.adjacency[b]),
                              np.asarray(batch.edge_labels[b]), tile=tile)
             for b in range(batch.adjacency.shape[0])]
    k_max = max(max((np.bincount(o.coords[:, 0]).max(initial=0)
                     if o.n_nonempty else 0) for o in osets), 1)
    return stack_row_panel_packs(
        [pack_row_panels(o, k_max=int(k_max), pack_dtype=pack_dtype)
         for o in osets])


def attention_chunked(q, k, v, *, causal: bool = True,
                      window: int | None = None, scale: float | None = None,
                      blk_q: int = 512, blk_k: int = 512):
    """Flash-attention algorithm in pure jnp: scan over query blocks, inner
    scan over KV blocks with online-softmax accumulation. Never
    materializes the S x S score matrix in HBM — the paper's on-the-fly
    regeneration insight applied to attention (DESIGN.md §5). This is the
    §Perf 'attention=chunked' variant; HBM traffic scales as
    O(S*D*(2 + S/blk_q)) instead of O(S^2).
    """
    B, Hq, S, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    def _fit(dim, blk):
        blk = min(blk, dim)
        while dim % blk:
            blk -= 1
        return blk
    blk_q = _fit(S, blk_q)
    blk_k = _fit(Sk, blk_k)
    qg = q.reshape(B, Hkv, rep, S, D)
    # [nq, B, G, R, blk_q, D] / [nk, B, G, blk_k, D]
    qs = jnp.moveaxis(qg.reshape(B, Hkv, rep, S // blk_q, blk_q, D), 3, 0)
    ks = jnp.moveaxis(k.reshape(B, Hkv, Sk // blk_k, blk_k, D), 2, 0)
    vs = jnp.moveaxis(v.reshape(B, Hkv, Sk // blk_k, blk_k, D), 2, 0)

    def q_block(_, inp):
        qi, qblk = inp                                # [], [B,G,R,blk_q,D]
        q0 = qi * blk_q

        def kv_block(carry, kin):
            acc, m, l = carry
            ki, kblk, vblk = kin
            s = jnp.einsum("bgrqd,bgkd->bgrqk", qblk, kblk) * scale
            pos_q = q0 + jax.lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 0)
            pos_k = ki * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            mask = jnp.ones((blk_q, blk_k), bool)
            if causal:
                mask &= pos_k <= pos_q
            if window is not None:
                mask &= pos_k > pos_q - window
            s = jnp.where(mask, s.astype(jnp.float32), -1e30)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1, keepdims=True)
            acc = acc * corr + jnp.einsum(
                "bgrqk,bgkd->bgrqd", p.astype(vblk.dtype), vblk
            ).astype(jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros(qblk.shape[:4] + (D,), jnp.float32)
        m0 = jnp.full(qblk.shape[:4] + (1,), -1e30, jnp.float32)
        l0 = jnp.zeros(qblk.shape[:4] + (1,), jnp.float32)
        nk = Sk // blk_k
        (acc, _, l), _ = jax.lax.scan(
            kv_block, (acc0, m0, l0), (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)
        return None, out.astype(q.dtype)

    nq = S // blk_q
    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qs))
    # outs: [nq, B, G, R, blk_q, D] -> [B, Hq, S, D]
    out = jnp.moveaxis(outs, 0, 3).reshape(B, Hkv, rep, S, D)
    return out.reshape(B, Hq, S, D)


def attention(q, k, v, *, impl: str = "reference", causal: bool = True,
              window: int | None = None, scale: float | None = None):
    """Attention dispatch used by the LM zoo layers."""
    if impl == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale)
    if impl == "chunked":
        return attention_chunked(q, k, v, causal=causal, window=window,
                                 scale=scale)
    if impl == "reference":
        # GQA-native grouped einsums (no kv repeat materialization)
        B, Hq, S, D = q.shape
        Hkv, Sk = k.shape[1], k.shape[2]
        rep = Hq // Hkv
        if scale is None:
            scale = D ** -0.5
        qg = q.reshape(B, Hkv, rep, S, D)
        logits = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k) * scale
        pos_q = jnp.arange(S)[:, None]
        pos_k = jnp.arange(Sk)[None, :]
        mask = jnp.ones((S, Sk), bool)
        if causal:
            mask &= pos_k <= pos_q
        if window is not None:
            mask &= pos_k > pos_q - window
        logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
        w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bgrqk,bgkd->bgrqd", w, v)
        return out.reshape(B, Hq, S, D)
    raise ValueError(f"unknown attention impl {impl!r}")
