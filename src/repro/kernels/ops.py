"""Jit'd dispatch wrappers over the Pallas kernels.

Every entry point auto-selects interpret mode off-TPU so the same call
sites run on CPU (tests, this container) and TPU (production) unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .ref import attention_ref, xmv_batched_ref, xmv_ref
from .xmv_block_sparse import RowPanelPack, TilePack, \
    device_weighted_pack, from_tiles, pack_graph, pack_graph_row_panels, \
    pack_octiles, pack_row_panels, to_tiles, xmv_block_sparse, \
    xmv_block_sparse_batched, xmv_gram_tile, xmv_row_panel, \
    xmv_row_panel_batched
from .xmv_dense import dense_row_panels, xmv_dense, xmv_dense_batched

__all__ = [
    "xmv_dense", "xmv_dense_batched", "xmv_block_sparse",
    "xmv_block_sparse_batched", "xmv_block_sparse_unrolled", "stack_packs",
    "pack_graph", "pack_octiles", "TilePack", "RowPanelPack",
    "pack_row_panels", "pack_graph_row_panels", "xmv_row_panel",
    "xmv_row_panel_batched", "xmv_gram_tile", "stack_row_panel_packs",
    "device_weighted_pack", "take_row_panel_pack",
    "row_panel_packs_for_batch", "flash_attention",
    "attention_ref", "xmv_ref", "xmv_batched_ref", "to_tiles",
    "from_tiles", "dense_row_panels",
]


def _stack_field(packs, field):
    """Stack one optional pack field: all-None -> None, else jnp.stack."""
    vals = [getattr(p, field) for p in packs]
    if any(v is None for v in vals):
        if not all(v is None for v in vals):
            raise ValueError(
                f"cannot stack packs mixing {field} and None")
        return None
    return jnp.stack(vals)


def stack_packs(packs: list[TilePack]) -> TilePack:
    """Stack per-pair TilePacks (same bucket => same shapes) to [B, ...];
    optional fields (``values_grad``) must be present in all or none."""
    return TilePack(*(_stack_field(packs, f) for f in TilePack._fields))


def take_row_panel_pack(pack: RowPanelPack, indices) -> RowPanelPack:
    """Gather a stacked RowPanelPack along its leading pair/graph axis
    (``indices`` int array) — the segmented-PCG pair-retirement remap
    and the Gram-tile -> per-pair pack expansion (core/mgk.py)."""
    idx = jnp.asarray(indices)
    return RowPanelPack(*(None if f is None else jnp.take(f, idx, axis=0)
                          for f in pack))


def stack_row_panel_packs(packs: list[RowPanelPack]) -> RowPanelPack:
    """Stack per-pair RowPanelPacks (same bucket => same shapes) to
    [B, ...]; optional fields (``values_w``/``values_grad``) must be
    present in all packs or in none."""
    return RowPanelPack(*(_stack_field(packs, f)
                          for f in RowPanelPack._fields))


def _bucket_osets(batch, tile: int):
    import numpy as np
    from repro.core.octile import octile_decompose
    n = batch.adjacency.shape[1]
    if n % tile:
        raise ValueError(
            f"batch padded to {n}, not a multiple of tile={tile}; pad the"
            f" bucket to a multiple of the tile edge")
    B = batch.adjacency.shape[0]
    return [octile_decompose(np.asarray(batch.adjacency[b]),
                             np.asarray(batch.edge_labels[b]), tile=tile)
            for b in range(B)]


def packs_for_batch(batch, tile: int = 8) -> TilePack:
    """Host-side: octile-decompose every graph of a GraphBatch and stack
    the legacy TilePacks to shared shapes (pads tile counts to the bucket
    max)."""
    import numpy as np
    osets = _bucket_osets(batch, tile)
    K = max(max(o.n_nonempty for o in osets), 1)
    k_max = max(max((np.bincount(o.coords[:, 0]).max(initial=0)
                     if o.n_nonempty else 0) for o in osets), 1)
    return stack_packs([pack_octiles(o.padded(K), k_max=int(k_max))
                        for o in osets])


def row_panel_packs_for_batch(batch, tile: int = 8, edge_kernel=None,
                              with_grad: bool = False,
                              pack_dtype=None) -> RowPanelPack:
    """Host-side: octile-decompose every graph of a GraphBatch into
    row-panel packs stacked to shared shapes (slot counts padded to the
    bucket max). Pass ``edge_kernel`` with a feature expansion to also
    precompute the MXU contraction operands (``values_w``);
    ``with_grad`` adds the ``values_grad`` adjoint companions.
    ``pack_dtype=jnp.bfloat16`` streams the value buffers at half the
    HBM bytes per matvec (f32 in-kernel accumulation, DESIGN.md §9.4)."""
    import numpy as np
    osets = _bucket_osets(batch, tile)
    k_max = max(max((np.bincount(o.coords[:, 0]).max(initial=0)
                     if o.n_nonempty else 0) for o in osets), 1)
    return stack_row_panel_packs(
        [pack_row_panels(o, edge_kernel=edge_kernel, k_max=int(k_max),
                         with_grad=with_grad, pack_dtype=pack_dtype)
         for o in osets])


def xmv_block_sparse_unrolled(packs1: TilePack, packs2: TilePack, P,
                              edge_kernel, *, diag=None, **kw):
    """Legacy loop-of-launches batched block-sparse XMV: one ``pallas_call``
    (and one jit dispatch) per pair. Superseded by the batched-grid
    :func:`~repro.kernels.xmv_block_sparse.xmv_block_sparse_batched`
    (one launch for the whole bucket); kept as the baseline arm of the
    BENCH_xmv comparison and the parity tests."""
    B = P.shape[0]

    def take(pack, b):
        return TilePack(*(None if arr is None else arr[b] for arr in pack))

    ys = [
        xmv_block_sparse(
            take(packs1, b), take(packs2, b),
            P[b], edge_kernel,
            diag=None if diag is None else diag[b], **kw)
        for b in range(B)
    ]
    return jnp.stack(ys)


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
