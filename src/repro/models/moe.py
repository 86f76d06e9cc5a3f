"""Mixture-of-Experts layer — grouped, gather-only, expert-parallel.

Distribution design (the §Perf cell-A/B hillclimbs; see EXPERIMENTS.md):

1. GROUPED ROUTING. Dispatch is grouped by sequence and vmapped over the
   batch axis: top-k, argsort, capacity ranking are all LOCAL to a data
   shard. (A global dispatch lowers to a sort over the sharded token axis:
   the baseline profile was 69x collective-bound because of it.)

2. GATHER-ONLY DATA MOVEMENT. Dispatch (slot <- token) and combine
   (token <- expert row) are both expressed as gathers, and — because the
   two index maps are exact duals — each one's custom_vjp is again a
   gather. No scatter appears in forward OR backward. (XLA expands
   scatters into sort-based code with full-buffer u32 key tensors;
   ~40 GB/layer of HBM traffic in the scatter-based version.)

3. EXPERT PARALLELISM via shard_map. Every model rank recomputes the
   cheap routing for its data shard, evaluates ONLY its E/n_model
   experts, combines locally, and one ACTIVATION-sized psum over "model"
   finishes the layer. Cross-device traffic per layer = |activations|,
   never |dispatch buffers|.

Shared (always-on) experts are plain TP matmuls outside the shard_map.
Capacity per group C = ceil(S * k / E * capacity_factor); overflow tokens
drop (standard capacity semantics; reduced()-config tests run dropless).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig

__all__ = ["MoEParams", "moe_init", "moe_layer"]


class MoEParams(NamedTuple):
    router: jnp.ndarray     # [d, E]
    w_gate: jnp.ndarray     # [E, d, ff]
    w_up: jnp.ndarray       # [E, d, ff]
    w_down: jnp.ndarray     # [E, ff, d]
    shared_gate: jnp.ndarray | None   # [d, n_shared*ff]
    shared_up: jnp.ndarray | None
    shared_down: jnp.ndarray | None


def moe_init(key, d: int, cfg: MoEConfig, dtype) -> MoEParams:
    ks = jax.random.split(key, 7)
    E, ff = cfg.n_experts, cfg.d_expert
    scale_d = d ** -0.5
    scale_f = ff ** -0.5

    def init(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    shared = cfg.n_shared
    return MoEParams(
        router=init(ks[0], (d, E), scale_d).astype(jnp.float32),
        w_gate=init(ks[1], (E, d, ff), scale_d),
        w_up=init(ks[2], (E, d, ff), scale_d),
        w_down=init(ks[3], (E, ff, d), scale_f),
        shared_gate=init(ks[4], (d, shared * ff), scale_d) if shared else None,
        shared_up=init(ks[5], (d, shared * ff), scale_d) if shared else None,
        shared_down=init(ks[6], (shared * ff, d), scale_f) if shared else None,
    )


class Route(NamedTuple):
    """Per-group routing indices (all local to a data shard).
    E_v = the visible expert slice (full E, or a rank's E_loc)."""
    tok_for_slot: jnp.ndarray   # [E_v, C] token feeding each slot
    valid: jnp.ndarray          # [E_v, C]
    gate_for_slot: jnp.ndarray  # [E_v, C] gate of the choice in the slot
    src: jnp.ndarray            # [T, k] flat local expert-output row
    live: jnp.ndarray           # [T, k] choice kept AND visible here
    gate_vals: jnp.ndarray      # [T, k]
    probs: jnp.ndarray          # [T, E] router softmax (aux loss)
    expert_idx: jnp.ndarray     # [T, k]


def _route_group(xt, logits, k: int, E: int, capacity: int) -> Route:
    """Routing bookkeeping for one token group (argsort/cumsum, local)."""
    T, _ = xt.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)      # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    flat_expert = expert_idx.reshape(-1)                 # [T*k]
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = order // k
    sorted_gate = flat_gate[order]
    counts = jnp.bincount(sorted_expert, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[sorted_expert]
    keep = pos < capacity

    slot_idx = starts[:, None] + jnp.arange(capacity)[None, :]   # [E, C]
    valid = jnp.arange(capacity)[None, :] < \
        jnp.minimum(counts, capacity)[:, None]
    clipped = jnp.clip(slot_idx, 0, T * k - 1)
    tok_for_slot = jnp.where(valid, sorted_token[clipped], 0)
    gate_for_slot = jnp.where(valid, sorted_gate[clipped], 0.0)

    inv = jnp.argsort(order)
    pos_flat = pos[inv].reshape(T, k)
    keep_flat = keep[inv].reshape(T, k)
    src = expert_idx * capacity + jnp.minimum(pos_flat, capacity - 1)
    return Route(tok_for_slot, valid, gate_for_slot, src, keep_flat,
                 gate_vals, probs, expert_idx)


def _localize(route: Route, e0, e_loc: int, capacity: int) -> Route:
    """Restrict a full-E Route to expert range [e0, e0+e_loc) and shift
    row indices into the local frame. e0 may be traced (axis_index)."""
    tok = jax.lax.dynamic_slice_in_dim(route.tok_for_slot, e0, e_loc, 0)
    val = jax.lax.dynamic_slice_in_dim(route.valid, e0, e_loc, 0)
    gfs = jax.lax.dynamic_slice_in_dim(route.gate_for_slot, e0, e_loc, 0)
    lo = e0 * capacity
    live = route.live & (route.src >= lo) & \
        (route.src < lo + e_loc * capacity)
    src = jnp.clip(route.src - lo, 0, e_loc * capacity - 1)
    return route._replace(tok_for_slot=tok, valid=val, gate_for_slot=gfs,
                          src=src, live=live)


# -- gather-only dispatch / combine with gather-only custom VJPs -------------

@jax.custom_vjp
def _dispatch(xt, route: Route):
    eb = xt[route.tok_for_slot]                          # [E_v, C, d]
    return eb * route.valid[..., None].astype(xt.dtype)


def _dispatch_fwd(xt, route):
    return _dispatch(xt, route), route


def _dispatch_bwd(route: Route, g_eb):
    ev, C = route.tok_for_slot.shape
    g_flat = (g_eb * route.valid[..., None].astype(g_eb.dtype)
              ).reshape(ev * C, -1)
    rows = g_flat[route.src]                             # [T, k, d] gather
    g_xt = jnp.einsum("tkd,tk->td", rows,
                      route.live.astype(g_eb.dtype))
    return g_xt.astype(g_eb.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(eo_flat, gate_vals, route: Route):
    rows = eo_flat[route.src]                            # [T, k, d] gather
    w = jnp.where(route.live, gate_vals, 0.0).astype(eo_flat.dtype)
    return jnp.einsum("tkd,tk->td", rows, w)


def _combine_fwd(eo_flat, gate_vals, route):
    return _combine(eo_flat, gate_vals, route), (eo_flat, gate_vals, route)


def _combine_bwd(res, g_out):
    eo_flat, gate_vals, route = res
    ev, C = route.tok_for_slot.shape
    g_rows = g_out[route.tok_for_slot.reshape(-1)]       # gather
    g_eo = g_rows * (route.gate_for_slot.reshape(-1, 1) *
                     route.valid.reshape(-1, 1)).astype(g_out.dtype)
    rows = eo_flat[route.src]
    g_gate = jnp.einsum("tkd,td->tk", rows, g_out.astype(rows.dtype))
    g_gate = jnp.where(route.live, g_gate, 0.0).astype(gate_vals.dtype)
    return g_eo.astype(eo_flat.dtype), g_gate, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _experts(eb, wg, wu, wd, dtype):
    g = jnp.einsum("becd,edf->becf", eb, wg)
    u = jnp.einsum("becd,edf->becf", eb, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    return jnp.einsum("becf,efd->becd", h, wd)


def _aux_loss(route: Route, B: int, S: int, k: int, E: int):
    me = route.probs.mean(axis=(0, 1))                   # [E]
    onehot = jax.nn.one_hot(route.expert_idx.reshape(B, -1), E,
                            dtype=jnp.float32)
    ce = onehot.sum(axis=(0, 1)) / (B * S * k)
    return E * jnp.sum(me * ce)


def _mesh_info():
    try:
        env = jax._src.mesh.thread_resources.env  # noqa: SLF001
        mesh = env.physical_mesh
        return None if mesh.empty else mesh
    except Exception:
        return None


def moe_layer(p: MoEParams, x, cfg: MoEConfig):
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar)."""
    B, S, d = x.shape
    k, E = cfg.top_k, cfg.n_experts
    capacity = int(max(1, round(S * k / E * cfg.capacity_factor)))

    mesh = _mesh_info()
    use_shardmap = False
    if mesh is not None and "model" in mesh.axis_names:
        n_model = mesh.shape["model"]
        batch_axes = tuple(a for a in mesh.axis_names if a != "model")
        batch_width = 1
        for a in batch_axes:
            batch_width *= mesh.shape[a]
        # decode (S == 1) stays on the GSPMD path: the shard_map in_specs
        # would reshard the FSDP-laid-out expert weights (an all-gather of
        # the full expert stack PER TOKEN — measured 15x collective
        # regression on deepseek decode_32k, see EXPERIMENTS §Perf B);
        # with one token of routing work GSPMD's plan is already fine.
        use_shardmap = (E % n_model == 0 and B % batch_width == 0
                        and n_model > 1 and S > 1)

    if not use_shardmap:
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p.router)
        route = jax.vmap(
            lambda xt, lg: _route_group(xt, lg, k, E, capacity))(x, logits)
        eb = jax.vmap(_dispatch)(x, route)               # [B, E, C, d]
        eo = _experts(eb, p.w_gate, p.w_up, p.w_down, x.dtype)
        out = jax.vmap(lambda e, r: _combine(
            e.reshape(E * capacity, d), r.gate_vals, r))(eo, route)
        aux = _aux_loss(route, B, S, k, E)
    else:
        from jax.sharding import PartitionSpec as P

        def body(xb, router, wg, wu, wd):
            e_loc = wg.shape[0]
            e0 = jax.lax.axis_index("model") * e_loc
            b_loc = xb.shape[0]
            logits = jnp.einsum("bsd,de->bse", xb.astype(jnp.float32),
                                router)
            route = jax.vmap(
                lambda xt, lg: _route_group(xt, lg, k, E, capacity))(
                    xb, logits)
            rloc = jax.vmap(lambda r: _localize(r, e0, e_loc, capacity))(
                route)
            ebl = jax.vmap(_dispatch)(xb, rloc)        # [B_loc,E_loc,C,d]
            eo = _experts(ebl, wg, wu, wd, xb.dtype)
            out_local = jax.vmap(lambda e, r: _combine(
                e.reshape(e_loc * capacity, d), r.gate_vals, r))(eo, rloc)
            out = jax.lax.psum(out_local, "model")     # activation-sized
            aux = _aux_loss(route, b_loc, S, k, E)
            for a in batch_axes:
                aux = jax.lax.pmean(aux, a)
            return out, aux

        smap = functools.partial(jax.shard_map, check_vma=False)
        out, aux = smap(
            body, mesh=mesh,
            in_specs=(P(batch_axes, None, None), P(None, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=(P(batch_axes, None, None), P()),
        )(x, p.router, p.w_gate, p.w_up, p.w_down)

    if p.shared_gate is not None:
        gs = jnp.einsum("bsd,df->bsf", x, p.shared_gate)
        us = jnp.einsum("bsd,df->bsf", x, p.shared_up)
        hs = jax.nn.silu(gs.astype(jnp.float32)).astype(x.dtype) * us
        out = out + jnp.einsum("bsf,fd->bsd", hs, p.shared_down)

    return out, aux
