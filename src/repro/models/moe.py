"""Mixture-of-Experts FFN: top-k token-choice routing with capacity, two paths.

- ``moe_apply_local``: single-shard sort-based dispatch (smoke tests, and the
  per-device compute inside the distributed path).
- ``moe_apply_ep``: expert parallelism via shard_map — tokens are sequence-
  sharded over the ``model`` axis, experts are sharded over the same axis, and
  two ``all_to_all`` collectives move token activations to/from their expert
  owners (the production EP pattern; DESIGN.md §5). Capacity-dropped tokens
  fall through on the residual path, standard for capacity-based MoE.

Routing uses softmax-then-top-k with gate renormalization and the switch-style
load-balance auxiliary loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import truncated_normal_init


def init_moe_params(key, d: int, f_expert: int, n_experts: int, n_shared: int,
                    d_ff_shared: int, dtype) -> dict:
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    p = {
        "router": truncated_normal_init(kr, (d, n_experts), 1.0, jnp.float32),
        "w_gate": truncated_normal_init(k1, (n_experts, d, f_expert), 1.0, dtype),
        "w_up": truncated_normal_init(k2, (n_experts, d, f_expert), 1.0, dtype),
        "w_down": truncated_normal_init(k3, (n_experts, f_expert, d), 1.0, dtype),
    }
    if n_shared:
        from repro.models.common import init_swiglu

        p["shared"] = init_swiglu(ks, d, n_shared * f_expert, dtype)
    return p


def route(router_w: jax.Array, x: jax.Array, k: int):
    """Top-k routing. x (T, d) → (ids (T,k), gates (T,k), me (E,), ce (E,)).

    me/ce are the switch load-balance statistics (mean router prob / top-1
    fraction per expert); the caller combines them as aux = E·Σ me·ce —
    distributed callers psum them FIRST so the loss matches the global batch.
    """
    logits = x.astype(jnp.float32) @ router_w                # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)                     # (T, k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    e = router_w.shape[1]
    me = jnp.mean(probs, axis=0)                             # (E,)
    one_hot_top1 = jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32)
    ce = jnp.mean(one_hot_top1, axis=0)
    return ids, gates, me, ce


def aux_loss(me: jax.Array, ce: jax.Array) -> jax.Array:
    return me.shape[0] * jnp.sum(me * ce)


def _dispatch_indices(flat_expert: jax.Array, n_buckets: int, capacity: int):
    """Sort slots by destination bucket; return (sort order, position-in-bucket,
    keep mask). Works for both rank buckets and local-expert buckets."""
    s = flat_expert.shape[0]
    order = jnp.argsort(flat_expert)                         # stable
    sorted_e = flat_expert[order]
    # position of each sorted slot within its bucket
    idx = jnp.arange(s)
    starts = jnp.searchsorted(sorted_e, jnp.arange(n_buckets))
    pos = idx - starts[sorted_e]
    keep = pos < capacity
    return order, sorted_e, pos, keep


def expert_ffn(w_gate, w_up, w_down, buf: jax.Array) -> jax.Array:
    """Per-expert SwiGLU. buf (E, C, d) with weights (E, d, f)/(E, f, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up
    )
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_apply_local(params: dict, x: jax.Array, k: int, capacity_factor: float):
    """Single-shard MoE on tokens x (T, d). Returns (y, aux_loss)."""
    t, d = x.shape
    e = params["router"].shape[1]
    ids, gates, me, ce = route(params["router"], x, k)
    aux = aux_loss(me, ce)
    cap = int(np.ceil(t * k / e * capacity_factor))
    cap = max(8, -(-cap // 8) * 8)                           # round up to 8

    flat_e = ids.reshape(-1)                                 # (T·k,)
    order, sorted_e, pos, keep = _dispatch_indices(flat_e, e, cap)
    tok = order // k                                         # source token per sorted slot
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[sorted_e, pos].add(jnp.where(keep[:, None], x[tok], 0))
    out_buf = expert_ffn(params["w_gate"], params["w_up"], params["w_down"], buf)
    # gather back: each sorted slot reads its expert output, weighted by gate
    slot_out = out_buf[sorted_e, pos] * jnp.where(keep, gates.reshape(-1)[order], 0.0)[:, None]
    y = jnp.zeros((t, d), x.dtype).at[tok].add(slot_out.astype(x.dtype))
    if "shared" in params:
        from repro.models.common import apply_swiglu

        y = y + apply_swiglu(params["shared"], x)
    return y, aux


def moe_apply_ep(params: dict, x: jax.Array, k: int, capacity_factor: float,
                 mesh: jax.sharding.Mesh, dp_axes: tuple[str, ...], ep_axis: str):
    """Distributed MoE: x (B, S, d) with B sharded over ``dp_axes`` and S over
    ``ep_axis``; experts sharded over ``ep_axis``. Two all_to_alls per layer."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    n_ep = mesh.shape[ep_axis]
    e = params["router"].shape[1]
    assert e % n_ep == 0, (e, n_ep)
    e_loc = e // n_ep

    def local_fn(router_w, w_gate, w_up, w_down, shared, xl):
        # xl: (B_l, S_l, d) — local tokens; experts local: w_* (E_loc, d, f)
        bl, sl, d = xl.shape
        tl = bl * sl
        xt = xl.reshape(tl, d)
        ids, gates, me, ce = route(router_w, xt, k)          # router is replicated
        # psum the statistics BEFORE the product — matches the global-batch loss
        for ax in (ep_axis, *dp_axes):
            me = jax.lax.pmean(me, ax)
            ce = jax.lax.pmean(ce, ax)
        aux = aux_loss(me, ce)

        # ---- A2A dispatch: bucket slots by owner rank -----------------------
        cap_s = int(np.ceil(tl * k / n_ep * capacity_factor))
        cap_s = max(8, -(-cap_s // 8) * 8)
        flat_e = ids.reshape(-1)
        rank = flat_e // e_loc
        order, sorted_r, pos, keep = _dispatch_indices(rank, n_ep, cap_s)
        tok = order // k
        send = jnp.zeros((n_ep, cap_s, d), xl.dtype)
        send = send.at[sorted_r, pos].add(jnp.where(keep[:, None], xt[tok], 0))
        # metadata rides along as fp32 lanes: local expert id, gate
        meta = jnp.zeros((n_ep, cap_s, 2), jnp.float32)
        meta = meta.at[sorted_r, pos].add(
            jnp.where(
                keep[:, None],
                jnp.stack([(flat_e[order] % e_loc).astype(jnp.float32) + 1.0,
                           gates.reshape(-1)[order]], axis=-1),
                0,
            )
        )
        recv = jax.lax.all_to_all(send, ep_axis, 0, 0, tiled=False)      # (n_ep, cap_s, d)
        meta_r = jax.lax.all_to_all(meta, ep_axis, 0, 0, tiled=False)

        # ---- local expert grouping -----------------------------------------
        rtok = recv.reshape(n_ep * cap_s, d)
        r_eid = meta_r.reshape(-1, 2)[:, 0]
        r_gate = meta_r.reshape(-1, 2)[:, 1]
        valid = r_eid > 0
        loc_e = jnp.where(valid, r_eid - 1.0, e_loc).astype(jnp.int32)   # invalid → overflow bucket
        cap_e = int(np.ceil(n_ep * cap_s / e_loc * capacity_factor))
        cap_e = max(8, -(-cap_e // 8) * 8)
        order2, sorted_e2, pos2, keep2 = _dispatch_indices(loc_e, e_loc + 1, cap_e)
        in_range = keep2 & (sorted_e2 < e_loc)
        buf = jnp.zeros((e_loc, cap_e, d), xl.dtype)
        buf = buf.at[jnp.minimum(sorted_e2, e_loc - 1), pos2].add(
            jnp.where(in_range[:, None], rtok[order2], 0)
        )
        out_buf = expert_ffn(w_gate, w_up, w_down, buf)
        slot_out = jnp.zeros((n_ep * cap_s, d), xl.dtype)
        slot_out = slot_out.at[order2].add(
            jnp.where(in_range[:, None], out_buf[jnp.minimum(sorted_e2, e_loc - 1), pos2], 0)
        )
        slot_out = slot_out * r_gate[:, None].astype(slot_out.dtype)

        # ---- A2A return + combine ------------------------------------------
        back = jax.lax.all_to_all(slot_out.reshape(n_ep, cap_s, d), ep_axis, 0, 0, tiled=False)
        flat_back = back.reshape(n_ep, cap_s, d)
        y = jnp.zeros((tl, d), xl.dtype)
        y = y.at[tok].add(jnp.where(keep[:, None], flat_back[sorted_r, pos], 0))
        yl = y.reshape(bl, sl, d)
        if shared is not None:
            from repro.models.common import apply_swiglu

            yl = yl + apply_swiglu(shared, xl)
        return yl, aux

    w_specs = (P(), P(ep_axis), P(ep_axis), P(ep_axis))
    x_spec = P(dp_axes, ep_axis, None)
    w_args = (params["router"], params["w_gate"], params["w_up"], params["w_down"])
    if "shared" in params:
        in_specs = w_specs + (P(), x_spec)
        args = w_args + (params["shared"], x)
        local = local_fn
    else:
        in_specs = w_specs + (x_spec,)
        args = w_args + (x,)

        def local(r, g, u, dn, xl):
            return local_fn(r, g, u, dn, None, xl)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(*args)
