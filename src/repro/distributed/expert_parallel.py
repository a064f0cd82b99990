"""Expert-parallel MoE with explicit all_to_all dispatch (shard_map).

The GSPMD path (``repro.models.moe.moe_ffn`` + sharding annotations) lets
XLA infer the dispatch collectives; this module is the production EP
implementation with the classic two-hop pattern made explicit:

  1. route: top-k experts per local token → destination device =
     expert // experts_per_device;
  2. dispatch: pack per-destination capacity buffers, ``all_to_all`` over
     the expert axis;
  3. local grouped FFN over the device's experts (capacity buffers, zero
     rows are harmless since the FFN has no biases);
  4. return ``all_to_all`` back to the source slots, weighted combine.

Capacity-based with drops (Switch-style) on both hops; token order is
restored exactly via the slot bookkeeping, so output == the dense oracle
up to dropped tokens (tested drop-free on small shapes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import act_fn, dense


def _sort_dispatch(values, dest, n_dest, capacity):
    """Scatter ``values`` (M, d) into (n_dest, capacity, d) buffers by
    ``dest`` (M,) with per-destination positions. Returns (buffers,
    slot_dev, slot_pos, keep)."""
    M, d = values.shape
    order = jnp.argsort(dest)
    sorted_dest = dest[order]
    starts = jnp.searchsorted(sorted_dest, jnp.arange(n_dest))
    pos = jnp.arange(M) - starts[sorted_dest]
    keep = pos < capacity
    pos_c = jnp.where(keep, pos, 0)
    buf = jnp.zeros((n_dest, capacity, d), values.dtype)
    buf = buf.at[sorted_dest, pos_c].add(
        jnp.where(keep[:, None], values[order], jnp.zeros((), values.dtype)))
    # slot of flat item i (original order): invert the sort
    inv = jnp.argsort(order)
    return buf, sorted_dest[inv], pos_c[inv], keep[inv]


def ep_moe_ffn(p, x, cfg, *, mesh, ep_axis: str = "model",
               dp_axis: str = "data", capacity_factor: float = 2.0):
    """x: (B, S, d) sharded over ``dp_axis``; expert weights (E, d, f)
    sharded over ``ep_axis`` on dim 0. Returns y like x.

    Requires cfg.num_experts % mesh.shape[ep_axis] == 0.
    """
    E, k = cfg.num_experts, cfg.top_k
    ep = mesh.shape[ep_axis]
    assert E % ep == 0
    E_loc = E // ep
    d = cfg.d_model

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, None, None),                 # router w (replicated)
                  {"up": P(ep_axis, None, None),
                   "down": P(ep_axis, None, None),
                   **({"gate": P(ep_axis, None, None)}
                     if "gate" in p["experts"] else {})},
                  P(dp_axis, None, None)),             # x
        out_specs=P(dp_axis, None, None),
        check_vma=False)
    def _inner(router_w, experts, x):
        B, S, _ = x.shape
        N = B * S
        xf = x.reshape(N, d)
        cd = x.dtype

        logits = (xf.astype(jnp.float32) @ router_w[0]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, -1)
        gates, eids = jax.lax.top_k(probs, k)          # (N, k) global ids
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)

        dest_dev = (eids // E_loc).reshape(-1)         # (N*k,)
        local_eid = (eids % E_loc).reshape(-1)
        token_of = jnp.repeat(jnp.arange(N), k)

        C = int(max(1, -(-N * k // ep) * capacity_factor))
        send_x, slot_dev, slot_pos, keep = _sort_dispatch(
            xf[token_of], dest_dev, ep, C)
        # ship the local expert id alongside (sentinel 0 + zero row is a
        # no-op through the bias-free FFN)
        eid_buf = jnp.zeros((ep, C), jnp.int32)
        eid_buf = eid_buf.at[slot_dev, slot_pos].set(
            jnp.where(keep, local_eid, 0))

        recv_x = jax.lax.all_to_all(send_x, ep_axis, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(eid_buf, ep_axis, 0, 0, tiled=False)
        rx = recv_x.reshape(ep * C, d)                 # tokens for MY experts
        re = recv_eid.reshape(ep * C)

        # local grouped FFN via a second capacity dispatch over E_loc
        C2 = int(max(1, -(-ep * C // E_loc)))
        ebuf, s2_dev, s2_pos, k2 = _sort_dispatch(rx, re, E_loc, C2)
        f = act_fn(cfg.activation)
        h = jnp.einsum("ecd,edf->ecf", ebuf.astype(cd),
                       experts["up"].astype(cd))
        if "gate" in experts:
            h = h * f(jnp.einsum("ecd,edf->ecf", ebuf.astype(cd),
                                 experts["gate"].astype(cd)))
        else:
            h = f(h)
        out_buf = jnp.einsum("ecf,efd->ecd", h, experts["down"].astype(cd))
        # back to the received-slot layout
        ry = jnp.where(k2[:, None], out_buf[s2_dev, s2_pos],
                       jnp.zeros((), cd))
        back = jax.lax.all_to_all(ry.reshape(ep, C, d), ep_axis, 0, 0,
                                  tiled=False)

        # combine at the source: read each flat item's slot, weight, add
        vals = back[slot_dev, slot_pos]
        vals = jnp.where(keep[:, None], vals, jnp.zeros((), cd))
        y = jnp.zeros((N, d), cd).at[token_of].add(
            vals * gates.reshape(-1)[:, None].astype(cd))
        return y.reshape(B, S, d)

    y = _inner(p["router"]["w"][None], p["experts"], x)
    if "shared" in p:
        from repro.models.layers import mlp
        y = y + mlp(p["shared"], x, cfg.activation, x.dtype)
    return y
