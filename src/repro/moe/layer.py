"""Expert-parallel MoE layer with DR-style dispatch.

The token -> expert exchange *is* the paper's keyed shuffle: keys are expert
ids, partitions are EP shards, and the routing table is the KIP placement
(``inv_place``: logical expert -> physical slot).  The layer runs under
``shard_map`` on the unified exchange plane (``repro.exchange``) — the same
capacity-padded ``route -> bucketize -> all_to_all -> unpack`` primitive as
``repro.core.shuffle`` — and emits per-expert load counts as the DRW
histogram, consumed by ``repro.moe.kip_placement``.

Two evaluation paths:

* ``moe_ref``     — dense oracle (every expert on every token, exact
  combine); used by tests and tiny CPU configs.
* ``moe_apply``   — the distributed dispatch (shard_map over (dp..., tp)):
  hop 1 ships records to the owning EP shard (a cross-shard exchange on the
  transport ``Policy.exchange_backend`` selects — dense or count-first
  ragged), hop 2 buckets received records into per-expert buffers (the
  local no-collective backend), and the combine rides the same lanes back
  (``backhaul`` + ``take_from``) — under the ragged transport the return
  trip reuses the forward hop's counts, so it ships compacted rows with no
  second count phase, and ``MoEOut.shipped_rows`` accounts both
  directions.  With generous capacity its output equals ``moe_ref``
  exactly, whatever the backend.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.configs.base import MoESpec
from repro.exchange import ExchangeSpec, Payload, make_exchange, take_from
from repro.models.modules import Array, Policy, act_fn, init_ffn, no_shard, normal

__all__ = ["init_moe", "moe_ref", "moe_apply", "MoEOut"]


class MoEOut(NamedTuple):
    y: Array          # [B, S, d]
    counts: Array     # f32[E] global tokens routed per logical expert
    overflow: Array   # f32[] dropped (token, expert) pairs
    aux_loss: Array   # f32[] load-balancing auxiliary loss
    # rows the exchange transport measured moving across *both* dispatch
    # directions (forward ship + combine backhaul), summed over shards;
    # None on paths with no cross-shard exchange (oracle, replicated decode)
    shipped_rows: Array = None  # int32[]
    # rows actually live in the exchanged lanes, both directions — the
    # backend-independent occupancy (what a ragged transport would ship;
    # under dense, shipped is the pad while this tracks the real load).
    # ``exchange_stats()`` packages both for ``Telemetry.record_exchange``.
    occupied_rows: Array = None  # int32[]

    def exchange_stats(self, *, padded_rows: int = 0, wall_s: float = 0.0,
                       backend: str | None = None):
        """Package this step's dispatch traffic as one plane-constructed
        :class:`~repro.exchange.ExchangeStats` — the record
        ``Telemetry.record_exchange`` takes.  ``padded_rows`` is what the
        dispatch specs provisioned (both directions); paths with no
        cross-shard exchange report zero rows."""
        from repro.exchange import ExchangeStats

        rows = 0 if self.shipped_rows is None else int(self.shipped_rows)
        occ = None if self.occupied_rows is None else int(self.occupied_rows)
        return ExchangeStats(rows=rows, wall_s=wall_s, padded_rows=padded_rows,
                             occupied_rows=occ, backend=backend)


def init_moe(key, d: int, spec: MoESpec, ffn_kind: str, dtype) -> dict:
    ks = jax.random.split(key, 4)
    e, f = spec.num_experts, spec.d_ff_expert
    gate = 2 if ffn_kind in ("swiglu", "geglu") else 1
    p = {
        "router": normal(ks[0], (d, e), d**-0.5, jnp.float32),
        "wi": normal(ks[1], (e, d, gate, f), d**-0.5, dtype),
        "wo": normal(ks[2], (e, f, d), f**-0.5, dtype),
    }
    if spec.shared_expert:
        p["shared"] = init_ffn(ks[3], d, f, ffn_kind, dtype)
    return p


def _route(router_w, t, spec: MoESpec):
    """[T, d] -> (weights [T, k], logical ids [T, k], probs [T, E])."""
    logits = (t.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, ids = jax.lax.top_k(logits, spec.top_k)
    if spec.top_k == 1:
        w = jax.nn.sigmoid(vals)  # llama4-style gate
    else:
        w = jax.nn.softmax(vals, axis=-1)
    return w, ids, probs


def _aux_loss(probs, ids, e: int):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    f = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    pm = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * pm)


def _expert_ffn(wi, wo, x, ffn_kind: str):
    """x [E, C, d] through per-expert gated FFN."""
    a = act_fn(ffn_kind)
    h = jnp.einsum("ecd,edgf->ecgf", x, wi)  # g = gate axis
    h = a(h[:, :, 0]) * h[:, :, 1] if wi.shape[2] == 2 else a(h[:, :, 0])
    return jnp.einsum("ecf,efd->ecd", h, wo)


# ---------------------------------------------------------------------------
# reference (dense) path
# ---------------------------------------------------------------------------


def moe_ref(p: dict, x: Array, spec: MoESpec, ffn_kind: str, pol: Policy,
            inv_place: Array | None = None) -> MoEOut:
    b, s, d = x.shape
    cd = pol.compute_dtype
    t = x.reshape(-1, d)
    w, ids, probs = _route(p["router"], t, spec)
    # every expert over every token (oracle; fine for smoke-scale E)
    all_out = _expert_ffn(p["wi"].astype(cd), p["wo"].astype(cd),
                          jnp.broadcast_to(t[None], (spec.num_experts,) + t.shape), ffn_kind)
    sel = jnp.take_along_axis(
        all_out.transpose(1, 0, 2), ids[:, :, None], axis=1
    )  # [T, k, d]
    y = jnp.sum(sel * w[..., None].astype(cd), axis=1)
    if "shared" in p:
        from repro.models.modules import apply_ffn

        y = y + apply_ffn(p["shared"], x, ffn_kind, pol).reshape(-1, d)
    counts = jnp.sum(jax.nn.one_hot(ids, spec.num_experts, dtype=jnp.float32), axis=(0, 1))
    return MoEOut(y.reshape(b, s, d), counts, jnp.zeros((), jnp.float32),
                  _aux_loss(probs, ids, spec.num_experts))


# ---------------------------------------------------------------------------
# distributed expert-parallel path (the paper's shuffle, keys = experts)
# ---------------------------------------------------------------------------


def moe_apply(p: dict, x: Array, spec: MoESpec, ffn_kind: str, pol: Policy,
              inv_place: Array) -> MoEOut:
    """x [B, S, d] sharded P(dp..., tp, None); experts sharded over tp."""
    mesh = pol.mesh
    dp_axes, tp = pol.dp_axes, pol.tp_axis
    ntp = mesh.shape[tp]
    e = spec.num_experts
    assert e % ntp == 0, f"experts {e} not a multiple of tp {ntp}"
    e_loc = e // ntp
    cf = pol.moe_capacity_factor or spec.capacity_factor
    cd = pol.compute_dtype
    all_axes = tuple(dp_axes) + (tp,)

    def body(router_w, wi, wo, shared, inv_pl, x_loc):
        # x_loc [b_l, s_l, d]; wi/wo local slots [e_loc, ...]
        b_l, s_l, d = x_loc.shape
        t = x_loc.reshape(-1, d)
        tn = t.shape[0]
        w, ids, probs = _route(router_w, t, spec)
        k = spec.top_k
        rec_tok = jnp.repeat(jnp.arange(tn, dtype=jnp.int32), k)
        rec_e = ids.reshape(-1)
        rec_w = w.reshape(-1)
        phys = inv_pl[rec_e]
        dev = phys // e_loc
        eloc = phys % e_loc

        # hop 1: ship records to the owning EP shard (cross-shard exchange);
        # the transport comes from the policy (dense / ragged), the combine
        # backhauls over the same backend
        c1 = max(8, int(np.ceil(cf * tn * k / ntp / 8.0) * 8))
        ship = make_exchange(ExchangeSpec(num_lanes=ntp, capacity=c1, axis=tp),
                             pol.exchange_backend)
        res1 = ship(
            dev,
            jnp.ones_like(dev, bool),
            [Payload(t[rec_tok].astype(cd), 0), Payload(eloc, 0)],
        )
        rvalid, (rxf, ref_) = res1.unpack()

        # hop 2: bucket received records into local per-expert buffers
        # (axis-free spec -> the local no-collective backend)
        c2 = max(8, int(np.ceil(cf * tn * k / e_loc / 8.0) * 8))
        local = make_exchange(ExchangeSpec(num_lanes=e_loc, capacity=c2))
        res2 = local.bucketize(ref_, rvalid, [Payload(rxf, 0)])
        overflow = (res1.send.overflow + res2.send.overflow).astype(jnp.float32)

        eout = _expert_ffn(wi.astype(cd), wo.astype(cd), res2.payloads[0], ffn_kind)

        # return trip: gather each record's result, ship back over the same
        # lanes, combine.  The forward hop's exchanged counts make the
        # backhaul ragged with no second count phase (dense forward: the
        # return trip ships the pad, exactly as before).
        back = take_from(eout, res2.send).reshape(ntp, c1, d)
        ret, back_shipped, back_occupied = ship.backhaul(back, forward=res1)
        val = take_from(ret, res1.send)
        y = jnp.zeros((tn, d), cd).at[rec_tok].add(val * rec_w[:, None].astype(cd))

        if shared is not None:
            from repro.models.modules import apply_ffn

            pol_in = dataclasses.replace(pol, shard=no_shard)  # manual mesh inside
            y = y + apply_ffn(shared, x_loc, ffn_kind, pol_in).reshape(-1, d)

        counts = jnp.zeros((e,), jnp.float32).at[rec_e].add(1.0)
        counts = jax.lax.psum(counts, all_axes)
        overflow = jax.lax.psum(overflow, all_axes)
        aux = jax.lax.pmean(_aux_loss(probs, ids, e), all_axes)
        # both directions of measured traffic: forward ship + combine
        # backhaul; occupied is the backend-independent live-row count
        # (forward: records that landed a slot; return: the backhaul's
        # counted occupancy) — honest even on the dense path
        shipped = jax.lax.psum(res1.shipped_rows + back_shipped, all_axes)
        fwd_occupied = jnp.asarray(tn * k, jnp.int32) - res1.send.overflow
        occupied = jax.lax.psum(fwd_occupied + back_occupied, all_axes)
        return y.reshape(b_l, s_l, d), counts, overflow, aux, shipped, occupied

    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(tp), P(tp), P(), P(), P(dp_spec, tp, None)),
        out_specs=(P(dp_spec, tp, None), P(), P(), P(), P(), P()),
        check_vma=False,
    )
    shared = p.get("shared")
    y, counts, overflow, aux, shipped, occupied = mapped(
        p["router"], p["wi"], p["wo"], shared, inv_place, x
    )
    return MoEOut(y, counts, overflow, aux, shipped, occupied)


def moe_apply_replicated(p: dict, x: Array, spec: MoESpec, ffn_kind: str, pol: Policy,
                         inv_place: Array) -> MoEOut:
    """Decode-path EP with expert tensor parallelism (no weight movement).

    Decode has a handful of tokens: moving weights to tokens (FSDP gathers)
    would ship GBs per decoded token.  Instead tokens are replicated to all
    shards; each (data, model) shard owns (its experts) x (an F-slice):
    experts sharded over ``model``, each expert's FFN hidden dim sharded
    over the data axes.  Every shard computes its partial contribution for
    all tokens and one psum over (data..., model) combines them.  The
    shared expert is F-sharded over ``model`` (scaled to ride the same
    psum).
    """
    mesh = pol.mesh
    dp_axes, tp = pol.dp_axes, pol.tp_axis
    ntp = mesh.shape[tp]
    e = spec.num_experts
    e_loc = e // ntp
    cd = pol.compute_dtype
    dpn = int(np.prod([mesh.shape[a] for a in dp_axes]))
    all_axes = tuple(dp_axes) + (tp,)
    a = act_fn(ffn_kind)

    def body(router_w, wi, wo, shared, inv_pl, x_loc):
        b_l, s_l, d = x_loc.shape  # replicated: b_l = full batch
        t = x_loc.reshape(-1, d)
        tn = t.shape[0]
        w, ids, probs = _route(router_w, t, spec)
        k = spec.top_k
        me = jax.lax.axis_index(tp)
        rec_tok = jnp.repeat(jnp.arange(tn, dtype=jnp.int32), k)
        rec_e = ids.reshape(-1)
        rec_w = w.reshape(-1)
        phys = inv_pl[rec_e]
        mine = (phys // e_loc) == me
        eloc = jnp.where(mine, phys % e_loc, 0)

        # local exchange: only this shard's (token, expert) pairs get slots
        c2 = max(8, int(np.ceil((pol.moe_capacity_factor or spec.capacity_factor)
                                * tn * k / max(e_loc, 1) / 8.0) * 8))
        local = make_exchange(ExchangeSpec(num_lanes=e_loc, capacity=c2))
        res = local.bucketize(eloc, mine, [Payload(t[rec_tok].astype(cd), 0)])
        overflow = res.send.overflow.astype(jnp.float32)
        # F-sliced expert FFN: wi [e_loc, d, g, F/dp], wo [e_loc, F/dp, d]
        h = jnp.einsum("ecd,edgf->ecgf", res.payloads[0], wi.astype(cd))
        h = a(h[:, :, 0]) * h[:, :, 1] if wi.shape[2] == 2 else a(h[:, :, 0])
        eout = jnp.einsum("ecf,efd->ecd", h, wo.astype(cd))  # partial over F
        val = take_from(eout, res.send)
        y = jnp.zeros((tn, d), cd).at[rec_tok].add(val * rec_w[:, None].astype(cd))
        if shared is not None:
            # shared expert F-sliced over model; identical on every data
            # shard, so scale by 1/dpn to survive the (data+model) psum
            swi, swo = shared["wi"].astype(cd), shared["wo"].astype(cd)
            sh = jnp.einsum("td,dgf->tgf", t, swi)
            sh = a(sh[:, 0]) * sh[:, 1] if swi.shape[1] == 2 else a(sh[:, 0])
            y = y + jnp.einsum("tf,fd->td", sh, swo) / dpn
        y = jax.lax.psum(y, all_axes)
        counts = jnp.zeros((e,), jnp.float32).at[rec_e].add(1.0)  # same on all shards
        overflow_g = jax.lax.pmean(overflow, all_axes) * ntp  # per-model-shard drops
        aux = _aux_loss(probs, ids, e)
        return y.reshape(b_l, s_l, d), counts, overflow_g, aux

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(),
            P(tp, None, None, dp_axes),   # wi: experts x model, F x data
            P(tp, dp_axes, None),          # wo
            P(),                           # shared: F x model handled below
            P(),
            P(None, None, None),           # tokens replicated
        ),
        out_specs=(P(None, None, None), P(), P(), P()),
        check_vma=False,
    )
    shared = p.get("shared")
    if shared is not None:
        # present the shared expert F-sliced over the model axis
        shared = {"wi": shared["wi"], "wo": shared["wo"]}
        shared_specs = {"wi": P(None, None, tp), "wo": P(tp, None)}
        mapped = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(), P(tp, None, None, dp_axes), P(tp, dp_axes, None),
                shared_specs, P(), P(None, None, None),
            ),
            out_specs=(P(None, None, None), P(), P(), P()),
            check_vma=False,
        )
    y, counts, overflow, aux = mapped(p["router"], p["wi"], p["wo"], shared, inv_place, x)
    return MoEOut(y, counts, overflow, aux)
