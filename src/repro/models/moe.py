"""Mixture-of-experts FFN (routed top-k + optional shared experts).

Two dispatch implementations, selectable via ``PerfConfig.moe_impl``:

  * ``dense``  — masked all-experts einsum, token-blocked with ``lax.map``
    so peak memory stays bounded.  Every expert processes every token and
    the router gate zeroes the unused results.  Simple, sharding-robust —
    and wasteful by a factor of E/k FLOPs.  This is the *baseline* the
    roofline's MODEL_FLOPS/HLO_FLOPs ratio exposes.
  * ``gather`` — capacity-based dispatch (Switch/GShard): tokens are
    ranked per expert, dropped beyond capacity, gathered into (E, C, d)
    buffers, processed by grouped matmuls, and combined with gates.
    FLOPs scale with k, not E — the §Perf hillclimb step.

Expert stacks are sharded E over ``ep`` (model axis) and d over ``ep2``
(data axis) so the 236B/400B configs fit per-chip HBM at serve time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.schema import Leaf
from repro.perf import PerfConfig, DEFAULT_PERF
from repro.sharding_ctx import constrain


def moe_schema(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.n_experts
    # experts: E over ep (model axis), f over ep2 (data axis).  Sharding
    # the FF dim (not d) lets the a2a dispatch run both GEMMs locally
    # with a single psum on the down-projection.
    sch = {
        "router": Leaf((d, E), dtype="float32"),
        "wg": Leaf((E, d, f), spec=("ep", None, "ep2")),
        "wu": Leaf((E, d, f), spec=("ep", None, "ep2")),
        "wd": Leaf((E, f, d), spec=("ep", "ep2"), init="small"),
    }
    if m.n_shared:
        fs = f * m.n_shared
        sch["shared"] = {
            "wg": Leaf((d, fs), spec=("fsdp", "tp")),
            "wu": Leaf((d, fs), spec=("fsdp", "tp")),
            "wd": Leaf((fs, d), spec=("tp", "fsdp"), init="small"),
        }
    return sch


def _router(cfg: ModelConfig, p, xf):
    """xf: (T, d) -> (probs (T,E) fp32, top-k ids (T,k), top-k gates (T,k))."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, ids, gates


def _aux_loss(cfg: ModelConfig, probs, ids):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    m = cfg.moe
    E = m.n_experts
    # fraction of (token, slot) assignments routed to each expert
    fe = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    fe = fe / jnp.maximum(ids.size, 1)
    pe = probs.mean(axis=0)
    return m.aux_coef * E * jnp.sum(fe * pe)


def _swiglu(x, wg, wu, wd):
    g = jnp.einsum("...td,edf->...tef", x, wg)
    u = jnp.einsum("...td,edf->...tef", x, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...tef,efd->...ted", h, wd)


def _dense_dispatch(cfg: ModelConfig, p, xf, ids, gates, *, token_block: int):
    """All-experts masked compute, token-blocked to bound peak memory."""
    m = cfg.moe
    T, d = xf.shape
    E = m.n_experts
    tb = min(token_block, T)
    pad = (-T) % tb
    xp = jnp.pad(xf, ((0, pad), (0, 0))).reshape(-1, tb, d)
    # per-token combine weights over experts (T, E)
    comb = jnp.zeros((T, E), xf.dtype)
    comb = comb.at[jnp.arange(T)[:, None], ids].add(gates.astype(xf.dtype))
    comb = jnp.pad(comb, ((0, pad), (0, 0))).reshape(-1, tb, E)

    def block(args):
        xb, cb = args                         # (tb, d), (tb, E)
        yb = _swiglu(xb, p["wg"], p["wu"], p["wd"])   # (tb, E, d)
        return jnp.einsum("ted,te->td", yb, cb)

    y = jax.lax.map(block, (xp, comb))
    return y.reshape(-1, d)[:T]


def _gather_dispatch(cfg: ModelConfig, p, xf, ids, gates, *,
                     capacity_factor: float):
    """Capacity-based dispatch: FLOPs scale with top_k, not n_experts."""
    m = cfg.moe
    T, d = xf.shape
    E, k = m.n_experts, m.top_k
    Tk = T * k
    cap = max(int(capacity_factor * Tk / E) + 1, 4)

    eid = ids.reshape(-1)                              # (Tk,)
    gate = gates.reshape(-1)
    tok = jnp.repeat(jnp.arange(T), k)
    # xrep replaces xf[tok]: the row pattern is static (each token row
    # repeated k times), so GSPMD shards it like xf instead of treating
    # it as a data-dependent gather (which it would replicate)
    xrep = jnp.repeat(xf, k, axis=0)                   # (Tk, d)

    # position of each assignment within its expert (stable rank)
    order = jnp.argsort(eid, stable=True)
    counts = jnp.zeros((E,), jnp.int32).at[eid].add(1)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(Tk, dtype=jnp.int32) - starts[eid[order]]
    pos = jnp.zeros((Tk,), jnp.int32).at[order].set(rank_sorted)
    keep = pos < cap
    posc = jnp.minimum(pos, cap - 1)

    # dispatch into (E, cap, d) buffers; constrain the expert buffers to
    # the expert-parallel layout (E over ep, d over ep2) — without this
    # GSPMD replicates the scatter result on every device
    buf = jnp.zeros((E, cap, d), xf.dtype)
    contrib = jnp.where(keep[:, None], xrep, 0).astype(xf.dtype)
    buf = buf.at[eid, posc].add(contrib)
    buf = constrain(buf, ("ep",))

    # grouped expert GEMMs: each expert sees only its (cap, d) buffer
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["wu"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xf.dtype) * u
    h = constrain(h, ("ep",))
    yb = jnp.einsum("ecf,efd->ecd", h, p["wd"])        # (E, cap, d)
    yb = constrain(yb, ("ep",))

    gathered = yb[eid, posc] * (gate * keep)[:, None].astype(xf.dtype)
    y = jnp.zeros((T, d), xf.dtype).at[tok].add(gathered)
    return constrain(y, ("act_batch",))


def _a2a_dispatch(cfg: ModelConfig, p, x, *, capacity_factor: float,
                  mesh, rules):
    """Expert-parallel dispatch with explicit all_to_all (shard_map).

    Per device: route LOCAL tokens, pack them into (E, c_loc, d) buffers
    (local scatter — no cross-device scatter for GSPMD to replicate),
    all_to_all over the expert axis so each device receives its own
    experts' tokens from every peer, run the expert GEMMs locally
    (f sharded over the data axis; one psum on the down-projection),
    reverse the all_to_all, and combine with gates.

    This is the production EP pattern; the pure-GSPMD 'gather' dispatch
    all-reduces whole (E, cap, d) buffers per layer instead (see
    EXPERIMENTS.md §Perf).
    """
    m = cfg.moe
    d = x.shape[-1]
    E, k = m.n_experts, m.top_k
    ep_axis = rules.get("ep")                     # mesh axis holding E
    ep2_axis = rules.get("ep2")                   # mesh axis holding f
    n_ep = mesh.shape[ep_axis]
    assert E % n_ep == 0
    e_loc = E // n_ep
    batch_axes = rules.get("act_batch") or ()
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    seq_axis = rules.get("act_seq")
    other = tuple(a for a in mesh.axis_names
                  if a not in (*batch_axes, seq_axis, ep_axis, ep2_axis))

    from jax.sharding import PartitionSpec as P
    x_spec = P(tuple(batch_axes) or None, seq_axis, None)
    w_up_spec = P(ep_axis, None, ep2_axis)
    w_dn_spec = P(ep_axis, ep2_axis, None)
    out_specs = (x_spec, P())

    def body(xl, router, wg, wu, wd):
        Tl = xl.shape[0] * xl.shape[1]
        xf = xl.reshape(Tl, d)
        probs, ids, gates = _router(cfg, {"router": router}, xf)
        aux = _aux_loss(cfg, probs, ids)
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        c_loc = max(int(capacity_factor * Tl * k / E) + 1, 4)
        eid = ids.reshape(-1)
        gate = gates.reshape(-1)
        tok = jnp.repeat(jnp.arange(Tl), k)
        order = jnp.argsort(eid)
        counts = jnp.zeros((E,), jnp.int32).at[eid].add(1)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.zeros((Tl * k,), jnp.int32).at[order].set(
            jnp.arange(Tl * k, dtype=jnp.int32) - starts[eid[order]])
        keep = rank < c_loc
        pos = jnp.minimum(rank, c_loc - 1)
        buf = jnp.zeros((E, c_loc, d), xl.dtype)
        buf = buf.at[eid, pos].add(
            jnp.where(keep[:, None], jnp.repeat(xf, k, axis=0), 0))
        # all_to_all over the expert axis: block j of my buffer goes to
        # peer j; I receive every peer's block for MY local experts
        recv = jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        rows = (recv.reshape(n_ep, e_loc, c_loc, d)
                .transpose(1, 0, 2, 3).reshape(e_loc, n_ep * c_loc, d))
        # FSDP-style expert-weight gather over ep2 (tokens differ across
        # that axis, so f-partials cannot be psummed; gathering the
        # weights keeps the GEMMs fully local — grads reduce-scatter
        # automatically through the all_gather VJP)
        if ep2_axis is not None:
            wg = jax.lax.all_gather(wg, ep2_axis, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, ep2_axis, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, ep2_axis, axis=1, tiled=True)
        g = jnp.einsum("ecd,edf->ecf", rows, wg)
        u = jnp.einsum("ecd,edf->ecf", rows, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(rows.dtype) * u
        yd = jnp.einsum("ecf,efd->ecd", h, wd)
        # reverse exchange back to the token owners
        back = (yd.reshape(e_loc, n_ep, c_loc, d)
                .transpose(1, 0, 2, 3).reshape(E, c_loc, d))
        sent = jax.lax.all_to_all(back, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        gathered = sent[eid, pos] * (gate * keep)[:, None].astype(xl.dtype)
        y = jnp.zeros((Tl, d), xl.dtype).at[tok].add(gathered)
        return y.reshape(xl.shape), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(), w_up_spec, w_up_spec, w_dn_spec),
        out_specs=out_specs, check_vma=False)
    y, aux = fn(x, p["router"], p["wg"], p["wu"], p["wd"])
    return y, aux


def moe_forward(cfg: ModelConfig, p, x, *, perf: PerfConfig = DEFAULT_PERF):
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar fp32)."""
    from repro.sharding_ctx import current_mesh, current_rules
    m = cfg.moe
    B, S, d = x.shape
    impl = perf.moe_impl
    mesh, rules = current_mesh(), current_rules()
    if impl == "a2a" and (mesh is None or rules is None
                          or rules.get("ep") is None
                          or rules.get("act_seq") is None):
        # a2a pays an FSDP-style expert-weight gather per layer — right
        # for full-sequence cells (train/prefill), wrong for decode's
        # handful of tokens; decode keeps the capacity-gather path
        impl = "gather"
    if impl == "a2a":
        y, aux = _a2a_dispatch(cfg, p, x, mesh=mesh, rules=rules,
                               capacity_factor=perf.capacity_factor)
        if m.n_shared:
            s = p["shared"]
            xf = x.reshape(-1, d)
            g = jnp.einsum("td,df->tf", xf, s["wg"])
            u = jnp.einsum("td,df->tf", xf, s["wu"])
            h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
            y = y + jnp.einsum("tf,fd->td", h, s["wd"]).reshape(B, S, d)
        return y, aux
    xf = x.reshape(-1, d)
    probs, ids, gates = _router(cfg, p, xf)
    if impl == "dense":
        y = _dense_dispatch(cfg, p, xf, ids, gates, token_block=1024)
    elif impl == "gather":
        y = _gather_dispatch(cfg, p, xf, ids, gates,
                             capacity_factor=perf.capacity_factor)
    else:
        raise ValueError(f"unknown moe impl {perf.moe_impl!r}")
    if m.n_shared:
        s = p["shared"]
        g = jnp.einsum("td,df->tf", xf, s["wg"])
        u = jnp.einsum("td,df->tf", xf, s["wu"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        y = y + jnp.einsum("tf,fd->td", h, s["wd"])
    return y.reshape(B, S, d), _aux_loss(cfg, probs, ids)
