"""Token-choice top-k Mixture-of-Experts FFN (GShard-style with capacity).

Covers mixtral-8x7b (8 experts, top-2, MoE every layer) and
llama4-maverick (128 experts, top-1, MoE on alternating layers).

Dispatch is scatter-based: per-assignment position-in-expert ranks come
from a cumsum over a one-hot (T·k, E) matrix; tokens beyond the capacity
``C = ceil(cf · T · k / E)`` are dropped (standard GShard semantics).  The
expert GEMMs are grouped einsums over stacked expert weights (E, D, F) —
the TPU-friendly formulation (shardable as EP over the model axis, or TP
inside experts for small E).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from jax import shard_map
from .layers import dense_init

__all__ = ["moe_init", "moe_apply", "set_moe_block_dispatch"]

# §Perf hook: dispatch tokens in ``n_blocks`` independent blocks whose
# leading axis is sharded over the data axes.  Routing capacity becomes
# per-block (the standard per-device semantics of production MoE stacks),
# and the dispatch scatter/cumsum stays shard-local instead of
# all-reducing a full (E, C, D) expert buffer every layer (measured 2.3
# TB/device/step on mixtral train_4k — EXPERIMENTS.md §Perf).
_MOE_BLOCKS = {"n": None, "sharding": None, "w_in": None, "w_out": None}

# §Perf mixtral iter4: bypass GSPMD auto-partitioning for the MoE layer
# entirely — a shard_map with explicit collectives: per-shard local
# dispatch (local capacity, zero dispatch comms) + TP expert GEMMs with a
# single psum over "model".  mesh/axes registered by the launch layer.
_MOE_SHARD_MAP = {"mesh": None, "dp": None, "tp": None}


def set_moe_block_dispatch(n_blocks, sharding, w_in=None, w_out=None) -> None:
    _MOE_BLOCKS["n"] = n_blocks
    _MOE_BLOCKS["sharding"] = sharding
    _MOE_BLOCKS["w_in"] = w_in
    _MOE_BLOCKS["w_out"] = w_out


def set_moe_shard_map(mesh, dp, tp="model") -> None:
    _MOE_SHARD_MAP["mesh"] = mesh
    _MOE_SHARD_MAP["dp"] = dp
    _MOE_SHARD_MAP["tp"] = tp


def moe_init(key, cfg: ArchConfig):
    k_r, k1, k2, k3 = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": dense_init(k_r, d, e, scale=0.02),
        "w_gate": jax.random.normal(k1, (e, d, f), jnp.float32) * (d ** -0.5),
        "w_up": jax.random.normal(k2, (e, d, f), jnp.float32) * (d ** -0.5),
        "w_down": jax.random.normal(k3, (e, f, d), jnp.float32) * (f ** -0.5),
    }


def _dispatch_block(xt, p, cfg: ArchConfig, cap: int):
    """Token-choice top-k dispatch + expert GEMMs for one token block.

    xt: (Tb, D) -> (y: (Tb, D), aux: scalar).
    """
    E, K = cfg.n_experts, cfg.top_k
    Tb, D = xt.shape

    logits = (xt @ p["router"].astype(xt.dtype)).astype(jnp.float32)  # (Tb, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, K)                # (Tb, K)
    gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch/GShard)
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_i, E, dtype=jnp.float32), axis=1), axis=0
    )
    aux = E * jnp.sum(me * ce)

    # position of each assignment within its expert queue
    eflat = gate_i.reshape(-1)                               # (Tb*K,)
    onehot = jax.nn.one_hot(eflat, E, dtype=jnp.int32)       # (Tb*K, E)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos, eflat[:, None], axis=1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, pos, 0)

    # dispatch: (E, C, D) expert buffers
    xt_rep = jnp.repeat(xt, K, axis=0)                       # (Tb*K, D)
    contrib = xt_rep * keep[:, None].astype(xt.dtype)
    buf = jnp.zeros((E, cap, D), xt.dtype)
    buf = buf.at[eflat, slot].add(contrib)

    # grouped expert GEMMs (ZeRO-3: gather weights bf16 at use time)
    def use(w, kind):
        w = w.astype(xt.dtype)
        s = _MOE_BLOCKS[kind]
        if s is not None and w.ndim == 3:
            w = jax.lax.with_sharding_constraint(w, s)
        return w

    g = jnp.einsum("ecd,edf->ecf", buf, use(p["w_gate"], "w_in"))
    u = jnp.einsum("ecd,edf->ecf", buf, use(p["w_up"], "w_in"))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, use(p["w_down"], "w_out"))

    # combine
    y = out[eflat, slot] * (gate_w.reshape(-1)[:, None] * keep[:, None]).astype(xt.dtype)
    y = y.reshape(Tb, K, D).sum(axis=1)
    return y, aux


def _moe_shard_map_apply(p, cfg: ArchConfig, x: jnp.ndarray):
    """Explicit-collective MoE (mixtral-class, experts replicated, TP on
    d_ff): each (dp, tp) shard dispatches its own tokens locally and the
    row-parallel w_down contraction psums once over the tp axis."""
    from jax.sharding import PartitionSpec as P

    mesh = _MOE_SHARD_MAP["mesh"]
    dp = _MOE_SHARD_MAP["dp"]
    tp = _MOE_SHARD_MAP["tp"]
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    n_dp = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        n_dp *= mesh.shape[a]
    T_loc = (B // n_dp) * S
    cap = max(int(cfg.capacity_factor * T_loc * K / E), 1)
    cap = min(cap, T_loc)

    def local(xl, router, wg, wu, wd):
        Bl, Sl, _ = xl.shape
        pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        y, aux = _dispatch_block(xl.reshape(Bl * Sl, D), pl, cfg, cap)
        # row-parallel w_down partial sums -> one psum over tp
        y = jax.lax.psum(y, tp)
        aux = jax.lax.pmean(aux, (dp if isinstance(dp, tuple) else (dp,)) + (tp,))
        return y.reshape(Bl, Sl, D), aux

    bf = jnp.bfloat16
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), P(None, None, tp),
                  P(None, None, tp), P(None, tp, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, p["router"].astype(bf), p["w_gate"].astype(bf),
      p["w_up"].astype(bf), p["w_down"].astype(bf))


def moe_apply(p, cfg: ArchConfig, x: jnp.ndarray):
    """x: (B, S, D) -> (y: (B, S, D), aux_loss: scalar)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S

    if (_MOE_SHARD_MAP["mesh"] is not None
            and cfg.n_experts < _MOE_SHARD_MAP["mesh"].shape[_MOE_SHARD_MAP["tp"]]):
        return _moe_shard_map_apply(p, cfg, x)

    nb = _MOE_BLOCKS["n"] or 1
    if T % nb or (nb > 1 and B % nb):
        nb = 1
    cap = max(int(cfg.capacity_factor * (T // nb) * K / E), 1)
    cap = min(cap, T // nb)

    if nb == 1:
        y, aux = _dispatch_block(x.reshape(T, D), p, cfg, cap)
        return y.reshape(B, S, D), aux

    # block-local dispatch: block axis aligned with the batch sharding
    xb = x.reshape(nb, T // nb, D)
    s = _MOE_BLOCKS["sharding"]
    if s is not None:
        xb = jax.lax.with_sharding_constraint(xb, s)
    y, aux = jax.vmap(lambda t: _dispatch_block(t, p, cfg, cap))(xb)
    if s is not None:
        y = jax.lax.with_sharding_constraint(y, s)
    return y.reshape(B, S, D), jnp.mean(aux)
