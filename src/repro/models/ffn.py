"""Feed-forward layers: SwiGLU and capacity-based top-k MoE.

The MoE dispatch is **sort-based with a fixed per-expert capacity**
(GShard/Switch style, implemented with argsort + gather instead of the
one-hot dispatch einsum): compute cost in the compiled HLO is the *active*
FLOPs  tokens x top_k x (3 d_model expert_ff)  plus O(tokens) gather
bookkeeping — not the n_experts-dense einsum, which for kimi-k2's 384
experts would inflate HLO FLOPs 48x and wreck both the roofline's
usefulness and actual TPU time.  Expert weights carry the "experts"
logical axis so the rule table can lay them out as EP (experts over a mesh
axis) or FSDP (d_model/d_ff sharded) per architecture.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ModelConfig, current_mesh, shard

__all__ = ["swiglu", "moe_layer", "moe_layer_ep", "router_top_k"]


def swiglu(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """x [.., D] with params wi_gate [D,F], wi_up [D,F], wo [F,D]."""
    gate = x @ params["wi_gate"]
    up = x @ params["wi_up"]
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    h = shard(h, ("batch", "seq", "d_ff"))
    return h @ params["wo"]


def router_top_k(
    logits: jnp.ndarray, top_k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Token router: logits [T, E] -> (weights [T, k], experts [T, k]).

    Softmax over the selected k (Mixtral-style renormalisation).
    """
    gates, experts = jax.lax.top_k(logits, top_k)  # [T, k]
    weights = jax.nn.softmax(gates.astype(jnp.float32), axis=-1)
    return weights, experts


def moe_layer(
    params: dict,
    x: jnp.ndarray,  # [B, S, D]
    cfg: ModelConfig,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k MoE with fixed capacity; returns (out [B,S,D], aux_loss []).

    Dispatch: flatten tokens, route, then for each (token, slot) pair sort
    by expert id and scatter into a [E, C, D] buffer; experts run as one
    batched matmul over the leading E axis; results gather back weighted
    by router probabilities.  Tokens beyond an expert's capacity C are
    dropped (standard capacity-factor semantics; the aux loss pushes the
    router toward balance, making drops rare).
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = max(1, int(cfg.capacity_factor * t * k / e))
    xf = x.reshape(t, d)

    logits = (xf @ params["router"]).astype(jnp.float32)  # [T, E]
    weights, experts = router_top_k(logits, k)  # [T,k]

    # Load-balancing auxiliary loss (Switch Transformer eq. 4).
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    me = probs.mean(axis=0)  # mean router prob per expert
    one_hot_top1 = jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32)
    ce = one_hot_top1.mean(axis=0)  # fraction of tokens (top-1) per expert
    aux = e * jnp.sum(me * ce)

    # ---- sort-based dispatch ---------------------------------------- #
    flat_expert = experts.reshape(-1)  # [T*k]
    flat_weight = weights.reshape(-1)  # [T*k]
    flat_token = jnp.repeat(jnp.arange(t), k)  # [T*k]
    order = jnp.argsort(flat_expert)  # stable
    se, sw, stok = flat_expert[order], flat_weight[order], flat_token[order]
    # segment rank: index of each routed slot within its expert's run
    seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")  # [E]
    pos_in_expert = jnp.arange(t * k) - seg_start[se]
    keep = pos_in_expert < cap
    slot = jnp.clip(pos_in_expert, 0, cap - 1)

    # scatter tokens into [E, C, D]
    buf = jnp.zeros((e, cap, d), dtype=x.dtype)
    src = jnp.where(keep[:, None], xf[stok], 0.0)
    buf = buf.at[se, slot].add(src)
    buf = shard(buf, ("experts", None, "d_model"))

    # batched expert matmuls: [E, C, D] x [E, D, F] -> [E, C, F] -> [E, C, D]
    gate = jnp.einsum("ecd,edf->ecf", buf, params["wi_gate"])
    up = jnp.einsum("ecd,edf->ecf", buf, params["wi_up"])
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    h = shard(h, ("experts", None, "d_ff"))
    out_e = jnp.einsum("ecf,efd->ecd", h, params["wo"])

    # gather back to tokens, weighted
    vals = out_e[se, slot]  # [T*k, D]
    vals = jnp.where(keep[:, None], vals * sw[:, None].astype(x.dtype), 0.0)
    out = jnp.zeros((t, d), dtype=x.dtype).at[stok].add(vals)

    # shared experts (kimi-k2): dense SwiGLU applied to every token
    if cfg.n_shared_experts > 0:
        out = out + swiglu(params["shared"], xf.reshape(b, s, d)).reshape(t, d)
    return out.reshape(b, s, d), aux


# --------------------------------------------------------------------- #
# shard_map expert parallelism (the collective-bound hillclimb, §Perf)
# --------------------------------------------------------------------- #
def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def moe_layer_ep(
    params: dict,
    x: jnp.ndarray,  # [B, S, D]
    cfg: ModelConfig,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map + explicit all_to_all.

    The GSPMD path (moe_layer) lets the partitioner handle the global
    scatter into the [E, C, D] dispatch buffer; at kimi-k2 scale the
    partitioner falls back to replicating the buffer (observed: 1.18 TB
    temp / 1.5 TB all-reduce per device).  This path makes the EP schedule
    explicit instead:

      per device (shard_map over the full mesh):
        route local tokens -> sort by destination EP shard -> fixed-
        capacity send buffer [n_ep, C, D] -> all_to_all('data') ->
        local dispatch to [E_loc, C2, D] (a LOCAL scatter: no SPMD
        repartitioning) -> batched expert matmuls (d_ff sliced over
        'model') -> partial down-proj -> gather back -> all_to_all
        ('data') -> weighted combine -> psum('model').

    Collectives per layer: 2 all_to_all of ~(tokens_loc * k * D) bytes +
    1 psum of the [B_loc, S, D] output — vs the GSPMD path's full-buffer
    all-reduces.  Tokens beyond capacity drop (capacity_factor), as in
    the GSPMD path.  Requires n_experts % (data-axis size) == 0.
    """
    mesh = current_mesh()
    if mesh is None or "data" not in mesh.axis_names:
        return moe_layer(params, x, cfg)
    n_ep = mesh.shape["data"]
    if cfg.n_experts % n_ep != 0:
        return moe_layer(params, x, cfg)
    e_loc = cfg.n_experts // n_ep
    bd = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    has_model = "model" in mesh.axis_names
    f = cfg.expert_ff
    f_axis = "model" if (has_model and f % mesh.shape["model"] == 0) else None

    # Make batch the only sharded activation dim at the boundary.
    x = jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, P(bd, None, None))
    )

    in_specs = (
        P(bd, None, None),  # x
        P(None, None),  # router (small; replicated)
        P("data", None, f_axis),  # wi_gate [E, D, F]
        P("data", None, f_axis),  # wi_up
        P("data", f_axis, None),  # wo [E, F, D]
    )
    args = [x, params["router"], params["wi_gate"], params["wi_up"], params["wo"]]
    has_shared = cfg.n_shared_experts > 0
    if has_shared:
        fs = f * cfg.n_shared_experts
        fs_axis = "model" if (has_model and fs % mesh.shape["model"] == 0) else None
        in_specs = in_specs + (
            P(None, fs_axis), P(None, fs_axis), P(fs_axis, None),
        )
        args += [params["shared"]["wi_gate"], params["shared"]["wi_up"], params["shared"]["wo"]]

    def body(xb, router, wg, wu, wo, *shared_w):
        b_loc, s, d = xb.shape
        t = b_loc * s
        xf = xb.reshape(t, d)
        logits = (xf @ router).astype(jnp.float32)  # [T, E] (global experts)
        weights, experts = router_top_k(logits, cfg.top_k)  # [T, k]

        probs = jax.nn.softmax(logits, axis=-1)
        # token-means are linear: pmean BEFORE the product so the aux loss
        # equals the global-batch formula exactly (tested vs moe_layer)
        me = jax.lax.pmean(probs.mean(axis=0), bd)
        ce = jax.lax.pmean(
            jax.nn.one_hot(experts[:, 0], cfg.n_experts, dtype=jnp.float32).mean(axis=0), bd
        )
        aux = cfg.n_experts * jnp.sum(me * ce)

        k = cfg.top_k
        flat_e = experts.reshape(-1)
        flat_w = weights.reshape(-1).astype(xb.dtype)
        flat_tok = jnp.repeat(jnp.arange(t), k)
        dest = flat_e // e_loc  # EP shard owning the expert
        local_e = flat_e % e_loc

        cap = _round_up(max(int(cfg.capacity_factor * t * k / n_ep), 8), 8)
        order = jnp.argsort(dest)
        d_s, tok_s, le_s, w_s = dest[order], flat_tok[order], local_e[order], flat_w[order]
        seg_start = jnp.searchsorted(d_s, jnp.arange(n_ep), side="left")
        pos = jnp.arange(t * k) - seg_start[d_s]
        keep = pos < cap
        slot = jnp.clip(pos, 0, cap - 1)

        send_x = jnp.zeros((n_ep, cap, d), xb.dtype).at[d_s, slot].add(
            jnp.where(keep[:, None], xf[tok_s], 0)
        )
        send_le = jnp.full((n_ep, cap), e_loc, jnp.int32).at[d_s, slot].min(
            jnp.where(keep, le_s, e_loc).astype(jnp.int32)
        )  # e_loc marks empty slots
        recv_x = jax.lax.all_to_all(send_x, "data", split_axis=0, concat_axis=0, tiled=False)
        recv_le = jax.lax.all_to_all(send_le, "data", split_axis=0, concat_axis=0, tiled=False)

        # local dispatch: [n_ep * cap] slots -> [E_loc, C2, D]
        rl = recv_le.reshape(-1)
        rx = recv_x.reshape(-1, d)
        c2 = _round_up(max(int(cfg.capacity_factor * n_ep * cap / e_loc), 8), 8)
        order2 = jnp.argsort(rl)  # empty slots (e_loc) sort to the end
        rl2, idx2 = rl[order2], order2
        seg2 = jnp.searchsorted(rl2, jnp.arange(e_loc), side="left")
        pos2 = jnp.arange(rl2.shape[0]) - seg2[jnp.clip(rl2, 0, e_loc - 1)]
        keep2 = (pos2 < c2) & (rl2 < e_loc)
        slot2 = jnp.clip(pos2, 0, c2 - 1)
        buf = jnp.zeros((e_loc, c2, d), xb.dtype).at[
            jnp.clip(rl2, 0, e_loc - 1), slot2
        ].add(jnp.where(keep2[:, None], rx[idx2], 0))

        gate = jnp.einsum("ecd,edf->ecf", buf, wg)
        up = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(xb.dtype) * up
        out_e = jnp.einsum("ecf,efd->ecd", h, wo)  # partial over sliced f

        # undo local dispatch: back to [n_ep * cap] slot order
        vals = out_e[jnp.clip(rl2, 0, e_loc - 1), slot2]
        vals = jnp.where(keep2[:, None], vals, 0)
        back = jnp.zeros((rl.shape[0], d), xb.dtype).at[idx2].add(vals)
        back = back.reshape(n_ep, cap, d)
        ret_x = jax.lax.all_to_all(back, "data", split_axis=0, concat_axis=0, tiled=False)

        # combine on the home device
        vals_home = ret_x[d_s, slot]
        vals_home = jnp.where(keep[:, None], vals_home * w_s[:, None], 0)
        out = jnp.zeros((t, d), xb.dtype).at[tok_s].add(vals_home)

        if shared_w:
            swg, swu, swo = shared_w
            hs = jax.nn.silu((xf @ swg).astype(jnp.float32)).astype(xb.dtype) * (xf @ swu)
            out = out + hs @ swo  # partial over sliced fs
        if has_model:
            out = jax.lax.psum(out, "model")
        return out.reshape(b_loc, s, d), aux

    out_specs = (P(bd, None, None), P())
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return fn(*args)
