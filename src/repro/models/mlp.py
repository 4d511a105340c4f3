"""Feed-forward blocks: SwiGLU/GELU MLP and capacity-based top-k MoE.

MoE uses scatter-based token dispatch into per-expert capacity buffers
(avoids the (tokens, E, C) one-hot blow-up), which both smoke-tests on CPU
and shards cleanly with the expert dim on the "model" mesh axis.  The
grouped expert matmul can be dispatched to the Pallas ``moe_gmm`` kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import maybe_constrain, normal_init
from .config import ArchConfig


def init_mlp_params(key, d: int, ff: int, act: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "w_in": normal_init(ks[0], (d, ff), d ** -0.5, dtype),
        "w_out": normal_init(ks[1], (ff, d), ff ** -0.5, dtype),
    }
    if act == "swiglu":
        p["w_gate"] = normal_init(ks[2], (d, ff), d ** -0.5, dtype)
    return p


def mlp_forward(params, x, act: str) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, params["w_in"])
    h = maybe_constrain(h, "batch", "seq", "model")  # pin column-parallel TP
    if act == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
        g = maybe_constrain(g, "batch", "seq", "model")
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, params["w_out"])


def init_moe_params(key, cfg: ArchConfig, dtype) -> dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": normal_init(ks[0], (d, e), d ** -0.5, jnp.float32),
        "w_in": normal_init(ks[1], (e, d, ff), d ** -0.5, dtype),
        "w_gate": normal_init(ks[2], (e, d, ff), d ** -0.5, dtype),
        "w_out": normal_init(ks[3], (e, ff, d), ff ** -0.5, dtype),
    }
    return p


def moe_capacity(cfg: ArchConfig, tokens_per_row: int) -> int:
    c = math.ceil(cfg.capacity_factor * tokens_per_row * cfg.top_k
                  / cfg.n_experts)
    return max(4, -(-c // 4) * 4)   # round up to a multiple of 4


def moe_forward(params, x, cfg: ArchConfig) -> tuple[jax.Array, jax.Array]:
    """Top-k capacity-dispatch MoE.  x (B,S,D) -> (y, aux_loss).

    Under a mesh with a "model" axis that divides n_experts, dispatch runs
    expert-parallel via shard_map: routing is computed per model-rank
    (replicated, cheap), each rank scatters only ITS experts' tokens into a
    local (B,E_loc,C,D) buffer, runs the local expert FFN, and one psum
    over "model" combines -- the same collective cost as a TP MLP.  GSPMD
    left to its own devices replicates the scatter (observed: 8x FLOPs,
    100+ GB of collectives per step on arctic-480b)."""
    from .common import SHARDING_MODE, ambient_mesh
    mesh = ambient_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and cfg.n_experts % mesh.shape["model"] == 0
            and cfg.kernel_mode == "ref"):
        if (SHARDING_MODE[0] == "fsdp"
                and x.shape[1] % mesh.shape["model"] == 0):
            return _moe_expert_parallel_a2a(params, x, cfg, mesh)
        return _moe_expert_parallel(params, x, cfg, mesh)
    return _moe_dense_dispatch(params, x, cfg)


def _moe_expert_parallel_a2a(params, x, cfg: ArchConfig, mesh):
    """GShard-style expert parallelism for the seq-sharded (FSDP) layout.

    Tokens are sharded (batch over data axes, seq over "model"); experts
    are sharded over "model".  Each rank routes its local tokens into
    per-expert capacity slots, an all_to_all ships slots to the expert-
    owning ranks, the local expert FFN runs, and a reverse all_to_all
    returns results -- data moves to compute (the paper's insight on-chip),
    two a2a's per layer instead of replicated-token psums."""
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    nm = mesh.shape["model"]
    baxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]
    bspec = baxes if (b % nb == 0 and b >= nb) else None
    s_loc = s // nm
    cap = moe_capacity(cfg, s_loc)

    def shard_fn(x_blk, router, w_in, w_gate, w_out):
        bl, sl, _ = x_blk.shape
        e_loc = w_in.shape[0]
        logits = jnp.einsum("bsd,de->bse", x_blk.astype(jnp.float32),
                            router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=(0, 1))
        ce = jax.nn.one_hot(top_i[..., 0], e).mean(axis=(0, 1))
        aux = e * jnp.sum(me * ce)

        flat_e = top_i.reshape(bl, sl * k)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=1) - onehot
        slot = jnp.take_along_axis(pos_in_e, flat_e[..., None],
                                   axis=-1)[..., 0]
        keep = slot < cap
        slot = jnp.where(keep, slot, 0)
        w = top_p.reshape(bl, sl * k) * keep

        x_tok = jnp.repeat(x_blk, k, axis=1).reshape(bl, sl * k, d)
        buf = jnp.zeros((bl, e, cap, d), dtype=x_blk.dtype)
        b_idx = jnp.broadcast_to(jnp.arange(bl)[:, None], (bl, sl * k))
        buf = buf.at[b_idx, flat_e, slot].add(
            x_tok * keep[..., None].astype(x_blk.dtype))

        # ship slots to the expert-owning ranks: split the expert dim,
        # concatenate received slots along the capacity dim
        recv = jax.lax.all_to_all(buf, "model", split_axis=1,
                                  concat_axis=2,
                                  tiled=True)      # (bl,e_loc,nm*cap,d)

        hin = jnp.einsum("becd,edf->becf", recv, w_in)
        if cfg.mlp_act == "swiglu":
            g = jnp.einsum("becd,edf->becf", recv, w_gate)
            hin = jax.nn.silu(g) * hin
        else:
            hin = jax.nn.gelu(hin)
        h = jnp.einsum("becf,efd->becd", hin, w_out)

        # return results to the source ranks
        back = jax.lax.all_to_all(h, "model", split_axis=2,
                                  concat_axis=1, tiled=True)  # (bl,e,cap,d)

        y_tok = back[b_idx, flat_e, slot] * (
            w * keep)[..., None].astype(x_blk.dtype)
        y = y_tok.reshape(bl, sl, k, d).sum(axis=2)
        return y, jax.lax.pmean(aux, "model")

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(bspec, "model", None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(bspec, "model", None), P()), check_vma=False,
    )
    return fn(x, params["router"].astype(jnp.float32), params["w_in"],
              params["w_gate"], params["w_out"])


def _moe_expert_parallel(params, x, cfg: ArchConfig, mesh):
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = moe_capacity(cfg, s)
    baxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]
    bspec = baxes if (b % nb == 0 and b >= nb) else None

    def shard_fn(x_blk, router, w_in, w_gate, w_out):
        bl = x_blk.shape[0]
        e_loc = w_in.shape[0]
        e0 = jax.lax.axis_index("model") * e_loc
        logits = jnp.einsum("bsd,de->bse", x_blk.astype(jnp.float32),
                            router)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=(0, 1))
        ce = jax.nn.one_hot(top_i[..., 0], e).mean(axis=(0, 1))
        aux = e * jnp.sum(me * ce)

        flat_e = top_i.reshape(bl, s * k)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=1) - onehot
        slot = jnp.take_along_axis(pos_in_e, flat_e[..., None],
                                   axis=-1)[..., 0]
        keep = slot < cap
        slot = jnp.where(keep, slot, 0)
        w = top_p.reshape(bl, s * k) * keep

        local = (flat_e >= e0) & (flat_e < e0 + e_loc)
        le = jnp.where(local, flat_e - e0, 0)
        gate = keep & local
        x_tok = jnp.repeat(x_blk, k, axis=1).reshape(bl, s * k, d)
        buf = jnp.zeros((bl, e_loc, cap, d), dtype=x_blk.dtype)
        b_idx = jnp.broadcast_to(jnp.arange(bl)[:, None], (bl, s * k))
        buf = buf.at[b_idx, le, slot].add(
            x_tok * gate[..., None].astype(x_blk.dtype))

        hin = jnp.einsum("becd,edf->becf", buf, w_in)
        if cfg.mlp_act == "swiglu":
            g = jnp.einsum("becd,edf->becf", buf, w_gate)
            hin = jax.nn.silu(g) * hin
        else:
            hin = jax.nn.gelu(hin)
        h = jnp.einsum("becf,efd->becd", hin, w_out)

        y_tok = h[b_idx, le, slot] * (
            w * gate)[..., None].astype(x_blk.dtype)
        y = y_tok.reshape(bl, s, k, d).sum(axis=2)
        y = jax.lax.psum(y, "model")
        return y, jax.lax.pmean(aux, "model")

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(bspec, None, None), P()), check_vma=False,
    )
    return fn(x, params["router"].astype(jnp.float32), params["w_in"],
              params["w_gate"], params["w_out"])


def _moe_dense_dispatch(params, x, cfg: ArchConfig):
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = moe_capacity(cfg, s)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (B,S,E)
    top_p, top_i = jax.lax.top_k(probs, k)                       # (B,S,k)
    top_p = top_p / jnp.clip(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    me = probs.mean(axis=(0, 1))                                 # (E,)
    ce = jax.nn.one_hot(top_i[..., 0], e).mean(axis=(0, 1))
    aux = e * jnp.sum(me * ce)

    # slot assignment: position of each routed token within its expert
    flat_e = top_i.reshape(b, s * k)                             # (B,T)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)          # (B,T,E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot               # (B,T,E)
    slot = jnp.take_along_axis(pos_in_e, flat_e[..., None],
                               axis=-1)[..., 0]                  # (B,T)
    keep = slot < cap
    slot = jnp.where(keep, slot, 0)
    w = top_p.reshape(b, s * k) * keep                           # (B,T)

    # scatter tokens into (B,E,C,D) buffers; pin E to the "model" axis
    # (expert parallelism) or GSPMD keeps the full expert dim per device
    x_tok = jnp.repeat(x, k, axis=1).reshape(b, s * k, d)        # (B,T,D)
    buf = jnp.zeros((b, e, cap, d), dtype=x.dtype)
    b_idx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s * k))
    buf = buf.at[b_idx, flat_e, slot].add(
        x_tok * keep[..., None].astype(x.dtype))
    buf = maybe_constrain(buf, "batch", None, None, None)

    # expert FFN (grouped matmul, optionally via the Pallas kernel)
    if cfg.kernel_mode in ("pallas", "interpret"):
        from ..kernels.moe_gmm.ops import grouped_ffn
        h = grouped_ffn(buf, params["w_in"], params["w_gate"],
                        params["w_out"], cfg.mlp_act,
                        interpret=cfg.kernel_mode == "interpret")
    else:
        hin = jnp.einsum("becd,edf->becf", buf, params["w_in"])
        if cfg.mlp_act == "swiglu":
            g = jnp.einsum("becd,edf->becf", buf, params["w_gate"])
            hin = jax.nn.silu(g) * hin
        else:
            hin = jax.nn.gelu(hin)
        h = jnp.einsum("becf,efd->becd", hin, params["w_out"])
    h = maybe_constrain(h, "batch", "model", None, None)

    # gather back and combine with routing weights
    y_tok = h[b_idx, flat_e, slot] * w[..., None].astype(x.dtype)  # (B,T,D)
    y = y_tok.reshape(b, s, k, d).sum(axis=2)
    return y, aux
