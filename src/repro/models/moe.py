"""Mixture-of-Experts: top-k router + sort-based capacity dispatch.

Dispatch is a sort-based grouped matmul (tokens permuted into per-expert
capacity slots, one batched einsum over experts, weighted scatter-add back) —
no (T, E, C) one-hot tensors, so it scales to 256 experts at 1M tokens.

Expert parallelism: `moe_ffn` optionally runs under shard_map with experts
sharded on the `model` mesh axis; token activations arrive replicated across
`model` (they are sharded on `data` only), each device dispatches to its local
expert shard, and a psum over `model` combines contributions. Chosen over
all-to-all token routing because GSPMD cannot infer a good a2a schedule from
gather/scatter dispatch (DESIGN.md §4); an explicit a2a variant is a §Perf
hillclimb candidate.

Also here: dense FFN variants (swiglu / gelu+bias) and the shared-expert and
dense-residual paths (DeepSeek-V3, Arctic).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import dense_init, gelu_mlp, swiglu


def init_moe_params(key, cfg, dtype):
    d = cfg.d_model
    eff = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 6)
    p = {
        "router": dense_init(ks[0], (d, E), dtype=jnp.float32),  # fp32 router
        "w_gate": dense_init(ks[1], (E, d, eff), dtype=dtype),
        "w_up": dense_init(ks[2], (E, d, eff), dtype=dtype),
        "w_down": dense_init(ks[3], (E, eff, d), dtype=dtype),
    }
    if cfg.n_shared_experts:
        se = cfg.n_shared_experts * eff
        p["shared"] = init_dense_ffn(ks[4], cfg, dtype, d_ff=se)
    if cfg.moe_dense_residual:
        p["dense_res"] = init_dense_ffn(ks[5], cfg, dtype, d_ff=cfg.d_ff)
    return p


def init_dense_ffn(key, cfg, dtype, d_ff=None):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.ffn_kind in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d, ff), dtype=dtype),
            "w_up": dense_init(ks[1], (d, ff), dtype=dtype),
            "w_down": dense_init(ks[2], (ff, d), dtype=dtype),
        }
    return {
        "w_up": dense_init(ks[0], (d, ff), dtype=dtype),
        "b_up": jnp.zeros((ff,), dtype),
        "w_down": dense_init(ks[1], (ff, d), dtype=dtype),
        "b_down": jnp.zeros((d,), dtype),
    }


def dense_ffn(p, cfg, x):
    if "w_gate" in p:
        if cfg.ffn_kind == "geglu":  # gemma2: gelu-gated
            g = jnp.einsum("...d,df->...f", x, p["w_gate"])
            u = jnp.einsum("...d,df->...f", x, p["w_up"])
            return jnp.einsum("...f,fd->...d", jax.nn.gelu(g) * u, p["w_down"])
        return swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
    return gelu_mlp(x, p["w_up"], p["w_down"], p.get("b_up"), p.get("b_down"))


def router_topk(router_w, x, top_k: int):
    """Returns (weights (T,k) fp32, expert ids (T,k), aux load-balance loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e (fraction_routed_e * mean_prob_e)
    E = router_w.shape[-1]
    onehot = jax.nn.one_hot(ids[:, 0], E)
    aux = E * jnp.sum(jnp.mean(onehot, 0) * jnp.mean(probs, 0))
    return w, ids, aux


def _dispatch_tables(ids, weights, n_experts: int, capacity: int):
    """Sort-based dispatch: (T,k) assignments -> (E, C) token-index tables.

    Returns (token_idx (E,C) int32, gate (E,C) fp32); empty slots point at
    token 0 with gate 0.
    """
    T, k = ids.shape
    flat_e = ids.reshape(-1)                      # (T*k,)
    flat_w = weights.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    t_sorted = flat_t[order]
    w_sorted = flat_w[order]

    counts = jnp.bincount(flat_e, length=n_experts)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * k) - offsets[e_sorted]      # rank within expert
    keep = pos_in_e < capacity

    slot = jnp.where(keep, e_sorted * capacity + pos_in_e, n_experts * capacity)
    token_idx = jnp.zeros((n_experts * capacity + 1,), jnp.int32).at[slot].set(
        t_sorted, mode="drop"
    )[:-1].reshape(n_experts, capacity)
    gate = jnp.zeros((n_experts * capacity + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, w_sorted, 0.0), mode="drop"
    )[:-1].reshape(n_experts, capacity)
    return token_idx, gate


def _expert_compute(p, x_ec):
    """x_ec: (E, C, d) -> (E, C, d) through each expert's swiglu."""
    g = jnp.einsum("ecd,edf->ecf", x_ec, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", x_ec, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])


def moe_ffn_local(p, cfg, x, *, expert_slice=None, n_total_experts=None):
    """MoE over flat tokens x: (T, d). Routing is over ALL experts; compute
    covers `expert_slice` (lo, size) when running as one expert-parallel shard
    (router outputs for non-local experts are masked out; psum happens in the
    shard_map wrapper).
    """
    T, d = x.shape
    E = n_total_experts or cfg.n_experts
    w, ids, aux = router_topk(p["router"], x, cfg.top_k)

    if expert_slice is not None:
        lo, size = expert_slice
        local = (ids >= lo) & (ids < lo + size)
        ids_local = jnp.where(local, ids - lo, size)       # size = drop bucket
        w = jnp.where(local, w, 0.0)
        n_exp = size
        capacity = max(
            int(cfg.capacity_factor * cfg.top_k * T * size / E), cfg.top_k
        )
        ids_for_dispatch = jnp.where(local, ids_local, n_exp)  # overflow slot
        # use n_exp+1 buckets, last one dropped via capacity table bounds
        token_idx, gate = _dispatch_tables(
            jnp.minimum(ids_for_dispatch, n_exp), w, n_exp + 1, capacity
        )
        token_idx, gate = token_idx[:n_exp], gate[:n_exp]
    else:
        n_exp = E
        capacity = max(int(cfg.capacity_factor * cfg.top_k * T / E), cfg.top_k)
        token_idx, gate = _dispatch_tables(ids, w, n_exp, capacity)

    x_ec = x[token_idx]                                    # (E_local, C, d)
    y_ec = _expert_compute(p, x_ec) * gate[..., None].astype(x.dtype)
    out = jnp.zeros_like(x).at[token_idx.reshape(-1)].add(
        y_ec.reshape(-1, d), mode="drop"
    )

    if "shared" in p:
        out = out + dense_ffn(p["shared"], cfg, x)
    if "dense_res" in p:
        out = out + dense_ffn(p["dense_res"], cfg, x)
    return out, aux


def moe_ffn_2d(p, cfg, x, mesh, model_axis: str = "model"):
    """Serving layout: experts on 'model' x expert-d_ff on 'data'; tokens
    replicated across the whole mesh (decode batches are tiny, expert weights
    are not — this removes the per-step FSDP weight all-gathers entirely;
    DESIGN.md §4, §Perf)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    B, S, d = x.shape
    flat = x.reshape(-1, d)
    n_model = mesh.shape[model_axis]
    ff_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    e_local = cfg.n_experts // n_model

    def shard_fn(p_sh, x_sh):
        idx = jax.lax.axis_index(model_axis)
        p_experts = {k: v for k, v in p_sh.items() if k not in ("shared", "dense_res")}
        out, aux = moe_ffn_local(
            p_experts, cfg, x_sh,
            expert_slice=(idx * e_local, e_local),
            n_total_experts=cfg.n_experts,
        )
        out = jax.lax.psum(out, mesh.axis_names)     # experts + d_ff partials
        aux = jax.lax.pmean(aux, mesh.axis_names)
        if "shared" in p_sh:
            out = out + dense_ffn(p_sh["shared"], cfg, x_sh)
        if "dense_res" in p_sh:
            out = out + dense_ffn(p_sh["dense_res"], cfg, x_sh)
        return out, aux

    p_specs = {
        "router": P(),
        "w_gate": P(model_axis, None, ff_axes),
        "w_up": P(model_axis, None, ff_axes),
        "w_down": P(model_axis, ff_axes, None),
    }
    for extra in ("shared", "dense_res"):
        if extra in p:
            p_specs[extra] = jax.tree.map(lambda _: P(), p[extra])

    out, aux = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(p_specs, P()),        # tokens replicated everywhere
        out_specs=(P(), P()),
        check_vma=False,
    )(p, flat)
    return out.reshape(B, S, d), aux


def moe_ffn(p, cfg, x, mesh=None, model_axis: str = "model"):
    """(B, S, d) MoE FFN; expert-parallel over `model_axis` when mesh given."""
    B, S, d = x.shape
    flat = x.reshape(-1, d)

    if mesh is None or mesh.shape.get(model_axis, 1) == 1:
        out, aux = moe_ffn_local(p, cfg, flat)
        return out.reshape(B, S, d), aux

    if getattr(cfg, "moe_2d", False):
        return moe_ffn_2d(p, cfg, x, mesh, model_axis)

    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    n_shards = mesh.shape[model_axis]
    e_local = cfg.n_experts // n_shards
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    dp_size = 1
    for a in data_axes:
        dp_size *= mesh.shape[a]
    if flat.shape[0] % dp_size != 0:
        data_axes = ()  # e.g. decode with batch 1: replicate tokens

    def shard_fn(p_sh, x_sh):
        idx = jax.lax.axis_index(model_axis)
        # shared/dense-residual paths are replicated; run on shard 0 only
        p_experts = {k: v for k, v in p_sh.items() if k not in ("shared", "dense_res")}
        out, aux = moe_ffn_local(
            p_experts, cfg, x_sh,
            expert_slice=(idx * e_local, e_local),
            n_total_experts=cfg.n_experts,
        )
        out = jax.lax.psum(out, model_axis)
        # per-shard load-balance estimator averaged over the whole mesh (the
        # E*sum(f_e p_e) statistic is nonlinear in the token set, so this is
        # an estimator of — not identical to — the global-batch aux loss)
        aux = jax.lax.pmean(aux, mesh.axis_names)
        if "shared" in p_sh:
            out = out + dense_ffn(p_sh["shared"], cfg, x_sh)
        if "dense_res" in p_sh:
            out = out + dense_ffn(p_sh["dense_res"], cfg, x_sh)
        return out, aux

    expert_spec = P(model_axis)
    p_specs = {
        "router": P(),
        "w_gate": expert_spec, "w_up": expert_spec, "w_down": expert_spec,
    }
    for extra in ("shared", "dense_res"):
        if extra in p:
            p_specs[extra] = jax.tree.map(lambda _: P(), p[extra])

    out, aux = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(p_specs, P(data_axes if data_axes else None)),
        out_specs=(P(data_axes if data_axes else None), P()),
        check_vma=False,
    )(p, flat)
    return out.reshape(B, S, d), aux
