"""Model assembly: stages of lax.scan'ed layer periods + train/prefill/decode.

A model = embed (or stub-frontend projection) -> stages -> final norm -> head.
Each stage scans over `n_periods` stacked copies of its `block_pattern`
(DESIGN.md §6); block kinds: attn | attn_local | mamba | rwkv. FFN per layer
is dense or MoE (statically known per pattern position; requires
pattern_len % moe_every == 0).

Three entry points, all pure and jit/pjit-able:
  * loss_fn(params, batch, key)                -> scalar   (training)
  * prefill(params, tokens/embeds)             -> (logits, cache)
  * decode_step(params, token, pos, cache)     -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mamba as mam
from . import moe as moe_mod
from . import rwkv as rwk
from .config import ModelConfig
from .layers import cross_entropy, dense_init, rms_norm, softcap


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, kind: str, is_moe: bool):
    dt = _dtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln": jnp.zeros((d,), dt)}
    if kind.startswith("attn"):
        p["attn"] = (
            attn.init_mla_params(ks[0], cfg, dt)
            if cfg.use_mla
            else attn.init_attn_params(ks[0], cfg, dt)
        )
    elif kind == "mamba":
        p["mamba"] = mam.init_mamba_params(ks[0], cfg, dt)
    elif kind == "rwkv":
        p["rwkv"] = rwk.init_rwkv_params(ks[0], cfg, dt)
    else:
        raise ValueError(kind)
    if cfg.post_norm:
        p["post_ln"] = jnp.zeros((d,), dt)
    p["ffn_ln"] = jnp.zeros((d,), dt)
    if kind != "rwkv":  # rwkv channel-mix lives in its own params
        p["ffn"] = (
            moe_mod.init_moe_params(ks[1], cfg, dt)
            if is_moe
            else moe_mod.init_dense_ffn(ks[1], cfg, dt)
        )
        if cfg.post_norm:
            p["post_ffn_ln"] = jnp.zeros((d,), dt)
    return p


def _stage_layout(cfg: ModelConfig):
    """[(stage_name, n_periods, [(kind, is_moe) per pattern pos])]."""
    out = []
    offset = 0
    for name, n_periods, moe_on in cfg.stages():
        pat = []
        for j, kind in enumerate(cfg.block_pattern):
            is_moe = moe_on and cfg.is_moe_layer(offset + j) and kind != "rwkv"
            pat.append((kind, is_moe))
        if cfg.n_experts and moe_on:
            # static pattern requires alignment of moe_every with pattern
            assert cfg.pattern_len % cfg.moe_every == 0 or cfg.moe_every == 1
        out.append((name, n_periods, pat))
        offset += n_periods * cfg.pattern_len
    return out


def init_params(key: jax.Array, cfg: ModelConfig):
    dt = _dtype(cfg)
    d = cfg.d_model
    k_embed, k_head, k_front, k_stages = jax.random.split(key, 4)
    params: dict[str, Any] = {
        "embed": dense_init(k_embed, (cfg.vocab, d), scale=0.02, dtype=dt),
        "final_ln": jnp.zeros((d,), dt),
    }
    if not cfg.tie_embeddings:
        out_dim = cfg.n_classes if cfg.arch_type == "audio" else cfg.vocab
        params["head"] = dense_init(k_head, (d, out_dim), dtype=dt)
    if cfg.frontend is not None:
        params["frontend_proj"] = dense_init(k_front, (cfg.frontend_dim, d), dtype=dt)

    stages = {}
    for si, (name, n_periods, pat) in enumerate(_stage_layout(cfg)):
        k_stage = jax.random.fold_in(k_stages, si)

        def init_period(k):
            kb = jax.random.split(k, len(pat))
            return {
                f"b{j}": _init_block(kb[j], cfg, kind, is_moe)
                for j, (kind, is_moe) in enumerate(pat)
            }

        stages[name] = jax.vmap(init_period)(jax.random.split(k_stage, n_periods))
    params["stages"] = stages
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _block_window(cfg, kind):
    return cfg.sliding_window if kind == "attn_local" else None


def _apply_block(p, cfg, kind, is_moe, x, positions, mesh, use_kernel):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    if kind.startswith("attn"):
        if cfg.use_mla:
            inner = attn.mla_forward(p["attn"], cfg, h, positions)
        else:
            inner = attn.gqa_forward(
                p["attn"], cfg, h, positions,
                window=_block_window(cfg, kind), use_kernel=use_kernel,
            )
        aux = 0.0
    elif kind == "mamba":
        st = mam.init_mamba_state(cfg, x.shape[0], x.dtype)
        inner, _ = mam.mamba_forward(p["mamba"], cfg, h, st)
        aux = 0.0
    else:  # rwkv
        st = rwk.init_rwkv_state(cfg, x.shape[0], x.dtype)
        inner, _ = rwk.time_mix(p["rwkv"], cfg, h, st)
        aux = 0.0
    if cfg.post_norm:
        inner = rms_norm(inner, p["post_ln"], cfg.norm_eps)
    x = x + inner

    if kind == "rwkv":
        st = rwk.init_rwkv_state(cfg, x.shape[0], x.dtype)
        h = rms_norm(x, p["ffn_ln"], cfg.norm_eps)
        out, _ = rwk.channel_mix(p["rwkv"], cfg, h, st)
        return x + out, aux

    h = rms_norm(x, p["ffn_ln"], cfg.norm_eps)
    if is_moe:
        out, moe_aux = moe_mod.moe_ffn(p["ffn"], cfg, h, mesh=mesh)
        aux = aux + moe_aux
    else:
        out = moe_mod.dense_ffn(p["ffn"], cfg, h)
    if cfg.post_norm:
        out = rms_norm(out, p["post_ffn_ln"], cfg.norm_eps)
    return x + out, aux


def _constrain_residual(x, mesh):
    """Keep the (B, S, d) residual stream replicated over 'model': embed
    output is d-sharded, and without the hint GSPMD re-gathers 1 GB f32
    activations around every block (§Perf)."""
    if mesh is None or "model" not in mesh.axis_names:
        return x
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import constrain, data_axes

    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    bspec = dp if (x.shape[0] % dp_size == 0 and dp) else None
    return constrain(x, mesh, P(bspec, None, None))


def _run_stages(params, cfg, x, positions, mesh, use_kernel, remat=True):
    total_aux = 0.0
    x = _constrain_residual(x, mesh)
    for name, n_periods, pat in _stage_layout(cfg):
        stage_params = params["stages"][name]

        def period_fn(x, p_period):
            aux = 0.0
            for j, (kind, is_moe) in enumerate(pat):
                x, a = _apply_block(
                    p_period[f"b{j}"], cfg, kind, is_moe, x, positions, mesh, use_kernel
                )
                aux = aux + a
            return _constrain_residual(x, mesh), aux

        if remat:
            period_fn = jax.checkpoint(period_fn)

        x, auxs = jax.lax.scan(lambda c, p: period_fn(c, p), x, stage_params)
        total_aux = total_aux + jnp.sum(auxs)
    return x, total_aux


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def _embed(params, cfg, batch):
    if cfg.frontend == "audio":
        x = jnp.einsum("bsf,fd->bsd", batch["frame_embeds"], params["frontend_proj"])
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        return x.astype(_dtype(cfg)), positions
    tokens = batch["tokens"]
    x = params["embed"][tokens]
    if cfg.frontend == "vision":
        pe = jnp.einsum("bpf,fd->bpd", batch["patch_embeds"], params["frontend_proj"])
        n_patch = pe.shape[1]
        x = jnp.concatenate([pe.astype(x.dtype), x[:, n_patch:]], axis=1)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    return x, positions


def _head(params, cfg, x, mesh=None):
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    if mesh is not None and "model" in mesh.axis_names:
        # keep the (B, S, V) logits vocab-sharded — replicated 256k-vocab
        # logits would be tens of GiB per device (DESIGN.md §6)
        from repro.parallel.sharding import batch_spec, constrain
        from jax.sharding import PartitionSpec as P

        dp = tuple(batch_spec(mesh))[0] if logits.shape[0] % _dp_size(mesh) == 0 else None
        logits = constrain(logits, mesh, P(dp, None, "model"))
    if cfg.final_softcap:
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits


def _dp_size(mesh):
    n = 1
    for a in mesh.axis_names:
        if a in ("pod", "data"):
            n *= mesh.shape[a]
    return n


def forward(params, cfg: ModelConfig, batch, mesh=None, use_kernel=False, remat=True):
    x, positions = _embed(params, cfg, batch)
    x, aux = _run_stages(params, cfg, x, positions, mesh, use_kernel, remat)
    return _head(params, cfg, x, mesh=mesh), aux


def loss_fn(params, cfg: ModelConfig, batch, mesh=None, use_kernel=False, remat=True):
    logits, aux = forward(params, cfg, batch, mesh, use_kernel, remat)
    mask = batch.get("mask") if cfg.arch_type == "audio" else None
    if mesh is not None and "model" in mesh.axis_names and logits.shape[-1] % mesh.shape["model"] == 0:
        loss = _sharded_cross_entropy(logits, batch["labels"], mesh, mask)
    else:
        loss = cross_entropy(logits, batch["labels"], mask=mask)
    if cfg.n_experts:
        loss = loss + 0.01 * aux / max(cfg.n_layers, 1)
    return loss


def _sharded_cross_entropy(logits, labels, mesh, mask=None):
    """CE with the vocab axis kept sharded end-to-end (shard_map + psum).

    The plain jnp CE on (dp, None, 'model')-sharded logits makes GSPMD
    all-gather AND all-reduce the full f32 (B, S, V) tensor (67 GB/device for
    gemma2's 256k vocab at train_4k) — measured in §Perf. Here each vocab
    shard reduces locally; only (B, S) statistics cross the link.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import batch_spec, data_axes

    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    bspec = dp if (logits.shape[0] % dp_size == 0 and dp) else None

    v_local = logits.shape[-1] // mesh.shape["model"]
    # the max shift is numerical stability only; computed outside the
    # shard_map (pmax has no differentiation rule, even under stop_gradient
    # its jvp is traced)
    m_global = jax.lax.stop_gradient(jnp.max(logits.astype(jnp.float32), -1))

    def shard_fn(lg, lb, mk, m):
        lg = lg.astype(jnp.float32)
        shard = jax.lax.axis_index("model")
        sumexp = jax.lax.psum(jnp.sum(jnp.exp(lg - m[..., None]), -1), "model")
        logz = m + jnp.log(sumexp)
        local = lb - shard * v_local
        in_shard = (local >= 0) & (local < v_local)
        onehot = jax.nn.one_hot(jnp.where(in_shard, local, 0), v_local, dtype=lg.dtype)
        gold = jax.lax.psum(
            jnp.sum(lg * onehot, -1) * in_shard.astype(lg.dtype), "model"
        )
        nll = logz - gold
        valid = mk & (lb >= 0)
        nll = jnp.where(valid, nll, 0.0)
        return (
            jax.lax.psum(jnp.sum(nll), dp) if dp else jnp.sum(nll),
            jax.lax.psum(jnp.sum(valid), dp) if dp else jnp.sum(valid),
        )

    if mask is None:
        mask_in = jnp.ones(labels.shape, jnp.bool_)
    else:
        mask_in = mask
    total, count = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(
            P(bspec, None, "model"), P(bspec, None), P(bspec, None),
            P(bspec, None),
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )(logits, labels, mask_in, m_global)
    return total / jnp.maximum(count, 1)


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    dt = _dtype(cfg)
    caches = {}
    for name, n_periods, pat in _stage_layout(cfg):
        def one_period(_):
            c = {}
            for j, (kind, _m) in enumerate(pat):
                if kind.startswith("attn"):
                    if cfg.use_mla:
                        c[f"b{j}"] = attn.init_mla_cache(cfg, batch, max_len, dt)
                    else:
                        c[f"b{j}"] = attn.init_kv_cache(
                            cfg, batch, max_len, _block_window(cfg, kind), dt
                        )
                elif kind == "mamba":
                    c[f"b{j}"] = mam.init_mamba_state(cfg, batch, dt)
                else:
                    c[f"b{j}"] = rwk.init_rwkv_state(cfg, batch, dt)
            return c

        caches[name] = jax.vmap(one_period)(jnp.arange(n_periods))
    return caches


def _decode_block(p, c, cfg, kind, is_moe, x, pos, mesh):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    if kind.startswith("attn"):
        if cfg.use_mla:
            inner, c = attn.mla_decode(p["attn"], cfg, h, pos, c)
        else:
            inner, c = attn.gqa_decode(
                p["attn"], cfg, h, pos, c, window=_block_window(cfg, kind)
            )
    elif kind == "mamba":
        inner, c = mam.mamba_forward(p["mamba"], cfg, h, c)
    else:
        inner, c_t = rwk.time_mix(p["rwkv"], cfg, h, c)
        c = {**c, **c_t}
    if cfg.post_norm:
        inner = rms_norm(inner, p["post_ln"], cfg.norm_eps)
    x = x + inner

    if kind == "rwkv":
        h = rms_norm(x, p["ffn_ln"], cfg.norm_eps)
        out, c2 = rwk.channel_mix(p["rwkv"], cfg, h, c)
        c = {**c, **c2}
        return x + out, c
    h = rms_norm(x, p["ffn_ln"], cfg.norm_eps)
    if is_moe:
        out, _ = moe_mod.moe_ffn(p["ffn"], cfg, h, mesh=mesh)
    else:
        out = moe_mod.dense_ffn(p["ffn"], cfg, h)
    if cfg.post_norm:
        out = rms_norm(out, p["post_ffn_ln"], cfg.norm_eps)
    return x + out, c


def decode_step(params, cfg: ModelConfig, token, pos, cache, mesh=None):
    """token: (B, 1) int32 (or (B,1,frontend) for audio); pos scalar int32."""
    cache = dict(cache)
    x = params["embed"][token]
    for name, n_periods, pat in _stage_layout(cfg):
        def period_fn(x, xs):
            p_period, c_period = xs
            new_c = {}
            for j, (kind, is_moe) in enumerate(pat):
                x, cj = _decode_block(
                    p_period[f"b{j}"], c_period[f"b{j}"], cfg, kind, is_moe,
                    x, pos, mesh,
                )
                new_c[f"b{j}"] = cj
            return x, new_c

        x, cache[name] = jax.lax.scan(
            period_fn, x, (params["stages"][name], cache[name])
        )
    logits = _head(params, cfg, x, mesh=mesh)
    return logits, cache


def prefill(params, cfg: ModelConfig, batch, mesh=None, use_kernel=False):
    """Full-sequence forward returning logits (cache build is exercised by the
    decode path; serving benchmarks measure prefill logits + decode steps)."""
    return forward(params, cfg, batch, mesh=mesh, use_kernel=use_kernel, remat=False)[0]
