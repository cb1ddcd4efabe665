"""Mixture-of-experts FFN: top-k token-choice routing with static capacity.

Two execution paths:

  * :func:`moe_ffn` — single-program formulation (global sort + capacity
    buckets).  Correct everywhere; used on CPU/tests and as the oracle.
    Under SPMD its token-expert dispatch tensors resist sharding
    propagation (measured: 618 GiB/device temp on the qwen3 prefill cell).
  * :func:`moe_ffn_ep` — the production expert-parallel path: an explicit
    ``shard_map`` where each device routes its LOCAL token shard, exchanges
    buckets with one ``all_to_all`` over the 'model' axis (experts live
    E/msize per device), runs its local experts, and reverses the exchange.
    FSDP'd expert weights are all-gathered over the data axes per layer
    inside the shard (ZeRO-3 semantics, grads reduce-scatter on the way
    back automatically).  Dispatch memory is O(local tokens), not O(global).

SEM note (DESIGN.md §4): top-k routing keeps only ``k/E`` of the expert
weights hot per token — the MoE analogue of "O(n) state in fast memory,
O(m) streamed on demand".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from .param import Mk

__all__ = ["init_moe", "moe_ffn", "moe_ffn_ep", "moe_capacity"]


def _shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (the EP body mixes
    pmean'd and sharded outputs)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def init_moe(mk: Mk, cfg: ModelConfig):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": mk.param((d, e), ("embed", "experts"), dtype=jnp.float32),
        "up": mk.param((e, d, ff), ("experts", "embed", "ffn")),
        "gate": mk.param((e, d, ff), ("experts", "embed", "ffn")),
        "down": mk.param((e, ff, d), ("experts", "ffn", "embed")),
    }


def moe_capacity(tokens: int, cfg: ModelConfig) -> int:
    cap = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-cap // 8) * 8)  # round up to 8


def _route(xf, router, cfg: ModelConfig, cap: int):
    """Shared routing: top-k -> expert-sorted capacity buckets.

    Returns (bucket [E, cap, d], dispatch indices for the inverse gather,
    gates, aux loss)."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [t, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # Load-balance auxiliary loss (Switch-style).
    density = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0
    )
    density_proxy = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * density_proxy)

    # ---- sort assignments by expert, compute slot within expert ----
    flat_e = expert_idx.reshape(-1)  # [t*k]
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e)
    se, stok, sgate = flat_e[order], flat_tok[order], flat_gate[order]
    # position within expert = index - start of that expert's run
    counts = jnp.bincount(se, length=e)
    starts = jnp.cumsum(counts) - counts
    slot = jnp.arange(t * k, dtype=jnp.int32) - starts[se]
    keep = slot < cap

    se_c = jnp.where(keep, se, 0)
    slot_c = jnp.where(keep, slot, cap - 1)
    vals = jnp.where(keep[:, None], xf[stok], 0)
    bucket = jnp.zeros((e, cap, d), xf.dtype).at[se_c, slot_c].add(vals)
    return bucket, (se_c, slot_c, stok, keep, sgate), aux


def _unroute(out, dispatch, t: int, d: int, dtype):
    se_c, slot_c, stok, keep, sgate = dispatch
    tok_out = out[se_c, slot_c] * jnp.where(keep, sgate, 0.0)[:, None].astype(dtype)
    return jnp.zeros((t, d), dtype).at[stok].add(tok_out)


def moe_ffn(p, x: jnp.ndarray, cfg: ModelConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (y: [B, S, d], aux_loss: scalar load-balance loss)."""
    b, s, d = x.shape
    t = b * s
    cap = moe_capacity(t, cfg)
    xf = x.reshape(t, d)
    bucket, dispatch, aux = _route(xf, p["router"], cfg, cap)

    # ---- expert FFN (einsum over the experts axis) ----
    up = jnp.einsum("ecd,edf->ecf", bucket, p["up"])
    gate = jnp.einsum("ecd,edf->ecf", bucket, p["gate"])
    h = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", h, p["down"])

    y = _unroute(out, dispatch, t, d, x.dtype)
    return y.reshape(b, s, d), aux


def moe_ffn_ep(
    p, x: jnp.ndarray, cfg: ModelConfig, mesh
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map (see module docstring).

    Token shards route locally; ONE all_to_all over 'model' exchanges
    capacity buckets into the expert-parallel layout and one inverts it.
    """
    from ..distributed.sharding import data_axes

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    msize = int(mesh.shape.get("model", 1))
    dp = data_axes(mesh)
    dpe = dp if len(dp) > 1 else (dp[0] if dp else None)
    dsize = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if e % msize or (dsize > 1 and b % dsize) or (s > 1 and s % msize):
        return moe_ffn(p, x, cfg)  # topology doesn't divide: dense fallback
    e_loc = e // msize
    seq_shard = s % msize == 0 and s > 1
    # serving (decode: s == 1): experts stay RESIDENT in a 2D layout
    # (experts x model, ffn x data — a 235B MoE cannot replicate over the
    # data axes), decode tokens are replicated over data (a few MB) and
    # the ffn-partial down-projection psums over the data axes.
    serving = s == 1
    x_spec = (
        P(None, None, None)
        if serving
        else P(dpe, "model" if seq_shard else None, None)
    )
    t_loc = (
        b if serving else (b // dsize) * (s // msize if seq_shard else s)
    )
    cap = moe_capacity(t_loc, cfg)
    all_axes = tuple(mesh.axis_names)

    def local(xl, router, up, gate, down):
        b_l, s_l, _ = xl.shape
        t_l = b_l * s_l
        xf = xl.reshape(t_l, d)
        bucket, dispatch, aux = _route(xf, router, cfg, cap)
        aux = jax.lax.pmean(aux, all_axes)

        if dp and not serving:
            # ZeRO-3: gather the FSDP'd d_model dim of the local experts
            up_g = jax.lax.all_gather(up, dp, axis=1, tiled=True)
            gate_g = jax.lax.all_gather(gate, dp, axis=1, tiled=True)
            down_g = jax.lax.all_gather(down, dp, axis=2, tiled=True)
        else:
            up_g, gate_g, down_g = up, gate, down

        # dispatch: experts are contiguous in the bucket, so peer j's
        # experts are rows [j*e_loc, (j+1)*e_loc)
        if msize > 1:
            recv = jax.lax.all_to_all(
                bucket, "model", split_axis=0, concat_axis=1, tiled=True
            )  # [e_loc, msize*cap, d]
        else:
            recv = bucket
        u = jnp.einsum("ecd,edf->ecf", recv, up_g)
        g = jnp.einsum("ecd,edf->ecf", recv, gate_g)
        h = jax.nn.silu(g) * u  # serving: h holds the LOCAL ffn slice
        out = jnp.einsum("ecf,efd->ecd", h, down_g)
        if serving and dp:
            out = jax.lax.psum(out, dp)  # sum ffn-slice partials
        if msize > 1:
            out = jax.lax.all_to_all(
                out, "model", split_axis=1, concat_axis=0, tiled=True
            )  # back to [E, cap, d]
        y = _unroute(out, dispatch, t_l, d, xl.dtype)
        return y.reshape(b_l, s_l, d), aux

    if serving:
        w_specs = (
            P("model", None, dpe),  # up   [E, d, ff] — ffn x data
            P("model", None, dpe),  # gate
            P("model", dpe, None),  # down [E, ff, d]
        )
    else:
        w_specs = (
            P("model", dpe, None),  # up: d_model FSDP'd
            P("model", dpe, None),
            P("model", None, dpe),
        )
    return _shard_map(
        local,
        mesh=mesh,
        in_specs=(x_spec, P(None, None)) + w_specs,
        out_specs=(x_spec, P()),
    )(x, p["router"].astype(jnp.float32), p["up"], p["gate"], p["down"])


def moe_apply(p, x, cfg: ModelConfig):
    """Dispatch: EP shard_map under a mesh scope, dense path otherwise."""
    from .shard_ctx import current_mesh

    mesh = current_mesh()
    if mesh is not None and int(mesh.shape.get("model", 1)) > 1:
        return moe_ffn_ep(p, x, cfg, mesh)
    return moe_ffn(p, x, cfg)
