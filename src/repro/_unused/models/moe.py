"""Mixture-of-Experts FFN with sort-based (fake-FLOP-free) dispatch.

The classic GShard dense-dispatch einsum costs O(tokens · E · capacity · d)
matmul FLOPs just to *move* tokens — for DeepSeek-V2's 160 experts that is
an order of magnitude more compute than the experts themselves. We instead
route with sort + static-capacity scatter/gather (MegaBlocks-style, adapted
to XLA's static shapes):

  1. top-k per token → (expert_id, weight) pairs, flattened to S·k entries;
  2. entries sorted by expert id (XLA row-wise sort — batch rows stay local
     to their data shard, so the sort never crosses devices);
  3. rank-in-expert = position − start-of-expert (via per-row searchsorted);
     entries with rank ≥ capacity are dropped (capacity_factor bounds skew);
  4. scatter token vectors into an (E, C, d) buffer → batched expert SwiGLU
     einsum → gather back with routing weights.

Expert weights are sharded expert-hidden over the `model` axis (always
divisible, unlike E itself) and FSDP over `data`; token buffers stay
data-sharded end to end. Shared experts (DeepSeek) run as a dense FFN.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.sharding.rules import shard
from .layers import cdtype, dense_init, pdtype

__all__ = ["moe_init", "moe_axes", "moe_apply", "moe_capacity"]


def moe_capacity(cfg: ModelConfig, seq_len: int) -> int:
    """Static per-expert capacity for one routing group (= one sequence)."""
    c = int(np.ceil(cfg.capacity_factor * seq_len * cfg.top_k / cfg.n_experts))
    return min(max(c, cfg.top_k), seq_len)


def moe_init(key, cfg: ModelConfig):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": {"w": jax.random.normal(ks[0], (d, E), dtype=jnp.float32) * scale},
        "wi": jax.random.normal(ks[1], (E, d, f), dtype=pdtype(cfg)) * scale,
        "wg": jax.random.normal(ks[2], (E, d, f), dtype=pdtype(cfg)) * scale,
        "wo": jax.random.normal(ks[3], (E, f, d), dtype=pdtype(cfg)) * (1.0 / np.sqrt(f)),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        p["shared"] = {
            "wi": dense_init(ks[4], d, (fs,), cfg),
            "wg": dense_init(ks[4], d, (fs,), cfg),
            "wo": dense_init(ks[4], fs, (d,), cfg),
        }
    return p


def moe_axes(cfg: ModelConfig):
    a = {
        "router": {"w": ("fsdp", None)},
        "wi": ("experts", "fsdp", "expert_mlp"),
        "wg": ("experts", "fsdp", "expert_mlp"),
        "wo": ("experts", "expert_mlp", "fsdp"),
    }
    if cfg.n_shared_experts:
        a["shared"] = {
            "wi": {"w": ("fsdp", "mlp")},
            "wg": {"w": ("fsdp", "mlp")},
            "wo": {"w": ("mlp", "fsdp")},
        }
    return a


def _ep_enabled(cfg: ModelConfig) -> str | None:
    """Returns the mesh axis for expert parallelism if usable, else None."""
    from repro.sharding.rules import current_rules

    r = current_rules()
    if r is None:
        return None
    ax = r.rules.get("experts")
    if isinstance(ax, tuple):
        ax = ax[0] if ax else None
    if ax is None or ax not in r.mesh.axis_names:
        return None
    if cfg.n_experts % r.mesh.shape[ax] != 0:
        return None
    return ax


def moe_apply(p, x: jnp.ndarray, cfg: ModelConfig):
    """Dispatch to the shard_map EP path when experts divide the `model`
    axis (deepseek 160, jamba 16 on a 16-way axis); otherwise the pjit
    dense path (hidden-dim TP — mixtral's 8 experts)."""
    ep_axis = _ep_enabled(cfg)
    if ep_axis is not None:
        return _moe_apply_ep(p, x, cfg, ep_axis)
    return _moe_apply_dense(p, x, cfg)


def _moe_apply_dense(p, x: jnp.ndarray, cfg: ModelConfig):
    """x (B, S, d) → (B, S, d). Routing groups = batch rows (data-local)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = moe_capacity(cfg, S)
    dtype = cdtype(cfg)

    # ---- routing -----------------------------------------------------------------
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)  # (B,S,k)
    if cfg.renorm_topk:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- sort entries by expert (per batch row) ------------------------------------
    ids_f = ids.reshape(B, S * k)
    tok_f = jnp.broadcast_to(jnp.arange(S)[None, :, None], (B, S, k)).reshape(B, S * k)
    gate_f = gate.reshape(B, S * k)
    order = jnp.argsort(ids_f, axis=-1)  # stable
    ids_s = jnp.take_along_axis(ids_f, order, axis=-1)
    tok_s = jnp.take_along_axis(tok_f, order, axis=-1)
    gate_s = jnp.take_along_axis(gate_f, order, axis=-1)

    # rank within expert = position − first-occurrence(expert)
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(E), side="left"))(ids_s)
    rank = jnp.arange(S * k)[None, :] - jnp.take_along_axis(starts, ids_s, axis=-1)
    keep = rank < C
    dest = jnp.where(keep, ids_s * C + rank, E * C)  # drop → overflow slot

    # ---- dispatch: scatter tokens into (B, E·C+1, d) --------------------------------
    xt = jnp.take_along_axis(x, tok_s[..., None], axis=1)  # (B, S·k, d)
    buf = jnp.zeros((B, E * C + 1, d), dtype)
    buf = buf.at[jnp.arange(B)[:, None], dest].set(xt.astype(dtype), mode="drop")
    buf = buf[:, : E * C].reshape(B, E, C, d)
    buf = shard(buf, ("batch", "experts", None, None))

    # ---- expert computation (SwiGLU), hidden dim tensor-parallel --------------------
    wi, wg, wo = (p[n].astype(dtype) for n in ("wi", "wg", "wo"))
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, wg)) * jnp.einsum("becd,edf->becf", buf, wi)
    h = shard(h, ("batch", "experts", None, "expert_mlp"))
    y = jnp.einsum("becf,efd->becd", h, wo)  # (B,E,C,d)
    y = shard(y, ("batch", "experts", None, None))

    # ---- combine: gather back and weight ---------------------------------------------
    y_flat = jnp.concatenate([y.reshape(B, E * C, d), jnp.zeros((B, 1, d), dtype)], axis=1)
    out_e = y_flat[jnp.arange(B)[:, None], dest]  # (B, S·k, d); dropped → 0
    out_e = out_e * gate_s[..., None].astype(dtype)
    # scatter-add back to token positions
    out = jnp.zeros((B, S, d), dtype)
    out = out.at[jnp.arange(B)[:, None], tok_s].add(out_e)

    # ---- shared experts (dense path) ---------------------------------------------------
    out = _add_shared(p, x, out, cfg)
    return out.astype(x.dtype)


def _add_shared(p, x, out, cfg):
    if "shared" in p:
        dtype = cdtype(cfg)
        sh = p["shared"]
        hsh = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, sh["wg"]["w"].astype(dtype)))
        hsh = hsh * jnp.einsum("bsd,df->bsf", x, sh["wi"]["w"].astype(dtype))
        hsh = shard(hsh, ("batch", None, "mlp"))
        out = out + jnp.einsum("bsf,fd->bsd", hsh, sh["wo"]["w"].astype(dtype))
    return out


def _moe_apply_ep(p, x: jnp.ndarray, cfg: ModelConfig, ep_axis: str):
    """Expert-parallel MoE via shard_map (the beyond-paper §Perf optimization).

    Experts stay sharded over ``ep_axis`` for their whole life — no FSDP
    all-gather of inactive expert weights (the dominant collective cost of
    FSDP-MoE: DeepSeek-V2 would otherwise gather 236B params/pass when only
    21B are active). Activations are already replicated across `model`
    inside a data shard, so dispatch is purely local:

      each model-shard computes the routed contribution of ITS E/ep experts
      over the local tokens → one psum over `model` combines.

    Collective cost per MoE layer: one (B_loc·S·d) psum — independent of E.
    Expert weights are FSDP-sharded on d and gathered bf16 per layer
    (E/ep-th of the naive FSDP gather).
    """
    from repro.sharding.rules import current_rules

    rules = current_rules()
    mesh = rules.mesh
    fsdp_ax = rules.rules.get("fsdp")
    if isinstance(fsdp_ax, tuple):
        fsdp_ax = fsdp_ax[0] if fsdp_ax else None

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    dtype = cdtype(cfg)
    P = jax.sharding.PartitionSpec

    x_spec = rules.spec(("batch", None, None), shape=x.shape)
    wi_spec = P(ep_axis, fsdp_ax, None)
    wo_spec = P(ep_axis, None, fsdp_ax)

    def body(xl, rw, wi, wg, wo):
        # xl (B_loc, S, d) — identical on every ep shard; w* (E_loc, ·, ·)
        E_loc = wi.shape[0]
        m_idx = jax.lax.axis_index(ep_axis)
        if fsdp_ax is not None:
            wi = jax.lax.all_gather(wi.astype(dtype), fsdp_ax, axis=1, tiled=True)
            wg = jax.lax.all_gather(wg.astype(dtype), fsdp_ax, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo.astype(dtype), fsdp_ax, axis=2, tiled=True)
        else:
            wi, wg, wo = wi.astype(dtype), wg.astype(dtype), wo.astype(dtype)

        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        C = int(np.ceil(cfg.capacity_factor * T * k / E))
        C = max(min(C, T), 1)

        logits = jnp.einsum("bsd,de->bse", xl.astype(jnp.float32), rw)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, ids = jax.lax.top_k(probs, k)
        if cfg.renorm_topk:
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        xt = xl.reshape(T, d)
        ids_f = ids.reshape(T * k)
        tok_f = jnp.repeat(jnp.arange(T), k)
        gate_f = gate.reshape(T * k)
        order = jnp.argsort(ids_f)
        ids_s, tok_s, gate_s = ids_f[order], tok_f[order], gate_f[order]
        starts = jnp.searchsorted(ids_s, jnp.arange(E), side="left")
        rank = jnp.arange(T * k) - starts[ids_s]
        keep = rank < C
        # slots of THIS shard's experts only
        dest = ids_s * C + rank - m_idx * E_loc * C
        valid = keep & (dest >= 0) & (dest < E_loc * C)
        dest = jnp.where(valid, dest, E_loc * C)

        buf = jnp.zeros((E_loc * C + 1, d), dtype).at[dest].set(
            xt[tok_s].astype(dtype), mode="drop"
        )
        buf = buf[: E_loc * C].reshape(E_loc, C, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
            "ecd,edf->ecf", buf, wi
        )
        y = jnp.einsum("ecf,efd->ecd", h, wo).reshape(E_loc * C, d)
        y = jnp.concatenate([y, jnp.zeros((1, d), dtype)], axis=0)
        contrib = y[dest] * (gate_s * valid)[:, None].astype(dtype)
        out = jnp.zeros((T, d), dtype).at[tok_s].add(contrib)
        out = jax.lax.psum(out, ep_axis)
        return out.reshape(Bl, Sl, d)

    routed = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, P(), wi_spec, wi_spec, wo_spec),
        out_specs=x_spec,
        check_vma=False,
    )(x, p["router"]["w"], p["wi"], p["wg"], p["wo"])

    routed = _add_shared(p, x, routed, cfg)
    return routed.astype(x.dtype)
