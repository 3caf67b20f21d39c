"""Mixture-of-Experts FFN: top-k router + capacity-bounded scatter dispatch.

TPU-native formulation (DESIGN.md §2): instead of the classic GShard
(T, E, C) one-hot dispatch tensor — O(T*E*C) memory, infeasible at 128
experts — we compute each token's *position within its expert* with a
(T, E) cumulative sum and scatter token activations into a dense
(E, C, d_model) buffer. Expert FFNs then run as one batched einsum whose
expert axis shards over the "model" mesh axis (expert parallelism); GSPMD
inserts the all-to-all at the scatter/gather boundaries.

Routing is performed *per batch row* so the routing math is fully
data-parallel (no cross-shard cumsum). Tokens overflowing the per-expert
capacity ``C = ceil(S * k / E * capacity_factor)`` are dropped (standard
capacity-factor semantics); the load-balance auxiliary loss keeps drops rare.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init
from repro.models.sharding import MODEL_AXIS, maybe_shard
from jax.sharding import PartitionSpec as P


def moe_init(key, cfg: ModelConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    s_in = (1.0 / d) ** 0.5
    s_out = (1.0 / f) ** 0.5
    return {
        "router": dense_init(k1, d, e),
        "w_gate": {"w": s_in * jax.random.normal(k2, (e, d, f), jnp.float32)},
        "w_up": {"w": s_in * jax.random.normal(k3, (e, d, f), jnp.float32)},
        "w_down": {"w": s_out * jax.random.normal(k4, (e, f, d), jnp.float32)},
    }


def _capacity(cfg: ModelConfig, s: int) -> int:
    c = int(s * cfg.n_experts_active / cfg.n_experts * cfg.capacity_factor)
    return max(c, cfg.n_experts_active)


def _route(params, cfg: ModelConfig, x):
    """Router + capacity bookkeeping. Returns (slot, top_p, keep, aux)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.n_experts_active
    C = _capacity(cfg, S)
    logits = jnp.einsum(
        "bsd,de->bse", x, params["router"]["w"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    probs = jax.nn.softmax(logits, axis=-1)                      # (B,S,E)
    top_p, top_e = jax.lax.top_k(probs, K)                       # (B,S,K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) assignment within its expert, per batch row
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)           # (B,S,K,E)
    flat = onehot.reshape(B, S * K, E)
    pos = jnp.cumsum(flat, axis=1) - 1                           # (B,S*K,E)
    pos_in_e = jnp.sum(pos * flat, axis=-1).reshape(B, S, K)     # (B,S,K)
    keep = pos_in_e < C
    slot = jnp.where(keep, top_e * C + pos_in_e, E * C)          # overflow slot

    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=(1, 2)
    ).mean(0)
    frac_probs = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_weight
    return slot, top_p, keep, aux, C


def moe_ffn(params, cfg: ModelConfig, x: jax.Array):
    """x: (B, S, d). Returns (out, aux_loss). Dispatches on cfg.moe_impl."""
    from repro.models.sharding import _active_mesh

    if cfg.moe_impl == "shardmap" and _active_mesh() is not None:
        return moe_ffn_shardmap(params, cfg, x)
    return moe_ffn_gspmd(params, cfg, x)


def moe_ffn_gspmd(params, cfg: ModelConfig, x: jax.Array):
    B, S, d = x.shape
    E, K, F = cfg.n_experts, cfg.n_experts_active, cfg.moe_d_ff
    slot, top_p, keep, aux, C = _route(params, cfg, x)

    def scatter_row(xr, slot_r):
        buf = jnp.zeros((E * C + 1, d), xr.dtype)
        src = jnp.repeat(xr, K, axis=0)                          # (S*K, d)
        return buf.at[slot_r.reshape(-1)].set(src)[: E * C]

    buffers = jax.vmap(scatter_row)(x, slot).reshape(B, E, C, d)
    buffers = maybe_shard(buffers, P(("pod", "data"), MODEL_AXIS, None, None))

    wg = params["w_gate"]["w"].astype(x.dtype)
    wu = params["w_up"]["w"].astype(x.dtype)
    wd = params["w_down"]["w"].astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buffers, wg)) * jnp.einsum(
        "becd,edf->becf", buffers, wu
    )
    h = maybe_shard(h, P(("pod", "data"), MODEL_AXIS, None, None))
    out_buf = jnp.einsum("becf,efd->becd", h, wd)                # (B,E,C,d)

    # gather back and combine with renormalized gate weights
    def gather_row(buf_r, slot_r):
        buf_flat = jnp.concatenate(
            [buf_r.reshape(E * C, d), jnp.zeros((1, d), buf_r.dtype)], axis=0
        )
        return buf_flat[slot_r]                                   # (S,K,d)

    gathered = jax.vmap(gather_row)(out_buf, slot)                # (B,S,K,d)
    w = (top_p * keep).astype(x.dtype)
    out = jnp.einsum("bskd,bsk->bsd", gathered, w)
    return out, aux


def moe_ffn_shardmap(params, cfg: ModelConfig, x: jax.Array):
    """Explicit per-model-shard expert schedule (EXPERIMENTS.md §Perf):

    The GSPMD path lets the partitioner place collectives around the scatter/
    gather dispatch; with seq-sharded activations and expert- or ff-sharded
    weights it chooses u32 index all-gathers and a full (B,E,C,d) fp32
    all-reduce per layer (~13 GB/device/layer at granite scale). Here the
    model axis is taken MANUAL: routing metadata is replicated (small), the
    token buffer is d-sharded so the dispatch scatter stays shard-local, the
    expert matmuls contract partial dims, and the cross-shard sums are
    explicit `psum_scatter`s (1/n of the all-reduce bytes).
    """
    from repro.models.sharding import _active_mesh

    B, S, d = x.shape
    E, K, F = cfg.n_experts, cfg.n_experts_active, cfg.moe_d_ff
    slot, top_p, keep, aux, C = _route(params, cfg, x)
    mesh = _active_mesh()

    def _rscatter(x_part, dim):
        """reduce-scatter along `dim` over the model axis.

        Expressed as psum + per-shard slice: XLA's collective-combiner
        rewrites this into reduce-scatter on TPU; the CPU host-device
        backend used for dry-runs crashes on an explicit tiled
        psum_scatter at 256+ devices (XLA bug), so we keep the
        pattern-matchable form. Collective-byte accounting treats the
        all-reduce as 2x reduce-scatter traffic (documented in
        EXPERIMENTS.md §Perf)."""
        n = jax.lax.axis_size(MODEL_AXIS)
        idx = jax.lax.axis_index(MODEL_AXIS)
        summed = jax.lax.psum(x_part, MODEL_AXIS)
        size = x_part.shape[dim] // n
        return jax.lax.dynamic_slice_in_dim(summed, idx * size, size, dim)

    def body(x_l, wg_l, wu_l, wd_l, slot_l, comb_l):
        # x_l: (B_loc, S, d) FULL d; wg_l/wu_l: (E, d, F/n); wd_l: (E, F/n, d)
        # Schedule: dispatch and the gate/up/act matmuls are fully local
        # (weights F-sharded, contractions unsharded); the only partial dim
        # is F in the down-projection, and its reduction is DEFERRED past
        # the (linear) gather+combine so the psum moves the (B,S,d) token
        # tensor, not the (B,E,C,d) expert buffer.
        Bl, dfull = x_l.shape[0], x_l.shape[-1]

        def scatter_row(xr, slot_r):
            buf = jnp.zeros((E * C + 1, dfull), xr.dtype)
            src = jnp.repeat(xr, K, axis=0)
            return buf.at[slot_r.reshape(-1)].set(src)[: E * C]

        buf = jax.vmap(scatter_row)(x_l, slot_l).reshape(Bl, E, C, dfull)
        g = jnp.einsum("becd,edf->becf", buf, wg_l)        # local, F/n
        u = jnp.einsum("becd,edf->becf", buf, wu_l)
        h = jax.nn.silu(g) * u                             # (B,E,C,F/n)
        out_part = jnp.einsum("becf,efd->becd", h, wd_l)   # partial over F

        def gather_row(buf_r, slot_r, comb_r):
            buf_flat = jnp.concatenate(
                [buf_r.reshape(E * C, -1),
                 jnp.zeros((1, buf_r.shape[-1]), buf_r.dtype)], axis=0)
            return jnp.einsum("skd,sk->sd", buf_flat[slot_r], comb_r)

        out_partial = jax.vmap(gather_row)(out_part, slot_l, comb_l)
        return _rscatter(out_partial, 2)                   # (B, S, d/n)

    comb = (top_p * keep).astype(x.dtype)
    wg = params["w_gate"]["w"].astype(x.dtype)
    wu = params["w_up"]["w"].astype(x.dtype)
    wd = params["w_down"]["w"].astype(x.dtype)
    # full-manual over every mesh axis (the partial-auto path crashes XLA's
    # CPU partitioner at 256+ host devices): batch over the data axes,
    # d / F over model, weights replicated across data inside the region.
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if batch_axes else None
    out = jax.shard_map(
        body,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(bspec, None, None),            # x full-d per shard
                  P(None, None, MODEL_AXIS),       # w_gate F-sharded
                  P(None, None, MODEL_AXIS),       # w_up F-sharded
                  P(None, MODEL_AXIS, None),       # w_down F-sharded
                  P(bspec, None, None),            # slot
                  P(bspec, None, None)),           # comb
        out_specs=P(bspec, None, MODEL_AXIS),
    )(x, wg, wu, wd, slot, comb)
    return out, aux
