"""Mixture-of-Experts block: top-k routing over every expert, then the
experts this model holds.

Without a mesh (serving, and every single-device forward) the layer is
dropless (``_held_experts``): the router's softmax spans all ``n_experts``,
each token keeps its ``top_k`` picks with their gates renormalised, and
every pick that lands on a held expert (ids ``[expert_offset,
expert_offset + n_experts_held)``) is computed, through grouped matmuls
over the picks sorted by expert.  Picks on experts held elsewhere add
nothing here: on one chip of an expert-parallel deployment this is the
chip's share of the layer, and the exchange that would sum the shares is
not part of this program.  No pick is ever dropped, so a token's output
does not depend on its batchmates.

The capacity-bucketed dispatch below (``_dispatch``, ``_combine``,
``_capacity``) serves only the sharded training path, ``_moe_sharded``,
in two sharding regimes selected by expert-count divisibility
(DESIGN.md §5):

* **EP** (arctic: 128 experts % 16 == 0): expert weights sharded over the
  ``model`` axis.  Activations arriving at the block are replicated over
  ``model`` (the TP convention between blocks), so each model shard gathers
  *its own* experts' tokens locally — dispatch needs **no collective at
  all**; only the combine is a psum over ``model`` (the same all-reduce a
  TP MLP needs).  This is implemented with ``shard_map`` for explicit,
  predictable lowering.

* **TP** (mixtral: 8 experts < 16 shards): every shard holds all experts
  with the FFN dim sliced over ``model``; dispatch is local, combine is the
  usual TP psum.

There, dispatch is a capacity-bucketed scatter: O(E·C·d) memory, never
the (T, E, C) one-hot tensor.  Tokens overflowing an expert's capacity
fall through to the residual path (standard Switch/GShard semantics).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers


def init_moe_params(key: jax.Array, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    """The router over all ``n_experts``; the weights of the held ones."""
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts_held
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], (d, cfg.n_experts), jnp.float32,
                                    scale=0.02),
        "w_gate": layers.dense_init(ks[1], (E, d, f), dtype),
        "w_up": layers.dense_init(ks[2], (E, d, f), dtype),
        "w_down": layers.dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.moe_dense_residual:
        fr = cfg.dense_residual_ff or f
        kd = jax.random.split(ks[4], 3)
        p["res_gate"] = layers.dense_init(kd[0], (d, fr), dtype)
        p["res_up"] = layers.dense_init(kd[1], (d, fr), dtype)
        p["res_down"] = layers.dense_init(kd[2], (fr, d), dtype)
    return p


def _route(x: jax.Array, router_w: jax.Array, top_k: int):
    """x: (T, d) -> (gates (T,k) fp32, experts (T,k) int32, aux_loss)."""
    logits = jnp.einsum(
        "td,de->te", x.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss
    E = router_w.shape[-1]
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.float32), axis=1), axis=0
    )
    aux = E * jnp.sum(me * ce)
    return gates, experts, aux


def _dispatch(x, gates, experts, e_offset: int, e_loc: int, capacity: int):
    """Capacity-bucketed scatter. Returns (buf (E_loc,C,d), slot, token_idx,
    combine_w)."""
    T, d = x.shape
    k = gates.shape[1]
    flat_e = experts.reshape(-1) - e_offset                       # (T*k,)
    mine = (flat_e >= 0) & (flat_e < e_loc)
    flat_e = jnp.where(mine, flat_e, 0)
    # rank of each assignment within its expert (token-major order)
    onehot = jax.nn.one_hot(flat_e, e_loc, dtype=jnp.int32) * mine[:, None].astype(jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot                   # exclusive
    rank = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
    keep = mine & (rank < capacity)
    slot = jnp.where(keep, flat_e * capacity + rank, e_loc * capacity)  # overflow row

    token_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    buf = jnp.zeros((e_loc * capacity + 1, d), x.dtype)
    buf = buf.at[slot].add(
        x[token_idx] * keep[:, None].astype(x.dtype), mode="drop"
    )
    buf = buf[: e_loc * capacity].reshape(e_loc, capacity, d)
    combine_w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(x.dtype)
    return buf, slot, token_idx, combine_w


def _combine(y, slot, token_idx, combine_w, T: int):
    """Weighted gather back to token order. y: (E_loc, C, d)."""
    e_loc, capacity, d = y.shape
    y_flat = jnp.concatenate(
        [y.reshape(e_loc * capacity, d), jnp.zeros((1, d), y.dtype)], axis=0
    )
    picked = y_flat[slot]                                         # (T*k, d)
    return jnp.zeros((T, d), y.dtype).at[token_idx].add(
        picked * combine_w[:, None]
    )


def _expert_ffn(buf, w_gate, w_up, w_down, dtype):
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", buf, w_gate,
                   preferred_element_type=jnp.float32)
    ).astype(dtype) * jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)                  # (E_loc, C, d)


def _dispatch_compute_combine(
    x: jax.Array,             # (T, d) tokens local to this shard
    gates: jax.Array,         # (T, k)
    experts: jax.Array,       # (T, k) int32, values in [0, E)
    w_gate: jax.Array,        # (E_loc, d, f_loc)
    w_up: jax.Array,
    w_down: jax.Array,        # (E_loc, f_loc, d)
    e_offset: int,            # first expert id owned by this shard
    capacity: int,
) -> jax.Array:
    """Capacity-bucketed scatter → expert SwiGLU → weighted gather."""
    T, _ = x.shape
    buf, slot, token_idx, cw = _dispatch(
        x, gates, experts, e_offset, w_gate.shape[0], capacity
    )
    y = _expert_ffn(buf, w_gate, w_up, w_down, x.dtype)
    return _combine(y, slot, token_idx, cw, T)


def _held_experts(p, x: jax.Array, cfg: ModelConfig):
    """The dropless expert layer on (T, d) tokens: (out (T, d), aux, rows).

    Route over all ``n_experts``; sort the (token, pick) pairs by held
    expert, the picks on experts not held here last and outside every
    group; run SwiGLU as three grouped matmuls (``lax.ragged_dot``) over
    the groups; put the rows back in (token, pick) order and sum each
    token's picks times their gates, in float32, in pick order.  ``rows``
    is the number of (token, held expert) rows the matmuls computed."""
    T, d = x.shape
    k = cfg.top_k
    e_loc = p["w_gate"].shape[0]
    gates, experts, aux = _route(x, p["router"], k)
    local = experts.reshape(-1) - cfg.expert_offset                # (T*k,)
    held = (local >= 0) & (local < e_loc)
    group = jnp.where(held, local, e_loc)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=e_loc + 1)[:e_loc].astype(jnp.int32)
    xs = jnp.take(x, order // k, axis=0)                         # (T*k, d)
    g = lax.ragged_dot(xs, p["w_gate"], sizes)
    u = lax.ragged_dot(xs, p["w_up"], sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = lax.ragged_dot(h, p["w_down"], sizes)   # rows past the groups: undefined
    y = jnp.zeros_like(y).at[order].set(y, unique_indices=True)
    w = jnp.where(held, gates.reshape(-1), 0.0)
    y = jnp.where(held[:, None], y.astype(jnp.float32), 0.0) * w[:, None]
    out = jnp.sum(y.reshape(T, k, d), axis=1).astype(x.dtype)
    return out, aux, jnp.sum(sizes)


def moe_block(
    p: Dict[str, jax.Array],
    x: jax.Array,             # (B, S, d)
    cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Returns (out (B,S,d), aux_loss scalar, rows).

    Without a mesh: the dropless layer of the held experts, and ``rows``
    the (token, held expert) rows it computed.  With a mesh: shard_map over
    (pod, data, model), capacity-bucketed, and ``rows`` None.
    """
    B, S, d = x.shape
    xf = x.reshape(B * S, d)

    rows = None
    if mesh is None or "model" not in mesh.axis_names:
        out, aux, rows = _held_experts(p, xf, cfg)
        out = out.reshape(B, S, d)
    else:
        out, aux = _moe_sharded(p, xf, cfg, mesh)
        out = out.reshape(B, S, d)

    if cfg.moe_dense_residual:
        out = out + layers.swiglu(x, p["res_gate"], p["res_up"], p["res_down"])
    return out, aux, rows


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(c, 4)


def _moe_sharded(p, xf, cfg: ModelConfig, mesh) -> Tuple[jax.Array, jax.Array]:
    """shard_map MoE: EP when E divides the model axis, else expert-TP.

    Expert weights arrive FSDP-sharded over 'data' (matching
    distributed.sharding rules) and are all-gathered inside the body — the
    explicit analogue of XLA's FSDP weight gathering.  The only other
    collective is the combine psum over 'model'.
    """
    if cfg.n_experts_held != cfg.n_experts:
        raise ValueError(f"{cfg.name}: the sharded path shards all "
                         f"{cfg.n_experts} experts; this model holds "
                         f"{cfg.n_experts_held}")
    axis_names = mesh.axis_names                     # ("pod","data","model") or ("data","model")
    batch_axes = tuple(a for a in axis_names if a != "model")
    model_size = mesh.shape["model"]
    E = cfg.n_experts
    ep = E % model_size == 0 and E >= model_size
    d, f = cfg.d_model, cfg.d_ff
    data_size = mesh.shape["data"]
    assert d % data_size == 0, (d, data_size)

    T_glob = xf.shape[0]
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    if T_glob % n_batch_shards == 0 and T_glob >= n_batch_shards:
        x_spec = P(batch_axes, None)
        t_loc = T_glob // n_batch_shards
    else:
        # tiny token counts (long_500k decode: B=1): replicate tokens
        x_spec = P(None, None)
        t_loc = T_glob
    cap = _capacity(t_loc, cfg)                      # per data-shard capacity
    if ep:
        # EP: experts over 'model', FSDP over 'data' on d
        wg_spec = P("model", "data", None)   # (E, d, f)
        wd_spec = P("model", None, "data")   # (E, f, d)
        e_loc = E // model_size
    else:
        # expert-TP: FFN dim over 'model', FSDP over 'data'
        wg_spec = P(None, "data", "model")   # (E, d, f)
        wd_spec = P(None, "model", "data")   # (E, f, d)
        e_loc = E

    # ---- strategy choice (EXPERIMENTS.md §Perf arctic iteration) -----------
    # weight-gather moves ~3·E_loc·d·f_eff bf16 bytes of expert weights per
    # layer over the 'data' axis; weight-stationary instead psums activation
    # partials: ~E_loc·cap·(2·f_eff + d) fp32.  Pick whichever moves less —
    # for arctic (128 experts, few tokens each) weight-stationary wins by
    # ~50×; for mixtral's big prefill token counts weight-gather wins.
    f_eff = f if ep else f // model_size
    gather_bytes = 2.0 * 3 * e_loc * d * f_eff
    ws_bytes = 4.0 * e_loc * cap * (2 * f_eff + d)
    weight_stationary = ws_bytes < gather_bytes
    d_loc = d // data_size

    def body(x_loc, router_w, w_gate, w_up, w_down):
        gates, experts, aux = _route(x_loc, router_w, cfg.top_k)
        if ep:
            idx = lax.axis_index("model")
            e_off = idx * e_loc
        else:
            e_off = 0

        if weight_stationary:
            # weights stay FSDP-sharded; contract local d/f slices and psum
            # small activation partials over 'data'
            buf, slot, token_idx, cw = _dispatch(
                x_loc, gates, experts, e_off, e_loc, cap
            )
            didx = lax.axis_index("data")
            buf_l = lax.dynamic_slice_in_dim(buf, didx * d_loc, d_loc, axis=2)
            h_g = lax.psum(
                jnp.einsum("ecd,edf->ecf", buf_l, w_gate,
                           preferred_element_type=jnp.float32), "data"
            )
            h_u = lax.psum(
                jnp.einsum("ecd,edf->ecf", buf_l, w_up,
                           preferred_element_type=jnp.float32), "data"
            )
            h = (jax.nn.silu(h_g) * h_u).astype(x_loc.dtype)
            y_l = jnp.einsum("ecf,efd->ecd", h, w_down)   # (E_loc, C, d_loc)
            y_full = lax.all_gather(y_l, "data", axis=2, tiled=True)
            y = _combine(y_full, slot, token_idx, cw, x_loc.shape[0])
        else:
            # FSDP weight gathering (explicit)
            w_gate = lax.all_gather(w_gate, "data", axis=1, tiled=True)
            w_up = lax.all_gather(w_up, "data", axis=1, tiled=True)
            w_down = lax.all_gather(w_down, "data", axis=2, tiled=True)
            y = _dispatch_compute_combine(
                x_loc, gates, experts, w_gate, w_up, w_down, e_off, cap
            )
        # combine across model shards (EP: partial token sums; TP: f-partials)
        y = lax.psum(y, "model")
        aux = lax.pmean(aux, "model")
        for a in batch_axes:
            aux = lax.pmean(aux, a)
        return y, aux

    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), wg_spec, wg_spec, wd_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(xf, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux
