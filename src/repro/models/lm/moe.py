"""Top-k MoE with sort-based dispatch (expert-parallel over the model axis).

The dense-compute formulation MaxText-style: assignments are sorted by
expert, each expert processes a static-capacity buffer ``[E, C, d]``, and
results scatter back weighted by the router gate.  FLOPs scale with
``E · C ≈ T · top_k · capacity_factor`` — the *active* compute — not with
the full expert count, so cost_analysis reflects real MoE arithmetic.
Experts are sharded on the ``model`` axis; the dispatch/combine scatters
become the all-to-alls visible in the roofline collective term.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.lm.config import LMConfig

__all__ = [
    "init_moe_params",
    "moe_ffn",
    "init_dense_ffn",
    "dense_ffn",
    "moe_capacity",
    "set_shard_map_context",
]

# (mesh, data_axes, model_axis) — when set (by the launcher), moe_ffn runs
# the explicit shard_map dispatch instead of relying on GSPMD propagation.
# GSPMD cannot partition the data-dependent dispatch/combine scatters and
# falls back to replicating [T·k, d]-sized buffers (the "involuntary full
# rematerialization" warnings; see EXPERIMENTS.md §Perf iteration 1).
_SHARD_MAP_CTX: tuple | None = None


def set_shard_map_context(mesh=None, data_axes: tuple = (), model_axis: str = "model") -> None:
    """Enable (or with mesh=None disable) expert-parallel shard_map MoE."""
    global _SHARD_MAP_CTX
    _SHARD_MAP_CTX = None if mesh is None else (mesh, tuple(data_axes), model_axis)


def _init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[-2]
    return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)


# ------------------------------------------------------------- dense FFN


def init_dense_ffn(key: jax.Array, d: int, ff: int, activation: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"w1": _init(ks[0], (d, ff), dtype), "w2": _init(ks[1], (ff, d), dtype)}
    if activation in ("silu", "geglu"):
        p["w3"] = _init(ks[2], (d, ff), dtype)  # gate
    return p


def _act(h, activation):
    if activation == "silu":
        return jax.nn.silu(h)
    if activation == "geglu":
        return jax.nn.gelu(h)
    if activation == "gelu":
        return jax.nn.gelu(h)
    raise ValueError(activation)


def dense_ffn(params: dict, x: jax.Array, activation: str) -> jax.Array:
    from repro.models.lm.tp import maybe_row_parallel

    h = x @ params["w1"]
    if "w3" in params:
        h = _act(h, activation) * (x @ params["w3"])
    else:
        h = _act(h, activation)
    return maybe_row_parallel(h, params["w2"])


# -------------------------------------------------------------------- MoE


def moe_capacity(num_tokens: int, cfg: LMConfig) -> int:
    m = cfg.moe
    c = int(num_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8, floor 8


def init_moe_params(key: jax.Array, cfg: LMConfig, dtype) -> dict:
    m = cfg.moe
    d, ff = cfg.d_model, m.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, m.n_experts), jnp.float32, fan_in=d),
        "we1": _init(ks[1], (m.n_experts, d, ff), dtype, fan_in=d),
        "we2": _init(ks[2], (m.n_experts, ff, d), dtype, fan_in=ff),
        "we3": _init(ks[3], (m.n_experts, d, ff), dtype, fan_in=d),
    }
    if m.n_shared > 0:
        ff_sh = m.d_ff_shared or m.n_shared * ff
        p["shared"] = init_dense_ffn(ks[4], d, ff_sh, cfg.activation, dtype)
    return p


def moe_ffn(params: dict, x: jax.Array, cfg: LMConfig) -> tuple[jax.Array, jax.Array]:
    """Returns (output [B,S,d], aux load-balance loss scalar)."""
    if _SHARD_MAP_CTX is not None:
        return _moe_ffn_shard_map(params, x, cfg, *_SHARD_MAP_CTX)
    return _moe_ffn_gspmd(params, x, cfg)


def _moe_ffn_gspmd(params: dict, x: jax.Array, cfg: LMConfig) -> tuple[jax.Array, jax.Array]:
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = m.top_k
    e = m.n_experts
    cap = moe_capacity(t, cfg)

    xf = x.reshape(t, d)
    logits = (xf.astype(jnp.float32)) @ params["router"]  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Load-balance aux loss (Switch-style): E * Σ_e f_e · p_e
    pe = probs.mean(0)
    fe = jnp.zeros(e, jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(fe * pe)

    # ---- sort-based dispatch ------------------------------------------
    flat_e = expert_idx.reshape(-1)  # [T*k]
    sort_idx = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[sort_idx]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank = jnp.arange(t * k) - starts[sorted_e]
    keep = rank < cap
    dest = jnp.where(keep, sorted_e * cap + rank, e * cap)  # overflow bin
    token_of = sort_idx // k

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xf[token_of])
    buf = buf[:-1].reshape(e, cap, d)

    # ---- expert compute (grouped einsum; E sharded on 'model') --------
    h = jnp.einsum("ecd,edf->ecf", buf, params["we1"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["we3"])
    h = _act(h, cfg.activation) * g
    y = jnp.einsum("ecf,efd->ecd", h, params["we2"])  # [E, C, d]

    # ---- combine -------------------------------------------------------
    yf = y.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None], yf[jnp.minimum(dest, e * cap - 1)], 0.0)
    w = gate_vals.reshape(-1)[sort_idx][:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[token_of].add(gathered * w)

    if "shared" in params:
        out = out + dense_ffn(params["shared"], xf, cfg.activation)
    return out.reshape(b, s, d), aux


# --------------------------------------------- explicit expert parallelism


def _moe_ffn_shard_map(
    params: dict, x: jax.Array, cfg: LMConfig, mesh, data_axes: tuple, model_axis: str
) -> tuple[jax.Array, jax.Array]:
    """Megatron-style MoE: tokens sharded on data axes, experts on 'model'.

    Every device owns its expert block AND its token block, so dispatch and
    combine are purely local scatters; the only cross-device traffic is ONE
    bf16 psum of the [T_local, d] output over the model axis (which also
    folds in the tensor-parallel shared-expert partial) — versus GSPMD's
    replicated [T·k, d] buffers.  Batch=1 shapes pass ``data_axes=()``
    (tokens replicated over data, still expert-parallel over model).
    """
    m = cfg.moe
    k = m.top_k
    dspec = P(*( (data_axes if data_axes else None), None, None ))

    has_shared = "shared" in params
    shared_specs = {}
    if has_shared:
        shared_specs = {
            "w1": P(None, model_axis),
            "w2": P(model_axis, None),
        }
        if "w3" in params["shared"]:
            shared_specs["w3"] = P(None, model_axis)
    param_specs = {
        "router": P(None, None),
        "we1": P(model_axis, None, None),
        "we2": P(model_axis, None, None),
        "we3": P(model_axis, None, None),
    }
    if has_shared:
        param_specs["shared"] = shared_specs

    def local_fn(params_l, x_l):
        b_l, s, d = x_l.shape
        t = b_l * s
        cap = moe_capacity(t, cfg)
        xf = x_l.reshape(t, d)
        logits = xf.astype(jnp.float32) @ params_l["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        e_loc = params_l["we1"].shape[0]
        e_start = jax.lax.axis_index(model_axis) * e_loc
        flat_e = expert_idx.reshape(-1)
        local_e = jnp.where(
            (flat_e >= e_start) & (flat_e < e_start + e_loc), flat_e - e_start, e_loc
        )
        sort_idx = jnp.argsort(local_e)
        sorted_e = local_e[sort_idx]
        starts = jnp.searchsorted(sorted_e, jnp.arange(e_loc))
        rank = jnp.arange(t * k) - starts[jnp.minimum(sorted_e, e_loc - 1)]
        keep = (sorted_e < e_loc) & (rank < cap)
        dest = jnp.where(keep, sorted_e * cap + rank, e_loc * cap)
        token_of = sort_idx // k

        buf = jnp.zeros((e_loc * cap + 1, d), x_l.dtype).at[dest].set(xf[token_of])
        buf = buf[:-1].reshape(e_loc, cap, d)
        h = jnp.einsum("ecd,edf->ecf", buf, params_l["we1"])
        g = jnp.einsum("ecd,edf->ecf", buf, params_l["we3"])
        y = jnp.einsum("ecf,efd->ecd", _act(h, cfg.activation) * g, params_l["we2"])
        yf = y.reshape(e_loc * cap, d)
        gathered = jnp.where(keep[:, None], yf[jnp.minimum(dest, e_loc * cap - 1)], 0.0)
        w = gate_vals.reshape(-1)[sort_idx][:, None].astype(x_l.dtype)
        out = jnp.zeros((t, d), x_l.dtype).at[token_of].add(gathered * w)

        if has_shared:
            sp = params_l["shared"]
            hs = xf @ sp["w1"]
            if "w3" in sp:
                hs = _act(hs, cfg.activation) * (xf @ sp["w3"])
            else:
                hs = _act(hs, cfg.activation)
            out = out + hs @ sp["w2"]  # partial over the sharded ff dim

        out = jax.lax.psum(out, model_axis)

        pe = probs.mean(0)
        fe = jnp.zeros(m.n_experts, jnp.float32).at[expert_idx.reshape(-1)].add(1.0) / (t * k)
        aux = m.n_experts * jnp.sum(fe * pe)
        if data_axes:
            aux = jax.lax.pmean(aux, data_axes)
        return out.reshape(b_l, s, d), aux

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_specs, dspec),
        out_specs=(dspec, P()),
        check_vma=False,
    )
    return fn(
        {kk: params[kk] for kk in param_specs},
        x,
    )
