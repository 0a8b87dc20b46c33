"""Mixture-of-Experts with the paper's secure MapReduce shuffle as dispatch.

The paper's pipeline *is* expert parallelism:
    map      = router (token -> top-k expert keys)
    shuffle  = all_to_all keyed by expert id  (paper: hash(key) % rcount)
    reduce   = expert FFN + gate-weighted combine
`dispatch="shuffle"` runs exactly this inside shard_map over the 'model'
axis (experts sharded E/axis, sequence sharded over the same axis while in
the block), reusing `core.shuffle.bucket_pack` / `keyed_all_to_all` — with
optional ChaCha20 on the expert payloads (`secure_moe`): ciphertext crosses
ICI, plaintext exists only chip-locally. `dispatch="dense"` is the same
token->expert packing without collectives, left to XLA's auto-SPMD (oracle
path for equivalence tests).

Token dropping: per-expert capacity = ceil(k·n_loc/E_pad · capacity_factor),
dropped tokens pass through (standard capacity-factor semantics); the drop
count is returned as aux.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.core.shuffle import SecureShuffleConfig, bucket_pack, keyed_all_to_all
from repro.models.layers import _key, act_fn, ninit


def padded_experts(cfg, n_model: int = 1) -> int:
    e = cfg.n_experts
    return -(-e // n_model) * n_model


def moe_init(key, cfg, n_model: int = 1):
    d, f, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, padded_experts(cfg, n_model)
    p = {
        "router": ninit(_key(key, "router"), (d, e)),
        "wi": ninit(_key(key, "ewi"), (e, d, f)),
        "wg": ninit(_key(key, "ewg"), (e, d, f)),
        "wo": ninit(_key(key, "ewo"), (e, f, d), fan_in=f),
    }
    if cfg.n_shared_experts:
        fs = cfg.shared_d_ff or cfg.n_shared_experts * f
        p["shared"] = {
            "wi": ninit(_key(key, "swi"), (d, fs)),
            "wg": ninit(_key(key, "swg"), (d, fs)),
            "wo": ninit(_key(key, "swo"), (fs, d), fan_in=fs),
            "gate": ninit(_key(key, "sgate"), (d, 1)),
        }
    return p


def moe_axes(cfg):
    fs = "fsdp" if getattr(cfg, "moe_fsdp", True) else None
    a = {
        "router": ("fsdp", None),
        "wi": ("experts", fs, "expert_mlp"),
        "wg": ("experts", fs, "expert_mlp"),
        "wo": ("experts", "expert_mlp", fs),
    }
    if cfg.n_shared_experts:
        a["shared"] = {
            "wi": ("fsdp", "mlp"),
            "wg": ("fsdp", "mlp"),
            "wo": ("mlp", "fsdp"),
            "gate": ("fsdp", None),
        }
    return a


def _route(cfg, router_w, x2, e_pad):
    """x2: (n, d) -> gates (n, k), experts (n, k)."""
    logits = jnp.einsum("nd,de->ne", x2, router_w.astype(x2.dtype)).astype(jnp.float32)
    # padding experts never win
    if e_pad > cfg.n_experts:
        neg = jnp.full((x2.shape[0], e_pad - cfg.n_experts), -1e30, jnp.float32)
        logits = jnp.concatenate([logits[:, : cfg.n_experts], neg], axis=1)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, cfg.n_experts_per_tok)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # aux: load-balance statistics (Switch-style)
    load = jnp.mean(jax.nn.one_hot(eidx[:, 0], e_pad, dtype=jnp.float32), axis=0)
    importance = jnp.mean(probs, axis=0)
    aux_loss = e_pad * jnp.sum(load * importance)
    return gates.astype(x2.dtype), eidx.astype(jnp.int32), aux_loss


def _expert_ffn(cfg, wi, wg, wo, xe):
    """xe: (E_loc, C, d) -> (E_loc, C, d), batched over local experts."""
    dt = xe.dtype
    h = jnp.einsum("ecd,edf->ecf", xe, wi.astype(dt))
    g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt))
    return jnp.einsum("ecf,efd->ecd", act_fn(cfg)(g) * h, wo.astype(dt))


def _shared_expert(cfg, sp, x2):
    dt = x2.dtype
    h = jnp.einsum("nd,df->nf", x2, sp["wi"].astype(dt))
    g = jnp.einsum("nd,df->nf", x2, sp["wg"].astype(dt))
    y = jnp.einsum("nf,fd->nd", act_fn(cfg)(g) * h, sp["wo"].astype(dt))
    gate = jax.nn.sigmoid(
        jnp.einsum("nd,do->no", x2, sp["gate"].astype(dt)).astype(jnp.float32)
    ).astype(dt)
    return y * gate


def _capacity(cfg, n_tokens: int, e_pad: int) -> int:
    c = int(n_tokens * cfg.n_experts_per_tok / e_pad * cfg.capacity_factor) + 1
    return max(4, -(-c // 4) * 4)


def _moe_local(cfg, params, x2, e_pad: int, capacity: int | None = None):
    """Single-domain path: pack -> batched expert FFN -> combine (no comms)."""
    n, d = x2.shape
    gates, eidx, aux = _route(cfg, params["router"], x2, e_pad)
    k = cfg.n_experts_per_tok
    cap = capacity or _capacity(cfg, n, e_pad)

    entry_expert = eidx.reshape(-1)
    entry_token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    entry_keys = jnp.arange(n * k, dtype=jnp.int32)
    _, packed, dropped, pos = bucket_pack(
        entry_keys, entry_expert, {"x": x2[entry_token]}, e_pad, cap,
        return_positions=True,
    )
    y_buf = _expert_ffn(cfg, params["wi"], params["wg"], params["wo"], packed["x"])
    flat = jnp.concatenate([y_buf.reshape(e_pad * cap, d), jnp.zeros((1, d), y_buf.dtype)])
    contrib = flat[pos] * gates.reshape(-1)[:, None]
    y = jax.ops.segment_sum(contrib, entry_token, num_segments=n)
    if cfg.n_shared_experts:
        y = y + _shared_expert(cfg, params["shared"], x2)
    return y.astype(x2.dtype), aux, dropped


def _moe_decode_body(x, router, wi, wg, wo, shared, *, cfg, n_model: int, all_axes):
    """Replicated-dispatch EP for short sequences (decode): every rank holds
    the same tokens, computes only its local experts, partial sums psum'd."""
    b, t, d = x.shape
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    e_pad = padded_experts(cfg, n_model)
    e_loc = e_pad // n_model
    my_first = lax.axis_index("model").astype(jnp.int32) * e_loc

    gates, eidx, aux = _route(cfg, router, x2, e_pad)
    k = cfg.n_experts_per_tok
    entry_expert = eidx.reshape(-1)
    entry_token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    mine = (entry_expert >= my_first) & (entry_expert < my_first + e_loc)
    entry_keys = jnp.where(mine, jnp.arange(n * k, dtype=jnp.int32), -1)
    cap = max(4, n)  # worst case: all local tokens on one local expert
    _, packed, dropped, pos = bucket_pack(
        entry_keys, entry_expert - my_first, {"x": x2[entry_token]}, e_loc, cap,
        return_positions=True,
    )
    ye = _expert_ffn(cfg, wi, wg, wo, packed["x"])
    flat = jnp.concatenate([ye.reshape(e_loc * cap, d), jnp.zeros((1, d), ye.dtype)])
    contrib = flat[pos] * gates.reshape(-1)[:, None]
    y = jax.ops.segment_sum(contrib, entry_token, num_segments=n)
    y = lax.psum(y, "model")
    if cfg.n_shared_experts:
        y = y + _shared_expert(cfg, shared, x2)
    return (
        y.reshape(b, t, d).astype(x.dtype),
        lax.pmean(aux, all_axes),
        lax.psum(dropped, all_axes) // n_model,  # replicated over model ranks
    )


def _moe_shuffle_body(x, router, wi, wg, wo, shared, *, cfg, n_model: int, all_axes,
                      secure: SecureShuffleConfig | None):
    """shard_map body: x (B_loc, T_loc, d); experts sharded over 'model'."""
    b, t, d = x.shape
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    e_pad = padded_experts(cfg, n_model)
    e_loc = e_pad // n_model
    gates, eidx, aux = _route(cfg, router, x2, e_pad)
    k = cfg.n_experts_per_tok
    cap = _capacity(cfg, n, e_pad)

    # --- map: emit (expert_key, token_vector); shuffle: hash(key) = key ------
    entry_expert = eidx.reshape(-1)
    entry_token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    entry_keys = jnp.arange(n * k, dtype=jnp.int32)
    _, packed, dropped, pos = bucket_pack(
        entry_keys, entry_expert, {"x": x2[entry_token]}, e_pad, cap,
        return_positions=True,
    )
    send = packed["x"].reshape(n_model, e_loc * cap, d)  # dest-device-major
    recv = keyed_all_to_all({"x": send}, "model", secure)["x"]  # (n_model, e_loc*cap, d)
    recv = checkpoint_name(recv, "moe_recv")  # saveable under moe_remat=save_shuffle

    # --- reduce: local experts over tokens from every source ------------------
    xe = recv.reshape(n_model, e_loc, cap, d).transpose(1, 0, 2, 3).reshape(
        e_loc, n_model * cap, d
    )
    ye = _expert_ffn(cfg, wi, wg, wo, xe)

    # --- return shuffle (the reducer->client leg) ------------------------------
    back = ye.reshape(e_loc, n_model, cap, d).transpose(1, 0, 2, 3).reshape(
        n_model, e_loc * cap, d
    )
    sec_back = None
    if secure is not None:
        sec_back = SecureShuffleConfig(
            key_words=secure.key_words,
            nonce_words=secure.nonce_words,
            counter0=secure.counter0 + (1 << 20),
        )
    got = checkpoint_name(
        keyed_all_to_all({"x": back}, "model", sec_back)["x"], "moe_back"
    ).reshape(e_pad * cap, d)

    flat = jnp.concatenate([got, jnp.zeros((1, d), got.dtype)])
    contrib = flat[pos] * gates.reshape(-1)[:, None]
    y = jax.ops.segment_sum(contrib, entry_token, num_segments=n)
    if cfg.n_shared_experts:
        y = y + _shared_expert(cfg, shared, x2)
    return (
        y.reshape(b, t, d).astype(x.dtype),
        lax.pmean(aux, all_axes),
        lax.psum(dropped, all_axes),
    )


def moe_apply(cfg, params, x, *, mesh=None, dp_spec=("pod", "data"),
              secure: SecureShuffleConfig | None = None):
    """x: (B, T, d). Uses shuffle dispatch when cfg.moe_dispatch=='shuffle'
    and a mesh with a 'model' axis is provided; else the local/XLA-auto path.
    Sequences shorter than the model axis (decode) use replicated-dispatch EP.
    """
    if cfg.moe_dispatch == "shuffle" and mesh is not None and "model" in mesh.axis_names:
        n_model = mesh.shape["model"]
        dp = tuple(a for a in (dp_spec if isinstance(dp_spec, tuple) else (dp_spec,))
                   if a in mesh.axis_names) or None
        all_axes = ((dp or ()) if isinstance(dp, tuple) else (dp,)) + ("model",)
        shared = params.get("shared", {"_": jnp.zeros((1,), jnp.float32)})
        seq_shardable = x.shape[1] % n_model == 0 and x.shape[1] >= n_model
        if seq_shardable:
            body = partial(_moe_shuffle_body, cfg=cfg, n_model=n_model,
                           all_axes=all_axes, secure=secure)
            x_spec = P(dp, "model", None)
        else:
            body = partial(_moe_decode_body, cfg=cfg, n_model=n_model, all_axes=all_axes)
            x_spec = P(dp, None, None)
        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                x_spec,                      # x: batch over dp (+ seq over model)
                P(None, None),               # router replicated
                P("model", None, None),      # experts sharded
                P("model", None, None),
                P("model", None, None),
                jax.tree.map(lambda _: P(), shared),
            ),
            out_specs=(x_spec, P(), P()),
            check_vma=False,
        )
        return fn(x, params["router"], params["wi"], params["wg"], params["wo"], shared)

    b, t, d = x.shape
    e_pad = params["wi"].shape[0]
    y, aux, dropped = _moe_local(cfg, params, x.reshape(-1, d), e_pad)
    return y.reshape(b, t, d), aux, dropped
