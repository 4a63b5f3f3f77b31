"""Mixture-of-Experts transformer (grok-1-314b, qwen2-moe-a2.7b).

Router: softmax top-k with capacity-bounded sort-based dispatch — no
[T, E, C] one-hot tensors (32k-seq prefill would not survive them).  Tokens
are argsorted by expert id, truncated to per-expert capacity, processed as
a dense [E, C, d] einsum against stacked expert weights, and combined with
router weights.  Static shapes throughout (pjit-safe).

Sharding posture (DESIGN.md §5): tokens DP over (pod, data); expert FFN
hidden dim TP over "model"; optionally (qwen2-moe hillclimb) experts padded
to a multiple of the mesh axis for true expert parallelism.

qwen2-moe extras: 4 shared experts (one fused always-on SwiGLU of width
4*1408) + routed top-4 over 60 experts, per the public config.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import transformer as T
from repro.models.attention import attention


@dataclasses.dataclass(frozen=True)
class MoEConfig(T.TransformerConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    n_shared_experts: int = 0
    d_ff_shared: int = 0  # width of the fused shared-expert SwiGLU
    router_aux_coef: float = 0.01
    pad_experts_to: Optional[int] = None  # EP knob: pad experts for sharding
    # Dispatch is vmapped over token groups sharded across the whole mesh:
    # each group sorts/capacities its own tokens (per-device capacity, the
    # production EP semantics) so no global argsort / token gather appears.
    dispatch_groups: int = 512

    @property
    def n_experts_padded(self) -> int:
        return self.pad_experts_to or self.n_experts

    def n_params(self) -> int:
        d, f, v, l = self.d_model, self.d_ff, self.vocab, self.n_layers
        hd = self.head_dim
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) + (self.n_heads * hd) * d
        moe = 3 * d * f * self.n_experts + d * self.n_experts
        shared = 3 * d * self.d_ff_shared if self.n_shared_experts else 0
        per_layer = attn + moe + shared + 2 * d
        emb = v * d * (1 if self.tie_embeddings else 2)
        return l * per_layer + emb + d

    def n_active_params(self) -> int:
        d, f, v, l = self.d_model, self.d_ff, self.vocab, self.n_layers
        hd = self.head_dim
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) + (self.n_heads * hd) * d
        moe = 3 * d * f * self.top_k + d * self.n_experts
        shared = 3 * d * self.d_ff_shared if self.n_shared_experts else 0
        per_layer = attn + moe + shared + 2 * d
        emb = v * d * (1 if self.tie_embeddings else 2)
        return l * per_layer + emb + d


def layer_init(key, cfg: MoEConfig):
    ks = jax.random.split(key, 12)
    d, hd = cfg.d_model, cfg.head_dim
    ep = cfg.n_experts_padded
    p = {
        "ln1": L.rmsnorm_init(d, cfg.pdtype),
        "ln2": L.rmsnorm_init(d, cfg.pdtype),
        "wq": L.dense_init(ks[0], d, cfg.n_heads * hd, cfg.pdtype),
        "wk": L.dense_init(ks[1], d, cfg.n_kv_heads * hd, cfg.pdtype),
        "wv": L.dense_init(ks[2], d, cfg.n_kv_heads * hd, cfg.pdtype),
        "wo": L.dense_init(ks[3], cfg.n_heads * hd, d, cfg.pdtype),
        "router": L.dense_init(ks[4], d, ep, cfg.pdtype, scale=0.02),
        "we_gate": jax.random.normal(ks[5], (ep, d, cfg.d_ff), jnp.float32).astype(cfg.pdtype) * (d ** -0.5),
        "we_up": jax.random.normal(ks[6], (ep, d, cfg.d_ff), jnp.float32).astype(cfg.pdtype) * (d ** -0.5),
        "we_down": jax.random.normal(ks[7], (ep, cfg.d_ff, d), jnp.float32).astype(cfg.pdtype) * (cfg.d_ff ** -0.5),
    }
    if cfg.n_shared_experts:
        p["ws_gate"] = L.dense_init(ks[8], d, cfg.d_ff_shared, cfg.pdtype)
        p["ws_up"] = L.dense_init(ks[9], d, cfg.d_ff_shared, cfg.pdtype)
        p["ws_down"] = L.dense_init(ks[10], cfg.d_ff_shared, d, cfg.pdtype)
    return p


def init(key, cfg: MoEConfig):
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    stacked = jax.vmap(lambda k: layer_init(k, cfg))(
        jax.random.split(k_layers, cfg.n_layers)
    )
    params = {
        "embed": L.embed_init(k_emb, cfg.vocab, cfg.d_model, cfg.pdtype),
        "layers": stacked,
        "ln_f": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(k_out, cfg.d_model, cfg.vocab, cfg.pdtype)
    return params


def _dispatch_group(xt, router, we_gate, we_up, we_down, shared_w, cfg: MoEConfig,
                    partial_tp: bool = False):
    """Dispatch one token group [T_loc, d] -> ([T_loc, d], aux scalar).

    With ``partial_tp=True`` the expert ffn weights are local ff-dim shards
    and the returned output is a *partial* sum (caller psums over the TP
    axis) — the shard_map path.
    """
    t, d = xt.shape
    ep = cfg.n_experts_padded
    logits = (xt @ router.astype(cfg.cdtype)).astype(jnp.float32)
    if ep != cfg.n_experts:  # padded experts never routed
        pad_mask = jnp.arange(ep) < cfg.n_experts
        logits = jnp.where(pad_mask[None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)  # [T, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )
    # load-balance auxiliary loss (Switch style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(gate_idx[:, 0], ep, dtype=jnp.float32), axis=0
    )
    aux = cfg.router_aux_coef * ep * jnp.sum(me * ce)

    # sort-based capacity dispatch (local to the group)
    cap = int(cfg.capacity_factor * t * cfg.top_k / cfg.n_experts) + 1
    flat_expert = gate_idx.reshape(-1)  # [T*K]
    flat_token = jnp.repeat(jnp.arange(t), cfg.top_k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    seg_pos = _segment_positions(se)  # position within each expert's run
    keep = seg_pos < cap
    slot = se * cap + seg_pos  # [T*K] in [0, EP*cap)
    slot = jnp.where(keep, slot, ep * cap)  # overflow -> dropped sink
    # scatter tokens into [EP*cap, d]
    buf = jnp.zeros((ep * cap + 1, d), cfg.cdtype)
    buf = buf.at[slot].set(jnp.take(xt, st, axis=0))
    buf = buf[:-1].reshape(ep, cap, d)
    # expert computation (ff dim possibly a local TP shard)
    h = jnp.einsum("ecd,edf->ecf", buf, we_gate.astype(cfg.cdtype))
    u = jnp.einsum("ecd,edf->ecf", buf, we_up.astype(cfg.cdtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, we_down.astype(cfg.cdtype))
    y = y.reshape(ep * cap, d)
    # combine back
    contrib = jnp.take(y, jnp.minimum(slot, ep * cap - 1), axis=0)
    contrib = jnp.where(keep[:, None], contrib, 0) * sg[:, None].astype(cfg.cdtype)
    out = jnp.zeros((t, d), cfg.cdtype).at[st].add(contrib)
    if shared_w is not None:
        ws_gate, ws_up, ws_down = shared_w
        out = out + L.swiglu(
            xt,
            ws_gate.astype(cfg.cdtype),
            ws_up.astype(cfg.cdtype),
            ws_down.astype(cfg.cdtype),
        )
    return out, aux


def moe_ffn(lp, x, cfg: MoEConfig, acts=None):
    """x: [B, S, d] -> ([B, S, d], aux_loss scalar).

    Tokens are regrouped [G, T/G, d]; the dispatch is group-local (per-group
    capacity — the production EP semantics), so argsort/top-k/scatter never
    cross a shard.

    Distribution: GSPMD handles the vmapped gather poorly ("involuntary
    full rematerialization", 32 GiB replicated buffers measured on
    qwen2-moe train_4k), so when the acts dict carries a ``moe_shard``
    entry the dispatch runs under **shard_map**: token groups sharded over
    the dp axes, expert ffn hidden dim a local TP shard over "model", one
    psum combining the down-projection partials (textbook Megatron-style
    TP with manual collective control; EXPERIMENTS §Perf).
    """
    from repro.distributed.actshard import constrain

    b, s, d = x.shape
    t = b * s
    g = min(cfg.dispatch_groups, t)
    while t % g:
        g -= 1
    xt = x.reshape(g, t // g, d)
    shared = (
        (lp["ws_gate"], lp["ws_up"], lp["ws_down"]) if cfg.n_shared_experts else None
    )
    moe_shard = acts.get("moe_shard") if acts else None
    if moe_shard is None:  # single-device / smoke path
        out, aux = jax.vmap(
            lambda xg: _dispatch_group(
                xg, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"],
                shared, cfg,
            )
        )(xt)
        return out.reshape(b, s, d), jnp.mean(aux)

    mesh, token_axes, tp = moe_shard
    from jax.sharding import PartitionSpec as P

    def body(xt_l, router, wg, wu, wd, *shared_l):
        sh = shared_l if shared_l else None

        # scan (not vmap) over the device's local groups: the dispatch
        # scatter/gather working set stays one group wide, and the remat'd
        # backward recomputes per group instead of materializing every
        # group's buffers at once (-20 GiB measured; EXPERIMENTS §Perf).
        @jax.checkpoint
        def step(aux_acc, xg):
            out_g, aux_g = _dispatch_group(
                xg, router, wg, wu, wd, sh, cfg, partial_tp=True
            )
            return aux_acc + aux_g, out_g

        aux_sum, out_l = jax.lax.scan(step, jnp.zeros((), jnp.float32), xt_l)
        out_l = jax.lax.psum(out_l, tp)  # combine ff-shard partials
        aux = jax.lax.pmean(aux_sum / xt_l.shape[0], token_axes)
        return out_l, aux

    shared_args = tuple(shared) if shared is not None else ()
    shared_specs = tuple(
        [P(None, tp), P(None, tp), P(tp, None)]
    ) if shared is not None else ()
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(token_axes, None, None),
            P(None, None),  # router replicated
            P(None, None, tp),  # we_gate [E, d, ff/tp]
            P(None, None, tp),
            P(None, tp, None),  # we_down [E, ff/tp, d]
            *shared_specs,
        ),
        out_specs=(P(token_axes, None, None), P()),
        check_vma=False,
    )(xt, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"], *shared_args)
    return out.reshape(b, s, d), aux


def _segment_positions(sorted_ids):
    """Position of each element within its run of equal ids (sorted input)."""
    n = sorted_ids.shape[0]
    idx = jnp.arange(n)
    is_start = jnp.concatenate([jnp.ones(1, bool), sorted_ids[1:] != sorted_ids[:-1]])
    start_idx = jnp.where(is_start, idx, 0)
    run_start = jax.lax.associative_scan(jnp.maximum, start_idx)
    return idx - run_start


def layer_fwd(lp, x, cfg: MoEConfig, cos, sin, positions=None, attn_backend=None,
              acts=None):
    b, s, _ = x.shape
    q, k, v, _ = T._qkv(lp, x, cfg, positions, cos, sin)
    o = attention(q, k, v, causal=True, local_window=cfg.local_window,
                  backend=attn_backend, q_chunk=cfg.attn_q_chunk,
                  kv_chunk=cfg.attn_kv_chunk)
    o = o.swapaxes(1, 2).reshape(b, s, cfg.n_heads * cfg.head_dim)
    x = x + o @ lp["wo"].astype(cfg.cdtype)
    xn = L.rmsnorm(x, lp["ln2"])
    y, aux = moe_ffn(lp, xn, cfg, acts=acts)
    return x + y, aux


def forward(params, tokens, cfg: MoEConfig, attn_backend=None, acts=None):
    from repro.distributed.actshard import constrain

    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    x = constrain(x, acts, "res")
    s = tokens.shape[1]
    cos, sin = L.rope_freqs(cfg.head_dim, s, cfg.rope_theta)

    def body(carry, lp):
        x, aux = carry
        x, a = layer_fwd(lp, x, cfg, cos, sin, attn_backend=attn_backend, acts=acts)
        return (constrain(x, acts, "res"), aux + a), None

    body_fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = L.rmsnorm(x, params["ln_f"])
    unemb = params.get("unembed", None)
    w = unemb if unemb is not None else params["embed"].T
    logits = (x @ w.astype(cfg.cdtype)).astype(jnp.float32)
    return constrain(logits, acts, "logits"), aux


def forward_hidden(params, tokens, cfg: MoEConfig, attn_backend=None, acts=None):
    from repro.distributed.actshard import constrain

    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    x = constrain(x, acts, "res")
    s = tokens.shape[1]
    cos, sin = L.rope_freqs(cfg.head_dim, s, cfg.rope_theta)

    def body(carry, lp):
        x, aux = carry
        x, a = layer_fwd(lp, x, cfg, cos, sin, attn_backend=attn_backend, acts=acts)
        return (constrain(x, acts, "res"), aux + a), None

    body_fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)), params["layers"])
    return L.rmsnorm(x, params["ln_f"]), aux


def loss_fn(params, batch, cfg: MoEConfig, acts=None):
    x, aux = forward_hidden(params, batch["tokens"], cfg, acts=acts)
    unemb = params.get("unembed", None)
    w = unemb if unemb is not None else params["embed"].T
    return L.lm_loss_fused(
        x[:, :-1], w, batch["labels"][:, 1:], cfg.z_loss, acts=acts
    ) + aux


# --------------------------- serving ----------------------------------- #
def prefill(params, tokens, cfg: MoEConfig, acts=None):
    from repro.distributed.actshard import constrain

    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
    x = constrain(x, acts, "res")
    b, s = tokens.shape
    cos, sin = L.rope_freqs(cfg.head_dim, s, cfg.rope_theta)

    def body(x, lp):
        q, k, v, _ = T._qkv(lp, x, cfg, None, cos, sin)
        o = attention(q, k, v, causal=True, local_window=cfg.local_window,
                      q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
        o = o.swapaxes(1, 2).reshape(b, s, cfg.n_heads * cfg.head_dim)
        x = x + o @ lp["wo"].astype(cfg.cdtype)
        xn = L.rmsnorm(x, lp["ln2"])
        y, _ = moe_ffn(lp, xn, cfg, acts=acts)
        return constrain(x + y, acts, "res"), (k, v)

    body_fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    x, (ks, vs) = jax.lax.scan(body_fn, x, params["layers"])
    x = L.rmsnorm(x, params["ln_f"])
    unemb = params.get("unembed", None)
    w = unemb if unemb is not None else params["embed"].T
    logits = (x[:, -1] @ w.astype(cfg.cdtype)).astype(jnp.float32)
    return {"k": ks, "v": vs}, constrain(logits, acts, "logits")


def decode_step(params, token, kv, pos, cfg: MoEConfig, acts=None):
    from repro.distributed.actshard import constrain
    from repro.kernels.flash_attention.ref import decode_ref

    b = token.shape[0]
    x = jnp.take(params["embed"], token, axis=0).astype(cfg.cdtype)[:, None, :]
    x = constrain(x, acts, "res")
    smax = kv["k"].shape[3]
    cos, sin = L.rope_freqs(cfg.head_dim, smax, cfg.rope_theta)
    positions = jnp.full((1,), pos, jnp.int32)

    def body(x, inp):
        lp, kc, vc = inp
        q, k, v, _ = T._qkv(lp, x, cfg, positions, cos, sin)
        kc = T.cache_update_add(kc, k[:, :, 0], pos)
        vc = T.cache_update_add(vc, v[:, :, 0], pos)
        o = decode_ref(q[:, :, 0], kc, vc, pos + 1, window=cfg.local_window)
        o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim)
        x = x + o @ lp["wo"].astype(cfg.cdtype)
        xn = L.rmsnorm(x, lp["ln2"])
        y, _ = moe_ffn(lp, xn, cfg, acts=acts)
        return constrain(x + y, acts, "res"), (kc, vc)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], kv["k"], kv["v"]))
    x = L.rmsnorm(x, params["ln_f"])
    unemb = params.get("unembed", None)
    w = unemb if unemb is not None else params["embed"].T
    logits = (x[:, 0] @ w.astype(cfg.cdtype)).astype(jnp.float32)
    return constrain(logits, acts, "logits"), {"k": ks, "v": vs}
