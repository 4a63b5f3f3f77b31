"""Step builders: (arch, shape, mesh) -> jit-able fn + shardings + arg specs.

Everything here works on ``jax.ShapeDtypeStruct`` stand-ins — params, opt
state and batches are *never allocated*; ``jax.eval_shape`` over the init
functions produces the shape trees the dry-run lowers against.

One builder per family:

* LM train   — value_and_grad(loss) + optimizer update (AdamW-bf16 for the
  <10B archs, Adafactor for grok-1), FSDP×TP shardings.
* LM prefill — prompt pass returning (kv cache, last logits).
* LM decode  — one token against a full KV cache (seq sharded over model).
* GNN train  — full-batch or sampled-subgraph step, edges sharded over dp.
* recsys     — train / serve / bulk / retrieval.
* paper-gwq  — the sharded two-stage window query (the paper's data plane).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding_rules as SR
from repro.launch.mesh import dp_axes_of
from repro.models import gnn as G
from repro.models import moe as MoE
from repro.models import recsys as R
from repro.models import transformer as T
from repro.optim.optimizers import adafactor, adamw
from repro.optim.schedules import cosine_schedule


@dataclasses.dataclass
class BuiltStep:
    fn: Callable
    args: Tuple[Any, ...]  # ShapeDtypeStructs (pytrees)
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()
    static_argnums: Tuple[int, ...] = ()

    def lower(self, mesh):
        jitted = jax.jit(
            self.fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )
        with mesh:
            return jitted.lower(*self.args)


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _dp_spec(dp_axes):
    return dp_axes if len(dp_axes) > 1 else dp_axes[0]


def _shapes_of(fn, *args):
    return jax.eval_shape(fn, *args)


# ---------------------------------------------------------------------- #
#  LM family
# ---------------------------------------------------------------------- #
def _lm_module(cfg):
    return MoE if isinstance(cfg, MoE.MoEConfig) else T


def _lm_optimizer(cfg):
    if cfg.n_params() > 20e9:  # grok-1: factored state is the memory floor
        return adafactor(cosine_schedule(1e-4, 200, 10_000))
    return adamw(cosine_schedule(3e-4, 200, 10_000))


def _lm_param_specs(cfg, dp_axes):
    if isinstance(cfg, MoE.MoEConfig):
        ep = cfg.pad_experts_to is not None
        return SR.moe_param_specs(cfg, dp_axes, expert_parallel=ep)
    return SR.lm_param_specs(cfg, dp_axes)


def build_lm_train(cfg, mesh, shape_dims) -> BuiltStep:
    dp_axes = dp_axes_of(mesh)
    mod = _lm_module(cfg)
    opt = _lm_optimizer(cfg)
    params_s = _shapes_of(lambda: mod.init(jax.random.PRNGKey(0), cfg))
    opt_s = _shapes_of(opt.init, params_s)
    b, s = shape_dims["batch"], shape_dims["seq"]
    batch = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }

    from repro.distributed.actshard import lm_train_acts

    acts = lm_train_acts(dp_axes, mesh)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: mod.loss_fn(p, batch, cfg, acts=acts)
        )(params)
        params, opt_state, gnorm = opt.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, "gnorm": gnorm}

    pspec = _lm_param_specs(cfg, dp_axes)
    ospec = SR.opt_state_specs(pspec, opt_s)
    bspec = SR.lm_batch_specs(dp_axes)
    return BuiltStep(
        fn=train_step,
        args=(params_s, opt_s, batch),
        in_shardings=(_named(mesh, pspec), _named(mesh, ospec), _named(mesh, bspec)),
        out_shardings=(
            _named(mesh, pspec),
            _named(mesh, ospec),
            {"loss": NamedSharding(mesh, P()), "gnorm": NamedSharding(mesh, P())},
        ),
        donate_argnums=(0, 1),
    )


def build_lm_prefill(cfg, mesh, shape_dims) -> BuiltStep:
    dp_axes = dp_axes_of(mesh)
    mod = _lm_module(cfg)
    params_s = _shapes_of(lambda: mod.init(jax.random.PRNGKey(0), cfg))
    b, s = shape_dims["batch"], shape_dims["seq"]
    tokens = jax.ShapeDtypeStruct((b, s), jnp.int32)

    from repro.distributed.actshard import lm_prefill_acts

    acts = lm_prefill_acts(dp_axes, mesh)

    def prefill_step(params, tokens):
        return mod.prefill(params, tokens, cfg, acts=acts)

    pspec = _lm_param_specs(cfg, dp_axes)
    d = _dp_spec(dp_axes)
    kv_spec = {"k": P(None, d, None, "model", None), "v": P(None, d, None, "model", None)}
    return BuiltStep(
        fn=prefill_step,
        args=(params_s, tokens),
        in_shardings=(_named(mesh, pspec), NamedSharding(mesh, P(d, None))),
        out_shardings=(
            _named(mesh, kv_spec),
            NamedSharding(mesh, P(d, "model")),
        ),
    )


def build_lm_decode(cfg, mesh, shape_dims) -> BuiltStep:
    dp_axes = dp_axes_of(mesh)
    mod = _lm_module(cfg)
    params_s = _shapes_of(lambda: mod.init(jax.random.PRNGKey(0), cfg))
    b, s = shape_dims["batch"], shape_dims["seq"]
    hd = cfg.head_dim
    kv = {
        "k": jax.ShapeDtypeStruct((cfg.n_layers, b, cfg.n_kv_heads, s, hd), cfg.cdtype),
        "v": jax.ShapeDtypeStruct((cfg.n_layers, b, cfg.n_kv_heads, s, hd), cfg.cdtype),
    }
    token = jax.ShapeDtypeStruct((b,), jnp.int32)

    from repro.distributed.actshard import lm_decode_acts

    acts = lm_decode_acts(dp_axes, mesh)

    def decode(params, token, kv):
        return mod.decode_step(params, token, kv, s - 1, cfg, acts=acts)

    pspec = _lm_param_specs(cfg, dp_axes)
    d = _dp_spec(dp_axes)
    ndp = int(np.prod([mesh.shape[a] for a in dp_axes]))
    if b >= ndp:
        tok_spec = P(d)
        kv_spec = {"k": P(None, d, None, "model", None),
                   "v": P(None, d, None, "model", None)}
        logit_spec = P(d, "model")
    else:
        # long-context single-sequence decode (long_500k): batch cannot
        # shard, so the KV sequence shards over the ENTIRE mesh
        flat = tuple(dp_axes) + ("model",)
        tok_spec = P()
        kv_spec = {"k": P(None, None, None, flat, None),
                   "v": P(None, None, None, flat, None)}
        logit_spec = P(None, "model")
    return BuiltStep(
        fn=decode,
        args=(params_s, token, kv),
        in_shardings=(
            _named(mesh, pspec),
            NamedSharding(mesh, tok_spec),
            _named(mesh, kv_spec),
        ),
        out_shardings=(
            NamedSharding(mesh, logit_spec),
            _named(mesh, kv_spec),
        ),
        donate_argnums=(2,),
    )


# ---------------------------------------------------------------------- #
#  GNN family
# ---------------------------------------------------------------------- #
def _gnn_init_and_fwd(cfg: G.GNNConfig):
    if cfg.kind == "gcn":
        return G.gcn_init, "gcn"
    if cfg.kind == "sage":
        return G.sage_init, "sage"
    if cfg.kind == "gat":
        return G.gat_init, "gat"
    if cfg.kind == "meshgraphnet":
        return lambda k, c: G.mgn_init(k, c), "mgn"
    raise ValueError(cfg.kind)


def gnn_loss(params, batch, cfg: G.GNNConfig, n: int, node_spec=None):
    es, ed = batch["edge_src"], batch["edge_dst"]
    feats = batch["feats"]
    if cfg.kind == "gcn":
        out = G.gcn_forward(params, feats, es, ed, batch["edge_w"], n, cfg,
                            node_spec=node_spec)
    elif cfg.kind == "sage":
        out = G.sage_forward(params, feats, es, ed, n, cfg, node_spec=node_spec)
    elif cfg.kind == "gat":
        out = G.gat_forward(params, feats, es, ed, n, cfg, node_spec=node_spec)
    else:
        out = G.mgn_forward(params, feats, batch["edge_feats"], es, ed, n, cfg,
                            node_spec=node_spec)
    if cfg.kind == "meshgraphnet":
        # regression on node targets
        return jnp.mean(jnp.square(out - batch["targets"]))
    labels = batch["labels"]
    mask = batch.get("label_mask", None)
    logits = out.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def build_gnn_train(cfg: G.GNNConfig, mesh, dims: Dict[str, int]) -> BuiltStep:
    dp_axes = dp_axes_of(mesh)
    # edges shard over the ENTIRE mesh (all axes): message passing is
    # edge-bound, so using only the dp axes left 16x parallelism (and 16x
    # per-device edge memory) on the table (§Perf iteration A2)
    d = tuple(dp_axes) + ("model",)
    init_fn, _ = _gnn_init_and_fwd(cfg)
    params_s = _shapes_of(lambda: init_fn(jax.random.PRNGKey(0), cfg))
    opt = adamw(cosine_schedule(1e-3, 100, 10_000))
    opt_s = _shapes_of(opt.init, params_s)

    n = dims.get("sub_n", dims["n"] * dims.get("batch", 1))
    e = dims.get("sub_e", dims["e"] * dims.get("batch", 1))
    # pad edge count to a lane multiple and the full mesh extent
    ndev = int(np.prod([mesh.shape[a] for a in d]))
    e_pad = -(-e // (128 * ndev)) * (128 * ndev)
    n_total = n
    batch = {
        "feats": jax.ShapeDtypeStruct((n_total, dims["d_feat"]), jnp.float32),
        "edge_src": jax.ShapeDtypeStruct((e_pad,), jnp.int32),
        "edge_dst": jax.ShapeDtypeStruct((e_pad,), jnp.int32),
    }
    bspec = {"feats": P(), "edge_src": P(d), "edge_dst": P(d)}
    if cfg.kind == "gcn":
        batch["edge_w"] = jax.ShapeDtypeStruct((e_pad,), jnp.float32)
        bspec["edge_w"] = P(d)
    if cfg.kind == "meshgraphnet":
        batch["edge_feats"] = jax.ShapeDtypeStruct((e_pad, 3), jnp.float32)
        batch["targets"] = jax.ShapeDtypeStruct((n_total, cfg.d_out), jnp.float32)
        bspec["edge_feats"] = P(d, None)
        bspec["targets"] = P()
    else:
        batch["labels"] = jax.ShapeDtypeStruct((n_total,), jnp.int32)
        batch["label_mask"] = jax.ShapeDtypeStruct((n_total,), jnp.float32)
        bspec["labels"] = P()
        bspec["label_mask"] = P()

    # node states shard over the full mesh too: replicated [N, d] carries
    # were the residual memory hog on ogb_products (§Perf iteration A3)
    node_spec = P(d, None)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: gnn_loss(p, batch, cfg, n_total, node_spec=node_spec)
        )(params)
        params, opt_state, gnorm = opt.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, "gnorm": gnorm}

    pspec = jax.tree_util.tree_map(lambda _: P(), params_s)
    ospec = SR.opt_state_specs(pspec, opt_s)
    return BuiltStep(
        fn=train_step,
        args=(params_s, opt_s, batch),
        in_shardings=(_named(mesh, pspec), _named(mesh, ospec), _named(mesh, bspec)),
        out_shardings=(
            _named(mesh, pspec),
            _named(mesh, ospec),
            {"loss": NamedSharding(mesh, P()), "gnorm": NamedSharding(mesh, P())},
        ),
        donate_argnums=(0, 1),
    )


# ---------------------------------------------------------------------- #
#  recsys family
# ---------------------------------------------------------------------- #
def build_fm_step(cfg: R.FMConfig, mesh, case_kind: str, dims) -> BuiltStep:
    dp_axes = dp_axes_of(mesh)
    d = _dp_spec(dp_axes)
    params_s = _shapes_of(lambda: R.init(jax.random.PRNGKey(0), cfg))
    pspec = {"emb": P("model", None), "w1": P("model"), "bias": P()}

    if case_kind == "train":
        opt = adamw(cosine_schedule(1e-3, 100, 10_000))
        opt_s = _shapes_of(opt.init, params_s)
        batch = {
            "x": jax.ShapeDtypeStruct((dims["batch"], cfg.n_fields), jnp.int32),
            "y": jax.ShapeDtypeStruct((dims["batch"],), jnp.float32),
        }
        bspec = {"x": P(d, None), "y": P(d)}

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(lambda p: R.loss_fn(p, batch, cfg))(params)
            params, opt_state, gnorm = opt.update(grads, opt_state, params)
            return params, opt_state, {"loss": loss, "gnorm": gnorm}

        ospec = SR.opt_state_specs(pspec, opt_s)
        return BuiltStep(
            fn=train_step,
            args=(params_s, opt_s, batch),
            in_shardings=(_named(mesh, pspec), _named(mesh, ospec), _named(mesh, bspec)),
            out_shardings=(
                _named(mesh, pspec),
                _named(mesh, ospec),
                {"loss": NamedSharding(mesh, P()), "gnorm": NamedSharding(mesh, P())},
            ),
            donate_argnums=(0, 1),
        )
    if case_kind == "serve":
        x = jax.ShapeDtypeStruct((dims["batch"], cfg.n_fields), jnp.int32)

        def serve_step(params, x):
            return R.forward(params, x, cfg)

        return BuiltStep(
            fn=serve_step,
            args=(params_s, x),
            in_shardings=(_named(mesh, pspec), NamedSharding(mesh, P(d, None))),
            out_shardings=NamedSharding(mesh, P(d)),
        )
    if case_kind == "retrieval":
        x = jax.ShapeDtypeStruct((1, cfg.n_fields), jnp.int32)
        cand = jax.ShapeDtypeStruct((dims["n_candidates"],), jnp.int32)

        def retrieve(params, x, cand_rows):
            return R.retrieval_scores(params, x, cand_rows, cfg)

        return BuiltStep(
            fn=retrieve,
            args=(params_s, x, cand),
            in_shardings=(
                _named(mesh, pspec),
                NamedSharding(mesh, P(None, None)),
                NamedSharding(mesh, P(d)),
            ),
            out_shardings=NamedSharding(mesh, P(d)),
        )
    raise ValueError(case_kind)


# ---------------------------------------------------------------------- #
#  paper-gwq family: the sharded window-query data plane
# ---------------------------------------------------------------------- #
def build_gwq_step(plan_dims: Dict[str, int], mesh) -> BuiltStep:
    """Sharded two-stage DBIndex query at production scale.

    plan_dims: n (vertices), nb (blocks), m (member rows), l (link rows).
    Inputs are the tile-plan arrays as ShapeDtypeStructs; the step is the
    shard_map'd two-pass segment-sum with psum combine (engine_jax).
    """
    dp_axes = dp_axes_of(mesh)
    d = _dp_spec(dp_axes)
    n, nb = plan_dims["n"], plan_dims["nb"]
    m, l = plan_dims["m"], plan_dims["l"]
    ndev = int(np.prod([mesh.shape[a] for a in dp_axes]))
    m_pad = -(-m // (128 * ndev)) * (128 * ndev)
    l_pad = -(-l // (128 * ndev)) * (128 * ndev)

    args = (
        jax.ShapeDtypeStruct((m_pad,), jnp.int32),  # p1 gather (member ids)
        jax.ShapeDtypeStruct((m_pad,), jnp.int32),  # p1 seg (block ids)
        jax.ShapeDtypeStruct((l_pad,), jnp.int32),  # p2 gather (block ids)
        jax.ShapeDtypeStruct((l_pad,), jnp.int32),  # p2 seg (owner ids)
        jax.ShapeDtypeStruct((n,), jnp.float32),  # vertex attribute
    )

    bf = plan_dims.get("boundary_frac")

    def gwq_query(p1g, p1s, p2g, p2s, vals):
        ok1 = p1s >= 0
        t = jax.ops.segment_sum(
            jnp.where(ok1, jnp.take(vals, p1g), 0.0),
            jnp.where(ok1, p1s, nb),
            num_segments=nb + 1,
        )[:nb]
        ok2 = p2s >= 0
        out = jax.ops.segment_sum(
            jnp.where(ok2, jnp.take(t, p2g), 0.0),
            jnp.where(ok2, p2s, n),
            num_segments=n + 1,
        )[:n]
        return out

    def gwq_query_partitioned(p1g, p1s, p2g, p2s, vals):
        """Blocks/owners co-located with their rows (MinHash clusters are
        locality groups): pass-1/pass-2 segment sums run shard-locally
        under shard_map; only the 1/bf boundary slices are psum'd."""
        nb_b = nb // bf
        n_b = n // bf
        nb_loc = nb - nb_b
        n_loc = n - n_b

        def local(p1g_l, p1s_l, p2g_l, p2s_l, vals_l):
            ok1 = p1s_l >= 0
            t_all = jax.ops.segment_sum(
                jnp.where(ok1, jnp.take(vals_l, p1g_l), 0.0),
                jnp.where(ok1, p1s_l, nb),
                num_segments=nb + 1,
            )[:nb]
            # interior blocks stay local; boundary slice is combined
            t_boundary = jax.lax.psum(t_all[nb_loc:], axes)
            t = jnp.concatenate([t_all[:nb_loc], t_boundary])
            ok2 = p2s_l >= 0
            out_all = jax.ops.segment_sum(
                jnp.where(ok2, jnp.take(t, p2g_l), 0.0),
                jnp.where(ok2, p2s_l, n),
                num_segments=n + 1,
            )[:n]
            out_boundary = jax.lax.psum(out_all[n_loc:], axes)
            return jnp.concatenate([out_all[:n_loc], out_boundary])

        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axes), P(axes), P(axes), P(axes), P()),
            out_specs=P(), check_vma=False,
        )
        return fn(p1g, p1s, p2g, p2s, vals)

    axes = (d,) if isinstance(d, str) else tuple(d)
    row = NamedSharding(mesh, P(d))
    rep = NamedSharding(mesh, P())
    return BuiltStep(
        fn=gwq_query_partitioned if bf else gwq_query,
        args=args,
        in_shardings=(row, row, row, row, rep),
        out_shardings=rep,
    )
