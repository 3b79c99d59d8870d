"""Step builders + abstract input specs + shardings for the launcher.

Everything here is mesh-agnostic until called under ``sharding.use_sharding``
— the dry-run, the trainer and the server all share these builders.

Steps:
  train_step(params, opt_state, batch)   -> (params, opt_state, loss)
  prefill_step(params, batch)            -> logits        (inference prefill)
  serve_step(params, cache, batch)       -> (logits, cache)  (1-token decode)

``grad_sync``:
  "auto"     — plain pjit; XLA inserts the cross-replica reductions.
  "anycost"  — partial-manual shard_map over the "pod" axis with the
               paper-derived compressed collective (core/distributed.py).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shd
from repro.configs.base import ArchConfig, InputShape
from repro.core.distributed import anycost_gradient_sync
from repro.models import layers as L
from repro.models.registry import Model, loss_fn
from repro.train.optimizer import Optimizer

PyTree = Any


# -------------------------------------------------------------- input specs

def batch_logical_axes(cfg: ArchConfig, shape: InputShape) -> dict:
    axes = {"tokens": ("batch", "seq")}
    if cfg.family == "vlm" and shape.kind != "decode":
        axes["patch_embeds"] = ("batch", "patches", "embed")
    if cfg.family == "encdec" and shape.kind != "decode":
        axes["frames"] = ("batch", "frames", "embed")
    return axes


def input_specs(cfg: ArchConfig, shape: InputShape) -> dict:
    """ShapeDtypeStruct stand-ins for the step's batch (no allocation)."""
    B = shape.global_batch
    S = 1 if shape.kind == "decode" else shape.seq_len
    specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.family == "vlm" and shape.kind != "decode":
        v = cfg.vlm
        specs["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, v.n_patches, v.patch_embed_dim), cfg.param_dtype)
    if cfg.family == "encdec" and shape.kind != "decode":
        e = cfg.encdec
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, e.n_frames, cfg.d_model), cfg.param_dtype)
    return specs


def abstract_cache(model: Model, shape: InputShape):
    return jax.eval_shape(
        functools.partial(model.init_cache, shape.global_batch,
                          shape.seq_len))


# --------------------------------------------------------------- shardings

def _axes_leaf(x):
    return isinstance(x, L.LogicalAxes)


def param_shardings(model: Model):
    """NamedShardings for params (requires an active sharding context)."""
    axes = model.logical_axes()
    shapes = model.abstract_params()
    return jax.tree.map(
        lambda ax, s: shd.sharding_for(s.shape, ax.names),
        axes, shapes, is_leaf=_axes_leaf)


def opt_state_shardings(opt: Optimizer, model: Model):
    pshard = param_shardings(model)
    abstract = jax.eval_shape(opt.init, model.abstract_params())
    out = {}
    for k, v in abstract.items():
        if k in ("m", "v"):
            out[k] = pshard
        else:
            out[k] = shd.sharding_for((), ())
    return out


def batch_shardings(cfg: ArchConfig, shape: InputShape):
    specs = input_specs(cfg, shape)
    axes = batch_logical_axes(cfg, shape)
    return {k: shd.sharding_for(specs[k].shape, axes[k]) for k in specs}


def _cache_leaf_axes(path: str, ndim: int) -> tuple:
    """Structural logical axes for KV/state cache leaves (stacked layers)."""
    last = path.split(".")[-1]
    if last == "pos":
        return ()
    if last == "k_pos":
        return ("layers", "cache_seq")[-ndim:]
    if last in ("k", "v"):
        return ("layers", "batch", "cache_seq", "kv_heads",
                "head_dim")[-ndim:]
    if last == "h":                       # ssm (L,B,di,N) vs rglru (L,B,W)
        return ("layers", "batch", "inner_act", "state") if ndim == 4 \
            else ("layers", "batch", "inner_act")[-ndim:]
    if last == "conv":
        return ("layers", "batch", None, "inner_act")[-ndim:]
    return tuple([None] * ndim)


def cache_shardings(model: Model, shape: InputShape):
    abstract = abstract_cache(model, shape)

    def walk(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}{k}.") for k, v in tree.items()}
        axes = _cache_leaf_axes(prefix[:-1], tree.ndim)
        return shd.sharding_for(tree.shape, axes)

    return walk(abstract)


# ------------------------------------------------------------------- steps

def make_train_step(model: Model, opt: Optimizer, *, remat: str = "full",
                    causal_skip: bool = False, grad_sync: str = "auto",
                    keep_frac: float = 1.0 / 16.0, mesh=None):
    cfg = model.cfg

    def loss_of(params, batch):
        return loss_fn(model, params, batch, remat=remat,
                       causal_skip=causal_skip)

    if grad_sync == "auto":
        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
            new_params, new_opt = opt.update(params, grads, opt_state)
            return new_params, new_opt, loss

        return train_step

    if grad_sync == "anycost":
        assert mesh is not None, "anycost sync needs the mesh"
        axes_tree = model.logical_axes()

        def train_step(params, opt_state, batch):
            def per_pod(params, batch):
                loss, grads = jax.value_and_grad(loss_of)(params, batch)
                grads = anycost_gradient_sync(grads, "pod",
                                              keep_frac=keep_frac,
                                              axes_tree=axes_tree)
                loss = jax.lax.pmean(loss, "pod")
                return loss, grads

            # partial-manual: only the pod axis is manual; data/model stay
            # under GSPMD. params replicated over pod; batch sharded on it.
            loss, grads = shard_map(
                per_pod, mesh=mesh, axis_names=frozenset({"pod"}),
                in_specs=(jax.tree.map(lambda _: P(), params),
                          jax.tree.map(lambda _: P("pod"), batch)),
                out_specs=(P(), jax.tree.map(lambda _: P(),
                                             model.abstract_params())),
                check_vma=False,
            )(params, batch)
            new_params, new_opt = opt.update(params, grads, opt_state)
            return new_params, new_opt, loss

        return train_step

    raise ValueError(grad_sync)


def grads_spec(model: Model):
    return model.abstract_params()


def make_prefill_step(model: Model, *, causal_skip: bool = False):
    def prefill_step(params, batch):
        return model.forward(params, batch, remat="none",
                             causal_skip=causal_skip)

    return prefill_step


def make_serve_step(model: Model):
    def serve_step(params, cache, batch):
        return model.decode(params, cache, batch)

    return serve_step


# ----------------------------------------------------- dry-run entry points

def rules_for(shape: InputShape, grad_sync: str = "auto") -> dict:
    """Per-shape logical-rule overrides (DESIGN.md §5)."""
    rules = {}
    if grad_sync == "anycost":
        # the pod axis is manual inside the per-pod shard_map; logical
        # rules must not mention it (a dim cannot mix Manual with Auto).
        rules["batch"] = "data"
        # vocab-sharded embedding gathers abort the partitioner inside
        # partial-manual regions (PartitionGather CHECK) — replicate the
        # vocab dim, shard the feature dim over model instead.
        rules["vocab"] = None
        rules["embed_fsdp"] = "model"
        # likewise the loss's label gather on logits sharded over both
        # data and model (vocab): keep the logits vocab dim whole.
        rules["vocab_act"] = None
    if shape.kind == "decode":
        # weight-stationary expert sharding for inference (§Perf P1.2):
        # shard expert d_ff over data instead of ZeRO on the input dim so
        # per-step all-gathers of expert weights disappear.
        rules.update({"expert_in": None, "expert_ff": "data"})
    if shape.kind == "decode" and shape.global_batch == 1:
        # batch unshardable: give the data axis to the KV cache sequence
        # (GSPMD flash-decoding: partial softmax + combine collectives)
        rules.update({"batch": None, "cache_seq": "data"})
    return rules


def make_step_and_args(model: Model, opt: Optional[Optimizer],
                       shape: InputShape, *, remat: str = "full",
                       causal_skip: bool = False, grad_sync: str = "auto",
                       keep_frac: float = 1.0 / 16.0, mesh=None):
    """(callable, abstract args, in_shardings, out_shardings) for jit.lower.

    Must be called inside ``sharding.use_sharding(mesh, rules_for(shape))``.
    """
    cfg = model.cfg
    batch = input_specs(cfg, shape)
    if grad_sync == "anycost":
        # partial-manual shard_map: a dim cannot mix Manual("pod") with
        # Auto("data"); the batch enters pod-sharded only and is data-
        # sharded inside the body via lc (rules must map batch -> "data").
        bshard = {k: NamedSharding(mesh, P("pod"))
                  for k in input_specs(cfg, shape)}
    else:
        bshard = batch_shardings(cfg, shape)
    pshard = param_shardings(model)
    params_abs = model.abstract_params()
    if shape.kind == "train":
        assert opt is not None
        step = make_train_step(model, opt, remat=remat,
                               causal_skip=causal_skip, grad_sync=grad_sync,
                               keep_frac=keep_frac, mesh=mesh)
        opt_abs = jax.eval_shape(opt.init, params_abs)
        oshard = opt_state_shardings(opt, model)
        args = (params_abs, opt_abs, batch)
        in_sh = (pshard, oshard, bshard)
        out_sh = (pshard, oshard, shd.sharding_for((), ()))
        return step, args, in_sh, out_sh
    if shape.kind == "prefill":
        step = make_prefill_step(model, causal_skip=causal_skip)
        logits_sh = shd.sharding_for(
            (shape.global_batch, shape.seq_len, cfg.vocab_size),
            ("batch", "seq", "vocab_act"))
        return step, (params_abs, batch), (pshard, bshard), logits_sh
    if shape.kind == "decode":
        step = make_serve_step(model)
        cache_abs = abstract_cache(model, shape)
        cshard = cache_shardings(model, shape)
        logits_sh = shd.sharding_for(
            (shape.global_batch, 1, cfg.vocab_size),
            ("batch", "seq", "vocab_act"))
        return step, (params_abs, cache_abs, batch), \
            (pshard, cshard, bshard), (logits_sh, cshard)
    raise ValueError(shape.kind)
