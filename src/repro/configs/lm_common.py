"""Shared plumbing for the five LM architectures: shapes, input specs, and
step builders (train / prefill / decode) with production shardings."""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as tf
from repro.optim import AdamWConfig, adamw_update
from repro.optim.adamw import AdamWState
from repro.sharding.rules import (dp_axes, lm_batch_pspecs, lm_cache_pspecs,
                                  lm_param_pspecs)

# (seq_len, global_batch, kind)
LM_SHAPES: Dict[str, Tuple[int, int, str]] = {
    "train_4k": (4096, 256, "train"),
    "prefill_32k": (32768, 32, "prefill"),
    "decode_32k": (32768, 128, "decode"),
    "long_500k": (524288, 1, "decode"),
}


def _shape_dims(shape: str, smoke: bool):
    """(seq, batch, kind); smoke shrinks to CPU-executable sizes."""
    seq, batch, kind = LM_SHAPES[shape]
    if smoke:
        seq, batch = min(seq, 128), min(batch, 4)
    return seq, batch, kind


def lm_input_specs(cfg: tf.TransformerConfig, shape: str,
                   smoke: bool = False) -> dict:
    seq, batch, kind = _shape_dims(shape, smoke)
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    if kind == "train":
        return {"tokens": tok, "labels": tok}
    if kind == "prefill":
        return {"tokens": tok}
    # decode: one new token against a seq-long cache
    return {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
            "cache_len": jax.ShapeDtypeStruct((), jnp.int32)}


def opt_specs(param_specs_tree) -> AdamWState:
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    return AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=jax.tree.map(f32, param_specs_tree),
        nu=jax.tree.map(f32, param_specs_tree),
    )


def opt_pspecs(param_pspecs_tree) -> AdamWState:
    return AdamWState(step=P(),
                      mu=jax.tree.map(lambda p: p, param_pspecs_tree),
                      nu=jax.tree.map(lambda p: p, param_pspecs_tree))


def make_sharded_ce(cfg: tf.TransformerConfig, mesh: Mesh):
    """Vocab-sharded cross-entropy: the LM-head matmul + softmax reductions
    run per vocab shard inside shard_map; only O(B·S) max/sum scalars cross
    the `model` axis — the full (B, S, V) f32 logits are NEVER materialized
    or gathered (they peak at ~40 GB/chip on the train_4k cells otherwise).
    """
    dp = dp_axes(mesh)
    axes = tuple(mesh.axis_names)
    n_model = mesh.shape["model"]

    def body(x_l, head_l, labels_l):
        # x_l: (b_l, S, d) — batch-sharded over dp, replicated over model.
        # head_l: (d_f, V/m) — vocab-sharded; d still FSDP-sharded: gather.
        if dp:
            head_l = jax.lax.all_gather(head_l, dp, axis=0, tiled=True)
        logits = (x_l @ head_l.astype(x_l.dtype)).astype(jnp.float32)
        # global max via all_gather of the (b_l, S) per-shard maxima (pmax
        # has no AD rule; the gathered stats are ~KBs).
        shard_max = jnp.max(logits, -1)                        # (b_l, S)
        gmax = jax.lax.stop_gradient(jnp.max(
            jax.lax.all_gather(shard_max, "model", axis=0), axis=0))
        sumexp = jnp.sum(jnp.exp(logits - gmax[..., None]), -1)
        lse = gmax + jnp.log(jax.lax.psum(sumexp, "model"))

        v_l = logits.shape[-1]
        col = labels_l - jax.lax.axis_index("model") * v_l
        in_shard = (col >= 0) & (col < v_l)
        ll_local = jnp.take_along_axis(
            logits, jnp.clip(col, 0, v_l - 1)[..., None], -1)[..., 0]
        ll = jax.lax.psum(jnp.where(in_shard, ll_local, 0.0), "model")

        total = jax.lax.psum(jnp.sum(lse - ll), axes)
        count = jax.lax.psum(jnp.float32(lse.size), axes)
        return total / count

    F = dp if dp else None

    def loss(params, batch):
        x = tf.forward(cfg, params, batch["tokens"], return_hidden=True)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        head_spec = P(F, "model")   # embed.T of P('model', F) / lm_head
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(dp, None, None), head_spec, P(dp, None)),
                           out_specs=P(), check_vma=False)
        return fn(x, head, batch["labels"])

    return loss


def build_lm_step(cfg: tf.TransformerConfig, shape: str, mesh: Mesh,
                  opt_cfg: AdamWConfig = AdamWConfig(),
                  variant: Tuple[str, ...] = (),
                  smoke_shapes: bool = False):
    """Returns (fn, arg_specs, in_shardings) ready for jax.jit(...).lower().

    variant: perf A/B switches (see EXPERIMENTS.md §Perf).
      "naive_cache"     — decode caches head/dim-sharded instead of the
                          flash-decoding sequence-sharded layout (baseline).
      "tp_only_params"  — params replicated over dp (no FSDP gathers);
                          serving layout for models whose TP shard fits HBM.
      "sharded_ce"      — vocab-sharded distributed-softmax loss: never
                          materializes the (B, S, V) f32 logits.
      "int8_kv"         — decode caches stored int8 with per-(pos, head)
                          scales; dequantized in-register.
    """
    if "int8_kv" in variant and cfg.mla is None:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    seq, batch, kind = _shape_dims(shape, smoke_shapes)
    p_specs = tf.param_specs(cfg)
    p_pspecs = lm_param_pspecs(cfg, mesh,
                               fsdp="tp_only_params" not in variant)
    ns = lambda tree: jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), tree,
        is_leaf=lambda x: isinstance(x, P))
    batch_specs = lm_input_specs(cfg, shape, smoke=smoke_shapes)
    dp = dp_axes(mesh)

    if kind == "train":
        o_specs = opt_specs(p_specs)
        o_pspecs = opt_pspecs(p_pspecs)
        if "sharded_ce" in variant:
            loss_of = make_sharded_ce(cfg, mesh)
        else:
            loss_of = lambda p, b: tf.loss_fn(cfg, p, b)

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: loss_of(p, batch))(params)
            params, opt_state, metrics = adamw_update(
                opt_cfg, params, grads, opt_state)
            return params, opt_state, loss

        # params + opt_state are donated (aliased in-place) in production.
        train_step.donate_argnums = (0, 1)
        args = (p_specs, o_specs, batch_specs)
        shardings = (ns(p_pspecs), ns(o_pspecs),
                     ns(lm_batch_pspecs(mesh)))
        return train_step, args, shardings

    if kind == "prefill":
        def prefill_step(params, batch):
            logits = tf.forward(cfg, params, batch["tokens"])
            return logits[:, -1]

        args = (p_specs, {"tokens": batch_specs["tokens"]})
        shardings = (ns(p_pspecs), {"tokens": NamedSharding(mesh, P(dp, None))})
        return prefill_step, args, shardings

    # decode.  batch=1 (long_500k) seq-shards the cache: sequence parallelism.
    c_specs = tf.cache_specs(cfg, batch, seq)
    c_pspecs = lm_cache_pspecs(cfg, mesh, seq_shard=(batch == 1),
                               model_seq_shard="naive_cache" not in variant)

    def decode_fn(params, cache, batch):
        logits, new_cache = tf.decode_step(
            cfg, params, cache, batch["tokens"], batch["cache_len"])
        return logits, new_cache

    # The KV cache is donated — the decode loop updates it in place; without
    # donation every step would copy the full cache (+2x HBM traffic).
    if "no_donate" not in variant:
        decode_fn.donate_argnums = (1,)

    tok_spec = P(None, None) if batch == 1 else P(dp, None)
    args = (p_specs, c_specs, batch_specs)
    shardings = (ns(p_pspecs), ns(c_pspecs),
                 {"tokens": NamedSharding(mesh, tok_spec),
                  "cache_len": NamedSharding(mesh, P())})
    return decode_fn, args, shardings


@dataclasses.dataclass(frozen=True)
class LMArch:
    arch_id: str
    full_config: Callable[[], tf.TransformerConfig]
    smoke_config: Callable[[], tf.TransformerConfig]
    shapes: Tuple[str, ...]
    skip_notes: Dict[str, str] = dataclasses.field(default_factory=dict)
    family: str = "lm"

    def input_specs(self, shape: str, smoke: bool = False):
        cfg = self.smoke_config() if smoke else self.full_config()
        return lm_input_specs(cfg, shape, smoke=smoke)

    def config(self, smoke: bool = False, n_repeats: int | None = None,
               scan_layers: bool = True) -> tf.TransformerConfig:
        cfg = self.smoke_config() if smoke else self.full_config()
        repl = {}
        if n_repeats is not None:
            repl["n_layers"] = len(cfg.layer_windows) * n_repeats
        if not scan_layers:
            repl["scan_layers"] = False
        return dataclasses.replace(cfg, **repl) if repl else cfg

    def build_step(self, shape: str, mesh: Mesh, smoke: bool = False,
                   n_repeats: int | None = None, scan_layers: bool = True,
                   variant: Tuple[str, ...] = ()):
        """n_repeats + scan_layers=False are the dry-run cost-accounting
        variants: XLA cost_analysis counts while-loop bodies once, so the
        dry-run compiles UNROLLED r=1 and r=2 stacks and extrapolates
        linearly to the full depth."""
        return build_lm_step(self.config(smoke, n_repeats, scan_layers),
                             shape, mesh, variant=variant,
                             smoke_shapes=smoke)
