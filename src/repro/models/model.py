"""Top-level model: embeddings, stacks, head, loss; train/prefill/decode.

``build_model(cfg)`` → :class:`Model` with explicit param pytrees (schema
ParamSpecs). Three entry modes:

  * ``train``   — tokens (B,S) [+ stub frontend embeddings] → logits (B,S,V)
  * ``prefill`` — builds the decode cache, returns last-position logits
  * ``decode``  — one token per sequence against the cache

Sharding notes: the embedding table is sharded on the *feature* dim
("embed_shard" → model) so lookups are comm-free and the residual gathers
once; the LM head is vocab-sharded so logits stay distributed and the loss
reduces over the sharded vocab axis (partial-sum all-reduce of (B,S) only).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.layers import apply_norm, norm_spec
from repro.models.schema import ParamSpec, axes_tree, init_tree, param_count
from repro.sharding import lac


class Model:
    def __init__(self, cfg):
        self.cfg = cfg

    # ------------------------------------------------------------- params
    def spec(self) -> dict:
        cfg = self.cfg
        d, v = cfg.d_model, cfg.vocab_size
        spec: Dict[str, Any] = {
            "embed": ParamSpec((v, d), ("vocab_table", "embed_shard"), scale=1.0,
                               fan_in_axis=-1),
            "stack": T.stack_spec(cfg, decoder=cfg.encoder_decoder),
            "final_ln": norm_spec(cfg),
        }
        if not cfg.tie_embeddings:
            spec["lm_head"] = ParamSpec((d, v), ("embed", "vocab"))
        if cfg.encoder_decoder:
            spec["enc_stack"] = T.stack_spec(cfg, cfg.num_encoder_layers, decoder=False)
            spec["enc_ln"] = norm_spec(cfg)
        return spec

    def init(self, key: jax.Array):
        return init_tree(self.spec(), key, self.cfg.param_dtype)

    def abstract_params(self):
        from repro.models.schema import abstract_tree

        return abstract_tree(self.spec(), self.cfg.param_dtype)

    def param_axes(self):
        return axes_tree(self.spec())

    def n_params(self) -> int:
        return param_count(self.spec())

    # -------------------------------------------------------------- cache
    def cache_spec(self, batch: int, max_len: int):
        cfg = self.cfg
        c = {
            "stack": T.stack_cache_spec(
                cfg, batch, max_len, decoder=cfg.encoder_decoder
            ),
            "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
        }
        return c

    def init_cache(self, batch: int, max_len: int):
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.cache_spec(batch, max_len)
        )

    def cache_axes(self):
        cfg = self.cfg
        return {
            "stack": T.stack_cache_axes(cfg, decoder=cfg.encoder_decoder),
            "pos": ("cache_batch",),
        }

    # ------------------------------------------------------------ forward
    def _embed(self, params, tokens):
        cfg = self.cfg
        e = params["embed"].astype(cfg.compute_dtype)
        x = jnp.take(e, tokens, axis=0)  # (B,S,D): feature-sharded lookup
        if cfg.embedding_multiplier is not None:
            x = x * cfg.embedding_multiplier
        return lac(x, "batch", "act_seq", "embed_shard")

    def _head(self, params, x):
        cfg = self.cfg
        x = apply_norm(params["final_ln"], x)
        if cfg.tie_embeddings:
            w = params["embed"].astype(cfg.compute_dtype)  # (V,D)
            logits = jnp.einsum("bsd,vd->bsv", x, w)
        else:
            logits = jnp.einsum(
                "bsd,dv->bsv", x, params["lm_head"].astype(cfg.compute_dtype)
            )
        if cfg.logits_scaling is not None:
            logits = logits / cfg.logits_scaling
        return lac(logits, "batch", "act_seq", "logit_vocab")

    def encode(self, params, frames):
        """frames (B,F,D) stub embeddings → enc_out (B,F,D)."""
        cfg = self.cfg
        x = frames.astype(cfg.compute_dtype)
        pos = jnp.arange(x.shape[1])[None, :]
        x, _, aux = T.apply_stack(
            params["enc_stack"], cfg, x, positions=pos, mode="train", causal=False
        )
        return apply_norm(params["enc_ln"], x), aux

    def apply(
        self,
        params: dict,
        batch: Dict[str, jax.Array],
        *,
        mode: str = "train",
        cache: Optional[dict] = None,
        max_len: Optional[int] = None,
    ):
        """Returns (logits, new_cache, aux).

        batch keys: tokens (B,S) int32; optional frontend (B,F,D) stub
        embeddings (vlm: prepended to the sequence; audio enc-dec: encoder
        input). decode: tokens (B,1) + cache.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B = tokens.shape[0]
        aux = {}

        enc_out = None
        if cfg.encoder_decoder and mode != "decode":
            enc_out, enc_aux = self.encode(params, batch["frontend"])
            aux.update({f"enc_{k}": v for k, v in enc_aux.items()})

        x = self._embed(params, tokens)
        if cfg.frontend == "vision" and mode != "decode":
            fe = batch["frontend"].astype(cfg.compute_dtype)  # (B,F,D) patches
            x = jnp.concatenate([fe, x], axis=1)

        S = x.shape[1]
        if mode == "decode":
            assert cache is not None
            positions = cache["pos"][:, None]  # (B,1)
        else:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

        stack_cache = cache["stack"] if cache is not None else None
        x, new_stack_cache, saux = T.apply_stack(
            params["stack"], cfg, x,
            positions=positions, caches=stack_cache, mode=mode,
            enc_out=enc_out, causal=True, decoder=cfg.encoder_decoder,
            max_len=max_len,
        )
        for k, v in saux.items():
            aux[k] = aux.get(k, 0.0) + v

        new_cache = None
        if mode == "prefill":
            logits = self._head(params, x[:, -1:])  # last position only
            new_cache = {
                "stack": new_stack_cache,
                "pos": jnp.full((B,), S, jnp.int32),
            }
        elif mode == "decode":
            logits = self._head(params, x)
            new_cache = {"stack": new_stack_cache, "pos": cache["pos"] + 1}
        else:
            logits = self._head(params, x)
        return logits, new_cache, aux

    def train_loss(self, params, batch, *, chunk: int = 1024):
        """Memory-lean train loss: backbone → seq-chunked rematerialized
        head+CE (never materializes (B,S,V) logits). Returns (loss, metrics)
        with MoE aux terms folded in."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B = tokens.shape[0]
        aux = {}
        enc_out = None
        if cfg.encoder_decoder:
            enc_out, enc_aux = self.encode(params, batch["frontend"])
            aux.update({f"enc_{k}": v for k, v in enc_aux.items()})
        x = self._embed(params, tokens)
        if cfg.frontend == "vision":
            fe = batch["frontend"].astype(cfg.compute_dtype)
            x = jnp.concatenate([fe, x], axis=1)
        S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x, _, saux = T.apply_stack(
            params["stack"], cfg, x,
            positions=positions, caches=None, mode="train",
            enc_out=enc_out, causal=True, decoder=cfg.encoder_decoder,
        )
        for k, v in saux.items():
            aux[k] = aux.get(k, 0.0) + v
        if cfg.frontend == "vision":
            x = x[:, cfg.frontend_seq :]  # loss over text positions only
        loss, metrics = chunked_lm_loss(
            self, params, x, batch["labels"], batch.get("loss_mask"), chunk=chunk
        )
        for k in ("moe_aux", "moe_z", "enc_moe_aux", "enc_moe_z"):
            if k in aux:
                loss = loss + aux[k]
                metrics[k] = aux[k]
        return loss, metrics


def build_model(cfg) -> Model:
    return Model(cfg)


# ------------------------------------------------------------------- loss
def chunked_lm_loss(
    model: "Model",
    params: dict,
    x: jax.Array,
    labels: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    chunk: int = 1024,
    z_weight: float = 1e-4,
):
    """Head + cross-entropy over sequence chunks, each chunk rematerialized:
    the (B, chunk, V) logits exist only transiently instead of a full
    (B, S, V) buffer (the dominant train-step activation for big vocabs)."""
    B, S, D = x.shape
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    if S % chunk != 0:
        chunk = S  # fallback: single chunk
    nc = S // chunk
    xc = jnp.moveaxis(x.reshape(B, nc, chunk, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
    mc = jnp.moveaxis(mask.astype(jnp.float32).reshape(B, nc, chunk), 1, 0)

    @jax.checkpoint
    def one(args):
        xx, ll, mm = args
        logits = model._head(params, xx)  # (B,chunk,V) vocab-sharded
        V = logits.shape[-1]
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        oh = jax.nn.one_hot(ll, V, dtype=logits.dtype)
        lab = jnp.einsum("bsv,bsv->bs", oh, logits).astype(jnp.float32)
        ce = ((lse - lab) * mm).sum()
        zz = ((lse**2) * mm).sum()
        return ce, zz, mm.sum()

    ces, zzs, cnts = jax.lax.map(one, (xc, lc, mc))
    denom = jnp.maximum(cnts.sum(), 1.0)
    loss = ces.sum() / denom
    zloss = z_weight * zzs.sum() / denom
    return loss + zloss, {"ce": loss, "zloss": zloss}


def lm_loss(
    logits: jax.Array,
    labels: jax.Array,
    mask: Optional[jax.Array] = None,
    z_weight: float = 1e-4,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Cross-entropy with vocab-sharded logits. labels (B,S) int32; mask
    (B,S) {0,1}. Uses one-hot einsum (partitions over sharded vocab without
    gathering logits)."""
    V = logits.shape[-1]
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)  # (B,S)
    oh = jax.nn.one_hot(labels, V, dtype=logits.dtype)
    lab = jnp.einsum("bsv,bsv->bs", oh, logits).astype(jnp.float32)
    ce = lse - lab
    if mask is None:
        mask = jnp.ones_like(ce)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (ce * mask).sum() / denom
    zloss = z_weight * ((lse**2) * mask).sum() / denom
    metrics = {"ce": loss, "zloss": zloss}
    return loss + zloss, metrics
