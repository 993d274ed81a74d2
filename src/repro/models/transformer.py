"""Unified decoder-only language model over the ModelConfig space.

One implementation covers all ten assigned architectures:
  * dense GQA transformers (llama/qwen/minicpm/deepseek-coder family),
  * MoE transformers (olmoe, deepseek-moe: shared+routed, first-k-dense),
  * pure SSM (mamba2), hybrid attn/mamba interleave with MoE (jamba),
  * multimodal backbones (paligemma: prefix patch embeddings; musicgen:
    multi-codebook audio tokens with per-codebook heads).

Layer stacking uses **scan-over-groups**: the per-layer pattern is split
into (unscanned prefix, smallest repeating group); group params are stacked
on a leading axis and applied with ``lax.scan`` — this bounds HLO size and
compile time for the 80 dry-run lowerings regardless of depth, and is what
makes 72-layer Jamba lowering tractable on the CPU host.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ATTN, DENSE_FFN, MAMBA, MOE_FFN, NO_FFN, ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MoE

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Layer-group decomposition
# ---------------------------------------------------------------------------


def layer_groups(cfg: ModelConfig
                 ) -> Tuple[Tuple, Tuple, int]:
    """Split block pattern into (prefix, group, n_groups).

    ``prefix`` layers are applied unscanned; the remaining layers are
    ``n_groups`` repetitions of ``group``.  Minimizes the number of
    *unrolled* layers (prefix + group size) so HLO size stays bounded,
    breaking ties with the shortest prefix.
    """
    pattern = cfg.block_pattern()
    n = len(pattern)
    best = None
    for p in range(n + 1):
        rest = pattern[p:]
        if not rest:
            cand = (10 ** 9, p)   # all-prefix fallback: never preferred
            g = 0
        else:
            g = next(gg for gg in range(1, len(rest) + 1)
                     if len(rest) % gg == 0
                     and rest == rest[:gg] * (len(rest) // gg))
            cand = (p + g, p)
        if best is None or cand < best:
            best = cand
            best_split = (pattern[:p], rest[:g] if rest else (),
                          (len(rest) // g) if rest else 0)
    return best_split


# ---------------------------------------------------------------------------
# Single block (mixer + optional FFN)
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, kind: str, ffn: str) -> Params:
    k_mix, k_ffn = jax.random.split(key)
    p: Params = {}
    if kind == ATTN:
        p["mix"] = L.init_attention(k_mix, cfg)
    else:
        p["mix"] = M.init_mamba(k_mix, cfg)
    if ffn == DENSE_FFN:
        p["ffn"] = L.init_mlp(k_ffn, cfg.d_model, cfg.d_ff)
    elif ffn == MOE_FFN:
        p["ffn"] = MoE.init_moe(k_ffn, cfg)
    return p


def _zero_aux() -> Dict[str, jax.Array]:
    z = jnp.zeros((), jnp.float32)
    return {"load_balance_loss": z, "router_z_loss": z, "expert_frac_max": z,
            "n_moe": z}


def _residual(cfg: ModelConfig, y: jax.Array) -> jax.Array:
    """A mixer's or FFN's output as it joins the residual stream."""
    if cfg.residual_multiplier != 1.0:
        return y * cfg.residual_multiplier
    return y


def apply_block(p: Params, cfg: ModelConfig, kind: str, ffn: str,
                x: jax.Array, positions: jax.Array,
                attn_impl: str = "auto", window_slice: bool = False,
                use_ssd_kernel: bool = False
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    aux = _zero_aux()
    h = L.rms_norm(p["mix"]["norm"], x, cfg.norm_eps)
    if kind == ATTN:
        x = x + _residual(cfg, L.attention(p["mix"], cfg, h, positions,
                                           impl=attn_impl,
                                           window_slice=window_slice))
    else:
        x = x + _residual(cfg, M.mamba_mixer(p["mix"], cfg, h,
                                             use_kernel=use_ssd_kernel))
    if ffn == DENSE_FFN:
        h = L.rms_norm(p["ffn"]["norm"], x, cfg.norm_eps)
        x = x + _residual(cfg, L.mlp(p["ffn"], h, cfg.act_fn))
    elif ffn == MOE_FFN:
        h = L.rms_norm(p["ffn"]["norm"], x, cfg.norm_eps)
        y, moe_aux = MoE.moe_ffn(p["ffn"], cfg, h)
        x = x + _residual(cfg, y)
        for k in ("load_balance_loss", "router_z_loss"):
            aux[k] = aux[k] + moe_aux[k]
        aux["expert_frac_max"] = jnp.maximum(aux["expert_frac_max"],
                                             moe_aux["expert_frac_max"])
        aux["n_moe"] = aux["n_moe"] + 1.0
    return x, aux


def _remat_block(cfg: ModelConfig):
    """``apply_block``, an activation checkpoint of its own when
    ``cfg.remat``: the backward pass keeps each layer's input and
    recomputes one layer at a time."""
    if cfg.remat:
        return jax.checkpoint(apply_block, static_argnums=(1, 2, 3, 6, 7, 8))
    return apply_block


def apply_block_decode(p: Params, cfg: ModelConfig, kind: str, ffn: str,
                       x: jax.Array, cache: Params, index: jax.Array,
                       window_slice: bool = False, ring: bool = False
                       ) -> Tuple[jax.Array, Params]:
    h = L.rms_norm(p["mix"]["norm"], x, cfg.norm_eps)
    if kind == ATTN:
        if ring:
            y, ck, cv = L.attention_decode_ring(p["mix"], cfg, h,
                                                cache["k"], cache["v"],
                                                index)
        else:
            y, ck, cv = L.attention_decode(p["mix"], cfg, h, cache["k"],
                                           cache["v"], index,
                                           window_slice=window_slice)
        x = x + _residual(cfg, y)
        cache = {"k": ck, "v": cv}
    else:
        y, cache = M.mamba_decode(p["mix"], cfg, h, cache)
        x = x + _residual(cfg, y)
    if ffn == DENSE_FFN:
        h = L.rms_norm(p["ffn"]["norm"], x, cfg.norm_eps)
        x = x + _residual(cfg, L.mlp(p["ffn"], h, cfg.act_fn))
    elif ffn == MOE_FFN:
        h = L.rms_norm(p["ffn"]["norm"], x, cfg.norm_eps)
        y, _ = MoE.moe_ffn(p["ffn"], cfg, h)
        x = x + _residual(cfg, y)
    return x, cache


def apply_block_fill(p: Params, cfg: ModelConfig, kind: str, ffn: str,
                     x: jax.Array, positions: jax.Array, cache: Params,
                     attn_impl: str = "auto", window_slice: bool = False,
                     use_ssd_kernel: bool = False, ring: bool = False
                     ) -> Tuple[jax.Array, Params]:
    """Full-sequence block that also fills the decode cache (prefill)."""
    h = L.rms_norm(p["mix"]["norm"], x, cfg.norm_eps)
    if kind == ATTN:
        fill = L.attention_fill_ring if ring else L.attention_fill
        y, ck, cv = fill(p["mix"], cfg, h, positions,
                         cache["k"], cache["v"], impl=attn_impl,
                         window_slice=window_slice)
        x = x + _residual(cfg, y)
        cache = {"k": ck, "v": cv}
    else:
        y, cache = M.mamba_mixer_with_state(p["mix"], cfg, h,
                                            use_kernel=use_ssd_kernel)
        x = x + _residual(cfg, y)
    if ffn == DENSE_FFN:
        h = L.rms_norm(p["ffn"]["norm"], x, cfg.norm_eps)
        x = x + _residual(cfg, L.mlp(p["ffn"], h, cfg.act_fn))
    elif ffn == MOE_FFN:
        h = L.rms_norm(p["ffn"]["norm"], x, cfg.norm_eps)
        y, _ = MoE.moe_ffn(p["ffn"], cfg, h)
        x = x + _residual(cfg, y)
    return x, cache


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype) -> Params:
    if kind == ATTN:
        hd = cfg.resolved_head_dim
        return {
            "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        }
    return M.init_mamba_cache(cfg, batch, dtype)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


class LM:
    """Functional language model: ``params`` pytree in, arrays out."""

    def __init__(self, cfg: ModelConfig, attn_impl: str = "auto",
                 window_slice: bool = False, use_ssd_kernel: bool = False,
                 fused_xent: bool = False, logits_spec=None,
                 ring_cache: bool = False):
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.window_slice = window_slice
        self.use_ssd_kernel = use_ssd_kernel
        # fused_xent: compute CE as logsumexp - label logit (one-hot
        # contraction) instead of log_softmax + gather.  With the vocab dim
        # sharded over `model`, the gather forces XLA to ALL-GATHER the
        # full [tokens, vocab] logits; the contraction form partitions into
        # per-shard reductions + a scalar psum (EXPERIMENTS.md §Perf).
        self.fused_xent = fused_xent
        # logits_spec: optional PartitionSpec pinned onto the pre-loss
        # logits (vocab over `model`) so the partitioner keeps the CE
        # reduction sharded instead of all-gathering [tokens, vocab].
        self.logits_spec = logits_spec
        # ring_cache: sliding-window archs keep a rolling KV buffer of
        # length window+1 instead of the full sequence (§Perf).
        self.ring_cache = ring_cache and cfg.sliding_window > 0
        self.prefix, self.group, self.n_groups = layer_groups(cfg)

    # -- init ---------------------------------------------------------------

    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        r_emb, r_head, r_prefix, r_groups = jax.random.split(rng, 4)
        if cfg.n_codebooks > 1:
            embed = L.embed_init(r_emb,
                                 (cfg.n_codebooks, cfg.vocab_size, cfg.d_model))
        else:
            embed = L.embed_init(r_emb, (cfg.vocab_size, cfg.d_model))
        params: Params = {"embed": embed, "final_norm": L.init_rms_norm(cfg.d_model)}
        if not cfg.tie_embeddings:
            if cfg.n_codebooks > 1:
                params["lm_head"] = L.dense_init(
                    r_head, (cfg.n_codebooks, cfg.d_model, cfg.vocab_size),
                    in_axis_size=cfg.d_model)
            else:
                params["lm_head"] = L.dense_init(
                    r_head, (cfg.d_model, cfg.vocab_size))
        if self.prefix:
            keys = jax.random.split(r_prefix, len(self.prefix))
            params["prefix_layers"] = [
                init_block(k, cfg, kind, ffn)
                for k, (kind, ffn) in zip(keys, self.prefix)]
        if self.n_groups:
            gkeys = jax.random.split(r_groups, self.n_groups)

            def one_group(k):
                subkeys = jax.random.split(k, len(self.group))
                return {f"sub{i}": init_block(sk, cfg, kind, ffn)
                        for i, (sk, (kind, ffn))
                        in enumerate(zip(subkeys, self.group))}

            if cfg.scan_layers:
                params["groups"] = jax.vmap(one_group)(gkeys)
            else:
                params["groups"] = [one_group(k) for k in gkeys]
        return params

    # -- embedding ----------------------------------------------------------

    def embed(self, params: Params, tokens: jax.Array,
              prefix_emb: Optional[jax.Array]) -> jax.Array:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        emb = params["embed"]
        if cfg.n_codebooks > 1:
            # tokens: [B, n_cb, S] -> summed codebook embeddings
            cb = jnp.arange(cfg.n_codebooks)[None, :, None]      # [1,CB,1]
            x = jnp.sum(emb.astype(dtype)[cb, tokens], axis=1)   # [B, S, d]
        else:
            x = emb.astype(dtype)[tokens]                        # [B, S, d]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if prefix_emb is not None:
            x = jnp.concatenate([prefix_emb.astype(dtype), x], axis=1)
        return x

    def unembed(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        dtype = x.dtype
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"].astype(dtype))
        elif cfg.n_codebooks > 1:
            logits = jnp.einsum("bsd,cdv->bcsv", x,
                                params["lm_head"].astype(dtype))
        else:
            logits = x @ params["lm_head"].astype(dtype)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits

    # -- forward (train / prefill) -------------------------------------------

    def _group_fn(self, p_group: Params, x: jax.Array, positions: jax.Array
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """The group's layers in turn (``_remat_block``)."""
        cfg = self.cfg
        block = _remat_block(cfg)
        aux = _zero_aux()
        for i, (kind, ffn) in enumerate(self.group):
            x, a = block(p_group[f"sub{i}"], cfg, kind, ffn, x,
                         positions, self.attn_impl, self.window_slice,
                         self.use_ssd_kernel)
            aux = jax.tree.map(jnp.add, aux, a)
        return x, aux

    def forward(self, params: Params, tokens: jax.Array,
                prefix_emb: Optional[jax.Array] = None,
                last_only: bool = False
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x = self.embed(params, tokens, prefix_emb)
        positions = jnp.arange(x.shape[1])
        aux = _zero_aux()
        block = _remat_block(cfg)
        for p_layer, (kind, ffn) in zip(params.get("prefix_layers", []),
                                        self.prefix):
            x, a = block(p_layer, cfg, kind, ffn, x, positions,
                         self.attn_impl, self.window_slice,
                         self.use_ssd_kernel)
            aux = jax.tree.map(jnp.add, aux, a)
        if self.n_groups:
            group_fn = self._group_fn
            if cfg.scan_layers:
                def body(carry, p_group):
                    x, aux = carry
                    x, a = group_fn(p_group, x, positions)
                    return (x, jax.tree.map(jnp.add, aux, a)), None
                (x, aux), _ = lax.scan(body, (x, aux), params["groups"])
            else:
                for p_group in params["groups"]:
                    x, a = group_fn(p_group, x, positions)
                    aux = jax.tree.map(jnp.add, aux, a)
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        if last_only:
            # serving prefill: only the next-token logits are needed —
            # slicing BEFORE the unembedding avoids computing (and
            # all-gathering) the full [B, S, vocab] logits (§Perf).
            x = x[:, -1:]
        logits = self.unembed(params, x)
        return logits, aux

    # -- loss ---------------------------------------------------------------

    def loss(self, params: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Next-token cross-entropy (+ MoE aux). batch: tokens [B,S] or
        [B,CB,S]; optional prefix_emb [B,P,d]; optional loss_mask [B,S-1]."""
        cfg = self.cfg
        tokens = batch["tokens"]
        prefix_emb = batch.get("prefix_emb")
        logits, aux = self.forward(params, tokens, prefix_emb)
        n_prefix = prefix_emb.shape[1] if prefix_emb is not None else 0
        if cfg.n_codebooks > 1:
            pred = logits[:, :, :-1]                        # [B,CB,S-1,V]
            tgt = tokens[:, :, 1:]                          # [B,CB,S-1]
        else:
            pred = logits[:, n_prefix:-1]                   # [B,S-1,V]
            tgt = tokens[:, 1:]
        if self.logits_spec is not None and len(self.logits_spec) == pred.ndim:
            pred = jax.lax.with_sharding_constraint(pred, self.logits_spec)
        if self.fused_xent:
            logits32 = pred.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits32, axis=-1)
            onehot = jax.nn.one_hot(tgt, logits32.shape[-1],
                                    dtype=jnp.float32)
            label_logit = jnp.einsum("...v,...v->...", logits32, onehot)
            nll = lse - label_logit
        else:
            logp = jax.nn.log_softmax(pred.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(nll.shape, jnp.float32)
        else:
            mask = jnp.broadcast_to(mask, nll.shape).astype(jnp.float32)
        ce = jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
        loss = ce
        m = cfg.moe
        if m.enabled:
            loss = loss + m.router_aux_loss * aux["load_balance_loss"]
            loss = loss + m.router_z_loss * aux["router_z_loss"]
        metrics = {"ce_loss": ce, "loss": loss,
                   "load_balance_loss": aux["load_balance_loss"],
                   "router_z_loss": aux["router_z_loss"]}
        return loss, metrics

    def token_loss(self, params: Params, tokens: jax.Array) -> jax.Array:
        """Mean next-token cross entropy of ``tokens`` [B, S+1]: the
        model reads the first S tokens and predicts the last S."""
        logits, _ = self.forward(params, tokens[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll)

    def local_step(self, params: Params, batch: Dict[str, jax.Array],
                   lr: float) -> Tuple[Params, Dict[str, jax.Array]]:
        """One plain SGD step on ``token_loss`` over ``batch["tokens"]``:
        the compiled EL engine's local step (no optimizer state)."""
        loss, g = jax.value_and_grad(self.token_loss)(params,
                                                      batch["tokens"])
        return jax.tree.map(lambda p, d: p - lr * d, params, g), \
            {"loss": loss}

    # -- serving -------------------------------------------------------------

    def init_cache(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg
        if self.ring_cache:
            # ring length == window: slots cover positions
            # (index-window, index] exactly (and divides the mesh axes,
            # unlike window+1)
            max_len = min(max_len, cfg.sliding_window)
        dtype = jnp.dtype(cfg.dtype)
        cache: Params = {"index": jnp.zeros((), jnp.int32)}
        if self.prefix:
            cache["prefix_layers"] = [
                init_block_cache(cfg, kind, batch, max_len, dtype)
                for kind, _ in self.prefix]
        if self.n_groups:
            def one_group(_):
                return {f"sub{i}": init_block_cache(cfg, kind, batch,
                                                    max_len, dtype)
                        for i, (kind, _) in enumerate(self.group)}
            if cfg.scan_layers:
                cache["groups"] = jax.vmap(one_group)(
                    jnp.arange(self.n_groups))
            else:
                cache["groups"] = [one_group(i)
                                   for i in range(self.n_groups)]
        return cache

    def decode_step(self, params: Params, tokens: jax.Array, cache: Params
                    ) -> Tuple[jax.Array, Params]:
        """One-token decode. tokens: [B, 1] (or [B, CB, 1] multi-codebook)."""
        cfg = self.cfg
        index = cache["index"]
        x = self.embed(params, tokens, None)                 # [B, 1, d]
        new_cache: Params = {"index": index + 1}
        if self.prefix:
            new_prefix = []
            for p_layer, c_layer, (kind, ffn) in zip(
                    params.get("prefix_layers", []), cache["prefix_layers"],
                    self.prefix):
                x, c = apply_block_decode(p_layer, cfg, kind, ffn, x,
                                          c_layer, index,
                                          self.window_slice,
                                          self.ring_cache)
                new_prefix.append(c)
            new_cache["prefix_layers"] = new_prefix
        if self.n_groups:
            def body(x, scanned):
                p_group, c_group = scanned
                new_c = {}
                for i, (kind, ffn) in enumerate(self.group):
                    x, c = apply_block_decode(p_group[f"sub{i}"], cfg, kind,
                                              ffn, x, c_group[f"sub{i}"],
                                              index, self.window_slice,
                                              self.ring_cache)
                    new_c[f"sub{i}"] = c
                return x, new_c
            if cfg.scan_layers:
                x, new_groups = lax.scan(body, x,
                                         (params["groups"], cache["groups"]))
            else:
                new_groups = []
                for p_group, c_group in zip(params["groups"],
                                            cache["groups"]):
                    x, c = body(x, (p_group, c_group))
                    new_groups.append(c)
            new_cache["groups"] = new_groups
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self.unembed(params, x)
        return logits, new_cache

    def prefill(self, params: Params, tokens: jax.Array, cache: Params,
                prefix_emb: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Params]:
        """Run the full prompt through the model, filling the decode cache.

        Attention layers write K/V for positions [0, S); SSM layers store
        their final recurrent + conv state.  Returns full-sequence logits
        and the filled cache (index advanced to S).
        """
        cfg = self.cfg
        x = self.embed(params, tokens, prefix_emb)
        s = x.shape[1]
        positions = jnp.arange(s)
        new_cache: Params = {"index": cache["index"] + s}
        if self.prefix:
            new_prefix = []
            for p_layer, c_layer, (kind, ffn) in zip(
                    params.get("prefix_layers", []), cache["prefix_layers"],
                    self.prefix):
                x, c = apply_block_fill(p_layer, cfg, kind, ffn, x,
                                        positions, c_layer, self.attn_impl,
                                        self.window_slice,
                                        self.use_ssd_kernel,
                                        self.ring_cache)
                new_prefix.append(c)
            new_cache["prefix_layers"] = new_prefix
        if self.n_groups:
            def body(x, scanned):
                p_group, c_group = scanned
                new_c = {}
                for i, (kind, ffn) in enumerate(self.group):
                    x, c = apply_block_fill(
                        p_group[f"sub{i}"], cfg, kind, ffn, x, positions,
                        c_group[f"sub{i}"], self.attn_impl,
                        self.window_slice, self.use_ssd_kernel,
                        self.ring_cache)
                    new_c[f"sub{i}"] = c
                return x, new_c
            if cfg.scan_layers:
                x, new_groups = lax.scan(body, x,
                                         (params["groups"], cache["groups"]))
            else:
                new_groups = []
                for p_group, c_group in zip(params["groups"],
                                            cache["groups"]):
                    x, c = body(x, (p_group, c_group))
                    new_groups.append(c)
            new_cache["groups"] = new_groups
        x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self.unembed(params, x)
        return logits, new_cache
