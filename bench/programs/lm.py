"""The ``lm`` program: a language model's period trained by OL4EL edges
through the program's ``TokenLMExecutor`` and compiled sync engine.

The configuration holds the catalog's ``config`` keys (HF names) with the
cell's cut: ``num_hidden_layers`` layers of ``layer_types`` and a
``vocab_size`` slice, the published values under ``published``.  Every
width, multiplier and the layer pattern must be those of the program's
own configuration of ``arch`` (its smoke variant where the configuration
says ``"variant": "smoke"``), and the published depth and vocabulary its
depth and vocabulary: a mismatch is an error, never a silent resize.  The
program's model is then cut to the cell's depth and slice, in the
configuration's ``dtype``, with every layer unrolled and recomputed in the
backward pass, its Mamba-2 layers on the ``ssd_scan`` kernel.

The benchmark keeps parameters as one flat dict named as the reference
names them (``layers.<l>.mix.<leaf>``, the form its records and check
compare leaf by leaf); ``NamedLeaves`` hands the model the same arrays as
its own tree, a renaming without copies.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np

from benchlib import load_named

KIND = {"mamba": "mamba", "attention": "attn"}


class NamedLeaves:
    """The program's LM with its parameters as a flat dict keyed by the
    reference's names: ``local_step`` and ``token_loss`` (what the engine
    and the executor call) rebuild the model's tree from the dict's
    arrays and flatten its result back."""

    def __init__(self, lm):
        import jax
        self.lm, self.cfg = lm, lm.cfg
        shapes = jax.eval_shape(lm.init, jax.random.key(0))
        paths, self._tree = jax.tree_util.tree_flatten_with_path(shapes)
        self.names = [self._name(p) for p, _ in paths]

    def _name(self, path) -> str:
        keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        if keys[0] == "prefix_layers":
            layer = keys[1]
        elif keys[0] == "groups":
            layer = (len(self.lm.prefix) + keys[1] * len(self.lm.group)
                     + int(keys[2][3:]))
            keys = keys[:2] + keys[3:]
        else:
            return keys[0]
        return f"layers.{layer}." + ".".join(str(k) for k in keys[2:])

    def tree(self, flat: Dict[str, Any]):
        import jax
        return jax.tree_util.tree_unflatten(self._tree,
                                            [flat[n] for n in self.names])

    def flat(self, tree) -> Dict[str, Any]:
        import jax
        return dict(zip(self.names, jax.tree.leaves(tree)))

    def local_step(self, params, batch, lr):
        new, aux = self.lm.local_step(self.tree(params), batch, lr)
        return self.flat(new), aux

    def token_loss(self, params, tokens):
        return self.lm.token_loss(self.tree(params), tokens)

    def init(self, key):
        return self.flat(self.lm.init(key))


def model_config(cfg: dict):
    """The program's configuration of ``cfg["arch"]``, checked against
    the configuration's widths and cut to its depth and slice."""
    from repro.config import get_config, get_smoke_config
    get = get_smoke_config if cfg.get("variant") == "smoke" else get_config
    mc = get(cfg["arch"]).model
    pub = dict(cfg, **cfg.get("published", {}))
    kinds = [KIND[t] for t in cfg["layer_types"]]
    want = {
        "hidden_size": mc.d_model, "intermediate_size": mc.d_ff,
        "num_attention_heads": mc.n_heads,
        "num_key_value_heads": mc.n_kv_heads,
        "mamba_d_state": mc.mamba.d_state, "mamba_d_head": mc.mamba.head_dim,
        "mamba_expand": mc.mamba.expand, "mamba_d_conv": mc.mamba.d_conv,
        "mamba_chunk_size": mc.mamba.chunk_size,
        "mamba_n_heads": mc.mamba.n_heads(mc.d_model),
        "rms_norm_eps": mc.norm_eps, "tie_word_embeddings": mc.tie_embeddings,
        "embedding_multiplier": mc.embedding_multiplier,
        "residual_multiplier": mc.residual_multiplier,
        "attention_multiplier": mc.attention_multiplier,
        "logits_scaling": mc.logits_scaling,
        "position_embedding_type": "rope" if mc.rope else "nope",
        "num_hidden_layers": mc.n_layers, "vocab_size": mc.vocab_size,
        "layer_types": list(mc.layer_kinds())}
    have = dict(pub, layer_types=kinds[:pub["num_hidden_layers"]])
    bad = {k: (have[k], v) for k, v in want.items() if have[k] != v}
    if bad:
        raise ValueError(f"{cfg['name']}: the program's {cfg['arch']} "
                         f"differs from the configuration (config, "
                         f"program): {bad}")
    return dataclasses.replace(
        mc, n_layers=cfg["num_hidden_layers"], vocab_size=cfg["vocab_size"],
        dtype=cfg["dtype"], scan_layers=False, remat=True)


def build(cfg: dict, init: Dict[str, np.ndarray], mesh=None
          ) -> Dict[str, Any]:
    """The executor (token rows from ``datasets/<kind>.py``), the base
    run config and the initial params, left on the host: the session
    places them afresh for each run and hands the trained ones back."""
    del mesh
    from repro.config import OL4ELConfig
    from repro.federated import TokenLMExecutor
    from repro.models import build_model

    model = NamedLeaves(build_model(model_config(cfg), use_ssd_kernel=True))
    if sorted(model.names) != sorted(init):
        raise ValueError(f"{cfg['name']}: the reference's parameters are "
                         "not the program's")
    edges, held = load_named("datasets", cfg["data"]["kind"]).make(cfg)
    ex = TokenLMExecutor(model, [e["tokens"] for e in edges],
                         held["tokens"], batch=cfg["batch"], lr=cfg["lr"])
    base = OL4ELConfig(
        max_interval=cfg["max_interval"], mode="sync", cost_model="fixed",
        policy="ol4el", budget=float(cfg["budget"]),
        comp_cost=float(cfg["comp_cost"]), comm_cost=float(cfg["comm_cost"]),
        heterogeneity=float(cfg["heterogeneity"]), utility=cfg["utility"],
        async_alpha=float(cfg["async_alpha"]), async_batch_k=0,
        ucb_c=float(cfg["ucb_c"]), n_edges=cfg["n_edges"], seed=0)
    return {"executor": ex, "base": base,
            "n_samples": [len(e["tokens"]) for e in edges],
            "init": {k: np.asarray(v, np.float32) for k, v in init.items()},
            "metric": cfg["metric"]}
