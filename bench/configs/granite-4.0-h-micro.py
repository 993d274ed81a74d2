"""Plain reference of granite-4.0-h-micro as the cell runs it: the first
``num_hidden_layers`` layers of ``layer_types`` (one period: five Mamba-2
layers, one attention layer, four Mamba-2 layers), a dense SwiGLU MLP after
every mixer, tied embeddings over the cell's vocabulary slice, trained by
plain SGD on next-token cross entropy.  Written from the published
description (HF ``GraniteMoeHybrid`` with 0 experts, Mamba-2 §3), in
``jax.numpy`` and float32, every matrix product through ``M``:

    x      = 12 * embed[tokens]                       (embedding_multiplier)
    layer  : x = x + 0.22 * mixer(rms(x)),            (residual_multiplier)
             x = x + 0.22 * mlp(rms(x))
    mamba  : [z | x B C | dt] = h in_proj;  xBC = silu(causal conv4(xBC) + b)
             dt = softplus(dt + dt_bias);  A = -exp(A_log)
             y  = (L o C B^T) (dt x) + D x,  L[i,j] = exp(sum_{j<k<=i} dt_k A)
             out = out_proj( rms_gated(y, z) )        (rms(y * silu(z)))
    attn   : NoPE causal GQA, 32 query / 8 KV heads of 64, scale 1/64
    mlp    : (silu(h wg) * (h wu)) wo
    logits = rms(x) embed^T / 8                       (logits_scaling)
    loss   = mean_{b,s} -log softmax(logits)[token_{s+1}]

The SSD is the quadratic masked form ``y = (L o C B^T) X`` over the whole
sequence, mapped over blocks of ``HEAD_BLOCK`` heads with each block
recomputed in the backward pass, and ``L`` from the stable segment sum
(each entry sums only its own segment of ``dt A``); it does not use the
program's chunked algorithm.  Attention is mapped over the KV heads the
same way.  Every layer is recomputed in the backward pass.

Departures from HF, all of storage or of the cell's cut, none of the
mathematics: RMSNorm scales are stored as ``w - 1`` (zeros are the
identity), as the repo stores them; a parameter is named
``layers.<l>.mix.<leaf>`` / ``layers.<l>.ffn.<leaf>`` with the program's
leaf names and shapes (``wq`` ``[d, H, 64]``, ``in_proj`` ``[d, 2 di + 2 N +
H]`` in the order z, x, B, C, dt); the vocabulary is the cell's slice and
the depth its period (``reduced``); the weights are random from the seed.
Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

#: Mamba-2 heads per block of the quadratic SSD (and query heads per KV
#: group in attention): a block's [B, 4, S, S] decay fits beside the
#: rest of a step on one chip
HEAD_BLOCK = 4


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    return {"d": d, "di": di, "n": cfg["mamba_d_state"],
            "nh": cfg["mamba_n_heads"], "p": cfg["mamba_d_head"],
            "k": cfg["mamba_d_conv"], "h": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"],
            "hd": d // cfg["num_attention_heads"],
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"]}


def kinds(cfg: dict) -> list:
    """The kind of each layer the cell runs."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def shapes(cfg: dict) -> dict:
    """Every parameter's name and shape."""
    g = _dims(cfg)
    d, di, n, nh = g["d"], g["di"], g["n"], g["nh"]
    out = {"embed": (g["v"], d), "final_norm": (d,)}
    for l, kind in enumerate(kinds(cfg)):
        m = f"layers.{l}.mix."
        out[m + "norm"] = (d,)
        if kind == "mamba":
            out.update({m + "in_proj": (d, 2 * di + 2 * n + nh),
                        m + "conv_w": (g["k"], di + 2 * n),
                        m + "conv_b": (di + 2 * n,), m + "A_log": (nh,),
                        m + "D": (nh,), m + "dt_bias": (nh,),
                        m + "gate_norm": (di,), m + "out_proj": (di, d)})
        else:
            out.update({m + "wq": (d, g["h"], g["hd"]),
                        m + "wk": (d, g["kv"], g["hd"]),
                        m + "wv": (d, g["kv"], g["hd"]),
                        m + "wo": (g["h"], g["hd"], d)})
        f = f"layers.{l}.ffn."
        out.update({f + "norm": (d,), f + "wi_gate": (d, g["f"]),
                    f + "wi_up": (d, g["f"]), f + "wo": (g["f"], d)})
    return out


def init(cfg: dict, seed: int) -> dict:
    """Random float32 parameters: fan-in truncated normals for the
    projections, N(0, 0.02) embeddings, zero norms and conv bias, the
    Mamba-2 defaults for A_log (log 1..H), D (1) and dt_bias (softplus^-1
    of dt log-uniform in [1e-3, 0.1]).  Leaf ``i`` (in ``shapes`` order)
    draws from its own stream ``[seed, i]``, so the leaves are drawn at
    once on the host's cores."""
    import concurrent.futures
    import os

    def leaf(item):
        i, (name, shape) = item
        rng = np.random.default_rng([seed, i])
        kind = name.rsplit(".", 1)[-1]
        if kind in ("norm", "final_norm", "gate_norm", "conv_b"):
            return name, np.zeros(shape, np.float32)
        if kind == "embed":
            return name, 0.02 * rng.standard_normal(shape, np.float32)
        if kind == "A_log":
            return name, np.log(np.arange(1, shape[0] + 1, dtype=np.float32))
        if kind == "D":
            return name, np.ones(shape, np.float32)
        if kind == "dt_bias":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), shape))
            return name, (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        fan_in = (shape[0] * shape[1] if kind == "wo" and len(shape) == 3
                  else shape[0])
        w = np.clip(rng.standard_normal(shape, np.float32), -2.0, 2.0)
        w *= np.float32(1.0 / np.sqrt(fan_in))
        return name, w

    with concurrent.futures.ThreadPoolExecutor(os.cpu_count()) as pool:
        return dict(pool.map(leaf, enumerate(shapes(cfg).items())))


# -- the model ----------------------------------------------------------------


def _rms(w, x, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _ssd(M, xs, da, bm, cm):
    """The quadratic SSD: ``xs`` [B, S, H, P] (dt x), ``da`` [B, S, H]
    (dt A), ``bm``/``cm`` [B, S, N]; y[i] = sum_{j<=i} L[i,j] (C_i.B_j)
    x_j, mapped over blocks of heads."""
    import jax
    import jax.numpy as jnp
    b, s, h, p = xs.shape
    nb = h // HEAD_BLOCK
    g = M.einsum("bin,bjn->bij", cm, bm)                       # [B, S, S]
    i = jnp.arange(s)
    causal = i[:, None] >= i[None, :]
    after = i[:, None] > i[None, :]                            # k > j

    def block(args):
        xb, ab = args                          # [B, S, hb, P], [B, S, hb]
        a = ab.transpose(0, 2, 1)[..., :, None]                # [B,hb,S,1]
        # seg[i, j] = sum over j < k <= i of a[k]: a cumulative sum down
        # the rows of the strictly lower part, each entry its own segment
        seg = jnp.cumsum(jnp.where(after, a, 0.0), axis=-2)
        ell = jnp.where(causal, jnp.exp(seg), 0.0)             # [B,hb,S,S]
        return M.einsum("bhij,bjhp->bihp", ell * g[:, None], xb)

    xb = xs.reshape(b, s, nb, HEAD_BLOCK, p).transpose(2, 0, 1, 3, 4)
    ab = da.reshape(b, s, nb, HEAD_BLOCK).transpose(2, 0, 1, 3)
    y = jax.lax.map(jax.checkpoint(block), (xb, ab))
    return y.transpose(1, 2, 0, 3, 4).reshape(b, s, h, p)


def _mamba(M, cfg, p, pre, x):
    import jax
    import jax.numpy as jnp
    g = _dims(cfg)
    di, n, nh, hp, k = g["di"], g["n"], g["nh"], g["p"], g["k"]
    b, s, _ = x.shape
    zxbcdt = M.einsum("bsd,de->bse", x, p[pre + "in_proj"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                  zxbcdt[..., 2 * di + 2 * n:])
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    w = p[pre + "conv_w"]
    conv = sum(xp[:, t:t + s] * w[t] for t in range(k))
    xbc = jax.nn.silu(conv + p[pre + "conv_b"])
    xs, bm, cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p[pre + "dt_bias"])              # [B, S, H]
    a = -jnp.exp(p[pre + "A_log"])
    xh = xs.reshape(b, s, nh, hp)
    y = _ssd(M, xh * dt[..., None], dt * a, bm, cm)
    y = (y + xh * p[pre + "D"][:, None]).reshape(b, s, di)
    y = _rms(p[pre + "gate_norm"], y * jax.nn.silu(z),
             cfg["rms_norm_eps"])
    return M.einsum("bse,ed->bsd", y, p[pre + "out_proj"])


def _attention(M, cfg, p, pre, x):
    import jax
    import jax.numpy as jnp
    g = _dims(cfg)
    b, s, _ = x.shape
    q = M.einsum("bsd,dhk->bshk", x, p[pre + "wq"])
    kk = M.einsum("bsd,dhk->bshk", x, p[pre + "wk"])
    v = M.einsum("bsd,dhk->bshk", x, p[pre + "wv"])
    grp = g["h"] // g["kv"]
    i = jnp.arange(s)
    causal = i[:, None] >= i[None, :]
    scale = cfg["attention_multiplier"]

    def block(args):                   # one KV head and its query heads
        qb, kb, vb = args              # [B, S, G, hd], [B, S, hd] x 2
        logits = M.einsum("bsgd,btd->bgst", qb, kb) * scale
        probs = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
        return M.einsum("bgst,btd->bsgd", probs, vb)

    qg = q.reshape(b, s, g["kv"], grp, g["hd"]).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(jax.checkpoint(block), (
        qg, kk.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, s, g["h"], g["hd"])
    return M.einsum("bshk,hkd->bsd", out, p[pre + "wo"])


def _mlp(M, p, pre, x):
    import jax
    return M.mm(jax.nn.silu(M.mm(x, p[pre + "wi_gate"]))
                * M.mm(x, p[pre + "wi_up"]), p[pre + "wo"])


def logits(M, cfg: dict, p: dict, tokens):
    """The logits ``[B, S, V]`` of ``tokens`` [B, S]."""
    import jax
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = p["embed"][tokens] * cfg["embedding_multiplier"]

    def layer(x, lp, l, kind):
        mixer = _mamba if kind == "mamba" else _attention
        pre = f"layers.{l}."
        x = x + res * mixer(M, cfg, lp, pre + "mix.",
                            _rms(lp[pre + "mix.norm"], x, eps))
        return x + res * _mlp(M, lp, pre + "ffn.",
                              _rms(lp[pre + "ffn.norm"], x, eps))

    for l, kind in enumerate(kinds(cfg)):
        pre = f"layers.{l}."
        lp = {k: v for k, v in p.items() if k.startswith(pre)}
        x = jax.checkpoint(layer, static_argnums=(2, 3))(x, lp, l, kind)
    x = _rms(p["final_norm"], x, eps)
    return M.einsum("bsd,vd->bsv", x, p["embed"]) / cfg["logits_scaling"]


def loss(M, cfg: dict, p: dict, tokens):
    """Mean next-token cross entropy of ``tokens`` [B, S+1]."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits(M, cfg, p, tokens[:, :-1]), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def edge_step(M, cfg: dict, p: dict, tokens) -> dict:
    """One local SGD step of one edge on ``tokens`` [B, S+1]."""
    import jax
    g = jax.grad(lambda q: loss(M, cfg, q, tokens))(p)
    return {k: p[k] - cfg["lr"] * g[k] for k in p}


def eval_metric(M, cfg: dict, p: dict, tokens):
    """The in-run metric: the held-out rows' negative loss."""
    return -loss(M, cfg, p, tokens)


def weighted_sum(acc, p: dict, w) -> dict:
    """``acc + w p`` leaf by leaf (``acc`` ``None``: ``w p``)."""
    if acc is None:
        return {k: w * v for k, v in p.items()}
    return {k: acc[k] + w * p[k] for k in p}


# -- counts -------------------------------------------------------------------


def _forward_flops(cfg: dict, tokens_per_row: int, rows: int) -> float:
    """One forward pass over ``rows`` rows of ``tokens_per_row`` tokens:
    every projection (2 per weight per token), causal attention (QK^T and
    PV over the positions before each query), and the SSD in its chunked
    dual form (the state work and the within-chunk products, ``C B^T``
    once per chunk: one group)."""
    g = _dims(cfg)
    s, t = tokens_per_row, tokens_per_row * rows
    ch = min(cfg["mamba_chunk_size"], s)
    proj = g["d"] * g["v"]                                   # logits
    attn = ssd = 0.0
    for kind in kinds(cfg):
        proj += 3 * g["d"] * g["f"]
        if kind == "mamba":
            proj += g["d"] * (2 * g["di"] + 2 * g["n"] + g["nh"])
            proj += g["di"] * g["d"]
            ssd += t * (2 * ch * g["n"]
                        + g["nh"] * (2 * ch * g["p"] + 4 * g["p"] * g["n"]))
        else:
            proj += 2 * g["d"] * g["h"] * g["hd"]
            proj += 2 * g["d"] * g["kv"] * g["hd"]
            attn += 2.0 * 2 * g["h"] * g["hd"] * t * (s + 1) / 2
    return 2.0 * proj * t + attn + ssd


def step_flops(cfg: dict) -> float:
    """One local step on one edge: forward and backward (twice the
    forward) over ``batch`` rows of ``seq_len`` tokens."""
    return 3.0 * _forward_flops(cfg, cfg["data"]["seq_len"], cfg["batch"])


def n_params(cfg: dict) -> int:
    return int(sum(np.prod(s) for s in shapes(cfg).values()))


def eval_flops(cfg: dict) -> float:
    """The per-aggregation metric: a forward over the held-out rows."""
    return _forward_flops(cfg, cfg["data"]["seq_len"],
                          cfg["data"]["held_out"])
