"""The dense pre-norm transformer, in float32.

Token embedding (+ sinusoidal positions, or RoPE inside attention),
pre-norm blocks of causal multi-head attention (grouped key/value heads
repeated) and an MLP (tanh-GELU or SwiGLU), a final norm and an untied
output head; the softmax runs over the padded vocabulary, as
``pad_vocab_to`` in the configuration states; the loss is the mean
next-token cross-entropy over all positions but the last.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.models import META

F32 = jnp.float32
Q_CHUNK = 512


# the configuration keys this reference follows, and those it implements
# at one value only
KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "pad_vocab_to", "norm_kind", "norm_eps",
        "mlp_kind", "pos_kind", "rope_theta", "sliding_window",
        "mlp_output_bias", "dtype")
ONE_VALUE = {"attn_bias": False, "tie_embeddings": False}


def check_config(cfg: dict) -> None:
    """Refuse a configuration that states what this reference does not do."""
    for k, v in cfg.items():
        if k in META or k in KEYS or (k in ONE_VALUE and ONE_VALUE[k] == v):
            continue
        raise ValueError(f"the reference does not implement {k}={v!r}")


def padded_vocab(cfg: dict) -> int:
    m = cfg.get("pad_vocab_to", 1) or 1
    return -(-cfg["vocab_size"] // m) * m


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def param_shapes(cfg: dict) -> dict:
    """The weight layout the reference reads (layers stacked on axis 0)."""
    D, V, L, F = cfg["d_model"], padded_vocab(cfg), cfg["num_layers"], cfg["d_ff"]
    H, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], head_dim(cfg)
    ln = cfg["norm_kind"] == "ln"
    norm = lambda: {"scale": (D,), "bias": (D,)} if ln else {"scale": (D,)}
    attn = {"ln": norm(), "wq": (D, H * hd), "wk": (D, Hk * hd),
            "wv": (D, Hk * hd), "wo": (H * hd, D)}
    if cfg["mlp_kind"] == "gelu":
        mlp = {"ln": norm(), "wi": (D, F), "bi": (F,), "wo": (F, D),
               "bo": (D,)}
    else:
        mlp = {"ln": norm(), "wi": (D, F), "wg": (D, F), "wo": (F, D)}
    stack = lambda t: jax.tree.map(lambda s: (L,) + s, t,
                                   is_leaf=lambda x: isinstance(x, tuple))
    return {"embed": (V, D), "final_norm": norm(),
            "layers": {"l0": stack({"attn": attn, "mlp": mlp})},
            "lm_head": (D, V)}


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul, per model (embedding excluded)."""
    D, H, Hk, hd, F = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       head_dim(cfg), cfg["d_ff"])
    attn = D * H * hd + 2 * D * Hk * hd + H * hd * D
    mlp = (2 if cfg["mlp_kind"] == "gelu" else 3) * D * F
    return cfg["num_layers"] * (attn + mlp) + D * padded_vocab(cfg)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs per trained token."""
    attn = cfg["num_layers"] * 4 * seq_len * cfg["num_heads"] * head_dim(cfg)
    return 3.0 * (2 * matmul_params(cfg) + attn)


# ---------------------------------------------------------------------------
# the model, in float32
# ---------------------------------------------------------------------------

def _norm(cfg, p, x):
    eps = cfg.get("norm_eps", 1e-5)
    if cfg["norm_kind"] == "ln":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def _sinusoid(S, D):
    half = D // 2
    inv = 1.0 / (10000.0 ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _rope(x, theta):
    """Rotate (B, S, H, hd) pairs (i, i + hd/2) by position * theta^(-i/half)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(cfg, p, x):
    B, S, D = x.shape
    H, Hk, hd = cfg["num_heads"], cfg["num_kv_heads"], head_dim(cfg)
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, Hk, hd)
    v = (x @ p["wv"]).reshape(B, S, Hk, hd)
    if cfg.get("pos_kind", "rope") == "rope":
        theta = cfg.get("rope_theta", 10000.0)
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // Hk, axis=2)
    v = jnp.repeat(v, H // Hk, axis=2)
    window = cfg.get("sliding_window", 0)

    @jax.checkpoint
    def block(q0, qc):
        i = q0 + jnp.arange(qc.shape[1])
        j = jnp.arange(S)
        keep = j[None, :] <= i[:, None]
        if window:
            keep &= j[None, :] > i[:, None] - window
        sc = jnp.einsum("bqhd,bkhd->bhqk", qc, k) / math.sqrt(hd)
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)

    c = min(Q_CHUNK, S)
    out = jnp.concatenate([block(q0, q[:, q0:q0 + c])
                           for q0 in range(0, S, c)], axis=1)
    return out.reshape(B, S, H * hd) @ p["wo"]


def _mlp(cfg, p, x):
    if cfg["mlp_kind"] == "gelu":
        h = x @ p["wi"] + p["bi"]
        h = 0.5 * h * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                      * (h + 0.044715 * h ** 3)))
        out = h @ p["wo"]
        return out + p["bo"] if cfg.get("mlp_output_bias", True) else out
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def loss(cfg: dict, p: dict, tokens) -> jax.Array:
    """Mean next-token cross-entropy of (B, S) tokens; ``p`` in float32."""
    B, S = tokens.shape
    x = p["embed"][tokens]
    if cfg.get("pos_kind") == "sinusoidal":
        x = x + _sinusoid(S, cfg["d_model"])[None]

    @jax.checkpoint
    def layer(x, lp):
        x = x + _attention(cfg, lp["attn"], _norm(cfg, lp["attn"]["ln"], x))
        return x + _mlp(cfg, lp["mlp"], _norm(cfg, lp["mlp"]["ln"], x)), None

    x, _ = jax.lax.scan(layer, x, p["layers"]["l0"])
    x = _norm(cfg, p["final_norm"], x)
    logits = x[:, :-1] @ p["lm_head"]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
