"""Plain float32 reference of the first SAFL rounds of a cell.

Imports nothing of the program.  It follows the configuration file and the
paper's Algorithm 1 in straightforward ``jax.numpy`` at ``highest`` matmul
precision:

* the model: token embedding (+ sinusoidal positions, or RoPE inside
  attention), pre-norm blocks of causal multi-head attention (grouped
  key/value heads repeated) and an MLP (tanh-GELU or SwiGLU), a final norm
  and an untied output head; the softmax runs over the padded vocabulary,
  as ``pad_vocab_to`` in the configuration states; the loss is the mean
  next-token cross-entropy over all positions but the last;
* each client: ``local_steps`` SGD steps at ``client_lr``, parameters kept
  in the configuration's dtype between steps, gradients in float32; its
  delta is the start minus the end;
* the uplink: each parameter tensor (leaf ``i`` in sorted-key order) is
  count-sketched to ``b = max(min_b, ceil(n ratio))`` slots with the
  balanced hash family: element ``(k, c)`` of the tensor laid out as
  ``m = ceil(n / b)`` rows of ``b`` goes to slot ``(c + r_k) mod b`` with
  sign ``s``, ``r`` and ``s`` drawn from ``fold_in(round_key, i)``; tensors
  with ``b >= n`` go raw;
* the server: the mean of the clients' sketches, desketched (each element
  reads its slot back, times its sign) and fed to AMSGrad (no bias
  correction) with the learning rate scaled by the cell's schedule.

The sketch here is a segment sum; the program's is a gather.  Weights and
tokens come from the benchmark's own generators, made again from the seed.
"""

from __future__ import annotations

import collections
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

F32 = jnp.float32
Q_CHUNK = 512


# the configuration keys this reference follows, and those it implements
# at one value only
KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
        "d_ff", "vocab_size", "pad_vocab_to", "norm_kind", "norm_eps",
        "mlp_kind", "pos_kind", "rope_theta", "sliding_window",
        "mlp_output_bias", "dtype")
ONE_VALUE = {"attn_bias": False, "tie_embeddings": False}
META = ("name", "program_config", "source", "reduced", "assumed",
        "deployment")


def check_config(cfg: dict) -> None:
    """Refuse a configuration that states what this reference does not do."""
    for k, v in cfg.items():
        if k in META or k in KEYS or (k in ONE_VALUE and ONE_VALUE[k] == v):
            continue
        raise ValueError(f"the reference does not implement {k}={v!r}")


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


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


# ---------------------------------------------------------------------------
# one SAFL round
# ---------------------------------------------------------------------------

def client_delta(cfg: dict, lr: float, params, batch):
    """``batch`` (K, B, S): K SGD steps from ``params``; (delta, mean loss)."""
    vg = jax.value_and_grad(functools.partial(loss, cfg))

    def step(p, tokens):
        p32 = jax.tree.map(lambda a: a.astype(F32), p)
        l, g = vg(p32, tokens)
        return jax.tree.map(lambda a, gi, ref: (a - lr * gi).astype(ref.dtype),
                            p32, g, p), l

    p, losses = jax.lax.scan(step, params, batch)
    delta = jax.tree.map(lambda a, b: a.astype(F32) - b.astype(F32),
                         params, p)
    return delta, jnp.mean(losses)


def _cs_params(key, n: int, b: int):
    rkey, skey = jax.random.split(key)
    r = jax.random.randint(rkey, (-(-n // b),), 0, b)
    s = jax.random.rademacher(skey, (n,), dtype=F32)
    i = jnp.arange(n, dtype=jnp.int32)
    return (i % b + r[i // b]) % b, s


def sketch_width(job: dict, n: int) -> int:
    sk = job["sketch"]
    return min(max(sk["min_b"], int(math.ceil(n * sk["ratio"]))), n)


def sketch_leaf(b: int, key, x):
    """One tensor's payload: its elements summed into ``b`` signed slots."""
    v = x.reshape(-1)
    if b >= v.shape[0]:
        return v
    slot, s = _cs_params(key, v.shape[0], b)
    return jax.ops.segment_sum(v * s, slot, num_segments=b)


def desketch_leaf(b: int, key, y, shape):
    """Each element reads its slot back, times its sign."""
    n = int(np.prod(shape))
    if b >= n:
        return y.reshape(shape)
    slot, s = _cs_params(key, n, b)
    return (y[slot] * s).reshape(shape)


def amsgrad_leaf(server: dict, state, p, u, lr_scale):
    """AMSGrad (no bias correction) on one leaf; ``state`` is (m, v, vhat)."""
    b1, b2, eps = server["beta1"], server["beta2"], server["eps"]
    m, v, vhat = state
    m = b1 * m + (1 - b1) * u
    v = b2 * v + (1 - b2) * u * u
    vhat = jnp.maximum(vhat, v)
    new = p.astype(F32) - server["lr"] * lr_scale * m / (jnp.sqrt(vhat) + eps)
    return new.astype(p.dtype), (m, v, vhat)


def lr_scale(schedule: dict | None, t: int) -> float:
    """Server learning-rate multiplier of round ``t`` (0-based)."""
    if not schedule:
        return 1.0
    w, total, lo = schedule["warmup"], schedule["total"], schedule["min_frac"]
    warm = min(t / w, 1.0) if w else 1.0
    frac = min(max((t - w) / max(total - w, 1), 0.0), 1.0)
    return warm * (lo + (1 - lo) * 0.5 * (1 + math.cos(math.pi * frac)))


def _norm_dot(x, z):
    x = x.astype(F32)
    return jnp.sqrt(jnp.sum(jnp.square(x))), jnp.sum(x * z)


def run(cfg: dict, job: dict, params0, batches, round_keys, probe_key, *,
        half_batch: bool = False, wrong_key: bool = False) -> dict:
    """The first ``len(batches)`` rounds from ``params0``.

    ``batches[t]`` is round t's (G, K, B, S) tokens and ``round_keys[t]`` its
    sketch key.  Two planted faults: ``half_batch`` keeps only the first
    half of each client's sequences; ``wrong_key`` desketches with another
    leaf's key than the one that sketched.  Returns per-round losses and,
    per leaf, the norm of the first update the server receives and of the
    parameters' change over all rounds, and the dot product of each with
    the leaf's probe (``weights.probe_leaf`` under ``probe_key``), and the
    host seconds its client steps, sketches and server steps took.
    """
    lr = job["client_lr"]
    leaves, treedef = jax.tree.flatten(params0)
    del params0
    widths = [sketch_width(job, x.size) for x in leaves]
    leaves0 = leaves

    with jax.default_matmul_precision("highest"):
        client = jax.jit(functools.partial(client_delta, cfg, lr))
        sk = jax.jit(sketch_leaf, static_argnums=0)
        desk = jax.jit(desketch_leaf, static_argnums=(0, 3))
        step = jax.jit(functools.partial(amsgrad_leaf, job["server"]),
                       donate_argnums=0)
        read = jax.jit(lambda x, k, i: _norm_dot(
            x, weights.probe_leaf(k, i, x.shape)))
        state = [tuple(jnp.zeros(x.shape, F32) for _ in range(3))
                 for x in leaves]
        losses, first = [], None
        secs = collections.Counter()

        def tick(part, x, t0):
            jax.block_until_ready(x)
            secs[part] += time.perf_counter() - t0

        for t, (batch, key) in enumerate(zip(batches, round_keys)):
            if half_batch:
                batch = batch[:, :, : batch.shape[2] // 2]
            G, total, round_loss = batch.shape[0], None, 0.0
            params = jax.tree.unflatten(treedef, leaves)
            for c in range(G):
                t0 = time.perf_counter()
                delta, l = client(params, batch[c])
                tick("client_s", delta, t0)
                t0 = time.perf_counter()
                pays = [sk(b, jax.random.fold_in(key, i), x) for i, (b, x)
                        in enumerate(zip(widths, jax.tree.leaves(delta)))]
                total = pays if total is None else [
                    a + b for a, b in zip(total, pays)]
                tick("sketch_s", total, t0)
                round_loss += float(l)
                del delta
            del params
            scale = lr_scale(job.get("schedule"), t)
            new, reads = [], []
            t0 = time.perf_counter()
            for i, (b, x, y) in enumerate(zip(widths, leaves, total)):
                j = (i + 1) % len(leaves) if wrong_key else i
                u = desk(b, jax.random.fold_in(key, j), y / G, x.shape)
                p, state[i] = step(state[i], x, u, scale)
                reads.append(read(u, probe_key, i))
                new.append(p)
                del u, p
            leaves = new
            tick("server_s", leaves, t0)
            if first is None:
                first = np.asarray(jax.device_get(reads), np.float64)
            losses.append(round_loss / G)
        change = np.asarray(jax.device_get(
            [read(a.astype(F32) - b.astype(F32), probe_key, i)
             for i, (a, b) in enumerate(zip(leaves, leaves0))]), np.float64)
    return {"loss": np.asarray(losses), "grad_norms": first[:, 0],
            "grad_dots": first[:, 1], "change_norms": change[:, 0],
            "change_dots": change[:, 1], "seconds": dict(secs)}
