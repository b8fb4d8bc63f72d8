"""Plain float32 reference of the first SAFL rounds of a cell.

Imports nothing of the program.  It follows the configuration file and the
paper's Algorithm 1 in straightforward ``jax.numpy`` at ``highest`` matmul
precision:

* the model: the configuration's own reference module
  (``bench.models.of(cfg)``), whose ``loss`` the clients differentiate;
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

The sketch here is a segment sum over every element's slot; the program's
adds each row of the tensor, signed and rotated by ``r_k``.  Both
desketch by a gather.  Weights and tokens come from the benchmark's own
generators, made again from the seed.
"""

from __future__ import annotations

import collections
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import models, weights

F32 = jnp.float32


# ---------------------------------------------------------------------------
# one SAFL round
# ---------------------------------------------------------------------------

def client_delta(loss, lr: float, params, batch):
    """``batch`` (K, B, S): K SGD steps on ``loss(params_f32, tokens)``
    from ``params``; (delta, mean loss)."""
    vg = jax.value_and_grad(loss)

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
        loss = functools.partial(models.of(cfg).loss, cfg)
        client = jax.jit(functools.partial(client_delta, loss, lr))
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
