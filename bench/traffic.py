"""The benchmark's traffic generator: per-client token streams from a seed.

One general generator reads every traffic file (``bench/traffic/*.json``):

* ``clients``, ``local_steps``, ``seqs_per_step``, ``seq_len``: the round's
  batch is ``(clients, local_steps, seqs_per_step, seq_len)`` int32 tokens,
  the layout the program's round functions take;
* ``zipf_s``, ``heterogeneity``: each client's tokens are i.i.d. from a
  unigram over the model's full vocabulary.  A token is drawn by inverse
  CDF from a Zipf(``zipf_s``) law over ranks, and the rank is mapped to a
  token through a permutation shared by all clients, or, with probability
  ``heterogeneity``, through the client's own permutation.  So clients are
  not i.i.d. (the paper's harder setting).

It implements the drivers' sampler protocol (``init_state()``,
``sample(state, t)``).  Round ``t``'s tokens are a pure function of the
seed and ``t``; every round draws new rows.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _tables(key_data, vocab: int, clients: int, zipf_s: float):
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    w = ranks ** -zipf_s
    cdf = jnp.cumsum(w) / jnp.sum(w)
    key = jax.random.wrap_key_data(key_data)
    kp, kc = jax.random.split(jax.random.fold_in(key, 0))
    shared = jax.random.permutation(kp, vocab)
    own = jax.vmap(lambda k: jax.random.permutation(k, vocab))(
        jax.random.split(kc, clients))
    perm = jnp.concatenate([shared[None], own]).astype(jnp.int32)
    # the key rides in the state, so the round program is one for all seeds
    return {"cdf": cdf, "perm": perm, "key": key_data}


@dataclasses.dataclass(frozen=True)
class ZipfClients:
    clients: int
    local_steps: int
    seqs_per_step: int
    seq_len: int
    vocab: int
    zipf_s: float
    heterogeneity: float
    key_data: tuple          # the generator's key, as two uint32 words

    @classmethod
    def from_traffic(cls, traffic: dict, vocab: int, key) -> "ZipfClients":
        kd = tuple(int(x) for x in jax.device_get(jax.random.key_data(key)))
        return cls(traffic["clients"], traffic["local_steps"],
                   traffic["seqs_per_step"], traffic["seq_len"], vocab,
                   float(traffic["zipf_s"]), float(traffic["heterogeneity"]),
                   kd)

    def init_state(self):
        return _tables(jnp.asarray(self.key_data, jnp.uint32), self.vocab,
                       self.clients, self.zipf_s)

    def sample(self, state, t):
        shape = (self.clients, self.local_steps, self.seqs_per_step,
                 self.seq_len)
        key = jax.random.wrap_key_data(state["key"])
        ku, km = jax.random.split(jax.random.fold_in(key, t + 1))
        u = jax.random.uniform(ku, shape, jnp.float32)
        rank = jnp.minimum(jnp.searchsorted(state["cdf"], u, side="right"),
                           self.vocab - 1).astype(jnp.int32)
        own = jax.random.bernoulli(km, self.heterogeneity, shape)
        client = jnp.arange(1, self.clients + 1, dtype=jnp.int32)
        row = jnp.where(own, client[:, None, None, None], 0)
        return state, {"tokens": state["perm"][row, rank]}
