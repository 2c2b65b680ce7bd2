"""The one traffic generator: every input of a run, made from ``--seed`` and
the parameters of the cell's traffic file.

* ``MarkovTokens``: client token streams for federated rounds.  Each client
  samples from a first-order Markov chain over ``token_rows`` vocabulary
  rows: a shared, peaked base transition matrix mixed with a client-specific
  permutation of it (weight ``heterogeneity``), the generator of
  ``repro.data.synthetic.client_lm_datasets``.  Round ``r`` draws fresh
  sequences, so no two rounds share a row.
* ``planted_base`` / ``planted_cohort``: one aggregation round's client
  deltas, made on the device.  Every adapter module is a rank-``rank``
  common core ``u @ w`` whose client mixing ``w`` is jittered by ``drift``
  each round, plus a persistent sparse support (fraction ``sparsity``, values
  ``spike`` x N(0, 1), jittered by ``spike_jitter`` each round): the model of
  ``benchmarks/agg_engine_bench.py::make_round_trees`` at real adapter
  shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one beyond 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


class MarkovTokens:
    def __init__(self, traffic: dict, seed: int):
        self.clients = traffic["clients"]
        self.seqs = traffic["seqs_per_client"]
        self.length = traffic["seq_len"] + 1
        self.seed = seed
        v = traffic["token_rows"]
        rng = np.random.default_rng([seed, 0])
        trans = rng.random((v, v)) ** 4
        top = rng.integers(0, v, size=(v, 3))
        trans[np.arange(v)[:, None], top] += 0.6 * v / 3
        base = trans / trans.sum(axis=1, keepdims=True)
        h = traffic["heterogeneity"]
        cdfs = []
        for _ in range(self.clients):
            perm = rng.permutation(v)
            t = (1 - h) * base + h * base[perm][:, perm]
            cdfs.append(np.cumsum(t / t.sum(axis=1, keepdims=True), axis=1))
        self.cdfs = np.stack(cdfs)  # (clients, v, v)

    def round(self, r: int) -> np.ndarray:
        """(clients, seqs_per_client, seq_len + 1) int32 tokens of round r."""
        rng = np.random.default_rng([self.seed, 1, r])
        c, b, v = self.clients, self.seqs, self.cdfs.shape[1]
        out = np.empty((c, b, self.length), np.int32)
        out[:, :, 0] = rng.integers(0, v, size=(c, b))
        ci = np.arange(c)[:, None]
        for t in range(self.length - 1):
            u = rng.random((c, b))
            rows = self.cdfs[ci, out[:, :, t]]  # (c, b, v)
            out[:, :, t + 1] = np.minimum((u[..., None] >= rows).sum(-1), v - 1)
        return out


def planted_base(key, shapes: dict, traffic: dict) -> dict:
    """Per leaf the fixed part of the planted model: core ``u`` (layers, vec,
    rank), mixing ``w`` (layers, rank, clients) and the keys of the sparse
    support.  ``shapes`` maps leaf name -> (layers, *matrix)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        ku, kw, ks, kv = jax.random.split(jax.random.fold_in(key, i), 4)
        layers, vec = shape[0], int(np.prod(shape[1:]))
        out[name] = {
            "u": jax.random.normal(ku, (layers, vec, traffic["rank"])),
            "w": jax.random.normal(kw, (layers, traffic["rank"], traffic["clients"])),
            "support": jax.random.key_data(ks),
            "values": jax.random.key_data(kv),
        }
    return out


def planted_cohort(base: dict, key, shapes: dict, traffic: dict) -> dict:
    """One round's deltas ``{name: (clients, layers, *matrix)}`` float32.
    ``key`` is the round's key; the core and the support stay those of
    ``base``.  Sums of outer products, no matmul, so no matmul precision
    enters the inputs."""
    out = {}
    for i, (name, b) in enumerate(sorted(base.items())):
        kw, kj = jax.random.split(jax.random.fold_in(key, i))
        w = b["w"] + traffic["drift"] * jax.random.normal(kw, b["w"].shape)
        layers, vec, rank = b["u"].shape
        low = sum(jnp.transpose(w[:, k, :])[:, :, None] * b["u"][None, :, :, k]
                  for k in range(rank))
        full = (traffic["clients"], layers, vec)
        supp = jax.random.bernoulli(jax.random.wrap_key_data(b["support"]),
                                    traffic["sparsity"], full)
        vals = traffic["spike"] * jax.random.normal(jax.random.wrap_key_data(b["values"]), full)
        jitter = 1.0 + traffic["spike_jitter"] * jax.random.normal(kj, full)
        delta = low + jnp.where(supp, vals * jitter, 0.0)
        out[name] = jnp.reshape(delta, (traffic["clients"],) + tuple(shapes[name]))
    return out
