"""Tiny versions of the benchmark's cells, for runs on the CPU."""
from __future__ import annotations

import time

import jax

from bench import harness

SEED = 2**31 + 12345  # beyond 32 signed bits: --seed takes any such whole number

TINY = {
    "stablelm-1.6b": dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                          num_key_value_heads=4, head_dim=16, intermediate_size=128,
                          vocab_size=256),
    "deepseek-67b": dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                         num_key_value_heads=2, head_dim=16, intermediate_size=128,
                         vocab_size=256),
}
# A cell's limits are read at its own size on the chip.  A tiny round cell
# reads more bfloat16 noise against its float32 reference, so it carries
# limits of its own, read from tiny CPU runs on three seeds: the program at
# most 4.8e-5 / 0.041 / 0.019, the control at least 3.6e-4 / 0.053 / 0.043.
TINY_TRAFFIC = {
    "round": dict(clients=4, seqs_per_client=2, seq_len=16, token_rows=64, trace_rounds=3,
                  limits={"loss_gap": 2e-4, "update_gap": 0.05, "change_gap": 0.03}),
    "agg": dict(trace_rounds=3),
}


def cell(workload: str, **traffic):
    """A tiny copy of a ``BENCHMARK.json`` cell."""
    return _shrink(harness.resolve(workload), traffic)


def _shrink(c, traffic: dict):
    c.config.update(TINY[c.config["name"]])
    c.traffic.update(TINY_TRAFFIC[c.traffic["kind"]])
    c.traffic.update(traffic)
    return c


def drive(c, seed: int = SEED, seconds: float = 0.5):
    """A whole run of ``c`` on the CPU, past the harness's chip check."""
    devs = jax.devices()[: c.chips]
    return c.driver().run(c, seed, seconds, None, devs, harness.CompileCounter().install(),
                          time.perf_counter())
