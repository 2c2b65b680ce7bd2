#!/usr/bin/env python3
"""Record the small scoped TPU trace that ``test_bench_scopes.py`` reads.

    python3 bench/tests/record_scoped_trace.py <out.json.gz>

On one chip: a ``bench.window`` span holding three ``fed.round`` steps.
Each is a host sleep (``bench.host_wait``) that leaves the device idle,
then a jitted ``local_step`` dispatched under ``bench.local_dispatch`` (a
matmul chain differentiated under ``local.grad``, stepped under
``local.opt``) and a jitted ``agg_step`` dispatched under
``bench.agg_dispatch`` (an ``agg.admm`` loop of ``agg.svt`` eighs with an
elementwise pass after each, then ``agg.tail``), each waited for.  The
planes are stored in ``bench.trace``'s plain form with their
``op_scopes`` (``bench.scopes``); of the host's events only the spans are
kept.  The script prints what the reduction reads from them.
"""
import os
import sys
import tempfile
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import scopes, trace  # noqa: E402


def local_step(w, x):
    def loss(w):
        return jnp.mean(jnp.tanh(x @ w) ** 2)

    with jax.named_scope("local.grad"):
        g = jax.grad(loss)(w)
    with jax.named_scope("local.opt"):
        return w - 0.1 * g


def agg_step(m):
    def body(_, a):
        with jax.named_scope("agg.svt"):
            _, v = jnp.linalg.eigh(a @ a.T)
            low = v @ (v.T @ a)
        return 0.5 * (a + low)

    with jax.named_scope("agg.admm"):
        m = jax.lax.fori_loop(0, 4, body, m)
    with jax.named_scope("agg.tail"):
        return jnp.mean(m, axis=-1)


def main(path: str) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit("record_scoped_trace: needs a TPU")
    local, agg = jax.jit(local_step), jax.jit(agg_step)
    w = jnp.ones((2048, 2048), jnp.float32) * 0.01
    x = jnp.ones((1024, 2048), jnp.float32)
    m = jnp.ones((256, 256), jnp.float32) + jnp.eye(256)
    jax.block_until_ready((local(w, x), agg(m)))
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("fed.round"):
                    with jax.profiler.TraceAnnotation("bench.host_wait"):
                        time.sleep(0.005)
                    with jax.profiler.TraceAnnotation("bench.local_dispatch"):
                        out = local(w, x)
                    out.block_until_ready()
                    with jax.profiler.TraceAnnotation("bench.agg_dispatch"):
                        out = agg(m)
                    out.block_until_ready()
        jax.profiler.stop_trace()
        planes = scopes.load_xplane(d)
    keep = []
    for p in planes:
        if p["name"].startswith("/host:"):
            p = {"name": p["name"], "lines": {
                k: [e for e in v if e[0].startswith(scopes.SPAN_PREFIXES)]
                for k, v in p["lines"].items()}}
        if p["name"].startswith(("/device:TPU:", "/host:")):
            keep.append(p)
            print(p["name"], {k: len(v) for k, v in p["lines"].items()})
    trace.save_planes(keep, path)
    red = scopes.reduce(keep)
    print({k: v for k, v in red.items() if k != "breakdown"})
    print(red["breakdown"])
    print("saved", path, os.path.getsize(path), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
