#!/usr/bin/env python3
"""Record the small TPU trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_trace.py <out.json.gz>

On the chips present: a ``bench.window`` span holding three steps, each a
host sleep (``bench.host_wait``) that leaves the device idle, then a jitted
matmul chain (``bench.compute``) and, with more than one chip, a jitted
all-reduce over all of them (``bench.collective``).  The sleep comes first
because the device clock of a v5e trace runs about a millisecond behind
the host's: work dispatched at the window's first instant would show before
it.
The planes are stored in ``bench.trace``'s plain form, and the script
prints what the reduction reads from them.
"""
import os
import sys
import tempfile
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from bench import trace  # noqa: E402


def main(path: str) -> None:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    mm = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    mm(x).block_until_ready()
    red = None
    if len(devs) > 1:
        mesh = jax.make_mesh((len(devs),), ("d",), devices=devs)
        y = jax.device_put(jnp.ones((len(devs) * 1024, 1024)), NamedSharding(mesh, P("d")))
        red = jax.jit(lambda a: jnp.sum(a, axis=0), out_shardings=NamedSharding(mesh, P()))
        red(y).block_until_ready()
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.host_wait"):
                    time.sleep(0.005)
                with jax.profiler.TraceAnnotation("bench.compute"):
                    mm(x).block_until_ready()
                if red is not None:
                    with jax.profiler.TraceAnnotation("bench.collective"):
                        red(y).block_until_ready()
        jax.profiler.stop_trace()
        planes = trace.load_xplane(d)
    keep = []
    for p in planes:
        if p["name"].startswith("/host:"):
            p = {"name": p["name"], "lines": {
                k: [e for e in v if e[0].startswith(trace.SPAN_PREFIX)]
                for k, v in p["lines"].items()}}
        if p["name"].startswith(("/device:TPU:", "/host:")):
            keep.append(p)
            print(p["name"], {k: len(v) for k, v in p["lines"].items()})
    for p in trace.device_planes(keep)[:1]:
        for line, ev in p["lines"].items():
            print(line, ev[:4])
    trace.save_planes(keep, path)
    print({k: v for k, v in trace.reduce(keep).items()})
    print("saved", path, os.path.getsize(path), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
