"""Compile rehearsal: the main-path Pallas kernels, compiled for a v5e.

Interpret mode (every other kernel test) cannot show what the TPU lowering
refuses — unaligned blocks, scalar reads from vector memory, VMEM limits.
Here each kernel is compiled at stablelm-1.6b's widths for a v5e that is
described, not attached, so a refusal fails the suite with no chip.  So
are the two programs ``chip_smoke.py`` runs that could not be tried
otherwise: the local step at its sequence length (it must fit one chip's
16 GiB) and the aggregation sharded over a 2x2 mesh.  The SSD chunk kernels
compile at granite-4.0-h-small's mixer widths, and the granite cell's local
step, lowered for the chip, runs them in place of the (Q, Q) float32
tensors of ``ssd_chunked``.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every pytest worker imports this
file.  The compilation cache stays off around the compiles (a described
chip's entries could not be read back).
"""
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import configs as cfglib
from repro.core import AggregatorConfig, aggregate
from repro.core import engine as engine_lib
from repro.kernels import backend, rpca_admm, ssd_scan, svt_subspace
from repro.kernels.lora_matmul import gathered_lora_matmul, lora_matmul
from repro.launch import steps as steps_lib
from repro.models import init_lora_params, init_params

ARCH = "stablelm-1.6b"
CLIENTS = 8
TOKENS = 512  # rows of one local-step activation tile batch
ADAPTER_SLOTS = 8
HBM_BYTES = 16 * 2**30  # one v5e
# granite-4.0-h-small's mixer (``bench/configs``) on the cell's 8 clients x 1,024 tokens.
SSD = dict(clients=8, seq=1024, heads=128, head_dim=64, state=128, chunk=256)


@pytest.fixture(scope="module")
def smoke_args():
    """``--flag`` -> int value, for the training run ``chip_smoke.py`` drives."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    argv = smoke.TRAIN_ARGS
    return {a: int(v) for a, v in zip(argv, argv[1:]) if v.isdigit()}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def bucket():
    """(modules, vec, clients) of the stablelm-1.6b LoRA tree's one bucket."""
    cfg = cfglib.get_config(ARCH)
    lora = jax.eval_shape(lambda k: init_lora_params(k, cfg), jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((CLIENTS,) + x.shape, x.dtype), lora
    )
    plans = []

    def plan(tree):
        plans.append(engine_lib.plan_aggregation(
            tree, AggregatorConfig(method="fedrpca", svt_mode="subspace")
        ))
        return 0

    jax.eval_shape(plan, stacked)
    ((key, (b, vec)),) = plans[0].spec.bucket_dims.items()
    return b, vec, key[1]


def compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_bucket_is_stablelm_width(bucket):
    # 24 layers x (q, v) x (A, B), each 2048 x 8 = 16384 entries.
    assert bucket == (96, 16384, CLIENTS)


def test_admm_tail_compiles(bucket, one_chip):
    b, vec, nc = bucket
    t = jax.ShapeDtypeStruct((b, vec, nc), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one_chip)
    text = compile_text(
        lambda m, l, y, r, mu, th: rpca_admm.admm_tail(m, l, y, r, mu, th, interpret=False),
        t, t, t, s, s, s,
    )
    assert "tpu_custom_call" in text


def test_subspace_apply_compiles(bucket, one_chip):
    b, vec, nc = bucket
    t = jax.ShapeDtypeStruct((b, vec, nc), jnp.float32, sharding=one_chip)
    p = jax.ShapeDtypeStruct((b, nc, nc), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one_chip)
    text = compile_text(
        lambda m, sp, y, pr, r, mu, th: svt_subspace.subspace_apply(
            m, sp, y, pr, r, mu, th, interpret=False),
        t, t, t, p, s, s, s,
    )
    assert "tpu_custom_call" in text


def _projection_shapes(one_chip, slots=None):
    cfg = cfglib.get_config(ARCH)
    d, r = cfg.d_model, cfg.lora.rank
    bf = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    lead = () if slots is None else (slots,)
    return bf(TOKENS, d), bf(d, d), bf(*lead, d, r), bf(*lead, r, d)


def test_lora_matmul_compiles(one_chip):
    x, w, a, b = _projection_shapes(one_chip)
    text = compile_text(
        lambda x, w, a, b: lora_matmul(x, w, a, b, 2.0, interpret=False), x, w, a, b
    )
    assert "tpu_custom_call" in text


def test_gathered_lora_matmul_compiles(one_chip):
    x, w, a, b = _projection_shapes(one_chip, slots=ADAPTER_SLOTS)
    slot = jax.ShapeDtypeStruct((TOKENS,), jnp.int32, sharding=one_chip)
    text = compile_text(
        lambda x, w, a, b, s: gathered_lora_matmul(x, w, a, b, s, 2.0, interpret=False),
        x, w, a, b, slot,
    )
    assert "tpu_custom_call" in text


def test_local_step_fits_one_chip(one_chip, smoke_args):
    """The smoke's local step (8 clients x 4 sequences, 2 Adam steps) at its
    --seq: arguments + outputs + temporaries within one chip's HBM."""
    cfg = cfglib.get_config(ARCH)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), t)
    key = jax.random.PRNGKey(0)
    base = on_chip(jax.eval_shape(lambda k: init_params(k, cfg), key))
    lora = on_chip(jax.eval_shape(lambda k: init_lora_params(k, cfg), key))
    shape = (smoke_args["--clients"], smoke_args["--per-client-batch"], smoke_args["--seq"])
    tok = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    step = steps_lib.make_local_step(
        cfg, local_lr=1e-3, local_steps=smoke_args["--local-steps"],
        local_optimizer="adam", remat=False,
    )
    compiled = jax.jit(step).lower(
        base, lora, {"tokens": tok, "labels": tok}, on_chip(key)).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total < HBM_BYTES, f"{shape}: {total / 2**30:.2f} GiB"


@pytest.mark.parametrize("tail", ["xla", "fused"])
@pytest.mark.parametrize("svt_mode", ["gram", "subspace"])
def test_sharded_gram_aggregation_compiles(topo, monkeypatch, svt_mode, tail):
    """--four-chips: FedRPCA with the vec rows sharded 4 ways, the (B, C, C)
    Grams exchanged, in either SVT mode with the XLA or the fused tail on
    each chip.  The kernels are called deep inside ``aggregate``, so the CPU
    backend's interpret default is switched off for this compile."""
    monkeypatch.setattr(backend, "interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    rep = NamedSharding(mesh, PartitionSpec())
    cfg = cfglib.get_config(ARCH)
    lora = jax.eval_shape(lambda k: init_lora_params(k, cfg), jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((CLIENTS,) + x.shape, jnp.float32, sharding=rep),
        lora,
    )
    agg = AggregatorConfig(method="fedrpca", rpca_iters=30, svt_mode=svt_mode,
                           rpca_fused_tail=tail == "fused")
    text = compile_text(lambda t: aggregate(t, agg, engine="packed", mesh=mesh), stacked)
    assert ("tpu_custom_call" in text) == (tail == "fused")
    assert "all-gather" in text


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_ssd_scan_compiles(one_chip, direction):
    c, s, h, p, n = (SSD[k] for k in ("clients", "seq", "heads", "head_dim", "state"))
    f = lambda *shape: jax.ShapeDtypeStruct((c, 1) + shape, jnp.float32, sharding=one_chip)
    fwd = jax.vmap(lambda *a: ssd_scan.ssd_chunk_scan(*a, chunk=SSD["chunk"], interpret=False))
    bwd = jax.grad(lambda *a: sum(jnp.sum(t * t) for t in fwd(*a)), argnums=(0, 1, 2, 3))
    text = compile_text(fwd if direction == "forward" else bwd,
                        f(s, h * p), f(s, h), f(s, n), f(s, n))
    assert text.count("tpu_custom_call") == (1 if direction == "forward" else 2)


def test_granite_local_step_runs_the_ssd_kernels(one_chip, monkeypatch):
    """The granite cell's local step (remat per block, 2 Adam steps, 8
    clients x 1,024 tokens) lowered for the chip: each of its 9 mamba mixers
    runs the forward kernel twice (forward and recomputation) and the
    backward once, and no (..., 128 heads, 256, 256) float32 tensor is
    left.  ``apply_ssd`` asks the backend, which here is the CPU's."""
    from bench import harness
    from bench import hybrid_round_cell as hc

    monkeypatch.setattr(backend, "interpret_default", lambda: False)
    mcfg = hc.model_config(harness.load_json(f"{harness.BENCH}/configs/granite-4.0-h-small.json"))
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), t)
    key = jax.random.PRNGKey(0)
    base = on_chip(jax.eval_shape(lambda k: init_params(k, mcfg), key))
    lora = on_chip(jax.eval_shape(lambda k: init_lora_params(k, mcfg), key))
    tok = jax.ShapeDtypeStruct((SSD["clients"], 1, SSD["seq"]), jnp.int32, sharding=one_chip)
    step = steps_lib.make_local_step(mcfg, local_lr=1e-3, local_steps=2,
                                     local_optimizer="adam", remat=True)
    text = jax.jit(step).lower(base, lora, {"tokens": tok, "labels": tok}, on_chip(key)).as_text()
    mixers = mcfg.layer_pattern.count("ssd")
    assert mixers == 9
    assert text.count("tpu_custom_call") == 3 * mixers
    assert not re.search(r"tensor<[0-9x]*x128x256x256xf32>", text)
