"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler ships with jaxlib's TPU support and compiles for a chip
that is described, not attached, so a block layout or memory use the chip
refuses fails here, at the real widths, without a chip. Nothing runs: the
compiled text must hold the kernel (``tpu_custom_call``).

The kernels carry their names (``pallas_call(name=...)``) into the
compiled text, where a profiler trace reports them.

Widths: the GCN's Σ and gather at ogbn-arxiv shape (169,343 nodes,
1,166,243 edges plus a self loop per node; feature widths 128 and 256),
the logistic-regression matmul at 524,288 × 1,024 f32, a 4096² bf16
matmul, and a selective scan of 2,048 steps × 1,024 channels × 16 states.

The topology is described inside a module-scoped fixture, never at import:
the TPU library admits one process at a time, so describing it while a
pytest-xdist worker imports this file would fail the other workers.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gather.ops import gather_rows
from repro.kernels.matmul.ops import blocked_matmul
from repro.kernels.segsum.ops import segment_sum
from repro.kernels.ssm_scan.ops import ssm_scan

ARXIV_NODES = 169_343
ARXIV_EDGES = 1_166_243 + ARXIV_NODES


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    # Other test modules turn on x64 as they are imported; the program
    # runs without it, and Mosaic cannot legalize the kernels' 64-bit
    # index arithmetic, so compile as the program does.
    saved = {
        name: getattr(jax.config, name)
        for name in ("jax_enable_compilation_cache", "jax_enable_x64")
    }
    for name in saved:
        jax.config.update(name, False)
    yield SingleDeviceSharding(topo.devices[0])
    for name, value in saved.items():
        jax.config.update(name, value)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dim", [128, 256])
def test_segment_sum_compiles_at_arxiv_width(one_chip, dim):
    compiled = _compile(
        lambda msg, seg: segment_sum(msg, seg, ARXIV_NODES, interpret=False),
        one_chip,
        ((ARXIV_EDGES, dim), jnp.float32),
        ((ARXIV_EDGES,), jnp.int32),
    )
    _assert_kernel(compiled)
    # one launch per call, whose trace event the roofline reads
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    # the messages in segment order are the one E' × D copy the call
    # holds (a second, such as a take and then a pad, would double it)
    padded = (ARXIV_EDGES + (-ARXIV_EDGES) % 512) * dim * 4
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= padded + 64 * 2**20, (temp, padded)


@pytest.mark.parametrize("dim", [128, 256])
def test_gather_rows_compiles_at_arxiv_width(one_chip, dim):
    compiled = _compile(
        lambda table, rows: gather_rows(table, rows, interpret=False),
        one_chip,
        ((ARXIV_NODES, dim), jnp.float32),
        ((ARXIV_EDGES,), jnp.int32),
    )
    _assert_kernel(compiled)


@pytest.mark.parametrize(
    "m, k, n, dtype",
    [
        (524_288, 1_024, 1, jnp.float32),   # logistic regression's X·θ
        (4_096, 4_096, 4_096, jnp.bfloat16),
    ],
)
def test_blocked_matmul_compiles(one_chip, m, k, n, dtype):
    compiled = _compile(
        lambda x, y: blocked_matmul(x, y, interpret=False),
        one_chip,
        ((m, k), dtype),
        ((k, n), dtype),
    )
    _assert_kernel(compiled)


def test_ssm_scan_compiles(one_chip):
    shape = (1, 2_048, 1_024, 16)
    compiled = _compile(
        lambda a, b: ssm_scan(a, b, 256, 8, False, True),
        one_chip,
        (shape, jnp.float32),
        (shape, jnp.float32),
    )
    _assert_kernel(compiled)


@pytest.mark.parametrize(
    "name, kernel, shapes",
    [
        (
            "segment_sum",
            lambda msg, seg: segment_sum(msg, seg, 1_024, interpret=False),
            (((70_000, 128), jnp.float32), ((70_000,), jnp.int32)),
        ),
        (
            "gather_join",  # launches inside a lax.map: named all the same
            lambda table, rows: gather_rows(table, rows, interpret=False),
            (((1_024, 128), jnp.float32), ((140_000,), jnp.int32)),
        ),
        (
            "blocked_matmul",
            lambda x, y: blocked_matmul(x, y, interpret=False),
            (((1_024, 256), jnp.float32), ((256, 1), jnp.float32)),
        ),
    ],
    ids=["segment_sum", "gather_join", "blocked_matmul"],
)
def test_kernel_keeps_its_name_in_the_compiled_text(one_chip, name, kernel, shapes):
    text = _compile(kernel, one_chip, *shapes).as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and " = " in ln]
    assert calls
    for ln in calls:
        assert re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", ln), ln


PRODUCTS_NODES = 2_449_029
PRODUCTS_EDGES = 61_859_140 + PRODUCTS_NODES   # a self loop per node
PRODUCTS_EDGES_PADDED = PRODUCTS_EDGES + (-PRODUCTS_EDGES) % 4
PRODUCTS_EDGES_CHIP = PRODUCTS_EDGES_PADDED // 4


def test_products_gcn_step_compiles_nnz_sharded_on_four_chips(one_chip, topo):
    """The two-layer GCN's Adam step at ogbn-products shape (100
    features, hidden 256, 47 classes), its edges' nnz rows sharded over
    a described ``v5e:2x2``, through ``gcn_conv`` / ``rel_linear`` inside
    a user jit under ``Database(mesh=...)``: each chip's arguments and
    temporaries fit under 14 GiB, and no edges × width array, whole or a
    chip's share, is in the compiled step (the join-aggregate runs in
    blocks of edges per chip)."""
    import functools

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import repro
    from repro.core import kernels as K
    from repro.optim import adam_init, adam_update
    from repro.relational import gcn_conv, rel_linear

    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def loss(p, x, keys, w, y):
        h = jax.nn.relu(rel_linear(gcn_conv(x, keys, w), p["w1"]))
        logp = jax.nn.log_softmax(rel_linear(gcn_conv(h, keys, w), p["w2"]))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def step(p, opt, x, keys, w, y):
        value, grads = jax.value_and_grad(loss)(p, x, keys, w, y)
        p, opt = adam_update(p, grads, opt, lr=0.01)
        return p, opt, value

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = {"w1": sds((100, 256), jnp.float32, whole),
              "w2": sds((256, 47), jnp.float32, whole)}
    opt = jax.tree.map(lambda a: sds(a.shape, a.dtype, whole),
                       jax.eval_shape(adam_init, params))
    args = (params, opt,
            sds((PRODUCTS_NODES, 100), jnp.float32, whole),
            sds((PRODUCTS_EDGES_PADDED, 2), jnp.int32,
                NamedSharding(mesh, P("data", None))),
            sds((PRODUCTS_EDGES_PADDED,), jnp.float32, rows),
            sds((PRODUCTS_NODES,), jnp.int32, whole))
    db = repro.Database(mesh=mesh, dispatch=K.make_table(None, backend="tpu"))
    with db.activate():
        compiled = jax.jit(functools.partial(step)).lower(*args).compile()
    mem = compiled.memory_analysis()
    per_chip = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert per_chip < 14 * 2**30, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "reduce-scatter" in text
    for shape in re.findall(r"f32\[(\d+),(\d+)", text):
        e, d = int(shape[0]), int(shape[1])
        for edges in (PRODUCTS_EDGES_CHIP, PRODUCTS_EDGES_PADDED):
            assert not (edges <= e < edges + 4096 and d > 1), shape
