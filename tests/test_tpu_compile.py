"""Compile the main path's programs for a described TPU v5e, with no chip.

Each test lowers and compiles one program at deployment size against the
``v5e:2x2`` topology: the chip's compiler refuses what would not fit the
device, an unaligned Pallas block, or a primitive Mosaic cannot lower. Nothing
runs, so nothing here is a result or a time.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU compiler library.
"""
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import compat
from repro.core import bitrows
from repro.core.driver import AdwiseCore, FileSource, RingBuf, _run_scan_resident, _run_scan_ring
from repro.core.types import AdwiseConfig
from repro.engine.algorithms import pagerank_update
from repro.engine.gas import superstep_program
from repro.kernels.segment_sum import EB, SB, segment_sum_pallas
from repro.kernels.window_score import window_score_pallas

V = 1 << 22  # Graph500 scale 22
K = 32
W = 256
# Ops that re-lay-out a whole (V+1,) vertex table; the scan's step may
# scatter into the tables but must not run one of these over them.
RELAYOUT_OPS = {"reduce", "broadcast", "copy", "dynamic-update-slice"}
HLO_OP = re.compile(r"^(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([a-z][\w-]*)\(")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the persistent
    # cache without the chip: keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _adwise_inputs(one_chip, v=V, k=K):
    core = AdwiseCore(cfg=AdwiseConfig(k=k, window_max=W), num_vertices=v)
    base = jax.eval_shape(lambda: core.init_carry(0.0))
    carry = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype, sharding=one_chip),
        base,
    )
    vec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return core, carry, vec


def _computations(hlo: str) -> dict:
    """``{name: instruction lines}`` of a compiled module's HLO text."""
    comps: dict = {}
    lines = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            lines = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            lines = None
        elif lines is not None and line.strip():
            lines.append(line.strip())
    return comps


def step_loop_relayouts(hlo: str) -> list:
    """The V-sized ``RELAYOUT_OPS`` that run on every step of the scan: in
    the body of the ``while`` that carries the replica words and the
    (W, 2) window, or in any computation that body calls."""
    comps = _computations(hlo)
    (step,) = [
        line for lines in comps.values() for line in lines
        if " while(" in line
        and re.search(rf"u32\[(?:1,)?{V + 1}[],]", line.split(" while(")[0])
        and re.search(rf"s32\[(?:1,)?{W},2\]", line.split(" while(")[0])
    ]
    todo, seen = [re.search(r"body=%([^,\s]+)", step).group(1)], set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(r"(?:calls|body|condition|to_apply)=%([^,\s)]+)", line)
    found = []
    for name in seen:
        for line in comps[name]:
            op = HLO_OP.match(line)
            if (op and op.group(2) in RELAYOUT_OPS
                    and math.prod(int(d) for d in op.group(1).split(",") if d) == V + 1):
                found.append(line)
    return found


def test_adwise_resident_scan_compiles_at_scale_22(one_chip):
    per = (1 << 16) + W
    core, carry, vec = _adwise_inputs(one_chip)
    compiled = _run_scan_resident.lower(
        carry, vec((1, per, 2), jnp.int32), vec((1,), jnp.int32),
        vec((1, K), jnp.bool_), vec((1,), jnp.int32), vec((1, per), jnp.int32),
        core=core, n_steps=1024, n_shards=0,
    ).compile()
    mem = compiled.memory_analysis()
    # The packed (V+1, ⌈K/32⌉) replica words are the bulk of the arguments
    # and fit one chip.
    assert mem.argument_size_in_bytes > (V + 1) * bitrows.words(K) * 4
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    # A lone instance's step only scatters into its vertex tables.
    assert step_loop_relayouts(compiled.as_text()) == []


def _compile_ring_scan(one_chip, v, k, chunk_edges):
    cfg = AdwiseConfig(k=k, window_max=W)
    src = FileSource([types.SimpleNamespace(num_edges=1 << 20)],
                     chunk_edges=chunk_edges, cfg=cfg)
    core, carry, vec = _adwise_inputs(one_chip, v, k)
    buf = RingBuf(uv=vec((1, src.B, 2), jnp.int32), prev=vec((1, src.B), jnp.int32))
    return _run_scan_ring.lower(
        (carry, buf), vec((1,), jnp.int32), vec((1, k), jnp.bool_),
        vec((1,), jnp.int32), core=core, n_steps=src.scan_steps, n_shards=0,
    ).compile()


def test_adwise_ring_scan_compiles_at_scale_22(one_chip):
    """The file path's program: the donated ring rides in the carry."""
    compiled = _compile_ring_scan(one_chip, V, K, 1 << 16)
    assert (compiled.memory_analysis().argument_size_in_bytes
            > (V + 1) * bitrows.words(K) * 4)
    assert step_loop_relayouts(compiled.as_text()) == []


def test_adwise_ring_scan_compiles_at_scale_25_k512(one_chip):
    """Graph500 SCALE 25 at k = 512 on one chip: as (V+1, K) bools the
    replica table alone is 17.2 GB; packed it is 2.15 GB."""
    v, k = 1 << 25, 512
    mem = _compile_ring_scan(one_chip, v, k, 8192).memory_analysis()
    # Replica words, versions and degrees, all donated with the carry.
    assert mem.argument_size_in_bytes >= (v + 1) * (bitrows.words(k) + 2) * 4
    assert mem.argument_size_in_bytes < 3e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("w,k", [(256, 32), (256, 128)])
def test_window_score_kernel_compiles_for_v5e(one_chip, w, k):
    vec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(window_score_pallas).lower(
        vec((w, 2), jnp.int32), vec((w,), jnp.bool_), vec((w, k), jnp.bool_),
        vec((w, k), jnp.bool_), vec((w,), jnp.int32), vec((w,), jnp.int32),
        vec((k,), jnp.float32), vec((k,), jnp.bool_), vec((), jnp.float32),
        vec((), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_sum_kernel_compiles_at_engine_layout(one_chip):
    """Scale-22 destination layout: one segment block per 128 vertices."""
    n_sblocks = V // SB
    max_chunks = 4
    e_pad = n_sblocks * EB
    vec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn = lambda data, loc, ptr, nch: segment_sum_pallas(
        data, loc, ptr, nch, V, max_chunks=max_chunks)
    compiled = jax.jit(fn).lower(
        vec((e_pad, 1), jnp.float32), vec((e_pad,), jnp.int32),
        vec((n_sblocks,), jnp.int32), vec((n_sblocks,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_pagerank_superstep_compiles_on_four_chips(topo):
    """One PageRank superstep at V=2**22, k=32 sharded over a 2x2 mesh: the
    replica sync is a real all-reduce and each chip holds its slab."""
    mesh = compat.make_mesh((4,), ("parts",), devices=np.array(topo.devices))
    msg, apply = pagerank_update(V)
    program = superstep_program(mesh, msg, apply, V)
    e_max = 40960  # ~1.25x the mean partition of 2**20 streamed edges
    rep, parts = NamedSharding(mesh, P()), NamedSharding(mesh, P("parts"))
    args = (
        jax.ShapeDtypeStruct((V, 1), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((K, e_max, 2), jnp.int32, sharding=parts),
        jax.ShapeDtypeStruct((K, e_max), jnp.bool_, sharding=parts),
        jax.ShapeDtypeStruct((K, V), jnp.bool_, sharding=parts),
        jax.ShapeDtypeStruct((V,), jnp.int32, sharding=rep),
    )
    compiled = program.lower(*args).compile()
    assert "all-reduce" in compiled.as_text()
    mem = compiled.memory_analysis()
    per_device_args = (
        V * 4 + V * 4  # replicated state and degrees
        + (K // 4) * (e_max * 2 * 4 + e_max + V)  # this chip's slabs
    )
    assert mem.argument_size_in_bytes == per_device_args
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
