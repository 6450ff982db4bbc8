"""Compile the main path's Pallas kernels for a described v5e, no chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached (on-chip-measurement guide, section 2). Each
test lowers one kernel at the widths and capacities the planner produces
for the chip smoke's HiBench-Terasort cell — 100 B rows, 32,000,000 per
chip — and asserts that the kernel survives into the compiled program as
a ``tpu_custom_call``. What interpret mode cannot show is refused here:
slices not aligned to the tiling, more VMEM than a kernel may use.

The topology is described inside a module fixture — never at import, in
a ``skipif`` or in a ``parametrize`` argument — so that every xdist
worker collects the same tests and only the worker given this file
loads libtpu. The persistent compile cache is off around these
compiles: a program compiled for a described chip is written to the
cache but cannot be read back without one.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

TERASORT_ROWS = 32_000_000          # HiBench large, per chip
TERASORT_WIDTH = 25                 # int64 key (2 lanes) + 23 payload lanes
WORDCOUNT_WIDTH = 3                 # int64 word id + int32 count
WAVE_ROWS = TERASORT_ROWS // 8      # chip_smoke.py's a2a.waveRows on 4 chips


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def caps():
    """The plans the terasort cell's exchanges run: single-shot on one
    chip, and one wave per chip of four (3.2 GB a chip does not fit one
    exchange there: the receive buffer carries capacityFactor headroom)."""
    import dataclasses

    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.shuffle.plan import make_plan, wave_step_plan
    conf = TpuShuffleConf(use_env=False)
    one = make_plan(np.array([TERASORT_ROWS]), 1, 64, conf)
    four = make_plan(np.full(4, TERASORT_ROWS), 4, 256, conf)
    wave = wave_step_plan(dataclasses.replace(four, wave_rows=WAVE_ROWS),
                          conf)
    return one, wave


def _compiled_text(fn, *shapes) -> str:
    import jax
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_segment_reduce_kernel_compiles(one_chip, caps):
    """The receive-side combine of the four-chip wordcount: the blocked
    segment-reduce over a whole receive buffer (the kernel's output is
    gridded, so VMEM holds two small blocks at any capacity)."""
    import jax
    import jax.numpy as jnp

    from sparkucx_tpu.ops.pallas.segmented import segment_reduce_rows
    cap = caps[1].cap_out
    rows = jax.ShapeDtypeStruct((cap, WORDCOUNT_WIDTH), jnp.int32,
                                sharding=one_chip)
    part = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=one_chip)

    def fn(r, p):
        return segment_reduce_rows(r, p, 256, 1, np.int32, impl="pallas",
                                   interpret=False)
    text = _compiled_text(fn, rows, part)
    assert "tpu_custom_call" in text
    # named, so a profiler trace finds the kernel's own ops
    assert "%segreduce_blocked" in text


def test_segment_reduce_compaction_index_has_no_search(one_chip, caps):
    """The segment-reduce's compaction index at the four-chip wordcount
    wave's capacity and tile count compiles to no loop and no gather: a
    binary search of every output slot is a ``while`` of cap-length
    gathers on the chip."""
    import re

    import jax
    import jax.numpy as jnp

    from sparkucx_tpu.ops.pallas.segmented import _TILE, _compact_src
    cap = caps[1].cap_out
    counts = jax.ShapeDtypeStruct((-(-cap // _TILE),), jnp.int32,
                                  sharding=one_chip)
    text = _compiled_text(lambda c: _compact_src(c, cap, 2 * _TILE),
                          counts)
    assert re.search(r"\sscatter\(", text)
    assert not re.search(r"\swhile\(", text)
    assert not re.search(r"\sgather\(", text)


def test_fused_int8_reduce_kernel_compiles(one_chip, caps):
    """The int8-wire fused dequant segment-reduce over terasort-width
    float rows in wire format."""
    import jax
    import jax.numpy as jnp

    from sparkucx_tpu.ops.pallas.segmented import segment_reduce_wire_rows
    from sparkucx_tpu.shuffle.alltoall import int8_wire_words
    vw = TERASORT_WIDTH - 2
    cap = caps[1].cap_out
    rows = jax.ShapeDtypeStruct((cap, 2 + int8_wire_words(vw)), jnp.int32,
                                sharding=one_chip)
    part = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=one_chip)

    def fn(r, p):
        return segment_reduce_wire_rows(r, p, 256, TERASORT_WIDTH, vw,
                                        impl="pallas", interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, rows, part)


def test_int8_quantize_kernel_compiles(one_chip, caps):
    """The int8 wire's quantize kernel over one wave's send buffer of
    float payload lanes (a whole single-shot buffer does not fit: its
    23-lane intermediates are laid out 128 lanes wide in HBM)."""
    import jax
    import jax.numpy as jnp

    from sparkucx_tpu.ops.pallas.quant import quantize_rows
    x = jax.ShapeDtypeStruct((caps[1].cap_in, TERASORT_WIDTH - 2),
                             jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda v: quantize_rows(v, 0, impl="pallas"), x)
    assert "tpu_custom_call" in text


def test_merge_kernel_compiles_at_its_vmem_cap(one_chip):
    """The blocked merge keeps both sorted runs VMEM-resident, so the
    device merge fold only takes it up to ``merge_rows_cap`` rows per
    fold (reader.resolve_merge_impl); compile it at that cap."""
    import jax
    import jax.numpy as jnp

    from sparkucx_tpu.ops.pallas.segmented import merge_rows, merge_rows_cap
    ca = cb = merge_rows_cap(TERASORT_WIDTH) // 2
    a = jax.ShapeDtypeStruct((ca, TERASORT_WIDTH), jnp.int32,
                             sharding=one_chip)
    ap = jax.ShapeDtypeStruct((ca,), jnp.int32, sharding=one_chip)

    def fn(x, xp, y, yp):
        return merge_rows(x, xp, y, yp, 64, impl="pallas", interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, a, ap, a, ap)


def test_remote_dma_kernel_compiles_on_2x2(topo, caps):
    """The Pallas remote-DMA transport on the 2x2 mesh at one terasort
    wave's per-chip capacities: its DMA slices are whole 128-lane chunks
    of 100 B rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparkucx_tpu.ops.pallas.ragged_a2a import (align_rows,
                                                    chunk_rows_for,
                                                    pallas_ragged_all_to_all)
    n = 4
    chunk = chunk_rows_for(TERASORT_WIDTH)
    assert chunk * TERASORT_WIDTH % 128 == 0
    cap_in = align_rows(caps[1].cap_in, chunk) + n * chunk
    cap_out = align_rows(caps[1].cap_out, chunk) + n * chunk
    mesh = Mesh(np.array(topo.devices), ("x",))
    sh = NamedSharding(mesh, P("x"))

    def step(rows, sz):
        return pallas_ragged_all_to_all(rows, sz[0], "x",
                                        out_capacity=cap_out, num_devices=n)
    fn = jax.shard_map(step, mesh=mesh, in_specs=(P("x"), P("x")),
                       out_specs=(P("x"),) * 4, check_vma=False)
    text = _compiled_text(
        fn, jax.ShapeDtypeStruct((n * cap_in, TERASORT_WIDTH), jnp.int32,
                                 sharding=sh),
        jax.ShapeDtypeStruct((n, n), jnp.int32, sharding=sh))
    assert "tpu_custom_call" in text


def test_native_exchange_step_compiles_on_2x2(topo):
    """One plain exchange step of the production path over the 2x2 mesh
    with ``a2a.impl=native``: the ragged all-to-all spans all four
    chips in the compiled program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparkucx_tpu.shuffle.plan import ShufflePlan
    from sparkucx_tpu.shuffle.reader import _build_step_uncached
    n, cap = 4, 1 << 16
    plan = ShufflePlan(n, 64, cap_in=cap, cap_out=2 * cap, impl="native",
                       sort_impl="argsort", pallas_interpret=False)
    mesh = Mesh(np.array(topo.devices), ("shuffle",))
    sh = NamedSharding(mesh, P("shuffle"))
    step = _build_step_uncached(mesh, "shuffle", plan, TERASORT_WIDTH)
    text = step.lower(
        jax.ShapeDtypeStruct((n * cap, TERASORT_WIDTH), jnp.int32,
                             sharding=sh),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sh)
    ).compile().as_text()
    assert "ragged-all-to-all" in text
    assert "replica_groups={{0,1,2,3}}" in text.replace(" ", "")


def test_wordcount_combine_step_compiles_on_2x2(topo):
    """The four-chip WordCount exchange with ``read.mergeImpl=pallas``:
    the map-side combine, the ragged all-to-all of 3-word rows, the
    named segment-reduce kernel, and a last output of three int32 words
    a shard (``reader.combine_status``: flag, rows received, rows out)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.shuffle.plan import make_plan
    from sparkucx_tpu.shuffle.reader import _build_step_uncached
    n = 4
    plan = dataclasses.replace(
        make_plan(np.full(n, 4096), n, 256, TpuShuffleConf(use_env=False)),
        impl="native", combine="sum", combine_words=WORDCOUNT_WIDTH - 2,
        combine_dtype="<i4", kernel_impl="pallas", pallas_interpret=False)
    mesh = Mesh(np.array(topo.devices), ("shuffle",))
    sh = NamedSharding(mesh, P("shuffle"))
    step = _build_step_uncached(mesh, "shuffle", plan, WORDCOUNT_WIDTH)
    lowered = step.lower(
        jax.ShapeDtypeStruct((n * plan.cap_in, WORDCOUNT_WIDTH), jnp.int32,
                             sharding=sh),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sh))
    last = lowered.out_info[3]
    assert (last.shape, last.dtype) == ((3 * n,), jnp.int32)
    text = lowered.compile().as_text()
    assert "ragged-all-to-all" in text
    assert "%segreduce_blocked" in text


def test_wordcount_combine_step_compiles_carried(topo):
    """The one-chip WordCount exchange: its map-side combine sorts the
    3-word (key, count) rows twice with their columns carried, one sort
    signature, and gathers nothing of the capacity's length. 16,384 rows
    already take XLA:TPU's large-sort code path (a capacity of 4,096
    compiles ten times faster), while the cell's 38.9M rows would take a
    minute to compile here."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparkucx_tpu.config import TpuShuffleConf
    from sparkucx_tpu.shuffle.plan import make_plan
    from sparkucx_tpu.shuffle.reader import _build_step_uncached
    plan = dataclasses.replace(
        make_plan(np.array([1 << 14]), 1, 64, TpuShuffleConf(use_env=False)),
        combine="sum", combine_words=WORDCOUNT_WIDTH - 2, combine_dtype="<i4",
        pallas_interpret=False)
    mesh = Mesh(np.array(topo.devices[:1]), ("shuffle",))
    sh = NamedSharding(mesh, P("shuffle"))
    step = _build_step_uncached(mesh, "shuffle", plan, WORDCOUNT_WIDTH)
    text = step.lower(
        jax.ShapeDtypeStruct((plan.cap_in, WORDCOUNT_WIDTH), jnp.int32,
                             sharding=sh),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=sh)
    ).compile().as_text()
    col = "s32[%d]" % plan.cap_in
    # an instruction's result shape is the text between "=" and the op
    shapes = {op: [line.split(" = ", 1)[1].split(f" {op}(")[0]
                   for line in text.splitlines() if f" {op}(" in line]
              for op in ("sort", "gather")}
    assert [s.count(col) for s in shapes["sort"]] == [4, 4], shapes["sort"]
    assert not [s for s in shapes["gather"] if col in s], shapes["gather"]


def test_chip_smoke_refuses_without_a_tpu(tmp_path):
    """chip_smoke.py on the CPU backend exits non-zero and prints no
    result, and a --rows rehearsal runs both phases on their oracles
    but still never prints ``"ok": true``."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)      # one CPU device, as on one chip
    script = os.path.join(root, "chip_smoke.py")
    bare = subprocess.run([sys.executable, script], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert bare.returncode != 0 and '"ok"' not in bare.stdout
    rehearsal = subprocess.run(
        [sys.executable, script, "--rows", "4096"], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)
    assert rehearsal.returncode != 0, rehearsal.stderr
    assert '"ok"' not in rehearsal.stdout
    phases = [line for line in rehearsal.stdout.splitlines()
              if line.startswith("{")]
    assert ['"terasort"' in phases[0], '"wordcount"' in phases[1]] \
        == [True, True], rehearsal.stdout
