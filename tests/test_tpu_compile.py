"""The served kernels compile for a TPU v5e that is described, not attached.

Mosaic refuses what the pallas interpreter accepts (dynamic slices of
loaded values, int8 matmul accumulators, rank-3 temporaries, unaligned
SMEM blocks, VMEM over budget), so every program shape the served path
launches on the chip is compiled here by the chip's own compiler: the toy
bench tile, the vec cells' 20-trit add and the full-width qwen3-0.6b MAC
tiles on a 4096-row bank block, a VLIW-packed program, the four-chip
shard_map route and the packed-ternary matmul.  Nothing runs; these tests
say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each pytest worker imports
every test file.
"""
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import apc
from repro.apc.lower import compile_checksum, resolve_schedule
from repro.apc.mac import compile_mac_tiled, mac_acc_width
from repro.kernels.tap_pass.kernel import _tap_program_pallas, resolve_unroll
from repro.kernels.ternary_matmul.ap import default_k_tile
from repro.kernels.ternary_matmul.kernel import ternary_matmul

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _tiled(K: int, max_cols: int):
    """The TiledMac an APLinear builds for a K-wide projection (x_levels
    7, radix 3) on a bank of ``max_cols`` columns."""
    width = mac_acc_width(3, K, 7)
    kt = min(default_k_tile(max_cols, width), K)
    return compile_mac_tiled(3, K, width, kt, max_cols=max_cols)


def _assert_compiled(compiled):
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_HBM_BYTES
    assert "tpu_custom_call" in compiled.as_text()


def _compile_program(sharding, prog, rows: int, *, collect_stats: bool,
                     kernel_variant: str = "lanes", packed: bool = False):
    tensors, variant, pack, _ = resolve_schedule(prog, kernel_variant)
    if packed:                  # the lanes body replays a packed grid too
        p = prog.packed()
        tensors, pack = p.schedule_tensors, p.pack

    def run(arr, n_valid, *sched):
        return _tap_program_pallas(
            arr, sched, n_valid, block_rows=rows,
            collect_stats=collect_stats, hist_bins=8, interpret=False,
            unroll=resolve_unroll(None, variant, pack), variant=variant,
            pack=pack)

    args = [jax.ShapeDtypeStruct((rows, prog.min_cols), jnp.int8,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)]
    args += [jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=sharding)
             for t in tensors]
    return jax.jit(run).lower(*args).compile(), variant, pack


@pytest.mark.parametrize("collect_stats", [True, False])
def test_toy_bench_tile_compiles(one_chip, collect_stats):
    """The serve bench's d_ff=24 down-projection tile on its 64-row bank."""
    prog = _tiled(24, 64).programs[0]
    compiled, variant, pack = _compile_program(one_chip, prog, 64,
                                               collect_stats=collect_stats)
    assert (variant, pack) == ("lanes", 1)
    _assert_compiled(compiled)


def test_vec_add_block_compiles(one_chip):
    """The vec cells' 20-trit add (421 steps, 41 columns) on one 4096-row
    bank block."""
    prog = apc.compile_named("add", 3, 20)
    assert prog.min_cols == 41
    compiled, variant, _ = _compile_program(one_chip, prog, 4096,
                                            collect_stats=True)
    assert variant == "lanes"
    _assert_compiled(compiled)


@pytest.mark.parametrize("K", [1024, 3072])
def test_full_width_mac_tile_compiles(one_chip, K):
    """qwen3-0.6b gate/up (K=1024) and down (K=3072) tiles, ~9k steps
    each, on one 4096 x 256 bank block with counters on — the schedule
    streams through SMEM in chunks, the block fits VMEM."""
    tiled = _tiled(K, 256)
    assert tiled.programs[0].n_steps > 9000
    assert tiled.programs[0].min_cols == {1024: 250, 3072: 253}[K]
    for prog in (tiled.programs[0], tiled.reduce_programs[0]):
        compiled, variant, _ = _compile_program(one_chip, prog, 4096,
                                                collect_stats=True)
        assert variant == "lanes"
        _assert_compiled(compiled)


def test_packed_program_compiles(one_chip):
    """A VLIW-packed schedule under the one-hot body, and under the lanes
    body, which replays its slots one by one."""
    prog = apc.compile_named("max", 3, 8)
    compiled, variant, pack = _compile_program(
        one_chip, prog, 4096, collect_stats=True,
        kernel_variant="onehot_packed")
    assert variant == "onehot" and pack > 1
    _assert_compiled(compiled)
    compiled, variant, pack = _compile_program(
        one_chip, prog, 4096, collect_stats=True, packed=True)
    assert variant == "lanes" and pack > 1
    _assert_compiled(compiled)


def test_four_chip_route_compiles(topo, monkeypatch):
    """DevicePool's shard_map route over the 2x2 mesh: one kernel per
    chip, counters psummed across them."""
    from repro.apc.exec import sharded_program_run
    # the program kernel picks its Mosaic branch from the default backend,
    # which here is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4, 1),
                ("pod", "data", "model"))
    prog = _tiled(1024, 256).programs[0]
    tensors, variant, pack, _ = resolve_schedule(prog)
    assert variant == "lanes"

    def run(arr, *sched):
        return sharded_program_run(arr, sched, mesh, ("pod", "data"),
                                   3 * 4096, 4096, collect_stats=True,
                                   interpret=False, variant=variant,
                                   pack=pack, unroll=1)

    rep = NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct((4 * 4096, prog.min_cols), jnp.int8,
                                 sharding=NamedSharding(mesh, P("data")))]
    args += [jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=rep)
             for t in tensors]
    compiled = jax.jit(run).lower(*args).compile()
    _assert_compiled(compiled)
    assert "all-reduce" in compiled.as_text()


def test_kernel_keeps_the_name_the_benchmark_reads(one_chip, monkeypatch):
    """The launch ``tap_run_program`` dispatches (``_tap_run_program_jit``)
    lowers on the chip to a custom call whose instruction text is what
    the benchmark's kernel readers match in the device trace
    (``bench/work.py``'s ``KERNEL_PATTERN``): renaming the jit would
    otherwise silently zero ``kernel_roofline.*``."""
    from repro.kernels.tap_pass import kernel as K
    spec = importlib.util.spec_from_file_location(
        "bench_work", Path(__file__).resolve().parents[1] / "bench/work.py")
    work = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(work)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prog = apc.compile_named("add", 3, 20)        # the vec cells' program
    tensors, variant, pack, _ = resolve_schedule(prog)
    assert variant == "lanes"
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = K._tap_run_program_jit.lower(
        s((4096, prog.min_cols), jnp.int8),
        *[s(t.shape, t.dtype) for t in tensors], s((), jnp.int32),
        block_rows=4096, collect_stats=True, hist_bins=8, interpret=False,
        unroll=resolve_unroll(None, variant, pack), variant=variant,
        pack=pack).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "custom-call(" in ln]
    assert calls
    assert any(re.search(work.KERNEL_PATTERN, ln) for ln in calls), calls


def test_ternary_matmul_kernel_compiles(one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda x, w, sc: ternary_matmul(x, w, sc, interpret=False)).lower(
        s((128, 1024), jnp.float32), s((64, 3072), jnp.int32),
        s((3072,), jnp.float32)).compile()
    _assert_compiled(compiled)


# ---------------------------------------------------------------------------
# Variant resolution on TPU: every served program takes the lanes body
# ---------------------------------------------------------------------------

def test_served_programs_resolve_lanes_on_tpu(monkeypatch):
    """Every MAC tile, reduction and checksum program of the full-width
    served MLP, and the vec cells' add, resolves to the lanes body when the
    backend is a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    progs = []
    for K in (1024, 3072):
        tiled = _tiled(K, 256)
        progs += list(tiled.programs) + list(tiled.reduce_programs)
        progs += [compile_checksum(p.min_cols, 3)
                  for p in (tiled.programs[0], tiled.reduce_programs[0])]
    progs.append(apc.compile_named("add", 3, 20))
    for prog in progs:
        _, variant, pack, name = resolve_schedule(prog)
        assert (variant, pack, name) == ("lanes", 1, "lanes")


def test_duplicate_columns_raise_on_tpu(monkeypatch):
    """The gather fallback for duplicate columns has no Mosaic lowering:
    on TPU the resolver refuses instead of switching bodies silently."""
    prog = apc.compile_program((
        apc.CompareWrite(compare_cols=(0,), key=(1,), write_cols=(2, 2),
                         write_vals=(1, 2)),))
    assert resolve_schedule(prog, "onehot")[1] == "gather"   # CPU fallback
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="Mosaic"):
        resolve_schedule(prog, "onehot")
    # the lanes body replays duplicate columns as the gather body does
    assert resolve_schedule(prog)[1] == "lanes"


def test_gather_body_refused_compiled_on_tpu(monkeypatch):
    from repro.kernels.tap_pass.kernel import tap_run_program
    prog = apc.compile_named("add", 3, 2)
    arr = jnp.zeros((8, prog.min_cols), jnp.int8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="Mosaic"):
        tap_run_program(arr, *map(jnp.asarray, prog.schedule_tensors),
                        jnp.int32(8), block_rows=8, interpret=False,
                        variant="gather")
