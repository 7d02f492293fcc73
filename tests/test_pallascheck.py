"""Tests for the static Pallas kernel verifier (analysis/pallascheck).

One injected-violation fixture per finding kind — each built as a real
``pl.pallas_call`` traced through the same path as the registry — with the
localization asserted (kernel name, grid-point class, and the operand
named in the message), plus the clean-registry proof, the ``pallas``
contract section round-trip, the CLI surface, and the rule-12
``unregistered-pallas-call`` analyzer fixtures.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi4dl_tpu.analysis.pallascheck import (
    FINDING_KINDS,
    VMEM_BYTES,
    check_case,
    finding_counts,
    pallas_contract,
)
from mpi4dl_tpu.ops.kernel_registry import REGISTRY, KernelCase

F32 = jnp.float32
OUT8 = jax.ShapeDtypeStruct((8, 128), F32)


def _case(name, build, ring=None):
    return KernelCase(name=name, build=build, ring_size=ring)


def _kinds(findings):
    return {f.kind for f in findings}


def _by_kind(findings, kind):
    got = [f for f in findings if f.kind == kind]
    assert got, f"no {kind} finding in {[f.render() for f in findings]}"
    return got


def _copy_kernel(x_ref, o_ref, o2_ref):
    o_ref[...] = x_ref[...]
    o2_ref[...] = x_ref[...]


# ---------------------------------------------------------------------------
# grid/BlockSpec soundness fixtures (a)
# ---------------------------------------------------------------------------


def test_oob_block_localizes():
    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def build():
        x = jnp.zeros((16, 128), F32)
        f = pl.pallas_call(
            k,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i + 1, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), F32),
        )
        return f, (x,)

    fs = check_case(_case("fx:oob", build))
    f = _by_kind(fs, "oob-block")[0]
    assert f.kernel == "fx:oob"
    assert f.grid_class == "hi"  # the i+1 map walks off at the LAST point
    assert "out0" in f.message
    assert f.key == "fx:oob:hi:oob-block"


def test_overlapping_output_localizes():
    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def build():
        x = jnp.zeros((32, 128), F32)
        f = pl.pallas_call(
            k,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i % 2, 0)),
            out_shape=jax.ShapeDtypeStruct((32, 128), F32),
        )
        return f, (x,)

    fs = check_case(_case("fx:overlap", build))
    got = _by_kind(fs, "overlapping-output")
    # block (0,0) is re-clobbered at step 2 (interior) and (1,0) at step 3
    assert {f.grid_class for f in got} == {"mid", "hi"}
    assert all("out0" in f.message and "non-consecutively" in f.message
               for f in got)


def test_untiled_output_localizes():
    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def build():
        x = jnp.zeros((8, 128), F32)
        f = pl.pallas_call(
            k,
            grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), F32),
        )
        return f, (x,)

    fs = check_case(_case("fx:untiled", build))
    f = _by_kind(fs, "untiled-output")[0]
    assert f.grid_class == ""  # grid-wide property, not one point's
    assert "out0" in f.message and "never" in f.message


def test_misaligned_block_localizes():
    def k(x_ref, o_ref):
        o_ref[...] = jnp.zeros_like(o_ref)

    def build():
        x = jnp.zeros((8, 200), F32)
        f = pl.pallas_call(
            k,
            grid=(1,),
            in_specs=[pl.BlockSpec((8, 100), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=OUT8,
        )
        return f, (x,)

    fs = check_case(_case("fx:misaligned", build))
    f = _by_kind(fs, "misaligned-block")[0]
    assert "in0" in f.message and "lane" in f.message and "100" in f.message


def test_full_extent_and_singleton_blocks_are_aligned():
    """A block dim equal to the whole array extent (e.g. the conv kernel's
    300-channel weight slab) or squeezed to 1 must NOT trip alignment."""
    def k(x_ref, o_ref):
        o_ref[...] = jnp.zeros_like(o_ref)

    def build():
        x = jnp.zeros((1, 8, 200), F32)
        f = pl.pallas_call(
            k,
            grid=(1,),
            in_specs=[pl.BlockSpec((1, 8, 200), lambda i: (0, 0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=OUT8,
        )
        return f, (x,)

    fs = check_case(_case("fx:full-extent", build))
    assert "misaligned-block" not in _kinds(fs)


# ---------------------------------------------------------------------------
# VMEM budget fixture (b)
# ---------------------------------------------------------------------------


def test_vmem_overbudget_localizes():
    def k(x_ref, o_ref, big):
        o_ref[...] = x_ref[...]

    def build():
        x = jnp.zeros((8, 128), F32)
        f = pl.pallas_call(
            k,
            grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=OUT8,
            scratch_shapes=[pltpu.VMEM((4096, 4096), F32)],  # 64 MiB
        )
        return f, (x,)

    fs = check_case(_case("fx:vmem", build))
    f = _by_kind(fs, "vmem-overbudget")[0]
    assert "scratch0" in f.message and "16 MiB" in f.message


def test_vmem_frac_gate_tightens():
    """A kernel comfortably inside 16 MiB still fails a tight frac gate —
    the CI headroom knob is real, not cosmetic."""
    def k(x_ref, o_ref, buf):
        o_ref[...] = x_ref[...]

    def build():
        x = jnp.zeros((8, 128), F32)
        f = pl.pallas_call(
            k,
            grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=OUT8,
            scratch_shapes=[pltpu.VMEM((512, 1024), F32)],  # 2 MiB
        )
        return f, (x,)

    case = _case("fx:frac", build)
    assert "vmem-overbudget" not in _kinds(check_case(case))
    tight = check_case(case, require_vmem_frac=0.01)
    assert "vmem-overbudget" in _kinds(tight)


# ---------------------------------------------------------------------------
# DMA/semaphore discipline fixtures (c)
# ---------------------------------------------------------------------------

_ANY = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)


def _dma_fixture(kernel, n_sems=1, grid=(1,)):
    def build():
        x = jnp.zeros((8, 128), F32)
        f = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[_ANY],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=OUT8,
            scratch_shapes=[pltpu.VMEM((8, 128), F32)]
            + [pltpu.SemaphoreType.DMA] * n_sems,
        )
        return f, (x,)

    return build


def test_unmatched_dma_start_without_wait():
    def k(x_ref, o_ref, buf, sem):
        pltpu.make_async_copy(x_ref, buf, sem).start()
        o_ref[...] = jnp.zeros_like(o_ref)

    fs = check_case(_case("fx:nowait", _dma_fixture(k)))
    f = _by_kind(fs, "unmatched-dma")[0]
    assert "still in flight when the kernel ends" in f.message
    assert "scratch1" in f.message  # the semaphore is named


def test_unmatched_dma_wait_without_start():
    def k(x_ref, o_ref, buf, sem):
        pltpu.make_async_copy(x_ref, buf, sem).wait()
        o_ref[...] = buf[...]

    fs = check_case(_case("fx:nostart", _dma_fixture(k)))
    f = _by_kind(fs, "unmatched-dma")[0]
    assert "no copy in flight" in f.message


def test_dma_race_read_destination_before_wait():
    def k(x_ref, o_ref, buf, sem):
        cp = pltpu.make_async_copy(x_ref, buf, sem)
        cp.start()
        o_ref[...] = buf[...]  # reads the landing buffer pre-wait
        cp.wait()

    fs = check_case(_case("fx:read-early", _dma_fixture(k)))
    f = _by_kind(fs, "dma-race")[0]
    assert f.grid_class == "lo"
    assert "scratch0" in f.message and "read" in f.message


def test_dma_race_write_source_in_flight():
    """The WAR hazard Mosaic does not fence, as a checked invariant:
    storing into the source of an in-flight copy."""
    def k(x_ref, o_ref, a, b, sem):
        a[...] = x_ref[...]
        cp = pltpu.make_async_copy(a, b, sem)
        cp.start()
        a[...] = a[...] * 2.0  # clobbers the bytes still being read out
        cp.wait()
        o_ref[...] = b[...]

    def build():
        x = jnp.zeros((8, 128), F32)
        f = pl.pallas_call(
            k,
            grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=OUT8,
            scratch_shapes=[pltpu.VMEM((8, 128), F32),
                            pltpu.VMEM((8, 128), F32),
                            pltpu.SemaphoreType.DMA],
        )
        return f, (x,)

    fs = check_case(_case("fx:war", build))
    f = _by_kind(fs, "dma-race")[0]
    assert "SOURCE" in f.message and "scratch0" in f.message


def test_dma_disciplined_kernel_is_clean():
    """start/wait correctly paired, destination read only after the wait."""
    def k(x_ref, o_ref, buf, sem):
        cp = pltpu.make_async_copy(x_ref, buf, sem)
        cp.start()
        cp.wait()
        o_ref[...] = buf[...]

    assert check_case(_case("fx:dma-clean", _dma_fixture(k))) == []


def test_unmatched_dma_across_divergent_when():
    """A start guarded by a data-dependent predicate the interpreter cannot
    fold must pair with a wait on EVERY path, not just one."""
    def k(s_ref, x_ref, o_ref, buf, sem):
        @pl.when(s_ref[0] > 0)  # scalar-prefetch value: unknowable
        def _start():
            pltpu.make_async_copy(x_ref, buf, sem).start()

        o_ref[...] = jnp.zeros_like(o_ref)

    def build():
        s = jnp.zeros((1,), jnp.int32)
        x = jnp.zeros((8, 128), F32)
        f = pl.pallas_call(
            k,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(1,),
                in_specs=[_ANY],
                out_specs=pl.BlockSpec((8, 128), lambda i, s: (0, 0)),
                scratch_shapes=[pltpu.VMEM((8, 128), F32),
                                pltpu.SemaphoreType.DMA],
            ),
            out_shape=OUT8,
        )
        return f, (s, x)

    fs = check_case(_case("fx:diverge", build))
    assert "unmatched-dma" in _kinds(fs)


# ---------------------------------------------------------------------------
# remote-copy device-map fixtures (c, topology)
# ---------------------------------------------------------------------------


def _remote_fixture(device_id_of):
    def k(x_ref, o_ref, buf, send_sem, recv_sem):
        i = pl.program_id(0)
        cp = pltpu.make_async_remote_copy(
            x_ref, buf, send_sem, recv_sem,
            device_id=device_id_of(i),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        cp.start()
        cp.wait()
        o_ref[...] = buf[...]

    def build():
        x = jnp.zeros((8, 128), F32)
        f = pl.pallas_call(
            k,
            grid=(4,),
            in_specs=[_ANY],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=OUT8,
            scratch_shapes=[pltpu.VMEM((8, 128), F32),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
        )
        return f, (x,)

    return build


def test_ring_shift_device_map_is_clean():
    """The halo-exchange shape: grid point i sends to (i+1) mod ring."""
    fs = check_case(_case("fx:ring", _remote_fixture(lambda i: (i + 1) % 4),
                          ring=4))
    assert fs == []


def test_nonbijective_device_map_localizes():
    fs = check_case(_case("fx:const-dev", _remote_fixture(lambda i: 0),
                          ring=4))
    f = _by_kind(fs, "nonbijective-device-map")[0]
    assert "not injective" in f.message and "device 0" in f.message


def test_device_id_outside_declared_ring():
    fs = check_case(_case("fx:off-ring", _remote_fixture(lambda i: i + 2),
                          ring=4))
    f = _by_kind(fs, "nonbijective-device-map")[0]
    assert "outside the declared ring" in f.message


def test_remote_copy_without_declared_topology():
    fs = check_case(
        _case("fx:no-topo", _remote_fixture(lambda i: (i + 1) % 4)))
    f = _by_kind(fs, "nonbijective-device-map")[0]
    assert f.grid_class == ""
    assert "ring_size" in f.message


# ---------------------------------------------------------------------------
# accumulator-init fixtures (d)
# ---------------------------------------------------------------------------


def _acc_fixture(init_at):
    """The pallas_attention ki==0/ki==nk-1 shape with a parameterized init
    guard over a 2-long inner accumulation run."""
    def k(o_ref, acc):
        ki = pl.program_id(0)

        @pl.when(ki == init_at)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jnp.ones_like(acc)

        @pl.when(ki == 1)
        def _emit():
            o_ref[...] = acc[...]

    def build():
        f = pl.pallas_call(
            k,
            grid=(2,),
            in_specs=[],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=OUT8,
            scratch_shapes=[pltpu.VMEM((8, 128), F32)],
        )
        return f, ()

    return build


def test_uninit_accumulator_localizes():
    fs = check_case(_case("fx:uninit", _acc_fixture(init_at=1)))
    f = _by_kind(fs, "uninit-accumulator")[0]
    assert f.grid_class == "lo"  # first read happens at the FIRST grid step
    assert "scratch0" in f.message


def test_correctly_guarded_accumulator_is_clean():
    assert check_case(_case("fx:init-ok", _acc_fixture(init_at=0))) == []


def test_stale_accumulator_across_revisited_outputs():
    """Init guarded on the INNER index being 0 covers every revisit run;
    guarding on the OUTER index leaves run 2's accumulator carrying run
    1's values — the exact bug class of a wrong flash-attention guard."""
    def make(guard_outer):
        def k(o_ref, acc):
            qi = pl.program_id(0)
            ki = pl.program_id(1)
            pred = (qi == 0) if guard_outer else (ki == 0)

            @pl.when(pred)
            def _init():
                acc[...] = jnp.zeros_like(acc)

            acc[...] += jnp.ones_like(acc)

            @pl.when(ki == 1)
            def _emit():
                o_ref[...] = acc[...]

        def build():
            f = pl.pallas_call(
                k,
                grid=(2, 2),
                in_specs=[],
                out_specs=pl.BlockSpec((8, 128), lambda qi, ki: (qi, 0)),
                out_shape=jax.ShapeDtypeStruct((16, 128), F32),
                scratch_shapes=[pltpu.VMEM((8, 128), F32)],
            )
            return f, ()

        return build

    fs = check_case(_case("fx:stale", make(guard_outer=True)))
    f = _by_kind(fs, "uninit-accumulator")[0]
    assert f.grid_class == "hi-lo"  # first step of the second output run
    assert "revisit" in f.message
    assert check_case(_case("fx:fresh", make(guard_outer=False))) == []


def _two_grain_fixture(fine_init, coarse_init):
    """A flash backward's shape: grid (2 groups, 2 k tiles, 2 q tiles); the
    FINE output (dk, dv) leaves a k tile at a time, its accumulator zeroed at
    the first q tile; the COARSE output (dq) is resident over a group's k
    tiles, its accumulator (a row block a q tile) zeroed at the first k tile.
    ``fine_init`` / ``coarse_init``: the grid axis whose index being 0 guards
    each initialisation (2 and 1 are right)."""
    def k(fine_ref, coarse_ref, fine_acc, coarse_acc):
        ids = [pl.program_id(a) for a in range(3)]
        ki, qi = ids[1], ids[2]
        rows = pl.ds(qi * 8, 8)

        @pl.when(ids[fine_init] == 0)
        def _():
            fine_acc[...] = jnp.zeros_like(fine_acc)

        @pl.when(ids[coarse_init] == 0)
        def _():
            coarse_acc[rows] = jnp.zeros((8, 128), F32)

        fine_acc[...] += jnp.ones_like(fine_acc)
        coarse_acc[rows] += jnp.ones((8, 128), F32)

        @pl.when(ki == 1)
        def _():
            coarse_ref[0, rows] = coarse_acc[rows]

        @pl.when(qi == 1)
        def _():
            fine_ref[0] = fine_acc[...]

    def build():
        f = pl.pallas_call(
            k,
            grid=(2, 2, 2),
            in_specs=[],
            out_specs=[pl.BlockSpec((1, 8, 128), lambda g, j, i: (g, j, 0)),
                       pl.BlockSpec((1, 16, 128), lambda g, j, i: (g, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((2, 16, 128), F32),
                       jax.ShapeDtypeStruct((2, 16, 128), F32)],
            scratch_shapes=[pltpu.VMEM((8, 128), F32),
                            pltpu.VMEM((16, 128), F32)],
        )
        return f, ()

    return build


@pytest.mark.parametrize("fine_init,coarse_init,stale", [
    (2, 1, None), (1, 1, "scratch0"), (2, 0, "scratch1")],
    ids=["guards_right", "fine_guarded_on_k", "coarse_guarded_on_group"])
def test_accumulators_of_two_grains_are_held_to_the_output_each_feeds(
        fine_init, coarse_init, stale):
    """A scratch's runs are those of the output its values are stored into:
    the coarse accumulator lives across the fine output's blocks without a
    finding, and each accumulator guarded on the axis outside its own run is
    found, by its name."""
    fs = check_case(_case("fx:two-grain",
                          _two_grain_fixture(fine_init, coarse_init)))
    if stale is None:
        assert fs == []
        return
    found = _by_kind(fs, "uninit-accumulator")
    assert found and all(stale in f.message and "revisit" in f.message
                         for f in found)


# ---------------------------------------------------------------------------
# the registry itself
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def registry_contract():
    return pallas_contract()


def test_registry_is_clean(registry_contract):
    """The acceptance bar: every registered kernel case (the raw fp32 path
    and the causal bf16 one) verifies clean."""
    kernels = registry_contract["kernels"]
    assert set(kernels) == {c.name for c in REGISTRY}
    assert len(kernels) >= 2
    for name, entry in kernels.items():
        assert entry["findings"] == {}, (name, entry["findings"])


def test_registry_fits_ci_vmem_gate(registry_contract):
    """CI gates at --require-vmem-frac 0.75: every kernel's re-derived
    per-grid-point total must leave that compiler headroom."""
    for name, entry in registry_contract["kernels"].items():
        assert entry["vmem_bytes"] <= 0.75 * VMEM_BYTES, (
            name, entry["vmem_bytes"])


def test_conv_contract_shape(registry_contract):
    """The attention row pins what the kernel actually stages: q, k, v as
    blocked VMEM operands the pipeline copies (no DMA of its own), three
    VMEM out blocks (o, m, l), three scratch accumulators."""
    entry = registry_contract["kernels"]["block_flash:float32"]
    assert entry["dma_starts"] == 0
    assert len(entry["grid"]) == 3
    names = set(entry["blocks"])
    assert {"in0", "in1", "in2", "out0", "out1", "out2",
            "scratch0", "scratch1", "scratch2"} == names


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_contract_shape(registry_contract, dtype):
    """Latent attention's forward kernel (PR 34): q, q_pe, kv and the one
    rotary key as blocked VMEM operands the pipeline copies, two heads a grid
    step at the published widths (blocks of 2 x 128, 2 x 64, 2 x 256 columns;
    the key's 64 are the array's own width), the output and the two
    statistics with the queries along the lanes, a scratch slab a head."""
    entry = registry_contract["kernels"][f"latent_flash:causal:{dtype}"]
    assert entry["dma_starts"] == 0 and entry["findings"] == {}
    assert entry["grid"] == [1, 2, 3, 3]
    blocks = entry["blocks"]
    assert [blocks[f"in{i}"][-1] for i in range(4)] == [256, 128, 512, 64]
    assert blocks["out0"] == [1, 128, 256]
    assert blocks["out1"] == blocks["out2"] == [1, 2, 1, 128]
    assert {blocks[f"scratch{i}"][0] for i in range(3)} == {2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_backward_contract_shape(registry_contract, dtype):
    """Latent attention's backward kernel (PR 37): the forward's four
    operands, its output and the cotangent as blocked VMEM operands the
    pipeline copies (no DMA of its own), m and l with the queries along the
    lanes; k tiles before q tiles in the grid; dq and dq_pe resident for a
    group's whole sequence (three tiles of 128) over float32 scratch of the
    same rows, dkv a k tile at a time over float32 scratch, the rotary key's
    gradient a float32 partial a group of heads, Δ a row a q tile and head.
    ``findings`` empty: every accumulator is written before it is read in
    the run of the output it feeds (initialised at the first tile, stored at
    the last)."""
    entry = registry_contract["kernels"][
        f"latent_flash_backward:causal:{dtype}"]
    assert entry["dma_starts"] == 0 and entry["findings"] == {}
    assert entry["grid"] == [1, 2, 3, 3]
    blocks = entry["blocks"]
    assert [blocks[f"in{i}"][-1] for i in range(6)] == [
        256, 128, 512, 64, 256, 256]
    assert blocks["in6"] == blocks["in7"] == [1, 2, 1, 128]
    assert blocks["out0"] == [1, 384, 256] and blocks["out1"] == [1, 384, 128]
    assert blocks["out2"] == [1, 128, 512] and blocks["out3"] == [1, 1, 128, 64]
    assert [blocks[f"scratch{i}"] for i in range(5)] == [
        [384, 256], [384, 128], [128, 512], [128, 64], [3, 2, 1, 128]]


@pytest.mark.parametrize("name", ["float32", "causal:bfloat16"])
def test_flash_backward_contract_shape(registry_contract, name):
    """``block_flash``'s backward kernel: q, k, v and the cotangent
    as FEATURE-MAJOR blocks the pipeline copies (64 rows by a tile of 128
    tokens along the lanes: no operand padded to the lanes in HBM), m and dl
    rows of a q tile; k tiles before q tiles in the grid; dq resident for the
    whole sequence (three tiles of 128) over float32 scratch of the same
    columns, dk and dv a k tile at a time over float32 scratch.  ``findings``
    empty: every accumulator is written before it is read in the run of the
    output it feeds."""
    entry = registry_contract["kernels"][f"block_flash_backward:{name}"]
    assert entry["dma_starts"] == 0 and entry["findings"] == {}
    assert entry["grid"] == [2, 3, 3]
    blocks = entry["blocks"]
    assert [blocks[f"in{i}"] for i in range(4)] == [[1, 64, 128]] * 4
    assert blocks["in4"] == blocks["in5"] == [1, 1, 128]
    assert blocks["out0"] == [1, 64, 384]
    assert blocks["out1"] == blocks["out2"] == [1, 64, 128]
    assert [blocks[f"scratch{i}"] for i in range(3)] == [
        [64, 384], [64, 128], [64, 128]]


def test_pallas_contract_roundtrip(registry_contract):
    from mpi4dl_tpu.analysis.contracts.diff import diff_pallas_contract

    assert diff_pallas_contract(registry_contract, registry_contract) == []


def test_pallas_contract_golden_matches_tree(registry_contract):
    """contracts/pallas.json (the CI contract-drift gate's golden) must
    round-trip against a fresh extraction of this tree."""
    import os

    from mpi4dl_tpu.analysis.contracts.__main__ import (
        default_contracts_dir,
        golden_path,
    )
    from mpi4dl_tpu.analysis.contracts.diff import diff_pallas_contract

    path = golden_path(default_contracts_dir(), "pallas")
    assert os.path.exists(path), f"missing golden {path}; run " \
        "`python -m mpi4dl_tpu.analysis contracts --engines pallas --update`"
    with open(path, "r", encoding="utf-8") as fh:
        golden = json.load(fh)
    drifts = [d for d in diff_pallas_contract(golden, registry_contract)
              if not (d["kind"] == "meta" and d["field"] == "jax")]
    assert drifts == []


def test_pallas_contract_diff_localizes(registry_contract):
    from mpi4dl_tpu.analysis.contracts.diff import diff_pallas_contract

    mutated = json.loads(json.dumps(registry_contract))
    name = "block_flash:float32"
    mutated["kernels"][name]["vmem_bytes"] += 1
    mutated["kernels"][name]["findings"]["dma-race"] = 1
    del mutated["kernels"]["block_flash:causal:bfloat16"]
    drifts = diff_pallas_contract(registry_contract, mutated)
    fields = {(d["kernel"], d["field"]) for d in drifts}
    assert (name, "vmem_bytes") in fields
    assert (name, "findings.dma-race") in fields
    assert ("block_flash:causal:bfloat16", "presence") in fields


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _cli(argv, capsys):
    from mpi4dl_tpu.analysis.pallascheck.__main__ import main

    rc = main(argv)
    out = capsys.readouterr()
    return rc, out.out, out.err


def test_cli_rejects_unknown_kernel(capsys):
    rc, _, err = _cli(["--kernels", "nope"], capsys)
    assert rc == 2 and "unknown kernel" in err


def test_cli_rejects_bad_vmem_frac(capsys):
    rc, _, err = _cli(["--require-vmem-frac", "1.5"], capsys)
    assert rc == 2 and "must be in" in err


def test_cli_findings_json_baseline_sarif(monkeypatch, tmp_path, capsys):
    import mpi4dl_tpu.ops.kernel_registry as kr

    fixture = _case("fx:cli-uninit", _acc_fixture(init_at=1))
    monkeypatch.setattr(kr, "REGISTRY", (fixture,))

    rc, out, _ = _cli(["--json"], capsys)
    assert rc == 1
    rows = json.loads(out)["findings"]
    assert rows and rows[0]["kind"] == "uninit-accumulator"
    assert rows[0]["kernel"] == "fx:cli-uninit"

    # a baseline accepting exactly those findings turns the gate green
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps(rows))
    rc, out, _ = _cli(["--json", "--baseline", str(baseline)], capsys)
    assert rc == 0 and json.loads(out)["findings"] == []

    sarif = tmp_path / "out.sarif"
    rc, _, _ = _cli(["--sarif", str(sarif)], capsys)
    assert rc == 1
    log = json.loads(sarif.read_text())
    results = log["runs"][0]["results"]
    assert results[0]["ruleId"] == "pallascheck/uninit-accumulator"
    uri = results[0]["locations"][0]["physicalLocation"]["artifactLocation"]
    assert uri["uri"] == "mpi4dl_tpu/ops/kernel_registry.py"


def test_cli_kernel_prefix_selects_variants(monkeypatch, capsys):
    import mpi4dl_tpu.ops.kernel_registry as kr

    fixtures = (
        _case("fxk:a", _acc_fixture(init_at=0)),
        _case("fxk:b", _acc_fixture(init_at=1)),
    )
    monkeypatch.setattr(kr, "REGISTRY", fixtures)
    rc, out, _ = _cli(["--json", "--kernels", "fxk"], capsys)
    assert rc == 1
    assert {r["kernel"] for r in json.loads(out)["findings"]} == {"fxk:b"}


def test_analysis_dispatch():
    """`python -m mpi4dl_tpu.analysis pallascheck` must dispatch (and the
    flag-first spelling must be rejected, not scanned as a path)."""
    ok = subprocess.run(
        [sys.executable, "-m", "mpi4dl_tpu.analysis", "pallascheck",
         "--help"],
        capture_output=True, text=True, check=False,
    )
    assert ok.returncode == 0 and "pallascheck" in ok.stdout
    bad = subprocess.run(
        [sys.executable, "-m", "mpi4dl_tpu.analysis", "--json",
         "pallascheck"],
        capture_output=True, text=True, check=False,
    )
    assert bad.returncode == 2 and "must come first" in bad.stderr


def test_finding_kind_registry_is_exact():
    """Every documented kind is producible and no check emits an
    undocumented kind: the fixture lane covers the classification 1:1."""
    assert set(FINDING_KINDS) == {
        "oob-block", "overlapping-output", "untiled-output",
        "misaligned-block", "vmem-overbudget", "unmatched-dma",
        "dma-race", "nonbijective-device-map", "uninit-accumulator",
    }
    fs = check_case(_case("fx:counts", _acc_fixture(init_at=1)))
    assert finding_counts(fs) == {"uninit-accumulator": 1}


# ---------------------------------------------------------------------------
# rule 12: unregistered-pallas-call (satellite)
# ---------------------------------------------------------------------------


def _scan(tmp_path, source, filename):
    from mpi4dl_tpu.analysis import RULES_BY_NAME, analyze_paths

    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return analyze_paths(
        [str(f)], root=str(tmp_path),
        rules=[RULES_BY_NAME["unregistered-pallas-call"]],
    )


_NEW_KERNEL = """
    from jax.experimental import pallas as pl

    def dispatch(k, x):
        return pl.pallas_call(k, out_shape=x)(x)
"""


def test_rule12_flags_unregistered_module(tmp_path):
    vs = _scan(tmp_path, _NEW_KERNEL, "mpi4dl_tpu/ops/halo_rdma.py")
    assert len(vs) == 1
    v = vs[0]
    assert v.rule == "unregistered-pallas-call"
    assert "mpi4dl_tpu.ops.halo_rdma" in v.message
    assert v.line == 5


def test_rule12_registered_module_is_exempt(tmp_path):
    # module name matches a registry import (the real pallas_attention row)
    vs = _scan(tmp_path, _NEW_KERNEL, "mpi4dl_tpu/ops/pallas_attention.py")
    assert vs == []


def test_rule12_benchmark_pragma_allowlists(tmp_path):
    flagged = _scan(tmp_path, _NEW_KERNEL, "benchmarks/bench_kernel.py")
    assert len(flagged) == 1
    ok = _scan(
        tmp_path,
        """
        from jax.experimental import pallas as pl

        # throwaway microbenchmark kernel; not a product kernel
        def dispatch(k, x):  # analysis: ok(unregistered-pallas-call)
            return pl.pallas_call(k, out_shape=x)(x)
        """,
        "benchmarks/bench_kernel2.py",
    )
    assert ok == []


def test_rule12_tests_are_exempt(tmp_path):
    vs = _scan(tmp_path, _NEW_KERNEL, "tests/test_fixture_kernels.py")
    assert vs == []


@pytest.mark.parametrize("name, grid", [
    ("sparse_flash_forward:causal:bfloat16", [2, 3, 3]),
    ("sparse_flash_backward:causal:bfloat16", [2, 3, 3]),
    ("indexer_select:bfloat16", [1, 3]),
    ("indexer_backward:bfloat16", [1, 2, 32]),
])
def test_sparse_attention_contract_shape(registry_contract, name, grid):
    """The sparse attention's kernels (PR 40): the attention's forward and
    backward under a key selection take the selection's words as one
    more blocked operand (a q tile's rows of 128 words; transposed for the
    backward) beside the forward's or backward's own blocks.  Both grid
    steps are a key-value group: the q block (and o, m and l, forward and
    backward; dô and dq, backward) holds its three heads, the k and v blocks
    one head as they are; forward scratch holds m and l of the three (o's
    block is the accumulator), backward scratch dq of the three and dk and
    dv of the group's whole sequence, a k tile a row; the indexer's
    selection holds a block's keys as int32 images in scratch for the whole
    padded key range (32 bits of 128 words: 4,096), and its gradient's key
    accumulator is resident for the sequence.  No DMA of their own, and
    ``findings`` empty."""
    entry = registry_contract["kernels"][name]
    assert entry["dma_starts"] == 0 and entry["findings"] == {}
    assert entry["grid"] == grid
    blocks = entry["blocks"]
    if name.startswith("sparse_flash_forward"):
        assert blocks["in3"] == [1, 128, 128]
        assert blocks["in0"] == blocks["out0"] == blocks["scratch1"] == [3, 128, 128]
        assert blocks["in1"] == blocks["in2"] == [1, 128, 128]
        assert "scratch2" not in blocks
    elif name.startswith("sparse_flash_backward"):
        assert blocks["in7"] == [1, 128, 768]
        assert blocks["in0"] == blocks["out0"] == blocks["scratch0"] == [3, 64, 768]
        assert blocks["in1"] == blocks["in2"] == blocks["out1"] == [1, 64, 768]
        assert blocks["scratch1"] == blocks["scratch2"] == [3, 64, 768]  # 3 k tiles
    elif name.startswith("indexer_select"):
        assert blocks["scratch0"] == [4096, 128] and blocks["out0"] == [1, 128, 128]
    else:
        assert blocks["out2"] == [1, 8, 4096] and blocks["scratch2"] == [8, 4096]
