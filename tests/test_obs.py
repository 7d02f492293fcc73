"""Tests for the obs telemetry subsystem (ISSUE 2).

Covers: RunLog JSONL schema round-trip; trace scopes visible in lowered
StableHLO for all four engine families (lp / sp / gems / gems_sp on the
virtual CPU mesh); cost_analysis FLOPs against a hand-computed conv count +
the MFU arithmetic; the report CLI's golden output; the StepMeter extension;
and the producer-thread shutdown fix in the batch prefetcher (now
mpi4dl_tpu.data.prefetch_batches).
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi4dl_tpu import obs
from mpi4dl_tpu.layer_ctx import SpatialCtx
from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.models.resnet import get_resnet_v2
from mpi4dl_tpu.obs.scopes import _reset_enabled_cache
from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step


# ---------------------------------------------------------------------------
# RunLog JSONL round-trip
# ---------------------------------------------------------------------------


def test_runlog_roundtrip(tmp_path):
    rl = obs.RunLog.create(str(tmp_path), prefix="t")
    rl.write_meta(config={"model": "resnet"}, mesh_spec=MeshSpec(spw=2),
                  family="sp", argv=["--image-size", "32"])
    rl.write("cost", flops=1e9, bytes_accessed=2e8,
             collectives={"all-reduce": {"count": 3, "bytes": 12}},
             peak_flops=1e11, peak_source="nominal-cpu", device_count=2)
    rl.write_step(epoch=0, step=0, ms=100.0, images_per_sec=40.0,
                  loss=2.3, accuracy=0.1, measured=False)
    rl.write_step(epoch=0, step=1, ms=10.0, images_per_sec=400.0,
                  loss=2.2, accuracy=0.2)
    rl.write("summary", steps=1, warmup_dropped=1)
    rl.close()

    recs = obs.read_runlog(rl.path)
    assert [r["kind"] for r in recs] == ["meta", "cost", "step", "step",
                                         "summary"]
    assert all(r["schema"] == 1 and "t" in r for r in recs)
    meta = recs[0]
    assert meta["config"] == {"model": "resnet"}
    assert meta["mesh"]["spw"] == 2  # dataclass serialized
    assert meta["jax_version"] == jax.__version__
    assert meta["device_count"] == len(jax.devices())
    assert isinstance(meta["hatches"], dict)
    step = recs[3]
    assert step["measured"] is True and step["ms"] == 10.0
    # host RSS watermark exists even on CPU backends
    assert step["host_rss_peak_bytes"] is None or step["host_rss_peak_bytes"] > 0


def test_runlog_truncated_line_skipped(tmp_path, capsys):
    p = tmp_path / "r.jsonl"
    p.write_text('{"kind": "meta", "schema": 1, "t": 0}\n{"kind": "st')
    recs = obs.read_runlog(str(p))
    assert len(recs) == 1 and recs[0]["kind"] == "meta"
    # the skip is audible: a crashed leg tears its last line mid-write and
    # the evidence reader must say so, not silently drop the record
    err = capsys.readouterr().err
    assert "[obs]" in err and "torn record" in err and ":2:" in err


def test_active_hatches_reflects_env(monkeypatch):
    monkeypatch.setenv("MPI4DL_NO_PACK", "1")
    assert obs.active_hatches().get("MPI4DL_NO_PACK") == "1"


# ---------------------------------------------------------------------------
# Trace scopes
# ---------------------------------------------------------------------------


def test_scope_disabled_is_nullcontext(monkeypatch):
    monkeypatch.setenv("MPI4DL_NO_SCOPES", "1")
    _reset_enabled_cache()
    try:
        assert isinstance(obs.scope("x"), contextlib.nullcontext)
        assert not obs.scopes_enabled()
    finally:
        monkeypatch.delenv("MPI4DL_NO_SCOPES")
        _reset_enabled_cache()
    assert obs.scopes_enabled()


_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _without_metadata(text: str) -> str:
    """A compiled module's text without what names and places its
    instructions: every ``metadata={...}`` and the tables of files,
    functions, locations and stack frames that the metadata points into."""
    import re

    blocks = [b for b in text.split("\n\n")
              if b.split("\n", 1)[0].strip() not in _DEBUG_TABLES]
    return re.sub(r", metadata=\{[^{}]*\}", "", "\n\n".join(blocks))


def _token_step(name):
    """A small train step of a token model at toy widths (the tests'
    configurations), lowered: LFM2 (routed experts, attention), granite (the
    Mamba-2 mixer and its scan), Kanana-2 (the shared expert beside the routed
    ones), Keye-VL-2.0 (the sparse attention and its indexer), Ouro (the
    loop's passes), and ``block_flash`` in interpret mode, forward and backward under
    ``jax.checkpoint``, for the scope round the kernel and in its rule."""
    import test_deepseek_v3
    import test_granitemoehybrid
    import test_keye_vl2
    import test_lfm2
    import test_ouro

    if name == "block_flash":
        from mpi4dl_tpu.ops.pallas_attention import flash_attention_local

        def loss(q, k, v):
            o = jax.checkpoint(lambda q, k, v: flash_attention_local(
                q, k, v, causal=True, interpret=True))(q, k, v)
            return jnp.sum(o)

        qkv = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.float32)
        return jax.jit(jax.grad(loss, (0, 1, 2))).lower(qkv, qkv, qkv)
    module = {"lfm2": test_lfm2, "granite": test_granitemoehybrid,
              "deepseek_v3": test_deepseek_v3, "keye_vl2": test_keye_vl2,
              "ouro": test_ouro}[name]
    model, params, _ = module._model()
    x, y = (test_granitemoehybrid._ids() if name == "granite"
            else test_lfm2._batch(seq=test_keye_vl2.SEQ) if name == "keye_vl2"
            else test_lfm2._batch())
    opt = Optimizer("sgd", lr=0.1)
    step = make_train_step(model, opt, compute_dtype=jnp.bfloat16, remat=True)
    return step.lower(TrainState.create(params, opt), x, y)


@pytest.fixture
def no_persistent_cache():
    """The persistent cache keys a program without its metadata, so with it
    on the second compile is the first one loaded, names and all."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name,scopes", [
    ("lfm2", ("expert_route", "expert_dispatch")),
    ("granite", ("ssm_mixer", "ssm_scan")),
    ("deepseek_v3", ("expert_route", "expert_dispatch", "shared_expert")),
    ("keye_vl2", ("sparse_indexer", "attention_core", "expert_route",
                  "expert_dispatch")),
    ("ouro", ("ut_loop", "ut_step0", "ut_step3")),
    ("block_flash", ("attention_core",)),
])
def test_scopes_are_metadata_only(monkeypatch, no_persistent_cache, name,
                                  scopes):
    """The step compiled with ``MPI4DL_NO_SCOPES=1`` and without is the same
    program once the metadata is taken out: a scope names instructions and
    changes none (so the driver's measurement with tracing off is the
    parent's).  The scoped program does carry each of the model's scopes."""
    texts = {}
    for off in (True, False):
        if off:
            monkeypatch.setenv("MPI4DL_NO_SCOPES", "1")
        else:
            monkeypatch.delenv("MPI4DL_NO_SCOPES")
        _reset_enabled_cache()
        try:
            texts[off] = _token_step(name).compile().as_text()
        finally:
            _reset_enabled_cache()
    for scope in scopes:
        assert f"/{scope}/" in texts[False] and f"/{scope}/" not in texts[True]
    assert _without_metadata(texts[True]) == _without_metadata(texts[False])


def _debug_text(step, *args) -> str:
    return obs.stablehlo_debug_text(step.lower(*args))


def _sp_model(batch=4, px=32):
    model = get_resnet_v2((batch, px, px, 3), depth=11, num_classes=10)
    params, _ = model.init(jax.random.key(0))
    return model, params


def test_scopes_lp_family(devices8):
    """LP/PP pipeline: stage + cell (+ handoff) scopes in lowered HLO."""
    from mpi4dl_tpu.parallel.partition import StagePartition
    from mpi4dl_tpu.parallel.pipeline import (
        init_pipeline_state, make_pipeline_train_step,
    )

    model, params = _sp_model()
    mesh = build_mesh(MeshSpec(stage=2), jax.devices()[:2])
    part = StagePartition.build(model, params, 2, (2, 32, 32, 3))
    opt = Optimizer("sgd", lr=0.01)
    step = make_pipeline_train_step(part, opt, mesh, parts=2)
    state = init_pipeline_state(part, params, opt, mesh)
    x = jnp.zeros((4, 32, 32, 3), jnp.float32)
    y = jnp.zeros((4,), jnp.int32)
    txt = _debug_text(step, state, x, y)
    for name in ("stage0", "stage1", "cell00", "stage_handoff",
                 "gpipe_scan", "optimizer_update", "mb_inject"):
        assert name in txt, f"{name} missing from lowered LP step"


def test_scopes_gems_family(devices8):
    from mpi4dl_tpu.parallel.gems import make_gems_train_step
    from mpi4dl_tpu.parallel.partition import StagePartition
    from mpi4dl_tpu.parallel.pipeline import init_pipeline_state

    model, params = _sp_model()
    mesh = build_mesh(MeshSpec(stage=2), jax.devices()[:2])
    part = StagePartition.build(model, params, 2, (1, 32, 32, 3))
    opt = Optimizer("sgd", lr=0.01)
    step = make_gems_train_step(part, opt, mesh, parts=2, times=1)
    state = init_pipeline_state(part, params, opt, mesh)
    x = jnp.zeros((4, 32, 32, 3), jnp.float32)
    y = jnp.zeros((4,), jnp.int32)
    txt = _debug_text(step, state, x, y)
    for name in ("gems_mirror", "gems_dual_scan", "stage0", "cell00",
                 "stage_handoff"):
        assert name in txt, f"{name} missing from lowered GEMS step"


def test_scopes_sp_family(devices8):
    """SP x PP (the sp family with a pipeline tail): cell, halo AND stage
    scopes all present — the acceptance triple."""
    from mpi4dl_tpu.parallel.sp_pipeline import (
        SPPipeline, init_sp_pipeline_state, make_sp_pipeline_train_step,
    )

    model, params = _sp_model()
    model.spatial_until = 2
    sp = SpatialCtx(axis_w="spw", grid_w=2)
    mesh = build_mesh(MeshSpec(stage=2, spw=2), jax.devices()[:4])
    spp = SPPipeline.build(model, params, 2, sp, 2, junction="gather")
    opt = Optimizer("sgd", lr=0.01)
    step = make_sp_pipeline_train_step(spp, opt, mesh, parts=2)
    state = init_sp_pipeline_state(spp, params, opt, mesh)
    x = jnp.zeros((4, 32, 32, 3), jnp.float32)
    y = jnp.zeros((4,), jnp.int32)
    txt = _debug_text(step, state, x, y)
    for name in ("cell00", "halo_exchange_spw", "stage0", "sp_region",
                 "junction_gather", "tail_scan", "stage_lineup"):
        assert name in txt, f"{name} missing from lowered SPxPP step"


def test_scopes_sp_single_level(devices8):
    """Pure SP (no pipeline): cell + halo scopes survive shard_map + remat."""
    from mpi4dl_tpu.train import make_spatial_train_step

    model, params = _sp_model()
    sp = SpatialCtx(axis_w="spw", grid_w=4)
    mesh = build_mesh(MeshSpec(spw=4), jax.devices()[:4])
    opt = Optimizer("sgd", lr=0.01)
    step = make_spatial_train_step(
        model, opt, mesh, sp, spatial_until=len(model.cells) - 1, remat=True,
    )
    state = TrainState.create(params, opt)
    x = jnp.zeros((4, 32, 32, 3), jnp.float32)
    y = jnp.zeros((4,), jnp.int32)
    txt = _debug_text(step, state, x, y)
    for name in ("cell00", "halo_exchange_spw", "junction_gather",
                 "sp_level0"):
        assert name in txt, f"{name} missing from lowered SP step"


def test_scopes_gems_sp_family(devices8):
    from mpi4dl_tpu.parallel.sp_pipeline import (
        SPPipeline, init_sp_pipeline_state, make_sp_gems_train_step,
    )

    model, params = _sp_model(batch=8)
    model.spatial_until = 2
    sp = SpatialCtx(axis_w="spw", grid_w=2)
    mesh = build_mesh(MeshSpec(stage=2, spw=2), jax.devices()[:4])
    spp = SPPipeline.build(model, params, 2, sp, 2, junction="gather")
    opt = Optimizer("sgd", lr=0.01)
    step = make_sp_gems_train_step(spp, opt, mesh, parts=2, times=1)
    state = init_sp_pipeline_state(spp, params, opt, mesh)
    x = jnp.zeros((8, 32, 32, 3), jnp.float32)
    y = jnp.zeros((8,), jnp.int32)
    txt = _debug_text(step, state, x, y)
    for name in ("cell00", "halo_exchange_spw", "stage0", "gems_mirror",
                 "sp_region"):
        assert name in txt, f"{name} missing from lowered GEMSxSPxPP step"


def test_scope_names_histogram():
    txt = '#loc1 = loc("jit(f)/jit(main)/cell03/halo_exchange_spw/add")'
    names = obs.scope_names(txt)
    assert names.get("cell03") == 1
    assert names.get("halo_exchange_spw") == 1
    assert "jit(f)" not in names


# ---------------------------------------------------------------------------
# Cost metrics: hand-computed conv FLOPs + MFU arithmetic
# ---------------------------------------------------------------------------


def test_cost_analysis_matches_hand_conv_flops():
    n, h, w, cin, cout, k = 2, 16, 16, 8, 16, 3

    @jax.jit
    def conv(x, kern):
        return jax.lax.conv_general_dilated(
            x, kern, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    x = jnp.zeros((n, h, w, cin), jnp.float32)
    kern = jnp.zeros((k, k, cin, cout), jnp.float32)
    cost = obs.step_cost(conv, x, kern)
    ho, wo = h - k + 1, w - k + 1
    hand = 2.0 * n * ho * wo * k * k * cin * cout  # 2 flops per MAC
    assert cost["flops"] is not None
    assert cost["flops"] == pytest.approx(hand, rel=0.01), (
        cost["flops"], hand,
    )
    ai = obs.arithmetic_intensity(cost["flops"], cost["bytes_accessed"])
    assert ai is not None and ai > 0


def test_mfu_arithmetic():
    # 1e9 flops in 10 ms = 1e11 FLOP/s; peak 1e12 -> 10% utilization.
    assert obs.mfu(1e9, 10.0, 1e12) == pytest.approx(0.1)
    assert obs.mfu(1e9, 10.0, 1e12, n_devices=2) == pytest.approx(0.05)
    assert obs.mfu(None, 10.0, 1e12) is None
    assert obs.mfu(1e9, 0.0, 1e12) is None


def test_peak_flops_sources():
    dev = jax.devices()[0]  # CPU under the test harness
    assert obs.peak_flops(dev) == (None, None)
    peak, src = obs.peak_flops(dev, allow_cpu_nominal=True)
    assert src == "nominal-cpu" and peak > 0


class _FakeDevice:
    def __init__(self, kind, platform="tpu"):
        self.device_kind, self.platform = kind, platform


@pytest.mark.parametrize("fn", ["peak_flops", "ici_bytes_per_s"])
def test_device_tables_know_the_v5e_and_refuse_a_stranger(fn):
    """A device that is not in the table is an error, not a default."""
    from mpi4dl_tpu.obs import costs

    value, src = getattr(costs, fn)(_FakeDevice("TPU v5 lite"))
    assert src == "table"
    assert value == {"peak_flops": 197e12, "ici_bytes_per_s": 2.0e11}[fn]
    with pytest.raises(ValueError, match="device kind 'tpu v9x'"):
        getattr(costs, fn)(_FakeDevice("TPU v9x"))


def test_collective_stats_from_compiled(devices8):
    from mpi4dl_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh(MeshSpec(spw=4), jax.devices()[:4])
    f = jax.jit(shard_map(
        lambda x: jax.lax.psum(x, "spw"),
        mesh=mesh, in_specs=P("spw"), out_specs=P(),
    ))
    stats = obs.compiled_collective_stats(
        f.lower(jnp.ones((8, 4), jnp.float32)).compile()
    )
    assert stats["all-reduce"]["count"] >= 1
    assert stats["total_bytes"] > 0


# ---------------------------------------------------------------------------
# Report CLI (golden output)
# ---------------------------------------------------------------------------


def _synthetic_runlog(tmp_path) -> str:
    rl = obs.RunLog.create(str(tmp_path), prefix="golden")
    rl.write_meta(config={"model": "resnet", "image_size": 32,
                          "batch_size": 4},
                  mesh_spec={"spw": 2}, family="sp")
    rl.write("cost", flops=2e9, bytes_accessed=5e8,
             arithmetic_intensity=4.0,
             collectives={
                 "collective-permute": {"count": 8, "bytes": 1024},
                 "all-reduce": {"count": 2, "bytes": 2048},
                 "all-gather": {"count": 0, "bytes": 0},
                 "reduce-scatter": {"count": 0, "bytes": 0},
                 "all-to-all": {"count": 0, "bytes": 0},
                 "total_count": 10, "total_bytes": 3072,
             },
             peak_flops=1e12, peak_source="table", device_count=2)
    rl.write_step(epoch=0, step=0, ms=1000.0, images_per_sec=4.0,
                  loss=2.31, accuracy=0.1, measured=False)
    rl.write_step(epoch=0, step=1, ms=100.0, images_per_sec=40.0,
                  loss=2.30, accuracy=0.1)
    rl.write_step(epoch=0, step=2, ms=50.0, images_per_sec=80.0,
                  loss=2.25, accuracy=0.2)
    rl.write("summary", steps=2, warmup_dropped=1)
    rl.close()
    return rl.path


def test_report_golden(tmp_path):
    from mpi4dl_tpu.obs.report import render_run

    out = render_run(_synthetic_runlog(tmp_path))
    for needle in (
        "steps: 2 measured, 1 warmup dropped",
        "step time ms: mean 75.00  median 75.00  p10 55.00  p90 95.00  "
        "min 50.00",
        "memory watermark:",
        "cost model: flops/step 2e+09",
        "arithmetic intensity 4.00 flops/byte",
        # median 75 ms at 2e9 flops -> 2.667e10 FLOP/s / 1e12 peak
        "mfu estimate: 0.0267",
        "collective-permute",
        "count    8",
        "all-reduce",
        "total",
    ):
        assert needle in out, f"missing {needle!r} in:\n{out}"


def test_report_hbm_skew_line(tmp_path):
    """Step records carrying ``hbm_skew`` render the hot-vs-cold spread
    line — the SP-imbalance signal the device-0-only watermark hid."""
    import json as _json

    from mpi4dl_tpu.obs.report import render_run

    p = tmp_path / "skew.jsonl"
    with open(p, "w") as fh:
        fh.write(_json.dumps({"kind": "meta", "schema": 1, "t": 0.0,
                              "config": {}}) + "\n")
        for i, skew in enumerate([64, 3 * 1024 ** 2, 1024]):
            fh.write(_json.dumps({
                "kind": "step", "schema": 1, "t": 1.0 + i, "epoch": 0,
                "step": i, "ms": 10.0, "images_per_sec": 800.0,
                "loss": 1.0, "measured": True,
                "memory_peak_bytes": 8 * 1024 ** 2, "hbm_skew": skew,
            }) + "\n")
    out = render_run(str(p))
    assert "hbm skew: 3.0 MiB max spread across local devices" in out
    # no skew fields -> no skew line (absent metric, not a lying zero)
    q = tmp_path / "noskew.jsonl"
    with open(q, "w") as fh:
        fh.write(_json.dumps({"kind": "step", "schema": 1, "t": 1.0,
                              "ms": 10.0, "images_per_sec": 800.0,
                              "loss": 1.0, "measured": True}) + "\n")
    assert "hbm skew" not in render_run(str(q))


def test_report_pipeline_line(tmp_path):
    """The `pipeline:` line: ticks + bubble fraction from the meta config,
    schedule corroborated by the cost record's tick scopes."""
    from mpi4dl_tpu.obs.report import render_run

    rl = obs.RunLog.create(str(tmp_path), prefix="pp")
    rl.write_meta(config={"model": "resnet", "split_size": 2, "parts": 6,
                          "schedule": "1f1b"},
                  mesh_spec={"stage": 2}, family="lp")
    rl.write("cost", flops=1e9, bytes_accessed=1e8,
             tick_scopes=["bwd_tick", "fwd_tick", "pp_1f1b_scan"],
             peak_flops=1e12, peak_source="table", device_count=2)
    rl.write_step(epoch=0, step=0, ms=10.0, images_per_sec=1.0,
                  loss=1.0, accuracy=0.5)
    rl.close()
    out = render_run(rl.path)
    # 1F1B: ticks = parts + 2(S-1) = 8; bubble = 2(S-1)/8 = 0.25.
    assert ("pipeline: schedule=1f1b  stages=2  parts=6  ticks/step=8  "
            "bubble=0.250") in out
    assert "scopes: bwd_tick,fwd_tick,pp_1f1b_scan" in out

    rl2 = obs.RunLog.create(str(tmp_path), prefix="pp-g")
    rl2.write_meta(config={"model": "resnet", "split_size": 4, "parts": 8},
                   mesh_spec={"stage": 4}, family="lp")
    rl2.close()
    out2 = render_run(rl2.path)
    # GPipe default: ticks = parts + S - 1 = 11; bubble = 3/11.
    assert ("pipeline: schedule=gpipe  stages=4  parts=8  ticks/step=11  "
            "bubble=0.273") in out2

    # family="single" must NOT render a pipeline line even when the config
    # carries pipeline-flag defaults (mem_probe's single-chip mode records
    # raw argparse vars, --split-size included).
    rl3 = obs.RunLog.create(str(tmp_path), prefix="pp-s")
    rl3.write_meta(config={"model": "resnet", "split_size": 2, "parts": 4,
                           "schedule": "both"},
                   mesh_spec={}, family="single")
    rl3.close()
    assert "pipeline:" not in render_run(rl3.path)


def test_report_cli_main(tmp_path, capsys):
    from mpi4dl_tpu.obs.__main__ import main

    path = _synthetic_runlog(tmp_path)
    assert main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "mfu estimate" in out and path in out
    assert main(["report", str(tmp_path / "missing.jsonl")]) == 2


# ---------------------------------------------------------------------------
# StepMeter extension (satellite 2)
# ---------------------------------------------------------------------------


def test_stepmeter_warmup_and_percentiles():
    from mpi4dl_tpu.utils import StepMeter

    m = StepMeter(batch_size=8, warmup_steps=1)
    assert m.add(9999.0) is False  # compile step dropped
    for ms in range(2, 12):  # 2..11
        assert m.add(float(ms)) is True
    st = m.stats()
    assert st["steps"] == 10 and st["warmup_dropped"] == 1
    assert st["min_ms"] == 2.0
    assert st["p10_ms"] == pytest.approx(2.9)
    assert st["p90_ms"] == pytest.approx(10.1)
    assert st["median_ms"] == pytest.approx(6.5)
    s = m.summary()
    for part in ("p10=2.90ms", "p90=10.10ms", "min=2.00ms",
                 "warmup_dropped=1"):
        assert part in s, s


def test_stepmeter_empty():
    from mpi4dl_tpu.utils import StepMeter

    m = StepMeter(4)
    assert m.summary() == "no steps recorded"
    assert m.images_per_sec() == 0.0
    assert m.stats()["steps"] == 0


# ---------------------------------------------------------------------------
# data.prefetch_batches producer shutdown (PR-2 satellite 1; the iterator
# moved from benchmarks/common._batches into the library for PR 3)
# ---------------------------------------------------------------------------


class _StubDataset:
    def batch(self, i, bs):
        return (np.zeros((bs, 2), np.float32), np.zeros((bs,), np.int32))


def _wait_threads(n0: int, timeout: float = 5.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if threading.active_count() <= n0:
            return True
        time.sleep(0.01)
    return False


def test_batches_completes_normally():
    from mpi4dl_tpu.data import prefetch_batches

    items = list(prefetch_batches(_StubDataset(), 4, 0, 5, num_workers=2))
    assert len(items) == 5


def test_batches_early_exit_stops_producer():
    """Regression: a consumer abandoning the iterator mid-epoch must not
    leave the producer blocked forever on a full queue."""
    from mpi4dl_tpu.data import prefetch_batches

    n0 = threading.active_count()
    gen = prefetch_batches(_StubDataset(), 4, 0, 10_000, num_workers=2)
    next(gen)
    gen.close()  # the exception-mid-epoch path: generator finalized early
    assert _wait_threads(n0), "producer thread did not terminate"


def test_batches_consumer_exception_stops_producer():
    from mpi4dl_tpu.data import prefetch_batches

    n0 = threading.active_count()
    with pytest.raises(RuntimeError):
        for i, _ in enumerate(
            prefetch_batches(_StubDataset(), 4, 0, 10_000, num_workers=1)
        ):
            if i == 2:
                raise RuntimeError("mid-epoch failure")
    assert _wait_threads(n0), "producer thread did not terminate"
