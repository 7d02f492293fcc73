"""Tests of the benchmark's own code: CPU, tiny sizes, one process.

    JAX_PLATFORMS=cpu python -m pytest perfbench/test_perfbench.py -q

(``perfbench/conftest.py`` sets the platform where it is not set.)
"""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, trace
from perfbench.catalog import ROOT, Catalog
from perfbench.references import plain

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _dump(path, obj):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark's files with a throw-away configuration, a
    throw-away traffic mix, two cells, a metric and a reader kind ADDED as
    new files and entries: nothing that is there is edited."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    p = root / "perfbench"
    _dump(p / "configs" / "tiny_resnet.json", {
        "model": "resnet", "reference": "resnet_v2",
        "sizes": {"num_layers": 1, "num_classes": 10},
        "argv": ["--model", "resnet", "--num-layers", "1", "--num-classes",
                 "10", "--precision", "bf_16"],
        "tolerances": {"loss": {"value": 1e-2}, "cell": {"value": 0.05}},
    })
    _dump(p / "configs" / "tiny_amoebanet.json", {
        "model": "amoebanet", "reference": "amoebanet_d",
        "sizes": {"num_layers": 3, "num_filters": 32, "num_classes": 10},
        "argv": ["--model", "amoebanet", "--num-layers", "3", "--num-filters",
                 "32", "--num-classes", "10", "--precision", "bf_16"],
        "tolerances": {"loss": {"value": 1e-2}, "cell": {"value": 0.5}},
    })
    _dump(p / "traffic" / "32.bs2.json", {
        "family": "lp",
        "argv": ["--image-size", "32", "--batch-size", "2", "--split-size",
                 "1", "--num-workers", "1"],
    })
    _dump(p / "layer_metrics" / "loss_wait_p10_ms.json", {
        "unit": "ms", "layer": "device", "moves": "img_per_s",
        "reader": "span_decile", "params": {"span": "loss_wait", "decile": 1},
    })
    (p / "readers" / "span_decile.py").write_text(
        "import statistics\n"
        "def read(record, span, decile):\n"
        "    v = record['spans'].get(span)\n"
        "    return statistics.quantiles(v, n=10)[decile - 1] if v else None\n")
    for name in ("tiny_resnet", "tiny_amoebanet"):
        bench["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"perfbench/configs/{name}.json"})
        bench["workloads"].append({
            "name": f"{name}.32.bs2", "config": name, "traffic": "32.bs2",
            "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "loss_wait_p10_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "device", "moves": "img_per_s",
        "workloads": ["tiny_resnet.32.bs2"]})
    _dump(root / "BENCHMARK.json", bench)
    return Catalog(str(root))


# --- the files and what names them ------------------------------------------


def test_benchmark_json_names_files_that_exist():
    cat = Catalog()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    for entry in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                  + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "source", "layer"):
            assert 1 <= len(entry.get(key, "x")) <= 200, (entry["name"], key)
    for w in BENCH["workloads"]:
        cell = cat.cell(w["name"])
        assert callable(cell.reference_cells())
        assert set(cell.config["tolerances"]) == {"loss", "cell"}
        assert cell.config["reduced"] == next(
            c["reduced"] for c in BENCH["configs"] if c["name"] == w["config"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        spec = json.load(open(os.path.join(
            cat.bench_dir, "layer_metrics", m["name"] + ".json")))
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
        assert os.path.exists(os.path.join(
            cat.bench_dir, "readers", spec["reader"] + ".py"))


def test_cell_configuration_metric_and_reader_are_added_as_files_only(tiny):
    cell = tiny.cell("tiny_resnet.32.bs2")
    assert cell.family == "lp" and cell.config["model"] == "resnet"
    names = [m["name"] for m in tiny.metrics("per_layer", cell.name)]
    assert "loss_wait_p10_ms" in names and "fetch_ms" in names
    assert "loss_wait_p10_ms" not in [
        m["name"] for m in tiny.metrics("per_layer", "amoebanet_d.2048.bs1")]
    record = {"spans": {"loss_wait": [float(i) for i in range(1, 12)]}}
    assert tiny.read_layer_metric("loss_wait_p10_ms", record) == pytest.approx(
        1.2)
    assert tiny.read_layer_metric("loss_wait_p10_ms", {"spans": {}}) is None
    with pytest.raises(KeyError):
        tiny.cell("no.such.cell")


def test_unknown_device_kind_raises():
    cat = Catalog()
    assert cat.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        cat.peak("TPU v9", "bf16_flops")


def test_seed_is_reduced_below_2_to_the_31():
    argv = Catalog().cell("amoebanet_d.2048.bs1").argv(2147483659)
    assert int(argv[argv.index("--seed") + 1]) == 2147483659 % (2**31 - 1) < 2**31
    assert argv[argv.index("--num-layers") + 1] == "18"
    assert argv[argv.index("--image-size") + 1] == "2048"


# --- the run ------------------------------------------------------------------


def test_cell_runs_through_build_train_and_run_supervised(tiny):
    """argv -> build_train -> run_supervised on a tiny model: the four
    end-to-end metrics, a correct run, and the details file."""
    cell = tiny.cell("tiny_resnet.32.bs2")
    lines = []
    result = harness.run_cell(
        tiny, cell, seed=2147483659, seconds=0.2, trace=False,
        t0=time.perf_counter(), devices=jax.devices()[:1],
        out_dir=os.path.join(tiny.bench_dir, "out"), say=lines.append)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= harness.MIN_WINDOW_STEPS
    assert set(result["metrics"]) == {"img_per_s", "step_ms_p90", "hbm_gib",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert sum(l.startswith("epoch ") for l in lines) == (
        harness.WARM_STEPS + result["attempted"])
    details = json.load(open(os.path.join(
        tiny.bench_dir, "out", f"{cell.name}.seed2147483659.trace0.json")))
    assert details["compiles"].get("window") is None
    assert details["memory_analysis"]["temp"] > 0
    assert len(details["spans_ms"]["period"]) == result["attempted"] - 1


def test_window_sizing():
    assert harness.window_steps(30, 1.3567) == 22
    assert harness.window_steps(30, 1.3567 * 1.005) == 22  # a reading 0.5 % off
    assert harness.window_steps(30, 0.2925) == 103
    assert harness.window_steps(1, 1.3567) == harness.MIN_WINDOW_STEPS
    assert harness.p90([float(i) for i in range(1, 12)]) == 10.0


def test_spans_and_idle_gap_shares():
    s = harness.Stamps(lambda st, x, y: (st, {"loss": 0.0}), echo=lambda t: None)
    for k in range(3):  # period 1 s: call, +10 ms return, +900 ms line
        s.calls.append(k * 1.0)
        s.returns.append(k * 1.0 + 0.010)
        s.lines.append(k * 1.0 + 0.900)
    spans = harness.spans_of(s, 0, 3, [880.0, 880.0, 880.0])
    assert spans["period"] == pytest.approx([1000.0, 1000.0])
    assert spans["fetch"] == pytest.approx([100.0, 100.0])
    assert spans["dispatch"] == pytest.approx([10.0] * 3)
    assert spans["loss_wait"] == pytest.approx([870.0] * 3)
    assert spans["loop_other"] == pytest.approx([20.0] * 3)
    info = {"op_seconds": {"fusion:bf16[8]": 2.0, "copy:f32[4]": 0.5},
            "chips": [{"step_ms": [850.0, 850.0, 850.0]}], "periods": 2}
    b = harness.breakdown(info, spans)
    assert b["device_ops"][0] == ["fusion:bf16[8]", 2.0]
    gaps = dict(b["idle_gaps"])  # 150 ms a period: 100 fetch, 20 other, 10, 20
    assert gaps == pytest.approx({"fetch": 0.2, "loop_other": 0.04,
                                  "dispatch": 0.02, "loss_wait": 0.04})


# --- `correct` ----------------------------------------------------------------

GOOD = dict(losses=[6.91, 6.90, 6.92], anomalies=0, state_finite=True,
            compiles_in_window=0, first_loss=6.91, reference_loss=6.912,
            loss_tolerance=1.5e-3, cell_rel_err_max=0.004, cell_tolerance=0.02)


@pytest.mark.parametrize("change, fails", [
    ({}, None),
    ({"losses": [6.91, math.nan, 6.92]}, "losses_finite"),
    ({"anomalies": 1}, "guard_silent"),
    ({"losses": [6.91, 6.91, 6.91]}, "loss_moves"),
    ({"state_finite": False}, "state_finite"),
    ({"compiles_in_window": 1}, "no_compile_in_window"),
    # the loss of another batch: 0.2 % off and more
    ({"first_loss": 6.926}, "first_loss_matches_reference"),
    ({"cell_rel_err_max": 0.3}, "cells_match_reference"),
])
def test_each_condition_of_correct_fails_when_it_should(change, fails):
    checks = harness.verdict(**{**GOOD, **change})
    assert [k for k, ok in checks.items() if not ok] == ([fails] if fails else [])


def test_nan_in_a_leaf_is_found():
    tree = {"a": jnp.ones((3,)), "step": jnp.zeros((), jnp.int32),
            "b": [jnp.ones((2, 2), jnp.bfloat16)]}
    assert harness.all_finite(tree)
    tree["b"][0] = tree["b"][0].at[1, 1].set(jnp.nan)
    assert not harness.all_finite(tree)


@pytest.mark.parametrize("config", ["resnet110_v2", "amoebanet_d_18_416"])
def test_bf16_cells_pass_and_a_wrong_layer_fails(config, monkeypatch):
    """The real configuration at 128x128: every cell of the program in bf16,
    fed the reference's activation, is off by rounding alone, and a
    reference with one layer wrong (average pools where max pools belong,
    the reference source's own slip; a leaky relu) is outside the
    configuration's tolerance.  The tolerance is the chip's.  The CPU
    backend rounds AmoebaNet's folded batch-norm affine in bf16, which the
    chip's fusions do not, and reads up to 0.18 where the chip reads 0.015:
    there the bf16 side is held to 0.25 here."""
    from mpi4dl_tpu.config import config_from_args, get_parser
    from mpi4dl_tpu.models import build_model

    cell = Catalog().cell(next(
        w["name"] for w in BENCH["workloads"] if w["config"] == config))
    tol = cell.config["tolerances"]["cell"]["value"]
    argv = cell.argv(3)
    argv[argv.index("--image-size") + 1] = "128"
    cfg = config_from_args(get_parser().parse_args(argv))
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(cfg.seed))
    x = np.random.default_rng(0).standard_normal((1, 128, 128, 3), np.float32)
    y = np.array([1], np.int32)
    good = harness.reference_check(cell, cfg, params, x, y)
    assert len(good["cell_rel_err"]) == len(model.cells)
    assert 1e-4 < good["cell_rel_err_max"] < (
        tol if config.startswith("resnet") else 0.25)
    if config.startswith("resnet"):
        monkeypatch.setattr(plain, "relu", lambda x: jnp.maximum(x, 0.1 * x))
    else:
        monkeypatch.setattr(plain, "max_pool", lambda x, k, s, p=0: (
            plain.avg_pool(x, k, s, p)))
    bad = harness.reference_check(cell, cfg, params, x, y)
    assert bad["cell_rel_err_max"] > max(2 * tol, 1.5 * good["cell_rel_err_max"])


# --- the reference and the FLOP count ----------------------------------------


@pytest.mark.parametrize("kind, tol", [("tiny_resnet", 1e-5),
                                       ("tiny_amoebanet", 2e-4)])
def test_reference_agrees_with_the_program_in_float32(tiny, kind, tol):
    """The two forwards are written apart and agree to rounding in float32,
    so a difference on the chip is the compute dtype or a fault."""
    from mpi4dl_tpu.config import config_from_args, get_parser
    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from mpi4dl_tpu.models import build_model

    cell = tiny.cell(kind + ".32.bs2")
    argv = cell.argv(5)
    argv[argv.index("--image-size") + 1] = "64"
    cfg = config_from_args(get_parser().parse_args(argv))
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 64, 64, 3), np.float32))
    with jax.default_matmul_precision("highest"):
        ref = plain.forward(
            cell.reference_cells()(params, cell.config["sizes"]), x)
        got = model.apply(params, x, ApplyCtx(train=True))
    assert ref.shape == (2, 10)
    assert float(jnp.max(jnp.abs(ref - got))) < tol * float(jnp.max(jnp.abs(ref)))


def test_model_flops_against_a_hand_count():
    """ResNet-(9+2) v2 at 32x32, batch 1, 10 classes, by hand: per output
    position, kernel area x channels in x channels out."""
    from mpi4dl_tpu.models import get_resnet
    from perfbench.references import resnet_v2

    hw = 32 * 32
    stem = hw * 9 * 3 * 16
    # a block: 3x3, 3x3, 1x1 and the 1x1 shortcut of a stage's first block
    s0 = hw * (9 * 16 * 16 + 9 * 16 * 16 + 16 * 64 + 16 * 64)
    s1 = (hw // 4) * (9 * 64 * 64 + 9 * 64 * 64 + 64 * 128 + 64 * 128)
    s2 = (hw // 16) * (9 * 128 * 128 + 9 * 128 * 128 + 128 * 256 + 128 * 256)
    conv_macs = stem + s0 + s1 + s2
    dense_macs = 256 * 10  # the 8x8 map pooled to 1x1, then flattened
    shapes = jax.eval_shape(
        lambda k: get_resnet((1, 32, 32, 3), depth=11, num_classes=10).init(k)[0],
        jax.random.key(0))
    tally = plain.Tally()
    jax.eval_shape(
        lambda p, x: plain.forward(resnet_v2.cells(p, {"num_layers": 1}, tally), x),
        shapes, jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32))
    assert (tally.conv_macs, tally.dense_macs) == (conv_macs, dense_macs)
    assert plain.model_flops(tally.macs) == 6 * (conv_macs + dense_macs)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_stored_model_flops_are_what_the_reference_counts(workload):
    """``model_flops_per_img`` in the configuration's file, at the cell's
    image size, against the count from shapes at that size (nothing runs)."""
    from mpi4dl_tpu.config import config_from_args, get_parser
    from mpi4dl_tpu.models import build_model

    cell = Catalog().cell(workload)
    cfg = config_from_args(get_parser().parse_args(cell.argv(0)))
    shapes = jax.eval_shape(lambda k: build_model(cfg).init(k)[0],
                            jax.random.key(0))
    tally = plain.Tally()
    x = jax.ShapeDtypeStruct(
        (cfg.batch_size, cfg.image_size, cfg.image_size, 3), jnp.float32)
    jax.eval_shape(lambda p, x: plain.forward(cell.reference_cells()(
        p, cell.config["sizes"], tally), x), shapes, x)
    assert cell.config["model_flops_per_img"][str(cfg.image_size)] == (
        plain.model_flops(tally.macs) // cfg.batch_size)
    assert cell.config["sizes"]["num_layers"] == cfg.num_layers


# --- the trace ----------------------------------------------------------------


def _profile(planes):
    def line(name, events):
        return SimpleNamespace(name=name, events=[
            SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in events])
    return SimpleNamespace(planes=[
        SimpleNamespace(name=name, lines=[line(*l) for l in lines])
        for name, lines in planes])


def test_busy_time_comes_from_modules_not_from_ops():
    """Two periods of 1000 ns; the step program runs 900 ns of each and its
    ops cover only 500 ns of that, with gaps between them (PR 23 read the
    gaps between ops as idle time)."""
    fusion = "%fusion.33 = bf16[1052676,208]{1,0:T(8,128)(2,1)} fusion(bf16[8] %p)"
    update = ("%multiply_subtract_fusion.13 = f32[1,1,3328,1664]{3,2,1,0:T(8,128)} "
              "fusion(f32[1,1,3328,1664] %a)")
    tup = "%fusion.15248 = (bf16[416]{0:T(512)}, bf16[416]{0}) fusion(bf16[4] %q)"
    loop = "%while.7 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t), body=%b"
    ops = []
    for base in (0, 1000, 2000):  # the while spans the two ops of its body
        ops += [(fusion, base + 0, 200), (loop, base + 300, 600),
                (update, base + 400, 200), (tup, base + 800, 100)]
    prof = _profile([
        ("/host:CPU", [("python", [("step", 0, 3000)])]),
        ("/device:TPU:0", [
            ("Steps", [("0", 0, 900)]),
            ("XLA Modules", [("jit_step(123)", 0, 900),
                             ("jit_step(123)", 1000, 900),
                             ("jit_convert(7)", 1950, 20),
                             ("jit_step(123)", 2000, 900)]),
            ("XLA Ops", ops)]),
    ])
    out = trace.reduce_planes(trace.read_planes(prof), harness.STEP_PROGRAM)
    assert out["periods"] == 2 and out["window_s"] == pytest.approx(2000e-9)
    assert out["busy_s"] == pytest.approx((900 + 900 + 20) * 1e-9)
    assert out["chips"][0]["step_ms"] == pytest.approx([900e-6] * 3)
    assert out["op_seconds"] == pytest.approx({
        "fusion:bf16[1052676,208]": 400e-9,
        "multiply_subtract_fusion:f32[1,1,3328,1664]": 400e-9,
        "fusion:bf16[416]": 200e-9})
    record = {"trace": out, "spans": {"period": [1000e-6] * 5}}
    cat = Catalog()
    assert cat.read_layer_metric("device_step_ms", record) == pytest.approx(900e-6)
    assert cat.read_layer_metric("device_idle_pct", record) == pytest.approx(10.0)
    assert cat.read_layer_metric("device_idle_pct", {"trace": None, "spans": {}}) is None


def test_mfu_reader():
    cat = Catalog()
    record = {"peaks": {"bf16_flops": 197e12}, "model": {"flops_per_img": 20e12},
              "run": {"img_per_s": 0.737, "chips": 1}}
    assert cat.read_layer_metric("mfu_pct", record) == pytest.approx(
        100 * 20e12 * 0.737 / 197e12)
    record["peaks"]["bf16_flops"] = None  # the CPU has no peak: no metric
    assert cat.read_layer_metric("mfu_pct", record) is None
