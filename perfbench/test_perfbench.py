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

from perfbench import harness, optable, trace
from perfbench.catalog import ROOT, Catalog
from perfbench.references import plain

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


VOCAB, HIDDEN = 1000, 32

# The plain reference of the token stub: embedding, one dense layer, a head.
TINY_TOKENS_REFERENCE = '''
import jax
import jax.numpy as jnp

from perfbench.references import plain


def embed(table, ids):
    return table.astype(jnp.float32)[ids]


def batch_spec(sizes, traffic):
    shape = (traffic["batch_size"], traffic["size"])
    return (jax.ShapeDtypeStruct(shape, jnp.int32),
            jax.ShapeDtypeStruct(shape, jnp.int32))


def cells(params, sizes, tally=None):
    assert params[0]["table"].shape == (sizes["vocab"], sizes["hidden"])
    return [lambda ids: embed(params[0]["table"], ids),
            lambda x: plain.relu(plain.dense(x, params[1], tally)),
            lambda x: plain.dense(x, params[2], tally)]
'''


def _dump(path, obj):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark's files with throw-away configurations,
    traffic mixes, cells, a reference, a metric and a reader kind ADDED as
    new files and entries: nothing that is there is edited.  Among them a
    token model (``tiny_tokens``: a reference with its own ``batch_spec``, a
    configuration and a traffic file, and no more) and a spatial cell over
    four devices."""
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
    _dump(p / "traffic" / "32.sp2x2.bs1.json", {
        "family": "sp", "size": 32, "batch_size": 1,
        "argv": ["--image-size", "32", "--batch-size", "1", "--split-size",
                 "1", "--num-spatial-parts", "4", "--slice-method", "square",
                 "--num-workers", "1"],
    })
    # The program has no token flags yet: the sequence length travels as
    # --image-size and the vocabulary as --num-classes, to the stubs below.
    _dump(p / "configs" / "tiny_tokens.json", {
        "model": "tokens", "reference": "tiny_tokens",
        "sizes": {"vocab": VOCAB, "hidden": HIDDEN},
        "argv": ["--num-classes", str(VOCAB), "--precision", "bf_16"],
        "model_flops_per_img": {"16": 6 * 16 * (HIDDEN * HIDDEN + HIDDEN * VOCAB)},
        "tolerances": {"loss": {"value": 1e-2}, "cell": {"value": 0.05}},
    })
    _dump(p / "traffic" / "seq16.bs2.json", {
        "family": "lp", "size": 16, "batch_size": 2,
        "argv": ["--image-size", "16", "--batch-size", "2", "--split-size",
                 "1", "--num-workers", "1"],
    })
    (p / "references" / "tiny_tokens.py").write_text(TINY_TOKENS_REFERENCE)
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
    bench["configs"].append({
        "name": "tiny_tokens", "source": "test", "reduced": [], "why": "test",
        "file": "perfbench/configs/tiny_tokens.json"})
    bench["workloads"] += [
        {"name": "tiny_tokens.seq16.bs2", "config": "tiny_tokens",
         "traffic": "seq16.bs2", "chips": 1, "why": "test"},
        {"name": "tiny_resnet.32.sp2x2.bs1", "config": "tiny_resnet",
         "traffic": "32.sp2x2.bs1", "chips": 4, "why": "test"}]
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


SEED = 2147483659


def _run(tiny, cell, chips=1, say=lambda text: None, trace=False):
    """One short run of ``cell``: the result and the details file."""
    out_dir = os.path.join(tiny.bench_dir, "out")
    result = harness.run_cell(
        tiny, cell, seed=SEED, seconds=0.2, trace=trace,
        t0=time.perf_counter(), devices=jax.devices()[:chips],
        out_dir=out_dir, say=say)
    with open(os.path.join(
            out_dir, f"{cell.name}.seed{SEED}.trace{int(trace)}.json")) as f:
        return result, json.load(f)


def test_cell_runs_through_build_train_and_run_supervised(tiny):
    """argv -> build_train -> run_supervised on a tiny model: the four
    end-to-end metrics, a correct run, and the details file."""
    cell = tiny.cell("tiny_resnet.32.bs2")
    lines = []
    result, details = _run(tiny, cell, say=lines.append)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= harness.MIN_WINDOW_STEPS
    assert list(result)[-1] == "compared" and all(
        0 <= c["value"] <= c["limit"] for c in result["compared"].values())
    assert list(result["compared"]) == list(details["checks"])
    assert result["compared"]["cells_match_reference"] == {
        "value": details["reference"]["cell_rel_err_max"], "limit": 0.05}
    assert set(result["metrics"]) == {"img_per_s", "step_ms_p90", "hbm_gib",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert sum(l.startswith("epoch ") for l in lines) == (
        harness.WARM_STEPS + result["attempted"])
    assert details["compiles"].get("window") is None
    assert details["memory_analysis"]["temp"] > 0
    assert len(details["spans_ms"]["period"]) == result["attempted"] - 1


def test_a_traced_run_without_a_device_plane_reads_no_device_metric(tiny):
    """``--trace 1`` through the whole of ``run_cell`` here: the step
    executable gives its text and the table is built, the CPU's trace has no
    device plane to join it to, so the line carries the host's metrics and
    none of the device's, and stays correct."""
    cell = tiny.cell("tiny_resnet.32.bs2")
    result, details = _run(tiny, cell, trace=True)
    assert result["correct"] and list(result)[-1] == "compared"
    joined = details["trace"]["joined"]
    assert joined["rows"] > 10 and not joined["holds"]
    assert joined["instructions"] == {} and details["trace"]["periods"] == 0
    assert "fetch_ms" in result["metrics"]
    assert not set(result["metrics"]) & {
        "device_step_ms", "product_ms", "move_ms", "recompute_ms"}
    assert "breakdown" not in result and "device_classes" not in result


# --- a token model and a spatial cell, added as files only -------------------


class _TokenDataset:
    """int32 ids below the vocabulary and a label for every position."""

    def __init__(self, cfg):
        self.seq, self.vocab, self.seed = cfg.image_size, cfg.num_classes, cfg.seed

    def __len__(self):
        return 320

    def batch(self, idx, batch_size):
        rng = np.random.default_rng(self.seed + idx)
        shape = (batch_size, self.seq)
        return (rng.integers(0, self.vocab, shape, dtype=np.int32),
                rng.integers(0, self.vocab, shape, dtype=np.int32))


def _token_model(cfg):
    """The stub ``CellModel``: embedding, a dense layer, a head, in bf16."""
    from mpi4dl_tpu.cells import CellModel, FnCell

    seq, vocab = cfg.image_size, cfg.num_classes

    def table_init(key, in_shape):
        return ({"table": jax.random.normal(key, (vocab, HIDDEN))},
                (*in_shape, HIDDEN))

    def dense_init(n_out):
        def init(key, in_shape):
            k = jax.random.normal(key, (in_shape[-1], n_out)) / in_shape[-1] ** 0.5
            return ({"kernel": k, "bias": jnp.full((n_out,), 0.1)},
                    (*in_shape[:-1], n_out))
        return init

    def dense(p, x):
        return x @ p["kernel"].astype(x.dtype) + p["bias"].astype(x.dtype)

    return CellModel([
        FnCell(table_init, lambda p, ids, ctx: p["table"].astype(jnp.bfloat16)[ids]),
        FnCell(dense_init(HIDDEN), lambda p, x, ctx: jnp.maximum(dense(p, x), 0)),
        FnCell(dense_init(vocab), lambda p, x, ctx: dense(p, x)),
    ], (cfg.batch_size, seq), vocab, name="tiny_tokens")


def _token_loss(logits, labels, from_probs=False):
    """The stub's own loss, written apart from the reference's: the mean over
    every position of logsumexp less the label's logit."""
    z = logits.astype(jnp.float32)
    picked = jnp.sum(z * jax.nn.one_hot(labels, z.shape[-1]), axis=-1)
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked)


@pytest.fixture
def token_program(monkeypatch):
    """What the program lacks for a token model, stubbed and not edited (the
    edits are the ``model_config`` PR's): a model, a dataset, and the two
    places where ``train.py``'s one-chip step takes its batch for an image:
    the cast of ``x`` to the compute dtype (a no-op once that dtype is the
    ids' own) and ``labels[:, None]``."""
    import mpi4dl_tpu.data
    import mpi4dl_tpu.models
    from mpi4dl_tpu import train

    make_train_step = train.make_train_step
    monkeypatch.setattr(mpi4dl_tpu.models, "build_model", _token_model)
    monkeypatch.setattr(mpi4dl_tpu.data, "make_dataset", _TokenDataset)
    monkeypatch.setattr(train, "make_train_step", lambda *a, **kw: (
        make_train_step(*a, **{**kw, "compute_dtype": jnp.int32})))
    monkeypatch.setattr(train, "cross_entropy", _token_loss)


def test_token_cell_runs_through_the_whole_of_run_cell(tiny, token_program):
    """Ids of 256 and over reach both first cells as the loader made them
    (cast to bf16 the program's embedding would look up other rows), the
    loss is the mean over positions, and the stored FLOPs are looked up
    under the traffic's size."""
    cell = tiny.cell("tiny_tokens.seq16.bs2")
    result, details = _run(tiny, cell)
    assert result["correct"], details["checks"]
    assert result["attempted"] >= harness.MIN_WINDOW_STEPS
    ref = details["reference"]
    assert len(ref["cell_rel_err"]) == 3 and ref["cell_rel_err"][0] < 0.01
    assert ref["model_flops_per_img"] == cell.stored_model_flops() > 0
    assert ref["forward_macs_per_img_by_kind"] == {
        "dense": 16 * (HIDDEN * HIDDEN + HIDDEN * VOCAB)}
    assert abs(ref["reference_loss"] - math.log(VOCAB)) < 1.0


def test_permuted_embedding_rows_fail_the_cell_check(tiny, token_program,
                                                     monkeypatch):
    from mpi4dl_tpu.config import config_from_args, get_parser

    cell = tiny.cell("tiny_tokens.seq16.bs2")
    cfg = config_from_args(get_parser().parse_args(cell.argv(7)))
    params, _ = _token_model(cfg).init(jax.random.key(0))
    x, y = _TokenDataset(cfg).batch(0, 2)
    assert x.max() >= 256
    tol = cell.config["tolerances"]["cell"]["value"]
    good = harness.reference_check(cell, cfg, params, x, y)
    assert good["cell_rel_err_max"] < tol
    monkeypatch.setattr(cell.reference(), "embed",
                        lambda table, ids: table.astype(jnp.float32)[::-1][ids])
    bad = harness.reference_check(cell, cfg, params, x, y)
    assert bad["cell_rel_err"][0] > 1.0 and bad["cell_rel_err_max"] > tol


def test_spatial_cell_over_four_devices_runs_through_run_cell(tiny):
    """The ``sp`` family on a 2x2 mesh of host devices: the mesh from
    ``MeshSpec.from_config``, the reference check on the replicated
    parameters, ``memory_analysis()`` a device.  The shard_map step retraces
    on its second call (PERF.md, PR 22); with three warm steps that falls
    in set-up, so the window builds nothing, and the recorder has counted
    the step program twice."""
    from mpi4dl_tpu.obs.spans import recorder

    assert len(jax.devices()) >= 4, "perfbench/conftest.py asks for four"
    cell = tiny.cell("tiny_resnet.32.sp2x2.bs1")
    assert cell.chips == 4 and cell.family == "sp"
    spec = json.load(open(os.path.join(
        tiny.bench_dir, "layer_metrics", "step_program_builds.json")))["params"]

    def step_program_builds():  # the recorder counts over the whole process
        return sum(n for program, n in recorder().programs(spec["kind"]).items()
                   if re.search(spec["pattern"], program))

    before = step_program_builds()
    result, details = _run(tiny, cell, chips=4)
    assert result["correct"], details["checks"]
    assert details["checks"]["no_compile_in_window"]
    assert result["device"]["count"] == 4
    assert result["device"]["memory_peak_bytes"] >= (
        details["memory_analysis"]["total"]) > 0
    assert step_program_builds() - before == 2


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
    # a cell that is not a number, wherever it stands among the cells
    ({"cell_rel_err_max": harness.worst([0.004, math.nan, 0.003])},
     "cells_match_reference"),
])
def test_each_condition_of_correct_fails_when_it_should(change, fails):
    checks = harness.verdict(harness.compared(**{**GOOD, **change}))
    assert [k for k, ok in checks.items() if not ok] == ([fails] if fails else [])


def test_the_worst_cell_is_not_a_number_where_any_cell_is_none():
    """``max()`` keeps its first argument against a NaN: PR 35's planted
    overflow of the state read as the cell before it."""
    assert max([0.004, math.nan]) == 0.004  # what the harness took
    assert math.isnan(harness.worst([0.004, math.nan]))
    assert math.isnan(harness.worst([math.nan, 0.004]))
    assert harness.worst([0.004, 0.3, 0.003]) == 0.3
    assert harness.worst([0.004, math.inf]) == math.inf  # fails by its size


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


@pytest.mark.parametrize("shape", [(4,), (2, 3)])
def test_cross_entropy_takes_labels_of_the_logits_leading_shape(shape):
    """``[B, V]`` with ``[B]`` is the number it always was, to the bit;
    ``[B, S, V]`` with ``[B, S]`` is the mean over every position."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((*shape, 7), np.float32))
    labels = jnp.asarray(rng.integers(0, 7, shape, dtype=np.int32))
    flat, flat_labels = logits.reshape(-1, 7), labels.reshape(-1)
    logp = jax.nn.log_softmax(flat, axis=-1)
    was = -jnp.mean(jnp.take_along_axis(logp, flat_labels[:, None], axis=-1))
    got = float(plain.cross_entropy(logits, labels))
    assert got == float(was) if len(shape) == 1 else got == pytest.approx(
        float(was), rel=1e-6)  # the mean over two axes sums in another order
    by_hand = -np.mean([np.log(np.exp(r[k]) / np.exp(r).sum())
                        for r, k in zip(np.asarray(flat, np.float64),
                                        np.asarray(flat_labels))])
    assert float(was) == pytest.approx(by_hand, rel=1e-6)


def test_tally_counts_under_any_name():
    tally = plain.Tally()
    assert (tally.macs, tally.conv_macs, tally.dense_macs) == (0, 0, 0)
    x = jax.ShapeDtypeStruct((2, 5, 8), jnp.float32)
    p = {"kernel": jax.ShapeDtypeStruct((8, 3), jnp.float32),
         "bias": jax.ShapeDtypeStruct((3,), jnp.float32)}
    jax.eval_shape(lambda x, p: plain.dense(x, p, tally), x, p)
    tally.add("attn_scores", 40)
    tally.add("attn_scores", 2)
    tally.add("conv", 7)
    assert tally.by_kind == {"dense": 2 * 5 * 8 * 3, "attn_scores": 42, "conv": 7}
    assert (tally.conv_macs, tally.dense_macs) == (7, 240)
    assert tally.macs == 240 + 42 + 7


def test_an_integer_leaf_keeps_its_dtype_and_a_floating_one_is_cast():
    ids = np.array([[0, 255, 256, 999]], np.int32)
    assert plain.cast_floating(ids, jnp.bfloat16).dtype == np.int32
    assert plain.cast_floating(
        jnp.ones((1, 2), jnp.bfloat16), jnp.float32).dtype == jnp.float32
    both = plain.cast_floating(
        {"ids": ids, "image": np.ones((1, 2), np.float32)}, jnp.bfloat16)
    assert (both["ids"].dtype, both["image"].dtype) == (np.int32, jnp.bfloat16)
    spec = plain.image_batch_spec({}, {"batch_size": 2, "size": 32})
    assert [(s.shape, s.dtype) for s in spec] == [
        ((2, 32, 32, 3), jnp.float32), ((2,), jnp.int32)]


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


@pytest.mark.parametrize("workload", [
    *(w["name"] for w in BENCH["workloads"]), "tiny_tokens.seq16.bs2"])
def test_stored_model_flops_are_what_the_reference_counts(workload, request):
    """``model_flops_per_img`` in the configuration's file, under the
    traffic's size, against the count from shapes at that size (nothing
    runs); the batch is the one the reference states for itself, and a key
    of ``sizes`` that the parsed flags carry too is the flag's value.  The
    token cell of the tiny catalog goes through the same lines as the cells
    of ``BENCHMARK.json``."""
    import mpi4dl_tpu.models as models
    from mpi4dl_tpu.config import config_from_args, get_parser

    if workload.startswith("tiny_"):
        request.getfixturevalue("token_program")  # the program's model: a stub
        catalog = request.getfixturevalue("tiny")
    else:
        catalog = Catalog()
    cell = catalog.cell(workload)
    cfg = config_from_args(get_parser().parse_args(cell.argv(0)))
    shapes = jax.eval_shape(lambda k: models.build_model(cfg).init(k)[0],
                            jax.random.key(0))
    tally = plain.Tally()
    x, _ = cell.batch_spec()
    jax.eval_shape(lambda p, x: plain.forward(cell.reference_cells()(
        p, cell.config["sizes"], tally), x), shapes, x)
    assert cell.stored_model_flops() == (
        plain.model_flops(tally.macs) // x.shape[0]) > 0
    sizes = cell.config["sizes"]
    held = {key for key in sizes if hasattr(cfg, key)}
    assert all(sizes[key] == getattr(cfg, key) for key in held), held
    if not workload.startswith("tiny_"):  # the image configurations' own
        assert "num_layers" in held


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
    # a conditional is named cond and a call closed_call: containers by opcode
    cond = ("%cond.3 = f32[32,512,192]{2,1,0:T(8,128)} conditional(s32[] %i, "
            "f32[8] %a, f32[8] %b), branch_computations={%x, %y}")
    call = "%closed_call.9 = bf16[8]{0} call(bf16[8]{0} %p), to_apply=%body.1"
    ops = []
    for base in (0, 1000, 2000):  # the while spans the two ops of its body
        ops += [(fusion, base + 0, 200), (loop, base + 300, 600),
                (cond, base + 350, 500), (call, base + 380, 300),
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
    assert out["inst_seconds"] == pytest.approx({
        "fusion.33": 400e-9, "multiply_subtract_fusion.13": 400e-9,
        "fusion.15248": 200e-9})
    record = {"trace": out, "spans": {"period": [1000e-6] * 5}}
    cat = Catalog()
    assert cat.read_layer_metric("device_step_ms", record) == pytest.approx(900e-6)
    assert cat.read_layer_metric("device_idle_pct", record) == pytest.approx(10.0)
    assert cat.read_layer_metric("device_idle_pct", {"trace": None, "spans": {}}) is None


def test_chips_of_a_mesh_are_averaged_and_the_first_is_broken_down():
    """Four chips run the step program side by side: busy time and the
    window are the chips' mean, the periods those every chip has, and the
    step's device time and the op sums the first chip's."""
    planes = [(f"/device:TPU:{chip}", [
        ("XLA Modules", [("jit_step(9)", k * 1000 + chip, 800 + 10 * chip)
                         for k in range(3 + (chip == 3))]),
        ("XLA Ops", [("%fusion.1 = bf16[8]{0} fusion(bf16[8] %p)",
                      k * 1000 + chip, 100 * (chip + 1)) for k in range(3)])])
        for chip in (2, 0, 3, 1)]
    out = trace.reduce_planes(trace.read_planes(_profile(planes)),
                              harness.STEP_PROGRAM)
    assert [c["chip"] for c in out["chips"]] == [0, 1, 2, 3]
    assert out["periods"] == 2
    assert out["busy_s"] == pytest.approx(
        (2 * 800 + 2 * 810 + 2 * 820 + 3 * 830) / 4 * 1e-9)
    assert out["window_s"] == pytest.approx((2000 + 2000 + 2000 + 3000) / 4 * 1e-9)
    assert out["op_seconds"] == pytest.approx({"fusion:bf16[8]": 200e-9})
    record = {"trace": out, "spans": {"period": [1000e-6] * 5}}
    assert Catalog().read_layer_metric("device_step_ms", record) == (
        pytest.approx(800e-6))


# --- what an instruction is ---------------------------------------------------

CUTS = os.path.join(ROOT, "perfbench", "fixtures", "compiled_step_cuts.hlo.txt")
SIX = ("attention_ms", "attention_roofline_pct", "mla_attention_ms",
       "mla_attention_roofline_pct", "ssm_scan_ms", "ssm_scan_roofline_pct")


@pytest.fixture(scope="module")
def cuts():
    with open(CUTS, encoding="utf-8") as f:
        return optable.parse(f.read())


@pytest.mark.parametrize("name, cls, op_pass, key", [
    # granite: a kOutput fusion whose FIRST result is a norm's scale gradient
    # and whose body is the backward's input-gradient product
    ("fusion.1069", "product", "backward",
     "fusion:f32[2048]+f32[2,8192]+bf16[2,8192,2048]{product}"),
    # ResNet: a 3x3 convolution with BatchNorm's two sums as by-outputs ...
    ("fusion.1207", "product", "forward",
     "fusion:f32[128]+f32[128]+bf16[1024,8,17,128]{product}"),
    # ... and the stand-alone sums of the same name and first result
    ("fusion.889", "reduce", "recompute", "fusion:f32[128]+f32[128]{reduce}"),
    ("fusion.8724", "move", "recompute",
     "fusion:bf16[512,1,528,128]+bf16[512,1,528,128]{move}"),
    # Kanana-2: a conditional named cond; one operation in two instructions
    ("cond.1540", "container", "recompute", None),
    ("slice-start.804", "move", "forward", None),
    ("slice-done.804", "move", "forward", "slice-done:pred[8192,6]{move}"),
    # the chip's own text: an async-done is read from what its start calls
    ("slice-start.482", "move", "forward", None),
    ("slice-done.482", "move", "forward", "slice-done:f32[1,32,8192]{move}"),
])
def test_the_compiled_text_says_what_an_instruction_is(cuts, name, cls, op_pass,
                                                       key):
    said = optable.describe(cuts[name])
    assert (said["cls"], said["pass"]) == (cls, op_pass)
    assert key is None or said["key"] == key
    if cuts[name]["opcode"] == "fusion":  # XLA's own word for the two kinds
        assert cuts[name]["kind"] == ("Output" if cls == "product" else "Loop")
    if cls == "product":  # by name and first result it read as a reduction
        line = next(l for l in open(CUTS) if l.lstrip().startswith(f"%{name} = "))
        assert re.match(r"fusion:f32\[(2048|128)\]$", trace.op_key(line.strip()))


def _plane_of(cuts, busy):
    """Module events of 1000 ns and, back to back inside each, op events for
    ``busy`` = [(instruction, ns)], their text the fixture's own lines, with
    the conditional spanning what follows it."""
    lines = {}
    for line in open(CUTS):
        head = trace.instruction_head(line.strip())
        if head:
            lines[head[0]] = line.strip()
    ops, modules = [], []
    for base in (0, 2000, 4000):
        at = base
        modules.append(("jit_step(5)", base, sum(ns for _, ns in busy)))
        for name, ns in busy:
            if cuts.get(name, {}).get("opcode") == "conditional":
                ops.append((lines[name], at, base + modules[-1][2] - at))
                continue
            ops.append((lines.get(name, f"%{name} = f32[8]{{0}} fusion(f32[8] %p)"),
                        at, ns))
            at += ns
    return _profile([("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)])])


def test_the_five_classes_sum_to_the_module_time(cuts):
    """Every op event of the step program is in one of the five classes, the
    conditional's in none (its body is listed op by op), a start and its done
    once each: the sum is the step program's device time."""
    busy = [("fusion.1069", 400), ("fusion.1207", 150), ("fusion.889", 50),
            ("cond.1540", 0), ("fusion.8724", 100), ("slice-start.804", 10),
            ("slice-done.804", 40)]
    out = trace.reduce_planes(trace.read_planes(_plane_of(cuts, busy)),
                              harness.STEP_PROGRAM)
    assert "cond.1540" not in out["inst_seconds"]
    out["joined"] = optable.join(out["inst_seconds"], cuts)
    line = harness.classes_line(out)
    assert line["join_holds"] and line["found_share"] == pytest.approx(1.0)
    assert line["sum_over_device_step"] == pytest.approx(1.0)
    record = {"trace": out}
    cat = Catalog()
    read = {m: cat.read_layer_metric(m + "_ms", record) for m in optable.CLASSES}
    # nothing of a class ran: nothing to read, not a nought
    assert read["kernel"] is None and read["elementwise"] is None
    assert (read["product"], read["reduce"], read["move"]) == pytest.approx(
        (550e-6, 50e-6, 150e-6))
    assert sum(v for v in read.values() if v) == pytest.approx(
        cat.read_layer_metric("device_step_ms", record))
    # what remat costs: the recomputed sums and relayout
    assert cat.read_layer_metric("recompute_ms", record) == pytest.approx(150e-6)
    # the breakdown's keys carry every result and the class; no container
    b = harness.breakdown(out, {k: [1.0] for k in (
        "period", "fetch", "loop_other", "dispatch")})
    keys = [k for k, _ in b["device_ops"]]
    assert keys[0] == "fusion:f32[2048]+f32[2,8192]+bf16[2,8192,2048]{product}"
    assert all(re.search(r"\{(product|kernel|reduce|move|elementwise)\}$", k)
               for k in keys)
    assert not [k for k in keys
                if k.startswith(("cond:", "while:", "conditional:", "call:"))]


def test_a_trace_of_another_executable_reads_no_class(cuts):
    """At least 99 % of the op time has to find its instruction; a step whose
    text is not the traced program's reads nothing, and the line says how
    much was lost."""
    busy = [("fusion.1069", 980), ("fusion.424242", 20)]
    out = trace.reduce_planes(trace.read_planes(_plane_of(cuts, busy)),
                              harness.STEP_PROGRAM)
    out["joined"] = optable.join(out["inst_seconds"], cuts)
    line = harness.classes_line(out)
    assert not line["join_holds"] and line["found_share"] == pytest.approx(0.98)
    assert line["lost_ms"] == pytest.approx(20e-6)
    cat = Catalog()
    assert cat.read_layer_metric("product_ms", {"trace": out}) is None
    assert cat.read_layer_metric("recompute_ms", {"trace": out}) is None
    # the breakdown falls back to name and first result
    b = harness.breakdown(out, {k: [1.0] for k in (
        "period", "fetch", "loop_other", "dispatch")})
    assert b["device_ops"][0][0] == "fusion:f32[2048]"
    busy[1] = ("fusion.424242", 9)  # under a hundredth lost: the join holds
    out = trace.reduce_planes(trace.read_planes(_plane_of(cuts, busy)),
                              harness.STEP_PROGRAM)
    out["joined"] = optable.join(out["inst_seconds"], cuts)
    assert cat.read_layer_metric("product_ms", {"trace": out}) == (
        pytest.approx(980e-6))


@pytest.mark.parametrize("metric", SIX)
def test_the_six_kernel_metrics_name_a_scope(metric):
    """Each names a scope of the program; a pattern may stand beside it for a
    program that has not opened the scope (and for tests/test_tpu_compile.py,
    which holds the compiled layers to the three ``_ms`` patterns)."""
    with open(os.path.join(ROOT, "perfbench", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    twin = metric.replace("_roofline_pct", "_ms")
    with open(os.path.join(ROOT, "perfbench", "layer_metrics",
                           twin + ".json")) as f:
        twin_params = json.load(f)["params"]
    assert spec["params"]["scope"] == (
        "ssm_scan" if metric.startswith("ssm") else "attention_core")
    assert spec["params"]["scope"] == twin_params["scope"]
    assert spec["params"].get("pattern") == twin_params.get("pattern")
    assert "padded to 256 lanes" not in spec["what"]
    assert spec["params"]["scope"] in spec["what"]


def _attention_layer_with_an_opaque_backward():
    """A layer compiled here: projections round an attention whose forward
    and whose backward rule are each ONE instruction the compiler cannot look
    into (a callback: what a Pallas kernel is to XLA), the scope
    ``attention_core`` round the call and inside the backward rule, under
    ``jax.checkpoint`` as the step runs its cells."""
    def opaque(*args, like):
        return jax.pure_callback(
            lambda *a: np.zeros(like.shape, like.dtype),
            jax.ShapeDtypeStruct(like.shape, like.dtype), *args)

    @jax.custom_vjp
    def attention(q, k, v):
        return opaque(q, k, v, like=q)

    def forward(q, k, v):
        return attention(q, k, v), (q, k, v)

    def backward(res, do):
        with jax.named_scope("attention_core"):
            dq = opaque(*res, do, like=do)
        return dq, dq, dq

    attention.defvjp(forward, backward)

    def layer(p, x):
        q, k, v = (jnp.tanh(x @ p[n]) for n in ("q", "k", "v"))
        with jax.named_scope("attention_core"):
            o = attention(q, k, v)
        return jnp.tanh(o) @ p["o"]

    def grads(p, x):
        # the loss too: a forward pass nobody reads is not compiled
        return jax.value_and_grad(
            lambda p, x: jnp.sum(jax.checkpoint(layer)(p, x)))(p, x)

    p = {n: jnp.ones((64, 64)) for n in "qkvo"}
    return jax.jit(grads).lower(p, jnp.ones((128, 64))).compile().as_text()


def test_an_opaque_backward_under_the_scope_keeps_the_roofline_under_100(
        monkeypatch):
    """The four attention metrics on a program whose backward is one opaque
    instruction: picked by the scope, forward, recomputed and backward, and
    nothing of the projections; the roofline of two forwards and a backward
    stays under 100 %.  By the shapes of XLA's tiles alone (PR 35's files)
    the same trace keeps the forward under the whole work's count: 117 %."""
    import mpi4dl_tpu.obs.spans as spans

    rows = optable.parse(_attention_layer_with_an_opaque_backward())
    fused = {id(f) for r in rows.values() for f in r["fused"]}
    said = {name: optable.describe(row) for name, row in rows.items()
            if id(row) not in fused}
    under = {n for n, d in said.items() if "attention_core" in d["scopes"]
             and d["cls"] != "container"}
    calls = {n for n in under if rows[n]["opcode"] == "custom-call"}
    passes = sorted(said[n]["pass"] for n in calls)
    assert passes == ["backward", "forward", "recompute"], passes
    assert not [n for n in under if said[n]["cls"] == "product"], under
    # Kanana-2's readings (PR 34): the forward kernel 273.8 ms in two passes,
    # the work's least time 28.87 % of 1,111.8 ms; a backward kernel of 400
    least_s = 0.2887 * 1.1118
    seconds = {n: 0.0 for n in said if said[n]["cls"] != "container"}
    for n in calls:
        seconds[n] = {"forward": 0.1369, "recompute": 0.1369,
                      "backward": 0.400}[said[n]["pass"]]
    projections = [n for n, d in said.items() if d["cls"] == "product"]
    assert projections
    for n in projections:
        seconds[n] = 0.05
    rec = spans.Recorder(annotate=False)
    monkeypatch.setattr(spans, "_RECORDER", rec)
    with rec.span("run", steps=3, profile=False, global_batch=4):
        for g in range(3):
            with rec.span("step", gstep=g):
                pass
    record = {
        "spans": {"dispatch": [1.0] * 3},
        "trace": {"periods": 1, "inst_seconds": seconds,
                  # by name and first result: the forward kernel alone
                  "op_seconds": {"block_flash_fwd:bf16[4,8192,4096]": 0.2738,
                                 "fusion:bf16[4,8192,2048]": 0.4},
                  "joined": optable.join(seconds, rows)},
        "model": {"forward_macs_per_img": {
            "attn_scores": least_s * 197e12 / ((2 + 832 / 320) * 2 * 4)}},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    }
    cat = Catalog()
    assert cat.read_layer_metric("mla_attention_ms", record) == pytest.approx(
        1e3 * (0.2738 + 0.400))
    roofline = cat.read_layer_metric("mla_attention_roofline_pct", record)
    assert roofline == pytest.approx(100 * least_s / 0.6738) and roofline < 100
    # the same program without the scope, read by the tiles' shapes
    record["trace"]["joined"] = None
    assert cat.read_layer_metric("mla_attention_roofline_pct", record) == (
        pytest.approx(117.2, abs=0.1))


def test_a_scope_is_read_where_the_program_opens_it_else_the_pattern(cuts):
    """``ssm_scan`` on instructions made up under and outside the scope; a
    program that opens no such scope, or a trace that found no rows, is read
    by the pattern; neither gives nothing."""
    text = """
%fused_computation.1 (p: f32[8]) -> f32[2,32,256,64] {
  %p = f32[8]{0} parameter(0)
  %d = f32[2,32,256,64]{3,2,1,0} dot(%p, %p), metadata={op_name="jit(step)/transpose(jvp(cell03))/jvp(cell03)/checkpoint/ssm_scan/dot_general"}
  ROOT %s = f32[2,32,256,64]{3,2,1,0} multiply(%d, %d), metadata={op_name="jit(step)/transpose(jvp(cell03))/jvp(cell03)/checkpoint/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %fusion.1 = f32[2,32,256,64]{3,2,1,0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(cell03))/jvp(cell03)/checkpoint/mul"}
  %copy.2 = f32[2,32,256,64,64]{4,3,2,1,0} copy(%a), metadata={op_name="jit(step)/jvp(cell03)/convert_element_type"}
  %fusion.3 = bf16[2,8192,8512]{2,1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1
}
"""
    rows = optable.parse(text)
    # the fusion's own op_name is its root's (the gate's); its product speaks
    assert "ssm_scan" in optable.describe(rows["fusion.1"])["scopes"]
    seconds = {"fusion.1": 0.3, "copy.2": 0.1, "fusion.3": 0.6}
    by_key = {"fusion:f32[2,32,256,64]": 0.3, "copy:f32[2,32,256,64,64]": 0.1,
              "fusion:bf16[2,8192,8512]": 0.6}
    trace_info = {"periods": 2, "op_seconds": by_key, "inst_seconds": seconds,
                  "joined": optable.join(seconds, rows)}
    cat = Catalog()
    # by the scope: the fusion; the copy XLA gave a neighbour's name is out
    # (fusion.3 shares the fused computation: a made-up text, not a program)
    assert cat.read_layer_metric("ssm_scan_ms", {"trace": trace_info}) == (
        pytest.approx(1e3 * (0.3 + 0.6) / 2))
    trace_info["joined"] = optable.join(seconds, {})  # no row found
    assert cat.read_layer_metric("ssm_scan_ms", {"trace": trace_info}) == (
        pytest.approx(1e3 * (0.3 + 0.1) / 2))  # PR 35's pattern
    del trace_info["joined"]
    assert cat.read_layer_metric("ssm_scan_ms", {"trace": trace_info}) == (
        pytest.approx(1e3 * (0.3 + 0.1) / 2))
    assert cat.read_layer_metric("attention_ms", {"trace": trace_info}) is None


def test_mfu_reader():
    cat = Catalog()
    record = {"peaks": {"bf16_flops": 197e12}, "model": {"flops_per_img": 20e12},
              "run": {"img_per_s": 0.737, "chips": 1}}
    assert cat.read_layer_metric("mfu_pct", record) == pytest.approx(
        100 * 20e12 * 0.737 / 197e12)
    record["peaks"]["bf16_flops"] = None  # the CPU has no peak: no metric
    assert cat.read_layer_metric("mfu_pct", record) is None
