"""The Keye-VL-2.0-30B-A3B configuration against its plain reference, on the
CPU: the file against the published row, the metric files against the
configuration, the two new readers on made-up records, and the real widths
through ``harness.reference_check`` in bf16 with six planted faults, each of
which must fall outside the tolerance (on weights whose queries are sharpened
for the attention's faults to show against, as Kanana-2's test does).

On the CPU the cell runs at 512 tokens with the indexer's ``topk`` cut to 128,
so that the selection does something there (at 2,048 every key of 512 is
kept); ``check(fault, seq=16384, batch=1, layers=2)`` is what read the faults
on the chip at the timed sizes (the configuration's file has the readings).

    JAX_PLATFORMS=cpu python -m pytest perfbench/test_keye_vl2.py -q
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.catalog import Catalog
from perfbench.test_deepseek_v3 import QUERY_PLANT
from perfbench.test_lfm2 import _projections_in_fp8

CELL = "keye_vl_2_0_30b_a3b.seq16384.train"
SEQ, TOPK = 512, 128
# The cell check's limit at this size on the CPU, from its own readings: good
# 0.00676 to 0.00804 (seeds 3, 5, 7, 11; four layers unplanted and two
# planted), the nearest fault 0.0302 (sigmoid scores for softmax, seed 5).  It
# is their geometric mean, 1.9 times each.  The configuration's limit (0.0078)
# is the chip's at 16,384 tokens, where good cells read 0.0031 to 0.0036.
CPU_CELL_LIMIT = 0.0156


@contextlib.contextmanager
def _topk(topk):
    """The model and the reference at another ``topk`` (None: as published)."""
    from mpi4dl_tpu.models import keye_vl2

    published = keye_vl2.PUBLISHED
    if topk is not None:
        keye_vl2.PUBLISHED = dataclasses.replace(
            published, sa_config={**published.sa_config, "topk": topk})
    try:
        yield
    finally:
        keye_vl2.PUBLISHED = published


def _setup(layers=None, seq=SEQ, batch=1, seed=3, topk=TOPK):
    """The cell's configuration at ``seq`` tokens, ``batch`` sequences and the
    indexer's ``topk`` (None: the published 2,048); ``layers`` cuts it."""
    from mpi4dl_tpu.config import config_from_args, get_parser
    from mpi4dl_tpu.models import build_model

    cell = Catalog().cell(CELL)
    argv = cell.argv(seed)
    argv[argv.index("--seq-len") + 1] = str(seq)
    argv[argv.index("--batch-size") + 1] = str(batch)
    if layers is not None:
        argv[argv.index("--num-layers") + 1] = str(layers)
        cell.config["sizes"]["num_layers"] = layers
    if topk is not None:
        cell.config["sizes"]["topk"] = topk
    cfg = config_from_args(get_parser().parse_args(argv))
    params, _ = build_model(cfg).init(jax.random.key(cfg.seed))
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    return cell, cfg, params, ids[:, :-1], ids[:, 1:]


def test_the_file_states_the_published_widths_and_the_cut():
    from mpi4dl_tpu.models import keye_vl2, lfm2

    config = Catalog().cell(CELL).config
    row = dataclasses.asdict(keye_vl2.PUBLISHED)
    cut = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
    for key, value in row.items():
        want = cut.get(key, value)
        if isinstance(want, dict):  # nested groups whole, lists as JSON has them
            want = {k: list(v) if isinstance(v, tuple) else v
                    for k, v in want.items()}
        assert config[key] == (list(want) if isinstance(want, tuple) else want), key
    assert config["num_local_experts"] == 128
    assert config["reduced"] == list(cut)
    assert config["published"] == {key: row[key] for key in cut}
    sizes = config["sizes"]
    assert lfm2.layers_run(keye_vl2.PUBLISHED, sizes["num_layers"]) == (0, 1, 2, 3)
    assert sizes["num_experts"] == config["num_experts"]
    assert sizes["num_experts_published"] == row["num_experts"]
    assert sizes["vocab_size"] * 8 == row["vocab_size"]  # an eighth, exactly
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "num_experts_per_tok",
                "rms_norm_eps", "rope_theta"):
        assert sizes[key] == row[key], key
    sa = row["sa_config"]
    assert (sizes["indexer_num_heads"], sizes["indexer_head_dim"],
            sizes["topk"]) == (sa["indexer_num_heads"], sa["indexer_head_dim"],
                               sa["topk"]) == (16, 64, 2048)
    flags = dict(zip(config["argv"][::2], config["argv"][1::2]))
    assert (flags["--model"], flags["--num-layers"], flags["--vocab-size"],
            flags["--experts-held"], flags["--expert-first"]) == (
                "keye_vl2", "4", "18992", "16", "0")


def _metric(name):
    cat = Catalog()
    with open(os.path.join(cat.bench_dir, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_rooflines_count_the_configurations_layers_and_widths():
    from mpi4dl_tpu.ops.pallas_attention import selection_width

    sizes = Catalog().cell(CELL).config["sizes"]
    width = selection_width(16384)
    assert _metric("sparse_attention_roofline_pct")["params"]["params"] == {
        "seq_len": 16384, "heads": sizes["num_attention_heads"],
        "head_dim": sizes["head_dim"], "width": width,
        "layers": sizes["num_layers"]}
    assert _metric("indexer_roofline_pct")["params"]["params"] == {
        "seq_len": 16384, "heads": sizes["indexer_num_heads"],
        "head_dim": sizes["indexer_head_dim"], "topk": sizes["topk"],
        "width": width, "layers": sizes["num_layers"]}
    for name, scope in (("sparse_attention_roofline_pct", "attention_core"),
                        ("indexer_ms", "sparse_indexer"),
                        ("indexer_roofline_pct", "sparse_indexer")):
        params = _metric(name)["params"]
        assert params["scope"] == scope and "pattern" not in params, name
    # attention_ms reads the scope before its pattern: the same instructions
    assert _metric("attention_ms")["params"]["scope"] == "attention_core"
    experts = _metric("keye_expert_ffn_roofline_pct")["params"]
    assert experts["pattern"] == _metric("expert_ffn_ms")["params"]["pattern"]
    assert experts["work"] == "experts" and experts["params"] == {
        "hidden": sizes["hidden_size"], "ffn": sizes["moe_intermediate_size"],
        "held": sizes["num_experts"], "layers": sizes["num_layers"]}
    bench = json.load(open(os.path.join(os.path.dirname(Catalog().bench_dir),
                                        "BENCHMARK.json")))
    lists = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    for name in ("attention_ms", "sparse_attention_roofline_pct", "indexer_ms",
                 "indexer_roofline_pct", "keye_expert_ffn_roofline_pct"):
        assert CELL in lists[name], name


def test_the_stored_flops_are_the_references_count_at_16384():
    """``model_flops_per_img`` at the traffic's size, from shapes alone, and
    the selected pairs counted by hand (the sum of min(2048, t + 1))."""
    from mpi4dl_tpu.models import keye_vl2
    from perfbench.references.plain import Tally, model_flops

    cell = Catalog().cell(CELL)
    model = keye_vl2.keye_vl2((1, 16384), num_layers=4, vocab_size=18992,
                              experts_held=16)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.key(0))
    tally = Tally()

    def walk(p, x):
        for fn in cell.reference_cells()(p, cell.config["sizes"], tally):
            x = fn(x)
        return x

    jax.eval_shape(walk, shapes, cell.batch_spec()[0])
    assert model_flops(tally.macs) == cell.stored_model_flops()
    assert tally.by_kind["sparse_attn"] == 31_458_304 * 32 * 256 * 4
    assert tally.by_kind["indexer_scores"] == 134_225_920 * 16 * 64 * 4
    assert tally.by_kind["experts"] == 16384 * 8 * 16 // 128 * 3 * 2048 * 768 * 4


def test_bf16_cells_pass_at_the_real_widths():
    """All six cells in bf16, each fed the reference's activation, inside the
    tolerance, and the counted products by kind."""
    with _topk(TOPK):
        cell, cfg, params, x, y = _setup()
        good = harness.reference_check(cell, cfg, params, x, y)
    assert len(good["cell_rel_err"]) == 6
    assert 1e-4 < good["cell_rel_err_max"] < CPU_CELL_LIMIT
    assert abs(good["reference_loss"] - np.log(cfg.vocab_size)) < 0.5
    kinds = good["forward_macs_per_img_by_kind"]
    assert set(kinds) == {"dense", "indexer_scores", "sparse_attn", "router",
                          "experts"}
    pairs = TOPK * (TOPK + 1) // 2 + (SEQ - TOPK) * TOPK
    assert kinds["sparse_attn"] == 4 * pairs * 32 * 256
    assert kinds["indexer_scores"] == 4 * SEQ * (SEQ + 1) // 2 * 16 * 64


# --- planted faults: each a reference with one thing wrong -----------------------


def _dense_for_the_selection(ref):
    return "select", lambda scores, causal, topk: jnp.broadcast_to(
        causal, scores.shape)


def _the_lowest_scores(ref):
    select = ref.select
    return "select", lambda scores, causal, topk: select(-scores, causal, topk)


def _indexer_without_relu(ref):
    def indexer_scores(iq, ik, w):
        z = jnp.einsum("bqjd,bkd->bqjk", iq, ik, precision=ref.HI)
        return jnp.einsum("bqj,bqjk->bqk", w, z, precision=ref.HI)
    return "indexer_scores", indexer_scores


def _sigmoid_for_softmax(ref):
    def route(h, p, sizes):
        r = jax.nn.sigmoid(jnp.dot(h, p["kernel"].astype(jnp.float32),
                                   precision=ref.HI))
        chosen = jnp.argsort(-r, axis=-1, stable=True)[
            ..., :sizes["num_experts_per_tok"]]
        w = jnp.take_along_axis(r, chosen, axis=-1)
        return chosen, w / jnp.sum(w, axis=-1, keepdims=True)
    return "route", route


def _no_qk_norms(ref):
    return "qk_norm", lambda x, p, eps: x


FAULTS = [_dense_for_the_selection, _the_lowest_scores, _indexer_without_relu,
          _sigmoid_for_softmax, _no_qk_norms, _projections_in_fp8]


def plant_sharper_queries(params):
    """Queries for the wrong attention layers to show against (Kanana-2's
    ``plant_sharper_queries``): ``q_proj`` times four; and a router as sharp
    for the wrong routers: under random weights the 128 scores of a token are
    a fraction of a unit apart, where softmax and sigmoid scores renormalised
    over the chosen eight nearly coincide (the sigmoid router read 1.25 times a
    good run on the CPU, 4.4 times with the router times four; a good run reads
    the same with it)."""
    for p in params[1:-1]:
        p["op"]["q_proj"] = {"kernel": p["op"]["q_proj"]["kernel"] * QUERY_PLANT}
        p["ffn"]["router"] = {"kernel": p["ffn"]["router"]["kernel"] * QUERY_PLANT}


def check(fault=None, plant=True, topk=TOPK, **setup):
    """``harness.reference_check`` of the program against the reference, the
    reference with ``fault`` planted where one is given, on weights with the
    sharper queries planted unless ``plant`` is false.  On the chip at the
    timed sizes: ``check(fault, seq=16384, batch=1, layers=2, topk=None)``."""
    with _topk(topk):
        cell, cfg, params, x, y = _setup(topk=topk, **setup)
        if plant:
            plant_sharper_queries(params)
        ref = cell.reference()
        name, wrong = fault(ref) if fault else (None, None)
        right = getattr(ref, name) if name else None
        if name:
            setattr(ref, name, wrong)
        try:
            return cell, harness.reference_check(cell, cfg, params, x, y)
        finally:
            if name:
                setattr(ref, name, right)


@pytest.fixture(scope="module")
def good():
    return check(layers=2)[1]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_layer_fails_the_cell_check(fault, good):
    """A reference with one thing wrong, or computed a precision lower, is
    outside this size's limit (the chip's readings at the timed sizes, by
    the configuration's own limit, are in the configuration's file)."""
    _, bad = check(fault, layers=2)
    assert good["cell_rel_err_max"] < CPU_CELL_LIMIT
    assert bad["cell_rel_err_max"] > max(1.25 * CPU_CELL_LIMIT,
                                         1.5 * good["cell_rel_err_max"])


def test_the_precision_below_fails_the_loss_limit():
    """The reference with every projection's operands in float8, taken for
    the program, fails ``first_loss_matches_reference`` through
    ``harness.compared`` under the configuration's loss limit, and only
    that of the loss's conditions (at this size it reads 4.5e-5 to 1.8e-4
    off the float32 reference's, seeds 3, 5, 7; the chip's readings at the
    timed size are in the configuration's file)."""
    cell, good = check(plant=False)
    _, low = check(_projections_in_fp8, plant=False)
    tol = cell.config["tolerances"]
    table = harness.compared(
        losses=[low["reference_loss"], 1.0], anomalies=0, state_finite=True,
        compiles_in_window=0, first_loss=low["reference_loss"],
        reference_loss=good["reference_loss"],
        loss_tolerance=tol["loss"]["value"], cell_rel_err_max=0.0,
        cell_tolerance=tol["cell"]["value"])
    verdict = harness.verdict(table)
    assert not verdict.pop("first_loss_matches_reference")
    assert all(verdict.values()), verdict


# --- the new readers ---------------------------------------------------------------


def test_the_new_readers_on_a_made_up_record(monkeypatch):
    """``sparse_attention_roofline`` and ``indexer_roofline``, and the
    metrics of time they are read over, on a recorder and a joined trace
    made by hand; and nothing (no metric on the line) from a program that
    runs none of it."""
    import mpi4dl_tpu.obs.spans as spans

    rec = spans.Recorder(annotate=False)
    monkeypatch.setattr(spans, "_RECORDER", rec)
    monkeypatch.setattr("perfbench.optable.seconds_where",
                        lambda joined, scope: {"attention_core": 0.4,
                                               "sparse_indexer": 0.2}.get(scope, 0.0))
    cat = Catalog()
    macs = {"sparse_attn": 1_030_825_705_472, "indexer_scores": 549_789_368_320}
    record = {"spans": {"dispatch": [1.0] * 3},
              "trace": {"periods": 2, "op_seconds": {}, "joined": {"holds": True}},
              "model": {"forward_macs_per_img": macs},
              "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    names = ("sparse_attention_roofline_pct", "indexer_roofline_pct")
    with rec.span("run", steps=3, profile=False):  # a run without its batch
        for g in range(3):
            with rec.span("step", gstep=g):
                pass
    assert all(cat.read_layer_metric(n, record) is None for n in names)
    with rec.span("run", steps=3, profile=False, global_batch=1):
        for g in range(3):
            with rec.span("step", gstep=g):
                pass
    assert cat.read_layer_metric("attention_ms", record) == pytest.approx(200.0)
    assert cat.read_layer_metric("indexer_ms", record) == pytest.approx(100.0)
    flops = 3.5 * 2 * macs["sparse_attn"]
    assert cat.read_layer_metric("sparse_attention_roofline_pct", record) == (
        pytest.approx(100 * flops / 197e12 / 0.2))
    backward = 2 * 31_458_304 * 16 * 64 * 4
    assert cat.read_layer_metric("indexer_roofline_pct", record) == (
        pytest.approx(100 * 2 * (macs["indexer_scores"] + backward) / 197e12 / 0.1))
    assert cat.read_layer_metric("attention_ms", {"trace": None}) is None
    assert cat.read_layer_metric("indexer_roofline_pct",
                                 {"trace": None, "peaks": {}}) is None
