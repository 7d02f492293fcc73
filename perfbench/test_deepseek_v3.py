"""The Kanana-2-30B-A3B configuration against its plain reference, on the CPU:
the real widths at 512 tokens through ``harness.reference_check`` in bf16,
and seven planted faults, each of which must fall outside the tolerance
(on weights whose queries are sharpened for the attention's faults to show
against: ``plant_sharper_queries``).
(512 tokens, as perfbench/test_lfm2.py and for its reason: a token whose
router gives it another expert than the reference's is one of few.)  The
chip's readings of the same faults at the timed sizes are in the
configuration's file; ``check(...)`` is what read them.

    JAX_PLATFORMS=cpu python -m pytest perfbench/test_deepseek_v3.py -q
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.catalog import Catalog
from perfbench.test_lfm2 import _projections_in_fp8

CELL = "kanana_2_30b_a3b.seq8192.train"
SEQ = 512


def _setup(layers=None, seq=SEQ, batch=1, seed=3):
    """The cell's configuration at ``seq`` tokens and ``batch`` sequences;
    ``layers`` cuts it further (the wrong layers are looked for in the first
    three: the dense layer and two expert layers)."""
    from mpi4dl_tpu.config import config_from_args, get_parser
    from mpi4dl_tpu.models import build_model

    cell = Catalog().cell(CELL)
    argv = cell.argv(seed)
    argv[argv.index("--seq-len") + 1] = str(seq)
    argv[argv.index("--batch-size") + 1] = str(batch)
    if layers is not None:
        argv[argv.index("--num-layers") + 1] = str(layers)
        cell.config["sizes"]["num_layers"] = layers
    cfg = config_from_args(get_parser().parse_args(argv))
    params, _ = build_model(cfg).init(jax.random.key(cfg.seed))
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    return cell, cfg, params, ids[:, :-1], ids[:, 1:]


def test_the_file_states_the_published_widths_and_the_cut():
    from mpi4dl_tpu.models import deepseek_v3, lfm2

    config = Catalog().cell(CELL).config
    published = deepseek_v3.PUBLISHED
    row = dataclasses.asdict(published)
    cut = {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16032}
    for key, value in row.items():
        assert config[key] == cut.get(key, value), key
    assert (config["hidden_size"], config["num_attention_heads"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["kv_lora_rank"]) == (
                2048, 32, 128, 64, 128, 512)
    assert (config["intermediate_size"], config["moe_intermediate_size"],
            config["n_shared_experts"], config["num_experts_per_tok"],
            config["routed_scaling_factor"], config["rope_theta"],
            config["rms_norm_eps"]) == (6144, 768, 2, 6, 2.448, 1000000, 1e-6)
    assert config["reduced"] == list(cut)
    assert config["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 128, "vocab_size": 128256}
    assert config["published"] == {key: row[key] for key in cut}
    sizes = config["sizes"]
    run = lfm2.layers_run(published, sizes["num_layers"])
    assert run == (0, 1, 2, 3, 4)  # layer 0, the dense one, is there once
    assert sizes["dense_layers"] == sum(
        i < published.first_k_dense_replace for i in run) == 1
    assert sizes["n_routed_experts"] == config["n_routed_experts"]
    assert sizes["n_routed_experts_published"] == row["n_routed_experts"]
    assert sizes["vocab_size"] == config["vocab_size"]
    assert sizes["vocab_size"] * 8 == row["vocab_size"]  # an eighth, exactly
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "n_shared_experts", "num_attention_heads", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                "rms_norm_eps", "rope_theta", "num_experts_per_tok",
                "routed_scaling_factor"):
        assert sizes[key] == row[key], key
    flags = dict(zip(config["argv"][::2], config["argv"][1::2]))
    assert (flags["--model"], flags["--num-layers"], flags["--vocab-size"],
            flags["--experts-held"], flags["--expert-first"]) == (
                "deepseek_v3", "5", "16032", "16", "0")


def _metric(name):
    cat = Catalog()
    with open(os.path.join(cat.bench_dir, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_rooflines_count_the_configurations_layers():
    """The layer counts and widths in the two roofline metrics' files are the
    configuration's (the recorder's site counts are not the step's: the
    reference check builds a second model in the same process)."""
    sizes = Catalog().cell(CELL).config["sizes"]
    experts = _metric("mla_expert_ffn_roofline_pct")["params"]
    assert experts["work"] == "experts"
    assert experts["params"] == {
        "hidden": sizes["hidden_size"], "ffn": sizes["moe_intermediate_size"],
        "held": sizes["n_routed_experts"],
        "layers": sizes["num_layers"] - sizes["dense_layers"]}
    attention = _metric("mla_attention_roofline_pct")["params"]
    assert attention["params"] == {
        "seq_len": 8192, "heads": sizes["num_attention_heads"],
        "qk_head_dim": sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        "v_head_dim": sizes["v_head_dim"], "layers": sizes["num_layers"]}
    assert attention["pattern"] == _metric("mla_attention_ms")["params"]["pattern"]


def test_bf16_cells_pass_at_the_real_widths():
    """All seven cells in bf16, each fed the reference's activation, inside
    the chip's tolerance, and the counted products by kind."""
    cell, cfg, params, x, y = _setup()
    tol = cell.config["tolerances"]["cell"]["value"]
    good = harness.reference_check(cell, cfg, params, x, y)
    assert len(good["cell_rel_err"]) == 7
    assert 1e-4 < good["cell_rel_err_max"] < tol
    assert abs(good["reference_loss"] - np.log(cfg.vocab_size)) < 0.5
    kinds = good["forward_macs_per_img_by_kind"]
    assert set(kinds) == {"dense", "attn_scores", "router", "experts",
                          "shared_expert"}
    assert kinds["experts"] == SEQ * 6 * 16 // 128 * 3 * 2048 * 768 * 4
    assert kinds["shared_expert"] == SEQ * 3 * 2048 * 1536 * 4
    assert kinds["attn_scores"] == 5 * 32 * (SEQ * (SEQ + 1) // 2) * (192 + 128)
    assert kinds["router"] == 4 * SEQ * 2048 * 128


def test_the_stored_flops_are_the_references_count_at_8192():
    """``model_flops_per_img`` at the traffic's size, from shapes alone."""
    from mpi4dl_tpu.models import deepseek_v3
    from perfbench.references.plain import Tally, model_flops

    cell = Catalog().cell(CELL)
    model = deepseek_v3.deepseek_v3((4, 8192), num_layers=5, vocab_size=16032,
                                    experts_held=16)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.key(0))
    tally = Tally()

    def walk(p, x):
        for fn in cell.reference_cells()(p, cell.config["sizes"], tally):
            x = fn(x)
        return x

    jax.eval_shape(walk, shapes, cell.batch_spec()[0])
    assert model_flops(tally.macs) // 4 == cell.stored_model_flops()
    assert tally.by_kind["attn_scores"] / tally.macs == pytest.approx(0.45, abs=0.01)


# --- planted faults: each a reference with one thing wrong -----------------------


def _no_rotary_on_the_shared_key(ref):
    return "key_rotary", lambda k_pe, theta: k_pe


def _scale_of_the_nope_width(ref):
    return "attention_scale", lambda sizes: sizes["qk_nope_head_dim"] ** -0.5


def _no_norm_on_the_compressed_row(ref):
    return "compressed_norm", lambda c, p, eps: c


def _no_shared_expert(ref):
    return "shared", lambda h, p, sizes, tally: jnp.zeros_like(h)


def _scaling_of_one(ref):
    route = ref.route
    return "route", lambda h, p, sizes: route(
        h, p, {**sizes, "routed_scaling_factor": 1.0})


def _softmax_for_sigmoid(ref):
    def route(h, p, sizes):
        s = jax.nn.softmax(jnp.dot(h, p["kernel"].astype(jnp.float32),
                                   precision=ref.HI), axis=-1)
        chosen = jnp.argsort(-(s + p["bias"]), axis=-1,
                             stable=True)[..., :sizes["num_experts_per_tok"]]
        w = jnp.take_along_axis(s, chosen, axis=-1)
        return chosen, (w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
                        * sizes["routed_scaling_factor"])
    return "route", route


FAULTS = [_no_rotary_on_the_shared_key, _scale_of_the_nope_width,
          _no_norm_on_the_compressed_row, _no_shared_expert, _scaling_of_one,
          _softmax_for_sigmoid, _projections_in_fp8]


QUERY_PLANT = 4.0


def plant_sharper_queries(params):
    """Queries for the wrong attention layers to show against: under the
    configuration's own random weights the scores are a third of a unit
    apart and the softmax is near uniform, where a wrong scale or a key left
    unrotated changes little (on the chip 1.2 and 1.5 times a good run).
    ``q_proj`` times four makes the scores differ by more than a unit, as a
    trained layer's do; a good run reads the same with it."""
    for p in params[1:-1]:
        p["op"]["q_proj"] = {"kernel": p["op"]["q_proj"]["kernel"] * QUERY_PLANT}


def check(fault=None, plant=True, **setup):
    """``harness.reference_check`` of the program against the reference, the
    reference with ``fault`` planted where one is given, on weights with the
    sharper queries planted unless ``plant`` is false: the worst cell's
    relative L2 error and the (wrong) reference's loss.  The chip's readings
    at the timed sizes were made with this (``seq=8192, batch=4``)."""
    cell, cfg, params, x, y = _setup(**setup)
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
    return check(layers=3)[1]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_layer_fails_the_cell_check(fault, good):
    """A reference with one layer wrong, or computed a precision lower, is
    outside the configuration's tolerance (the chip's readings at the timed
    sizes are in the configuration's file)."""
    cell, bad = check(fault, layers=3)
    tol = cell.config["tolerances"]["cell"]["value"]
    assert good["cell_rel_err_max"] < tol
    assert bad["cell_rel_err_max"] > max(1.25 * tol,
                                         1.5 * good["cell_rel_err_max"])


def test_the_new_reader_on_a_made_up_record(monkeypatch):
    """``latent_attention_roofline`` and the patterns of the three new
    metrics on a recorder and a trace made by hand; and nothing (no metric on
    the line) from a program that counts none of it."""
    import mpi4dl_tpu.obs.spans as spans

    rec = spans.Recorder(annotate=False)
    monkeypatch.setattr(spans, "_RECORDER", rec)
    cat = Catalog()
    record = {
        "spans": {"dispatch": [1.0] * 3},
        "trace": {"periods": 2, "op_seconds": {
            "ragged-dot-none:bf16[30720,768]": 0.030,
            "ragged-dot-none:bf16[16,2048,768]": 0.028,
            "ragged-dot-metadata:s32[17]": 0.002,
            "block_flash_fwd:f32[32,8192,128]": 0.300,
            "fusion:bf16[32,1024,512]": 0.200,
            "fusion:f32[32,512,192]": 0.150, "fusion:f32[32,1024,192]": 0.100,
            "fusion:f32[32,512,128]": 0.050, "cond:f32[32,512,192]": 0.700,
            "slice-done:f32[8,1024,192]": 0.020, "slice-done:f32[1024,2048]": 0.3,
            "fusion:bf16[4,8192,2048]": 9.0}},
        "model": {"forward_macs_per_img": {"attn_scores": 17 * 10**11}},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    }
    names = ("mla_attention_roofline_pct", "mla_expert_ffn_roofline_pct")
    with rec.span("run", steps=3, profile=False):  # a program that counts nothing
        for g in range(3):
            with rec.span("step", gstep=g):
                pass
    assert all(cat.read_layer_metric(n, record) is None for n in names)
    with rec.span("run", steps=3, profile=False, global_batch=4):
        for g in range(3):
            with rec.span("step", gstep=g) as step:
                step.set(expert_rows=98000.0 + g, expert_assignments=786432.0,
                         expert_load_max_over_mean=1.3)
    assert cat.read_layer_metric("mla_attention_ms", record) == pytest.approx(410.0)
    assert cat.read_layer_metric("expert_ffn_ms", record) == pytest.approx(30.0)
    flops = (2 + 832 / 320) * 2 * 17e11 * 4
    assert cat.read_layer_metric("mla_attention_roofline_pct", record) == (
        pytest.approx(100 * flops / 197e12 / 0.410))
    assert cat.read_layer_metric("mla_expert_ffn_roofline_pct", record) == (
        pytest.approx(100 * 4 * 2 * 3 * 2048 * 768 * 98001 / 197e12 / 0.030))
    assert cat.read_layer_metric("expert_rows_held_pct", record) == pytest.approx(
        100 * 98001 / 786432)
    assert cat.read_layer_metric("mla_attention_ms", {"trace": None}) is None
    assert cat.read_layer_metric("mla_attention_roofline_pct",
                                 {"trace": None, "peaks": {}}) is None
    # the bytes: q, k at 192 and v, o at 128 a head, bf16, three passes
    from perfbench.catalog import _load_module
    reader = _load_module(os.path.join(
        cat.bench_dir, "readers", "latent_attention_roofline.py"), "lar_test")
    _, nbytes = reader.latent_attention_work(
        record, seq_len=8192, heads=32, qk_head_dim=192, v_head_dim=128, layers=5)
    assert nbytes == 3 * 2 * 4 * 5 * 8192 * 32 * 2 * (192 + 128)
    record["peaks"]["hbm_bytes_per_s"] = 1e9  # a chip that the bytes bound
    assert cat.read_layer_metric("mla_attention_roofline_pct", record) == (
        pytest.approx(100 * nbytes / 1e9 / 0.410))
