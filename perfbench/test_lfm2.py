"""The LFM2-24B-A2B configuration against its plain reference, on the CPU:
the real widths at 512 tokens through ``harness.reference_check`` in bf16,
and five planted faults, each of which must fall outside the tolerance.
(512 tokens and not fewer: where the program's router, which sees the
activation rounded to bf16, gives one token another expert than the
reference's, that is one token of few; at 128 tokens a good reading swings
up to 0.02, at 512 it reads 0.012 as the chip does at 32,768.)

    JAX_PLATFORMS=cpu python -m pytest perfbench/test_lfm2.py -q
"""

from __future__ import annotations

import os
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.catalog import Catalog

CELL = "lfm2_24b_a2b.seq8192.train"
SEQ = 512


def _setup(layers=None):
    """The cell's configuration at 512 tokens and one sequence; ``layers``
    cuts it further (the wrong layers are looked for in the first three: the
    dense layer, an attention layer and a conv layer with experts)."""
    from mpi4dl_tpu.config import config_from_args, get_parser
    from mpi4dl_tpu.models import build_model

    cell = Catalog().cell(CELL)
    argv = cell.argv(3)
    argv[argv.index("--seq-len") + 1] = str(SEQ)
    argv[argv.index("--batch-size") + 1] = "1"
    if layers is not None:
        argv[argv.index("--num-layers") + 1] = str(layers)
        sizes = cell.config["sizes"]
        sizes["num_layers"] = layers
        sizes["layer_types"] = sizes["layer_types"][:layers]
    cfg = config_from_args(get_parser().parse_args(argv))
    params, _ = build_model(cfg).init(jax.random.key(cfg.seed))
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, SEQ + 1), dtype=np.int32)
    return cell, cfg, params, ids[:, :-1], ids[:, 1:]


def test_the_file_states_the_published_widths_and_the_cut():
    from mpi4dl_tpu.models import lfm2

    config = Catalog().cell(CELL).config
    published = lfm2.PUBLISHED
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "num_experts_per_tok", "norm_eps", "num_dense_layers",
                "max_position_embeddings", "routed_scaling_factor"):
        assert config[key] == getattr(published, key), key
    assert config["rope_parameters"] == dict(published.rope_parameters)
    assert tuple(config["layer_types"]) == published.layer_types
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (9, 8, 8192)
    assert config["published"] == {
        "num_hidden_layers": published.num_hidden_layers,
        "num_experts": published.num_experts, "vocab_size": published.vocab_size}
    sizes = config["sizes"]
    run = lfm2.layers_run(published, sizes["num_layers"])
    assert sizes["layer_types"] == [published.layer_types[i] for i in run]
    assert sizes["dense_layers"] == sum(i < published.num_dense_layers for i in run)
    assert sizes["head_dim"] * sizes["num_attention_heads"] == sizes["hidden_size"]
    assert sizes["num_experts"] == config["num_experts"]
    assert sizes["vocab_size"] == config["vocab_size"]


def test_the_rooflines_count_the_configurations_layers():
    """The layer counts in the two roofline metrics' files are the
    configuration's (the recorder's site counts are not the step's: the
    reference check builds a second model in the same process)."""
    import json
    import os

    cat = Catalog()
    sizes = cat.cell(CELL).config["sizes"]

    def params(name):
        with open(os.path.join(cat.bench_dir, "layer_metrics", name + ".json")) as f:
            return json.load(f)["params"]["params"]

    experts = params("expert_ffn_roofline_pct")
    assert experts["layers"] == sizes["num_layers"] - sizes["dense_layers"]
    assert (experts["hidden"], experts["ffn"], experts["held"]) == (
        sizes["hidden_size"], sizes["moe_intermediate_size"], sizes["num_experts"])
    attention = params("attention_roofline_pct")
    assert attention["layers"] == sizes["layer_types"].count("full_attention")
    assert (attention["heads"], attention["kv_heads"], attention["head_dim"]) == (
        sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"])


def test_bf16_cells_pass_at_the_real_widths():
    """All eleven cells in bf16, each fed the reference's activation, inside
    the chip's tolerance, and the counted products by kind."""
    cell, cfg, params, x, y = _setup()
    tol = cell.config["tolerances"]["cell"]["value"]
    good = harness.reference_check(cell, cfg, params, x, y)
    assert len(good["cell_rel_err"]) == 11
    assert 1e-4 < good["cell_rel_err_max"] < tol
    assert abs(good["reference_loss"] - np.log(cfg.vocab_size)) < 0.5
    kinds = good["forward_macs_per_img_by_kind"]
    assert set(kinds) == {"dense", "conv1d", "attn_scores", "router", "experts"}
    assert kinds["experts"] == SEQ * 4 * 8 // 64 * 3 * 2048 * 1536 * 8
    assert kinds["attn_scores"] == 2 * 2 * 32 * (SEQ * (SEQ + 1) // 2) * 64
    assert kinds["router"] == 8 * SEQ * 2048 * 64


def _router_in_bf16(ref):
    def route(h, p, sizes):
        bf = jnp.bfloat16
        s = jax.nn.sigmoid(jnp.dot(h.astype(bf), p["kernel"].astype(bf)))
        chosen = jnp.argsort(-(s + p["bias"].astype(bf)), axis=-1,
                             stable=True)[..., :sizes["num_experts_per_tok"]]
        w = jnp.take_along_axis(s, chosen, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + bf(1e-6))
        return chosen, w.astype(jnp.float32)
    return "route", route


def _softmax_for_sigmoid(ref):
    def route(h, p, sizes):
        s = jax.nn.softmax(jnp.dot(h, p["kernel"].astype(jnp.float32),
                                   precision=ref.HI), axis=-1)
        chosen = jnp.argsort(-(s + p["bias"]), axis=-1,
                             stable=True)[..., :sizes["num_experts_per_tok"]]
        w = jnp.take_along_axis(s, chosen, axis=-1)
        return chosen, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return "route", route


def _bias_in_the_weights(ref):
    def route(h, p, sizes):
        s = jax.nn.sigmoid(jnp.dot(h, p["kernel"].astype(jnp.float32),
                                   precision=ref.HI)) + p["bias"]
        chosen = jnp.argsort(-s, axis=-1,
                             stable=True)[..., :sizes["num_experts_per_tok"]]
        w = jnp.take_along_axis(s, chosen, axis=-1)  # the bias rides along
        return chosen, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return "route", route


def _centred_convolution(ref):
    def conv(u, p, tally):
        w = p["kernel"].astype(jnp.float32)
        padded = jnp.pad(u, ((0, 0), (1, 1), (0, 0)))
        return sum(w[j] * padded[:, j:j + u.shape[1]] for j in range(3))
    return "causal_conv", conv


def _projections_in_fp8(ref):
    """The nearest precision below the configuration's bf16: every
    projection's operands rounded to float8 (e4m3)."""
    def linear(x, p, tally):
        f8 = jnp.float8_e4m3fn
        k = p["kernel"].astype(f8).astype(jnp.float32)
        if tally is not None:
            tally.add("dense", x.size // x.shape[-1] * k.shape[0] * k.shape[1])
        return jnp.dot(x.astype(f8).astype(jnp.float32), k, precision=ref.HI)
    return "linear", linear


def planted_bias():
    """An expert bias for the wrong layers to show against: the
    configuration's own is zero, where adding it to the weights changes
    nothing.  As large as the scores, so that a weight made of ``s + b``
    is far from one made of ``s``."""
    return -0.55 + 0.1 * jnp.sin(2.3 * jnp.arange(64, dtype=jnp.float32))


FAULTS = [_router_in_bf16, _softmax_for_sigmoid, _bias_in_the_weights,
          _centred_convolution, _projections_in_fp8]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_layer_fails_the_cell_check(fault, monkeypatch):
    """A reference with one layer wrong, or computed a precision lower, is
    outside the configuration's tolerance (the chip's readings at the timed
    sizes are in the configuration's file)."""
    cell, cfg, params, x, y = _setup(layers=3)
    for p in params[2:4]:
        p["ffn"]["router"]["bias"] = planted_bias()
    tol = cell.config["tolerances"]["cell"]["value"]
    good = harness.reference_check(cell, cfg, params, x, y)
    assert good["cell_rel_err_max"] < tol
    name, wrong = fault(cell.reference())
    monkeypatch.setattr(cell.reference(), name, wrong)
    bad = harness.reference_check(cell, cfg, params, x, y)
    assert bad["cell_rel_err_max"] > max(1.25 * tol,
                                         1.5 * good["cell_rel_err_max"])


def test_new_readers_on_a_made_up_record(monkeypatch):
    """The three reader kinds this configuration adds, on a recorder and a
    trace made by hand: rows routed over assignments, the device time of the
    named instructions a period, and work over time over the peak; and
    nothing (no metric on the line) from a program that counts none of it."""
    import mpi4dl_tpu.obs.spans as spans
    from mpi4dl_tpu.models import lfm2
    from mpi4dl_tpu.ops import moe

    rec = spans.Recorder(annotate=False)
    monkeypatch.setattr(spans, "_RECORDER", rec)
    cat = Catalog()
    record = {
        "spans": {"dispatch": [1.0] * 3},
        "trace": {"periods": 2, "op_seconds": {
            "ragged-dot-none:bf16[20480,2048]": 0.039,
            "ragged-dot-none:bf16[8,2048,1536]": 0.020,
            "ragged-dot-metadata:s32[9]": 0.001,
            "block_flash_fwd:f32[32,8192,128]": 0.200,
            "fusion:bf16[32,1024,512]": 0.050, "cond:f32[32,512,64]": 0.500,
            "convert_reduce_fusion:u32[1024,512]": 0.010,
            "fusion:bf16[4,8192,2048]": 9.0}},
        "model": {"forward_macs_per_img": {"attn_scores": 2 * 10**11}},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    }
    names = ("expert_rows_held_pct", "expert_load_max_over_mean",
             "expert_ffn_roofline_pct", "attention_roofline_pct")
    with rec.span("run", steps=3, profile=False):  # the parent's loop
        for g in range(3):
            with rec.span("step", gstep=g):
                pass
    assert all(cat.read_layer_metric(n, record) is None for n in names)
    with rec.span("run", steps=3, profile=False, global_batch=4):
        for g in range(3):
            with rec.span("step", gstep=g) as step:
                step.set(expert_rows=130000.0 + g, expert_assignments=1048576.0,
                         expert_load_max_over_mean=1.4)
    # The sites of two models, as the benchmark's process holds them (the
    # step's and the reference check's): the readers count the layers of the
    # metric's file, not these.
    for i in range(16):
        rec.note_site("experts", moe.RoutedExperts(8, 8, 64, 4, 8), "ragged_dot")
    for i in range(4):
        rec.note_site("attention", lfm2.Attention(8, 2, 1, 4, 1e6, 1e-5),
                      "block_flash")
    assert cat.read_layer_metric("expert_rows_held_pct", record) == pytest.approx(
        100 * 130001 / 1048576)
    assert cat.read_layer_metric("expert_load_max_over_mean", record) == 1.4
    assert cat.read_layer_metric("expert_ffn_ms", record) == pytest.approx(30.0)
    assert cat.read_layer_metric("attention_ms", record) == pytest.approx(130.0)
    flops = 4 * 2 * 3 * 2048 * 1536 * 130001
    assert cat.read_layer_metric("expert_ffn_roofline_pct", record) == (
        pytest.approx(100 * flops / 197e12 / 0.030))
    assert cat.read_layer_metric("attention_roofline_pct", record) == (
        pytest.approx(100 * 4.5 * 2 * 2e11 * 4 / 197e12 / 0.130))
    assert cat.read_layer_metric("expert_ffn_ms", {"trace": None}) is None
    # The bytes: each row's operands and results and the 8 layers' held
    # weights, bf16, four passes.
    from perfbench.catalog import _load_module
    reader = _load_module(
        os.path.join(cat.bench_dir, "readers", "kernel_roofline.py"), "kr_test")
    _, nbytes_experts = reader.experts_work(record, hidden=2048, ffn=1536, held=8,
                                            layers=8)
    assert nbytes_experts == 4 * 2 * (130001 * 3 * (2048 + 1536) + 8 * 3 * 8 * 2048 * 1536)
    _, nbytes = reader.attention_work(record, seq_len=8192, head_dim=64, heads=32,
                                      kv_heads=8, layers=2)
    assert nbytes == 3 * 2 * 4 * 2 * 8192 * 64 * 2 * (32 + 8)
    record["peaks"]["hbm_bytes_per_s"] = 1e11  # a chip that the bytes bound
    assert cat.read_layer_metric("expert_ffn_roofline_pct", record) == (
        pytest.approx(100 * nbytes_experts / 1e11 / 0.030))
