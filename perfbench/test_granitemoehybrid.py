"""The granite-4.0-h-micro configuration against its plain reference, on the
CPU: the real widths at 512 tokens through ``harness.reference_check`` in
bf16, and the planted faults, each of which must fall outside the tolerance.
The chip's readings of the same faults at the timed sizes are in the
configuration's file; ``check(...)`` is what read them.

    JAX_PLATFORMS=cpu python -m pytest perfbench/test_granitemoehybrid.py -q
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

CELL = "granite_4_0_h_micro.seq8192.bs2.train"
SEQ = 512
# the wrong layers are looked for in the first six: five state-space layers
# and the attention layer at its published place
LAYERS = 6


def _setup(layers=None, seq=SEQ, batch=1, seed=3):
    """The cell's configuration at ``seq`` tokens and ``batch`` sequences;
    ``layers`` cuts it further."""
    from mpi4dl_tpu.config import config_from_args, get_parser
    from mpi4dl_tpu.models import build_model

    cell = Catalog().cell(CELL)
    argv = cell.argv(seed)
    argv[argv.index("--seq-len") + 1] = str(seq)
    argv[argv.index("--batch-size") + 1] = str(batch)
    if layers is not None:
        argv[argv.index("--num-layers") + 1] = str(layers)
        sizes = cell.config["sizes"]
        sizes["num_layers"] = layers
        sizes["layer_types"] = sizes["layer_types"][:layers]
    cfg = config_from_args(get_parser().parse_args(argv))
    model = build_model(cfg)
    params = model.per_cell(model.init(jax.random.key(cfg.seed))[0])
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    return cell, cfg, params, ids[:, :-1], ids[:, 1:]


def test_the_file_states_the_published_sizes_and_the_cut():
    from mpi4dl_tpu.models import granitemoehybrid, lfm2

    config = Catalog().cell(CELL).config
    published = granitemoehybrid.PUBLISHED
    row = dataclasses.asdict(published)
    row["layer_types"] = list(row["layer_types"])
    cut = {"num_hidden_layers": 10, "vocab_size": 25088}
    for key, value in row.items():
        assert config[key] == cut.get(key, value), key
    assert (config["hidden_size"], config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"], config["mamba_n_groups"],
            config["mamba_d_conv"], config["mamba_conv_bias"],
            config["mamba_chunk_size"]) == (2048, 64, 64, 128, 1, 4, True, 256)
    assert (config["num_attention_heads"], config["num_key_value_heads"],
            config["position_embedding_type"], config["shared_intermediate_size"],
            config["embedding_multiplier"], config["residual_multiplier"],
            config["attention_multiplier"], config["logits_scaling"],
            config["rms_norm_eps"], config["tie_word_embeddings"]) == (
                32, 8, "nope", 8192, 12, 0.22, 0.015625, 8, 1e-5, True)
    assert config["reduced"] == list(cut)
    assert config["published"] == {"num_hidden_layers": 40, "vocab_size": 100352}
    assert config["published"] == {key: row[key] for key in cut}
    sizes = config["sizes"]
    run = lfm2.layers_run(published, sizes["num_layers"])
    assert run == tuple(range(10))  # one whole period, from layer 0
    assert sizes["layer_types"] == [published.layer_types[i] for i in run]
    assert sizes["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert sizes["vocab_size"] == config["vocab_size"]
    assert sizes["vocab_size"] * 4 == row["vocab_size"]  # a quarter, exactly
    for key in ("hidden_size", "shared_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "attention_multiplier",
                "embedding_multiplier", "residual_multiplier", "logits_scaling",
                "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size"):
        assert sizes[key] == row[key], key
    flags = dict(zip(config["argv"][::2], config["argv"][1::2]))
    assert (flags["--model"], flags["--num-layers"], flags["--vocab-size"]) == (
        "granitemoehybrid", "10", "25088")
    assumed = config["assumed"]
    assert (granitemoehybrid.DT_RANGE, granitemoehybrid.A_RANGE) == (
        tuple(assumed["scan_initial_values"]["dt_range"]),
        tuple(assumed["scan_initial_values"]["A_range"]))


def _metric(name):
    cat = Catalog()
    with open(os.path.join(cat.bench_dir, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_roofline_counts_the_configurations_state_space_layers():
    sizes = Catalog().cell(CELL).config["sizes"]
    roofline = _metric("ssm_scan_roofline_pct")["params"]
    assert roofline["params"] == {
        "seq_len": 8192, "heads": sizes["mamba_n_heads"],
        "head_dim": sizes["mamba_d_head"], "state": sizes["mamba_d_state"],
        "layers": sizes["layer_types"].count("mamba")}
    assert roofline["params"]["layers"] == 9
    assert roofline["pattern"] == _metric("ssm_scan_ms")["params"]["pattern"]


def test_the_stored_flops_are_the_references_count_at_8192():
    """``model_flops_per_img`` at the traffic's size, from shapes alone."""
    from mpi4dl_tpu.models import granitemoehybrid
    from perfbench.references.plain import Tally, model_flops

    cell = Catalog().cell(CELL)
    model = granitemoehybrid.granitemoehybrid(
        (2, 8192), num_layers=10, vocab_size=25088)
    shapes = jax.eval_shape(
        lambda k: model.per_cell(model.init(k)[0]), jax.random.key(0))
    tally = Tally()

    def walk(p, x):
        for fn in cell.reference_cells()(p, cell.config["sizes"], tally):
            x = fn(x)
        return x

    jax.eval_shape(walk, shapes, cell.batch_spec()[0])
    assert model_flops(tally.macs) // 2 == cell.stored_model_flops()
    per_token = {k: v // (2 * 8192) for k, v in tally.by_kind.items()}
    assert per_token["ssm_scan"] == 9 * 2 * 128 * 64 * 64
    assert per_token["conv"] == 9 * 4 * 4352
    assert per_token["head"] == 2048 * 25088
    assert per_token["dense"] == 9 * (25821184 + 50331648) + 60817408
    assert tally.by_kind["attn_scores"] // 2 == 32 * (8192 * 8193 // 2) * 128


# --- planted faults: each a reference with one thing wrong -------------------


def _state_not_carried(ref):
    return "scan", lambda x, dt, a, b, c: ref.recurrence(
        x, dt, a, b, c, restart_every=256)


def _no_skip(ref):
    return "skip", lambda y, x, p: y


def _no_conv_bias(ref):
    return "conv_bias", lambda y, p: y


def _gate_after_the_norm(ref):
    return "gate_and_norm", lambda y, z, p, eps: (
        ref.rms_norm(y, p["norm"], eps) * ref.silu(z))


def _dt_without_softplus(ref):
    return "step_size", lambda dt, p: dt + p["dt_bias"].astype(jnp.float32)


def _residual_multiplier_of_one(ref):
    return "residual_multiplier", lambda sizes: 1.0


def _embedding_multiplier_of_one(ref):
    return "embedding_multiplier", lambda sizes: 1.0


def _no_logits_scaling(ref):
    return "logits_scaling", lambda sizes: 1.0


def _scale_of_the_square_root(ref):
    return "attention_scale", lambda sizes: (
        sizes["hidden_size"] // sizes["num_attention_heads"]) ** -0.5


def _rotary_on_q_and_k(ref):
    def rotate(x, theta=10000.0):
        s, hd = x.shape[1], x.shape[3]
        inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        a, b = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return "positions", lambda q, k: (rotate(q), rotate(k))


def _a_head_of_its_own(ref):
    return "head_table", lambda params: 0.02 * jax.random.normal(
        jax.random.key(7), params[0]["table"].shape, jnp.float32)


def _projections_in_fp8(ref):
    """The nearest precision below the configuration's bf16: every
    projection's operands, the head's among them, rounded to float8 (e4m3).
    Two names: ``check`` plants a list."""
    f8 = jnp.float8_e4m3fn

    def linear(x, p, tally):
        k = p["kernel"].astype(f8).astype(jnp.float32)
        if tally is not None:
            tally.add("dense", x.size // x.shape[-1] * k.shape[0] * k.shape[1])
        return jnp.dot(x.astype(f8).astype(jnp.float32), k, precision=ref.HI)

    def head_product(x, table):
        return jnp.dot(x.astype(f8).astype(jnp.float32),
                       table.astype(f8).astype(jnp.float32).T, precision=ref.HI)

    return ["linear", "head_product"], [linear, head_product]


# read on sharper queries (``plant_sharper_queries``), where the others are
# read on the configuration's own weights
ATTENTION_FAULTS = (_scale_of_the_square_root, _rotary_on_q_and_k)
FAULTS = [_state_not_carried, _no_skip, _no_conv_bias, _gate_after_the_norm,
          _dt_without_softplus, _residual_multiplier_of_one,
          _embedding_multiplier_of_one, _no_logits_scaling,
          _scale_of_the_square_root, _rotary_on_q_and_k, _a_head_of_its_own,
          _projections_in_fp8]

QUERY_PLANT = 32.0


def plant_sharper_queries(params):
    """Queries for a wrong attention layer to show against: under the
    configuration's own random weights the scores (q . k over 64 columns,
    times 1/64) are a twentieth of a unit apart and the softmax is uniform,
    where a wrong scale or a rotary embedding changes nothing that a
    comparison can read.  ``q_proj`` times 32 makes the scores differ by
    more than a unit, as a trained layer's do (PR 33 read its attention's
    faults so); the state-space layers have no ``q_proj`` and read as they
    did."""
    for p in params[1:-1]:
        if "q_proj" in p["op"]:
            p["op"]["q_proj"] = {"kernel": p["op"]["q_proj"]["kernel"] * QUERY_PLANT}


def check(fault=None, plant=False, **setup):
    """``harness.reference_check`` of the program against the reference, the
    reference with ``fault`` planted where one is given, on the
    configuration's own weights, or, ``plant``, with the sharper queries:
    the worst cell's relative L2 error and the (wrong) reference's loss.  The
    chip's readings at the timed sizes were made with this (``seq=8192,
    batch=2``)."""
    cell, cfg, params, x, y = _setup(**setup)
    if plant:
        plant_sharper_queries(params)
    ref = cell.reference()
    names, wrongs = fault(ref) if fault else ([], [])
    if isinstance(names, str):
        names, wrongs = [names], [wrongs]
    rights = [getattr(ref, name) for name in names]
    for name, wrong in zip(names, wrongs):
        setattr(ref, name, wrong)
    try:
        return cell, harness.reference_check(cell, cfg, params, x, y)
    finally:
        for name, right in zip(names, rights):
            setattr(ref, name, right)


def failed(cell, result, first_loss=None):
    """Whether a check's reading fails ``correct`` by one of the cell's
    limits, and by which."""
    tol = cell.config["tolerances"]
    by = []
    worst = result["cell_rel_err_max"]
    if not np.isfinite(worst) or worst > tol["cell"]["value"]:
        by.append("cell")
    if first_loss is not None:
        off = abs(first_loss - result["reference_loss"]) / abs(result["reference_loss"])
        if not np.isfinite(off) or off > tol["loss"]["value"]:
            by.append("loss")
    return by


@pytest.fixture(scope="module")
def good():
    return check(layers=LAYERS)[1]


def test_bf16_cells_pass_at_the_real_widths(good):
    """The embedding, six layers and the head in bf16, each fed the
    reference's activation, inside the chip's tolerance, and the counted
    products by kind."""
    cell = Catalog().cell(CELL)
    tol = cell.config["tolerances"]["cell"]["value"]
    assert len(good["cell_rel_err"]) == LAYERS + 2
    assert 1e-4 < good["cell_rel_err_max"] < tol
    assert abs(good["reference_loss"] - np.log(25088)) < 0.5
    kinds = good["forward_macs_per_img_by_kind"]
    assert set(kinds) == {"dense", "conv", "ssm_scan", "attn_scores", "head"}
    assert kinds["ssm_scan"] == SEQ * 5 * 2 * 128 * 64 * 64
    assert kinds["attn_scores"] == 32 * (SEQ * (SEQ + 1) // 2) * 128


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_layer_fails_the_cell_check(fault, good):
    """A reference with one thing wrong, or computed a precision lower, is
    outside the configuration's tolerances: the cell limit, or, where the
    reference's state overflows, the loss's (the chip's readings at the
    timed sizes are in the configuration's file).  The attention's two are
    read on sharper queries."""
    plant = fault in ATTENTION_FAULTS
    cell, bad = check(fault, plant=plant, layers=LAYERS)
    assert failed(cell, bad, first_loss=good["reference_loss"])
    if plant:  # a good run reads with the sharper queries as without
        assert not failed(cell, check(plant=True, layers=LAYERS)[1])


def test_the_new_reader_on_a_made_up_record(monkeypatch):
    """``ssm_scan_roofline`` and the patterns of the new metrics on a
    recorder and a trace made by hand; and nothing (no metric on the line)
    from a program that counts none of it."""
    import mpi4dl_tpu.obs.spans as spans

    rec = spans.Recorder(annotate=False)
    monkeypatch.setattr(spans, "_RECORDER", rec)
    cat = Catalog()
    macs = 9 * 8192 * 2 * 128 * 64 * 64
    record = {
        "spans": {"dispatch": [1.0] * 3},
        "trace": {"periods": 2, "op_seconds": {
            "fusion:f32[2,32,256,256]": 0.100,
            "convolution_convert_fusion:bf16[2,32,256,64,64]": 0.200,
            "copy-done:bf16[32,2,64,64,128]": 0.050,
            "bitcast_add_fusion:f32[2,64,64,128]": 0.030,
            "reduce-window:f32[2,32,64,2,128]": 0.010,
            "slice_convert_fusion:f32[2,8192,4096]": 0.010,
            # not the scan's: attention's tiles and stacks, the projections,
            # the gate and the norm, the MLP, the head
            "block_flash_fwd:f32[32,8192,128]": 0.300,
            "fusion:bf16[32,1024,512]": 0.200,
            "bitcast_dynamic-update-slice_fusion:f32[2,32,8192,64]": 0.1,
            "slice-done:f32[2,32,1024,64]": 0.1,
            "fusion:bf16[2,8192,4096]": 0.3, "fusion:bf16[2,8192,8512]": 0.3,
            "fusion:bf16[2,8192,8192]": 0.3, "fusion:f32[2,8192,25088]": 9.0}},
        "model": {"forward_macs_per_img": {"ssm_scan": macs}},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    }
    names = ("ssm_scan_roofline_pct", "ssm_carried_share_pct")
    with rec.span("run", steps=3, profile=False):  # a program that counts nothing
        for g in range(3):
            with rec.span("step", gstep=g):
                pass
    assert all(cat.read_layer_metric(n, record) is None for n in names)
    with rec.span("run", steps=3, profile=False, global_batch=2):
        for g in range(3):
            with rec.span("step", gstep=g) as step:
                step.set(ssm_carried_share=0.1 + 0.01 * g)
    assert cat.read_layer_metric("ssm_scan_ms", record) == pytest.approx(200.0)
    assert cat.read_layer_metric("attention_ms", record) == pytest.approx(250.0)
    assert cat.read_layer_metric("ssm_carried_share_pct", record) == (
        pytest.approx(11.0))
    nbytes = 4 * 2 * 2 * 9 * 8192 * (2 * 4096 + 2 * 128 + 64)
    flops = 4 * 2 * macs * 2
    assert flops / 197e12 < nbytes / 819e9  # the bytes bound it
    assert cat.read_layer_metric("ssm_scan_roofline_pct", record) == (
        pytest.approx(100 * nbytes / 819e9 / 0.200))
    record["peaks"]["hbm_bytes_per_s"] = 1e15  # a chip that the FLOPs bound
    assert cat.read_layer_metric("ssm_scan_roofline_pct", record) == (
        pytest.approx(100 * flops / 197e12 / 0.200))
    assert cat.read_layer_metric("ssm_scan_ms", {"trace": None}) is None
    assert cat.read_layer_metric("ssm_scan_roofline_pct",
                                 {"trace": None, "peaks": {}}) is None
