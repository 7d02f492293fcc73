"""The Ouro-2.6B configuration against its plain reference, on the CPU: the
real widths at 512 tokens through ``harness.reference_check`` in bf16, and
the planted faults, each of which must fall outside the tolerance.  The
chip's readings at the timed sizes are in the configuration's file;
``check(...)`` is what read them.

    JAX_PLATFORMS=cpu python -m pytest perfbench/test_ouro.py -q
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

CELL = "ouro_2_6b.seq8192.bs1.train"
SEQ = 512
# the wrong layers are looked for in the first two layers, over all passes
LAYERS = 2


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
        cell.config["sizes"]["num_layers"] = layers
    cfg = config_from_args(get_parser().parse_args(argv))
    model = build_model(cfg)
    params = model.per_cell(model.init(jax.random.key(cfg.seed))[0])
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    return cell, cfg, params, ids[:, :-1], ids[:, 1:]


def test_the_file_states_the_published_sizes_and_the_cut():
    from mpi4dl_tpu.models import ouro

    config = Catalog().cell(CELL).config
    row = dataclasses.asdict(ouro.PUBLISHED)
    row["layer_types"] = list(row["layer_types"])
    not_keys = ("attention_bias", "qk_norm")  # the model has neither
    cut = {"num_hidden_layers": 8}
    for key, value in row.items():
        if key not in not_keys:
            assert config[key] == cut.get(key, value), key
    assert not any(key in config for key in not_keys)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["total_ut_steps"],
            config["rms_norm_eps"], config["rope_theta"],
            config["tie_word_embeddings"], config["vocab_size"]) == (
                2048, 16, 16, 128, 5632, 4, 1e-6, 1000000, False, 49152)
    assert len(config["layer_types"]) == 48  # the published group, whole
    assert config["reduced"] == list(cut)
    assert config["published"] == {"num_hidden_layers": 48}
    sizes = config["sizes"]
    assert sizes["num_layers"] == 8 and sizes["num_hidden_layers_published"] == 48
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "intermediate_size", "rms_norm_eps", "rope_theta",
                "total_ut_steps", "vocab_size"):
        assert sizes[key] == row[key], key
    flags = dict(zip(config["argv"][::2], config["argv"][1::2]))
    assert (flags["--model"], flags["--num-layers"], flags["--vocab-size"]) == (
        "ouro", "8", "49152")
    assert "--total-ut-steps" not in flags  # the published key, not a flag


def _metric(name):
    cat = Catalog()
    with open(os.path.join(cat.bench_dir, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_roofline_counts_every_application_of_the_held_layers():
    sizes = Catalog().cell(CELL).config["sizes"]
    roofline = _metric("ouro_attention_roofline_pct")["params"]
    assert roofline["params"] == {
        "seq_len": 8192, "head_dim": sizes["head_dim"],
        "heads": sizes["num_attention_heads"],
        "kv_heads": sizes["num_key_value_heads"],
        "layers": sizes["num_layers"] * sizes["total_ut_steps"]}
    assert roofline["params"]["layers"] == 32
    assert roofline["scope"] == "attention_core" and "pattern" not in roofline
    assert _metric("ut_loop_ms")["params"] == {"scope": "ut_loop"}


def test_the_stored_flops_are_the_references_count_at_8192():
    """``model_flops_per_img`` at the traffic's size, from shapes alone: the
    32 applications of the 8 layers each counted."""
    from mpi4dl_tpu.models import ouro
    from perfbench.references.plain import Tally, model_flops

    cell = Catalog().cell(CELL)
    model = ouro.ouro((1, 8192), num_layers=8, vocab_size=49152)
    shapes = jax.eval_shape(
        lambda k: model.per_cell(model.init(k)[0]), jax.random.key(0))
    tally = Tally()

    def walk(p, x):
        for fn in cell.reference_cells()(p, cell.config["sizes"], tally):
            x = fn(x)
        return x

    jax.eval_shape(walk, shapes, cell.batch_spec()[0])
    assert model_flops(tally.macs) == cell.stored_model_flops()
    per_token = {k: v // 8192 for k, v in tally.by_kind.items()}
    assert per_token["attn_proj"] == 32 * 4 * 2048 * 2048
    assert per_token["mlp"] == 32 * 3 * 2048 * 5632
    assert per_token["head"] == 2048 * 49152
    assert tally.by_kind["attn_scores"] == 32 * 16 * (8192 * 8193 // 2) * 256
    loop = tally.macs - tally.by_kind["head"]
    assert round(100 * loop / tally.macs, 1) == 95.6


# --- planted faults: each a reference with one thing wrong -------------------


def _no_post_norm(ref):
    return "post_norm", lambda y, p, eps: y


def _a_pass_without_the_final_norm(ref):
    rms = ref.rms_norm
    return "pass_norm", lambda x, p, eps, t: x if t == 0 else rms(x, p, eps)


def _three_passes(ref):
    return "passes", lambda sizes: sizes["total_ut_steps"] - 1


def _projections_in_fp8(ref):
    """The nearest precision below the configuration's bf16: every
    projection's operands, the head's among them, rounded to float8 (e4m3)."""
    f8 = jnp.float8_e4m3fn

    def linear(x, p, tally, kind):
        k = p["kernel"].astype(f8).astype(jnp.float32)
        if tally is not None:
            tally.add(kind, x.size // x.shape[-1] * k.shape[0] * k.shape[1])
        return jnp.dot(x.astype(f8).astype(jnp.float32), k, precision=ref.HI)

    return "linear", linear


FAULTS = [_no_post_norm, _a_pass_without_the_final_norm, _three_passes,
          _projections_in_fp8]


def check(fault=None, **setup):
    """``harness.reference_check`` of the program against the reference, the
    reference with ``fault`` planted where one is given, on the
    configuration's own weights: the worst cell's relative L2 error, each
    cell's, and the (wrong) reference's loss.  The chip's readings at the
    timed sizes were made with this (``seq=8192``)."""
    cell, cfg, params, x, y = _setup(**setup)
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
    return check(layers=LAYERS)[1]


def test_bf16_cells_pass_at_the_real_widths(good):
    """The embedding, two layers and RMS_f in each of four passes, and the
    head, in bf16, each fed the reference's activation, inside the chip's
    tolerance, and the counted products by kind."""
    cell = Catalog().cell(CELL)
    tol = cell.config["tolerances"]["cell"]["value"]
    assert len(good["cell_rel_err"]) == 2 + 4 * (LAYERS + 1)
    assert 1e-4 < good["cell_rel_err_max"] < tol
    assert abs(good["reference_loss"] - np.log(49152)) < 1.0
    kinds = good["forward_macs_per_img_by_kind"]
    assert set(kinds) == {"attn_proj", "mlp", "attn_scores", "head"}
    assert kinds["attn_scores"] == 4 * LAYERS * 16 * (SEQ * (SEQ + 1) // 2) * 256


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_layer_fails_the_cell_check(fault, good):
    """A reference with one thing wrong, or computed a precision lower, is
    outside the configuration's cell limit."""
    cell, bad = check(fault, layers=LAYERS)
    limit = cell.config["tolerances"]["cell"]["value"]
    assert not bad["cell_rel_err_max"] <= limit, bad["cell_rel_err"]
