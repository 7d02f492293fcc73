"""``granitemoehybrid`` (granite-4.0-h-micro) as a token ``CellModel``: the
chunked scan against the recurrence, the program against the benchmark's plain
reference (perfbench/references/granitemoehybrid.py, which shares no code with
it and whose state-space layer is the recurrence) at small widths on the CPU,
the table that the embedding and the head share, the absence of any position
signal, and the path through ``build_train`` and ``run_supervised``."""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.config import config_from_args, get_parser
from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.models import build_model, lfm2
from mpi4dl_tpu.ops.ssd import ssd_chunked
from mpi4dl_tpu.train import Optimizer, TrainState, cross_entropy, make_train_step

import mpi4dl_tpu.models.granitemoehybrid as gmh
from test_lfm2 import _batch, _close, _first_losses

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# hidden 64, 4 Mamba heads of 32 (expand 2), state 8, chunks of 8, 4 taps,
# 4 query heads over 2 key-value heads of 16, an MLP of 96, 128 ids
TINY = dataclasses.replace(
    gmh.PUBLISHED, hidden_size=64, mamba_n_heads=4, mamba_d_head=32,
    mamba_d_state=8, mamba_chunk_size=8, num_attention_heads=4,
    num_key_value_heads=2, shared_intermediate_size=96, intermediate_size=96,
    vocab_size=128, layer_types=("mamba", "attention", "mamba"),
    num_hidden_layers=3)
CTX = ApplyCtx(train=True)
VOCAB, BATCH, SEQ = 128, 2, 16


def _reference():
    path = os.path.join(ROOT, "perfbench", "references", "granitemoehybrid.py")
    spec = importlib.util.spec_from_file_location(
        "reference_granitemoehybrid", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def ssd_recurrence(x, dt, a, b, c, d):
    """The recurrence itself, position by position in float32, with its
    ``D x`` skip: the reference's, which shares nothing with the chunks."""
    return REF.recurrence(x, dt, a, b, c) + d[:, None] * x


def _sizes(config, num_layers, vocab):
    """What the configuration's file states for the reference, for ``config``."""
    run = lfm2.layers_run(config, num_layers)
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "attention_multiplier", "embedding_multiplier",
            "residual_multiplier", "logits_scaling", "rms_norm_eps",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
            "mamba_d_conv", "mamba_chunk_size")
    return {"num_layers": num_layers, "vocab_size": vocab,
            "layer_types": [config.layer_types[i] for i in run],
            **{k: getattr(config, k) for k in keys}}


@pytest.fixture
def tiny(monkeypatch):
    """The published config at toy widths, for what builds from flags."""
    monkeypatch.setattr(gmh, "PUBLISHED", TINY)
    return TINY


def _model(num_layers=3, vocab=VOCAB, batch=BATCH, seq=SEQ, config=TINY):
    model = gmh.granitemoehybrid((batch, seq), num_layers=num_layers,
                                 vocab_size=vocab, config=config)
    params, _ = model.init(jax.random.key(3))
    return model, params, _sizes(config, num_layers, vocab)


def _ids(seq=SEQ):
    return _batch(vocab=VOCAB, batch=BATCH, seq=seq)


# --- the chunked scan against the recurrence ------------------------------------


def _scan_inputs(seq, seed=0):
    """Decays slow enough that a tenth and more of the output comes through
    the state carried into a chunk."""
    k = jax.random.split(jax.random.key(seed), 6)
    b, h, p, n = 2, 4, 16, 8
    x = jax.random.normal(k[0], (b, seq, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, seq, h)) - 3.0)
    a = -jnp.exp(jax.random.uniform(k[2], (h,), minval=-1.0, maxval=1.0))
    return (x, dt, a, jax.random.normal(k[3], (b, seq, n)),
            jax.random.normal(k[4], (b, seq, n)),
            jax.random.uniform(k[5], (h,), minval=0.5, maxval=1.5))


@pytest.mark.parametrize("seq", [32, 40])
@pytest.mark.parametrize("chunk", [4, 8])
def test_the_chunked_scan_is_the_recurrence(seq, chunk):
    args = _scan_inputs(seq)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ssd_recurrence)(*args)
        got, carried = jax.jit(lambda *t: ssd_chunked(
            *t, chunk=chunk, count_carried=True))(*args)
        assert ssd_chunked(*args, chunk=chunk)[1] is None
    _close(got, want, tol=2e-6)
    assert float(carried[0] / carried[1]) > 0.1  # the carried state matters


@pytest.mark.parametrize("chunk", [4, 8])
def test_every_gradient_of_the_chunked_scan_is_the_recurrences(chunk):
    args = _scan_inputs(40, seed=1)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(
            lambda *t: jnp.sum(w * ssd_chunked(*t, chunk=chunk)[0]),
            argnums=range(6)))(*args)
        want = jax.jit(jax.grad(lambda *t: jnp.sum(w * ssd_recurrence(*t)),
                                argnums=range(6)))(*args)
    for g, wnt in zip(got, want):
        assert float(jnp.max(jnp.abs(wnt))) > 0
        _close(g, wnt, tol=1e-5)


def test_the_result_does_not_depend_on_the_chunk():
    args = _scan_inputs(32, seed=2)
    with jax.default_matmul_precision("highest"):
        four = ssd_chunked(*args, chunk=4)[0]
        sixteen = ssd_chunked(*args, chunk=16)[0]
    _close(four, sixteen, tol=2e-6)


def test_a_sequence_the_chunk_does_not_divide_is_refused():
    with pytest.raises(ValueError, match="chunk of 8"):
        ssd_chunked(*_scan_inputs(36), chunk=8)
    with pytest.raises(ValueError, match="mamba_chunk_size 8 does not divide"):
        _model(seq=36)


def test_the_operands_of_the_products_are_the_compute_dtypes():
    x, dt, a, b, c, d = _scan_inputs(32)
    y, carried = ssd_chunked(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
                             c.astype(jnp.bfloat16), d, chunk=8,
                             count_carried=True)
    assert y.dtype == jnp.bfloat16 and carried.dtype == jnp.float32
    want = ssd_recurrence(x, dt, a, b, c, d)
    assert float(jnp.linalg.norm(y.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want)) < 0.02


# --- the program against the reference, float32 ---------------------------------


def test_the_cut_keeps_layer_types_in_order():
    assert lfm2.layers_run(gmh.PUBLISHED, 10) == tuple(range(10))
    assert lfm2.layers_run(gmh.PUBLISHED, 40) == tuple(range(40))
    with pytest.raises(ValueError):
        lfm2.layers_run(gmh.PUBLISHED, 41)
    kinds = gmh.PUBLISHED.layer_types
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [5, 15, 25, 35]
    model, _, _ = _model(num_layers=2)
    assert [c.name for c in model.cells] == [
        "embed", "layer00_mamba", "layer01_attention", "norm_head"]
    assert isinstance(model.cells[1].op, gmh.Mamba2Mixer)
    assert isinstance(model.cells[2].op, lfm2.Attention)
    assert all(isinstance(c.ffn, lfm2.SwiGLU) and c.residual_multiplier == 0.22
               for c in model.cells[1:3])
    assert model.tied == ((0, 3, "table"),)


@pytest.mark.parametrize("name, bad", [
    ("num_local_experts", {"num_local_experts": 8, "num_experts_per_tok": 2}),
    ("mamba_n_groups", {"mamba_n_groups": 2}),
    ("mamba_proj_bias", {"mamba_proj_bias": True}),
    ("attention_bias", {"attention_bias": True}),
    ("position_embedding_type", {"position_embedding_type": "rope"}),
])
def test_what_the_model_does_not_compute_is_refused(name, bad):
    with pytest.raises(ValueError, match=name):
        _model(config=dataclasses.replace(TINY, **bad))


@pytest.mark.parametrize("cell", [0, 1, 2, 4], ids=[
    "embedding", "mamba", "attention", "norm+tied head"])
def test_each_cell_kind_matches_the_reference(cell):
    model, params, sizes = _model()
    per_cell = model.per_cell(params)
    ref_cells = REF.cells(per_cell, sizes)
    x, _ = _ids()
    act = x if cell == 0 else jnp.asarray(np.random.default_rng(cell).standard_normal(
        (BATCH, SEQ, TINY.hidden_size), np.float32)) * 0.3
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda a: model.cells[cell].apply(per_cell[cell], a, CTX))(act)
        want = jax.jit(ref_cells[cell])(act)
    assert got.shape == want.shape and got.dtype == jnp.float32
    _close(got, want)


def _reference_loss(sizes, x, y, cells=REF.cells):
    def loss(p):
        act = x
        for fn in cells(p, sizes):
            act = fn(act)
        logp = jax.nn.log_softmax(act, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))
    return loss


def test_whole_model_loss_and_every_gradient_match_the_reference():
    """The reference has ONE leaf for the table; so has the state."""
    model, params, sizes = _model()
    x, y = _ids()
    assert "table" not in params[-1] and list(params[-1]) == ["norm"]

    def program(p):
        return cross_entropy(model.apply(p, x, CTX), y)

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(program))(params)
        want, g_want = jax.jit(jax.value_and_grad(
            _reference_loss(sizes, x, y)))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    paths = jax.tree_util.tree_flatten_with_path(g_want)[0]
    assert len(paths) == len(jax.tree.leaves(g_got)) > 30
    for (path, want_leaf), got_leaf in zip(paths, jax.tree.leaves(g_got)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['carried']"):  # a statistic
            assert not np.any(np.asarray(got_leaf)), name
            continue
        assert float(jnp.max(jnp.abs(want_leaf))) > 0, name
        _close(got_leaf, want_leaf, tol=2e-4)


def test_the_tables_gradient_is_the_sum_of_the_embeddings_and_the_heads():
    model, params, sizes = _model()
    x, y = _ids()
    table = params[0]["table"]

    def two_tables(embedding, head):
        """The model with the two uses of the table apart."""
        p = [{"table": embedding}, *params[1:-1], {**params[-1], "table": head}]
        act = x
        for i, cell in enumerate(model.cells):
            act = cell.apply(p[i], act, CTX)
        return cross_entropy(act, y)

    with jax.default_matmul_precision("highest"):
        g_embed, g_head = jax.jit(jax.grad(two_tables, argnums=(0, 1)))(table, table)
        g_tied = jax.jit(jax.grad(
            lambda p: cross_entropy(model.apply(p, x, CTX), y)))(params)[0]["table"]
        g_ref = jax.jit(jax.grad(_reference_loss(sizes, x, y)))(params)[0]["table"]
    assert float(jnp.max(jnp.abs(g_embed))) > 0 < float(jnp.max(jnp.abs(g_head)))
    _close(g_tied, g_embed + g_head, tol=1e-6)
    _close(g_tied, g_ref, tol=2e-4)
    # rows the batch never drew take the head's gradient alone
    unseen = np.setdiff1d(np.arange(VOCAB), np.asarray(x).ravel())
    assert len(unseen) > 0
    assert not np.any(np.asarray(g_embed)[unseen])
    assert np.any(np.asarray(g_tied)[unseen])


@pytest.mark.parametrize("remat", [False, True, "sqrt"])
def test_the_state_holds_the_table_once_and_the_step_applies_the_one_update(remat):
    model, params, sizes = _model()
    x, y = _ids()
    lr = 0.5
    opt = Optimizer("sgd", lr=lr)
    state = TrainState.create(params, opt)
    tables = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        state.params)[0] if "table" in jax.tree_util.keystr(path)]
    assert len(tables) == 1 and tables[0].shape == (VOCAB, TINY.hidden_size)
    with jax.default_matmul_precision("highest"):
        g_ref = jax.jit(jax.grad(_reference_loss(sizes, x, y)))(params)[0]["table"]
        new, metrics = make_train_step(model, opt, remat=remat)(state, x, y)
    _close(new.params[0]["table"], params[0]["table"] - lr * g_ref, tol=2e-5)
    assert 0 < float(metrics["counted"]["ssm_carried_share"]) < 1
    # the statistic of the first state-space layer is in the new state, and
    # no other layer counts it
    assert float(new.params[1]["op"]["carried"][1]) > 0
    assert "carried" not in new.params[3]["op"]


def test_per_cell_hands_the_reader_the_owners_leaf():
    model, params, _ = _model()
    per_cell = model.per_cell(params)
    assert per_cell[-1]["table"] is params[0]["table"]
    assert per_cell[1] is params[1]
    untied = lfm2.lfm2_moe((2, 8), num_layers=1, vocab_size=16, experts_held=2)
    assert untied.tied == () and untied.per_cell([1, 2, 3]) == [1, 2, 3]


def test_attention_has_no_position_signal_and_the_scan_has():
    """Permuting the tokens before t among themselves leaves the attention
    layer's output at t as it was, and changes the state-space layer's."""
    model, params, _ = _model()
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((1, SEQ, TINY.hidden_size), np.float32))
    t = 12
    order = np.concatenate([rng.permutation(t), np.arange(t, SEQ)])
    with jax.default_matmul_precision("highest"):
        attn = jax.jit(lambda a: model.cells[2].apply(params[2], a, CTX))
        scan = jax.jit(lambda a: model.cells[1].apply(params[1], a, CTX))
        _close(attn(h[:, order])[:, t], attn(h)[:, t], tol=1e-5)
        moved = jnp.max(jnp.abs(scan(h[:, order])[:, t] - scan(h)[:, t]))
    assert float(moved) > 1e-3 * float(jnp.max(jnp.abs(scan(h)[:, t])))


@pytest.mark.parametrize("key, other", [
    ("embedding_multiplier", 6.0), ("residual_multiplier", 0.5),
    ("attention_multiplier", 0.25), ("logits_scaling", 2.0)])
def test_each_multiplier_changes_the_loss(key, other):
    x, y = _ids()

    def loss(config):
        model, params, _ = _model(config=config)
        with jax.default_matmul_precision("highest"):
            return float(cross_entropy(model.apply(params, x, CTX), y))

    base = loss(TINY)
    assert abs(loss(dataclasses.replace(TINY, **{key: other})) - base) > 1e-6 * base


def test_the_scans_initial_values_are_mamba_2s():
    mixer = _model()[0].cells[1].op
    p = gmh.Mamba2Mixer(64, 64, 2, 8, 4, 8, 1e-5).init(
        jax.random.key(0), (1, 8, 64))[0]
    dt = jax.nn.softplus(p["dt_bias"])
    assert float(dt.min()) >= 0.001 * 0.999 and float(dt.max()) <= 0.1 * 1.001
    a = jnp.exp(p["A_log"])
    assert float(a.min()) >= 1 and float(a.max()) <= 16
    assert float(a.max() - a.min()) > 5 and np.all(np.asarray(p["D"]) == 1)
    assert p["conv1d"]["bias"].shape == (2 * 64 + 16,)
    assert mixer.conv_bias and mixer.chunk == 8


# --- through the entry point's builders -----------------------------------------

ARGV = ["--model", "granitemoehybrid", "--num-layers", "3", "--vocab-size", "128",
        "--seq-len", "16", "--batch-size", "4", "--lr", "0.5", "--app", "3"]


def test_one_chip_trains_through_build_train_and_run_supervised(tiny):
    result, losses, steps, run = _first_losses(ARGV, "lp", jax.devices()[:1], steps=3)
    assert result.anomalies == 0 and len(losses) == 3
    assert abs(losses[0] - np.log(128)) < 0.5 and losses[2] < losses[0]
    assert all(np.isfinite(losses))
    assert run.attrs["global_batch"] == 4
    for s in steps:
        assert 0 < s.attrs["ssm_carried_share"] < 1
    from mpi4dl_tpu.obs.spans import recorder

    summary = recorder().summary()
    assert summary["ssm_scan_paths"].get("chunked", 0) >= 2
    assert summary["tied_head_paths"].get("table_transposed", 0) >= 1
    assert summary["attention_paths"].get("einsum", 0) >= 1


def test_eval_params_are_per_cell(tiny):
    from benchmarks.common import build_train

    cfg = config_from_args(get_parser().parse_args(ARGV))
    step, state, eval_params_fn, _ = build_train(cfg, "lp", None)
    per_cell = eval_params_fn(state)
    assert "table" not in state.params[-1]
    assert per_cell[-1]["table"] is state.params[0]["table"]


def _one_step(argv, devices):
    """The first loss, and the per-cell parameters before and after one step
    through ``build_train``."""
    from benchmarks.common import build_train
    from mpi4dl_tpu.data import make_dataset
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh

    cfg = config_from_args(get_parser().parse_args(argv))
    mesh = build_mesh(MeshSpec(data=1, stage=max(cfg.split_size, 1)), devices)
    step, state, eval_params_fn, global_batch = build_train(cfg, "lp", mesh)
    before = jax.tree.map(np.asarray, eval_params_fn(state))
    x, y = make_dataset(cfg).batch(0, global_batch)
    state, metrics = step(state, x, y)
    return float(metrics["loss"]), before, jax.tree.map(
        np.asarray, eval_params_fn(state))


@pytest.mark.parametrize("stages, balance", [(2, None), (2, "1,4")])
def test_gpipe_gives_the_one_chip_loss_and_the_one_chip_update_of_the_table(
        tiny, stages, balance):
    """Across stages the embedding's stage and the head's each hold the
    table in their row; the GPipe schedule sums the two gradients over the
    stage axis before the update, so both copies take the one-chip step
    (and with the head's cell alone on its stage or not)."""
    assert len(jax.devices()) >= stages
    loss_one, before_one, after_one = _one_step(ARGV, jax.devices()[:1])
    extra = ["--split-size", str(stages), "--parts", "2"]
    if balance:
        extra += ["--balance", balance]
    loss, before, after = _one_step(ARGV + extra, jax.devices()[:stages])
    assert loss == pytest.approx(loss_one, rel=2e-5)
    np.testing.assert_array_equal(before[0]["table"], before_one[0]["table"])
    step_one = after_one[0]["table"] - before_one[0]["table"]
    assert np.abs(step_one).max() > 0
    for cell in (0, -1):  # the owner's copy and the reader's
        got = after[cell]["table"] - before[cell]["table"]
        assert np.abs(got - step_one).max() <= 2e-4 * np.abs(step_one).max()
    np.testing.assert_array_equal(after[0]["table"], after[-1]["table"])
    # every other leaf too (but the statistic, which the pipeline, as for an
    # expert layer's load, does not write back)
    paths = jax.tree_util.tree_flatten_with_path(after_one[1:-1])[0]
    for (path, b), a in zip(paths, jax.tree.leaves(after[1:-1])):
        if "carried" not in jax.tree_util.keystr(path):
            assert np.abs(a - b).max() <= 2e-4 * max(np.abs(b).max(), 1e-6), path


def test_the_other_pipelined_engines_refuse_the_tie_by_the_leafs_name():
    """GEMS, the spatial pipelines and 1F1B keep each stage's parameters in a
    row of its own and do not sum a tied leaf's gradients: one error, which
    names the leaf."""
    from mpi4dl_tpu.parallel.partition import StagePartition

    model, params, _ = _model()
    with pytest.raises(ValueError, match=r"reads the leaf 'table' of cell 0"):
        StagePartition.build(model, params, 2, (2, SEQ))
    part = StagePartition.build(model, params, 2, (2, SEQ), sums_tied_grads=True)
    (size, ((owner, owner_off), (reader, reader_off))), = part.tied_slots
    assert (owner, owner_off, reader, size) == (0, 0, 1, VOCAB * TINY.hidden_size)
    assert reader_off > 0  # after the head cell's norm, and after a layer
    from mpi4dl_tpu.parallel.pipeline import make_pipeline_train_step

    with pytest.raises(ValueError, match=r"the 1f1b schedule"):
        make_pipeline_train_step(part, Optimizer("sgd"), None, 2, schedule="1f1b")


@pytest.mark.parametrize("family, extra, why", [
    ("sp", [], "token model"),
    ("gems", ["--split-size", "2"], "token model"),
    ("gems_sp", ["--split-size", "2"], "token model"),
    ("lp", ["--split-size", "2", "--schedule", "1f1b"], "token model"),
])
def test_other_families_refuse_this_token_model_too(tiny, family, extra, why):
    from benchmarks.common import build_train

    cfg = config_from_args(get_parser().parse_args(ARGV + extra))
    with pytest.raises(ValueError, match=why):
        build_train(cfg, family, None)


def test_build_model_states_the_cut_in_flags_only():
    cfg = config_from_args(get_parser().parse_args(
        ["--model", "granitemoehybrid", "--num-layers", "10", "--vocab-size",
         "25088", "--seq-len", "8192", "--batch-size", "2", "--precision",
         "bf_16", "--experts-held", "7"]))  # a flag of routed models: ignored
    assert cfg.is_token_model
    model = build_model(cfg)
    assert len(model.cells) == 12 and model.in_shape == (2, 8192)
    assert [c.name.split("_")[1] for c in model.cells[1:-1]] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.key(0))
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    # in_proj 17,432,576 + conv 4 x 4352 + its bias 4352 + dt_bias, A_log, D
    # 3 x 64 + norm 4096 + out_proj 8,388,608, and, in the first such layer,
    # the statistic's 2
    assert count(shapes[1]["op"]) == 25_847_232 + 2
    assert count(shapes[2]) == 76_182_976
    assert count(shapes[6]) == 60_821_504
    assert count(shapes) == 797_850_560 + 2
    assert shapes[0]["table"].shape == (25088, 2048) and list(shapes[-1]) == ["norm"]
    assert shapes[1]["op"]["in_proj"]["kernel"].shape == (2048, 8512)
    assert shapes[1]["op"]["conv1d"]["kernel"].shape == (4, 4352)
    assert shapes[6]["op"]["k_proj"]["kernel"].shape == (2048, 512)
    assert "q_norm" not in shapes[6]["op"]
