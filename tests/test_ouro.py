"""``ouro`` (Ouro-2.6B, ByteDance's LoopLM) as a token ``CellModel``: the
program against the benchmark's plain reference (perfbench/references/ouro.py,
which shares no code with it) at small widths on the CPU, the layers applied
four times on one set of weights (``CellModel.tied``: each weight's gradient
the sum over its applications, one update), the sandwich norms and the norm
between passes, and the path through ``build_train``, ``run_supervised`` and
the GPipe schedule."""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.cells import CellModel
from mpi4dl_tpu.config import config_from_args, get_parser
from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.models import build_model, lfm2
from mpi4dl_tpu.train import Optimizer, TrainState, cross_entropy, make_train_step

import mpi4dl_tpu.models.ouro as ouro
from test_lfm2 import _batch, _close, _first_losses

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# hidden 64, 2 heads of 32 (as many key-value heads), an MLP of 96, 2 layers,
# 256 ids
TINY = dataclasses.replace(
    ouro.PUBLISHED, hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
    head_dim=32, intermediate_size=96, vocab_size=256,
    layer_types=("full_attention",) * 2, num_hidden_layers=2)
CTX = ApplyCtx(train=True)
VOCAB, BATCH, SEQ, LAYERS = 256, 2, 16, 2
# the names of a layer's parameters: its four norms, attention and MLP
LAYER_NAMES = ("op_norm", "op", "op_post_norm", "ffn_norm", "ffn", "ffn_post_norm")


def _reference():
    path = os.path.join(ROOT, "perfbench", "references", "ouro.py")
    spec = importlib.util.spec_from_file_location("reference_ouro", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def _sizes(config, num_layers=LAYERS, vocab=VOCAB):
    """What the configuration's file states for the reference, for ``config``."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "rms_norm_eps", "rope_theta",
            "total_ut_steps")
    return {"num_layers": num_layers, "vocab_size": vocab,
            **{k: getattr(config, k) for k in keys}}


@pytest.fixture
def tiny(monkeypatch):
    """The published config at toy widths, for what builds from flags."""
    monkeypatch.setattr(ouro, "PUBLISHED", TINY)
    return TINY


def _model(config=TINY, num_layers=LAYERS, seed=3):
    model = ouro.ouro((BATCH, SEQ), num_layers=num_layers, vocab_size=VOCAB,
                      config=config)
    params, _ = model.init(jax.random.key(seed))
    return model, params, _sizes(config, num_layers)


def _ids():
    return _batch(vocab=VOCAB, batch=BATCH, seq=SEQ)


def _loss(model, x, y):
    return lambda p: cross_entropy(model.apply(p, x, CTX), y)


def _perturbed(params, seed=11, scale=0.3):
    """Every leaf moved by a random amount: norm scales away from one, so
    that a norm applied twice, or not at all, shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf * (1 + scale * jax.random.normal(k, leaf.shape))
        for k, leaf in zip(keys, leaves)])


# --- the cells -------------------------------------------------------------------


def test_one_cell_an_application_and_the_later_passes_hold_nothing():
    model, params, _ = _model()
    names = [c.name for c in model.cells]
    assert len(names) == 2 + 4 * (LAYERS + 1) == 14
    assert names == ["embed", *(f"ut{t}_{n}" for t in range(4)
                                for n in ("layer00", "layer01", "norm")), "head"]
    assert [sorted(p) for p in params[1:3]] == [sorted(LAYER_NAMES)] * 2
    assert params[3] == {"norm": {"scale": params[3]["norm"]["scale"]}}
    assert all(p == {} for p in params[4:-1])
    assert list(params[-1]) == ["head"]  # no norm: RMS_f came before it
    # every later application reads each top-level name of its holder
    assert len(model.tied) == 3 * (LAYERS * len(LAYER_NAMES) + 1)
    for owner, reader, name in model.tied:
        assert model.cells[reader].cell is model.cells[owner].cell
        assert name in params[owner] and reader > owner
    per_cell = model.per_cell(params)
    for t in range(1, 4):
        for j in range(LAYERS + 1):
            got = per_cell[1 + t * (LAYERS + 1) + j]
            assert got.keys() == params[1 + j].keys()
            assert all(got[k] is params[1 + j][k] for k in got)


@pytest.mark.parametrize("name, bad", [
    ("use_sliding_window", {"use_sliding_window": True, "sliding_window": 4096}),
    ("attention_bias", {"attention_bias": True}),
    ("qk_norm", {"qk_norm": True}),
    ("tie_word_embeddings", {"tie_word_embeddings": True}),
    ("rope_scaling", {"rope_scaling": {"type": "yarn", "factor": 4}}),
])
def test_what_the_model_does_not_compute_is_refused(name, bad):
    with pytest.raises(ValueError, match=name):
        _model(config=dataclasses.replace(TINY, **bad))


@pytest.mark.parametrize("cell", [0, 1, 3, 5, 12, 13], ids=[
    "embedding", "layer", "final norm", "layer, pass 1", "final norm, pass 3",
    "head"])
def test_each_cell_matches_the_reference(cell):
    model, params, sizes = _model()
    params = _perturbed(params)
    per_cell = model.per_cell(params)
    ref_cells = REF.cells(per_cell, sizes)
    assert len(ref_cells) == len(model.cells)
    x, _ = _ids()
    act = x if cell == 0 else jnp.asarray(np.random.default_rng(cell).standard_normal(
        (BATCH, SEQ, TINY.hidden_size), np.float32))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda a: model.cells[cell].apply(per_cell[cell], a, CTX))(act)
        want = jax.jit(ref_cells[cell])(act)
    assert got.shape == want.shape and got.dtype == jnp.float32
    _close(got, want)


# --- the loop's gradients and update ---------------------------------------------


def test_whole_model_loss_and_every_gradient_match_the_reference():
    """The reference reads every pass's weights from pass 0's cells, so its
    gradient of a weight is the sum over the four passes: the state's."""
    model, params, sizes = _model()
    params = _perturbed(params)
    x, y = _ids()
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(_loss(model, x, y)))(params)
        want, g_want = jax.jit(jax.value_and_grad(
            lambda p: REF.loss(p, sizes, x, y)))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    paths = jax.tree_util.tree_flatten_with_path(g_want)[0]
    # the table, two layers of 11 leaves, RMS_f's scale, the head
    assert len(paths) == len(jax.tree.leaves(g_got)) == 1 + 2 * 11 + 1 + 1
    for (path, want_leaf), got_leaf in zip(paths, jax.tree.leaves(g_got)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(want_leaf))) > 0, name
        _close(got_leaf, want_leaf, tol=2e-4)


def test_a_shared_weights_gradient_is_the_sum_of_four_copies_gradients():
    """The same cells untied, each later application on a copy of its own:
    the tied model's gradient of a held leaf is the sum of the four copies'
    gradients, and no copy's is zero (every pass trains the weights)."""
    model, params, _ = _model()
    params = _perturbed(params)
    x, y = _ids()
    untied = CellModel(model.cells, model.in_shape, model.num_classes)
    copies = [dict(p) for p in model.per_cell(params)]
    with jax.default_matmul_precision("highest"):
        g_tied = jax.jit(jax.grad(_loss(model, x, y)))(params)
        g_copies = jax.jit(jax.grad(_loss(untied, x, y)))(copies)
    stride = LAYERS + 1
    for j in range(stride):
        for name in params[1 + j]:
            uses = [g_copies[1 + t * stride + j][name] for t in range(4)]
            for use in uses:
                assert all(float(jnp.max(jnp.abs(u))) > 0
                           for u in jax.tree.leaves(use)), (j, name)
            total = jax.tree.map(lambda *u: sum(u), *uses)
            jax.tree.map(lambda a, b: _close(a, b, tol=1e-5),
                         g_tied[1 + j][name], total)
    # the embedding and the head, read once, are the untied model's
    _close(g_tied[0]["table"], g_copies[0]["table"], tol=1e-6)
    _close(g_tied[-1]["head"]["kernel"], g_copies[-1]["head"]["kernel"], tol=1e-6)


@pytest.mark.parametrize("remat", [False, True, "sqrt"])
def test_the_state_holds_each_layer_once_and_the_step_updates_it_once(remat):
    model, params, sizes = _model()
    x, y = _ids()
    lr = 0.5
    opt = Optimizer("sgd", lr=lr)
    state = TrainState.create(params, opt)
    count = lambda tree: sum(int(a.size) for a in jax.tree.leaves(tree))
    d, f, heads = TINY.hidden_size, TINY.intermediate_size, 2 * 32
    per_layer = 4 * d * heads + 3 * d * f + 4 * d
    assert count(state.params) == 2 * VOCAB * d + LAYERS * per_layer + d
    with jax.default_matmul_precision("highest"):
        g_ref = jax.jit(jax.grad(lambda p: REF.loss(p, sizes, x, y)))(params)
        new, _ = make_train_step(model, opt, remat=remat)(state, x, y)
    assert jax.tree.structure(new.params) == jax.tree.structure(params)
    jax.tree.map(lambda n, p, g: _close(n, p - lr * g, tol=2e-5),
                 new.params, params, g_ref)


def test_one_pass_is_the_plain_model_of_its_layers():
    """``total_ut_steps`` 1: embedding, the two layers, the final norm and
    the head once, nothing tied: the logits of a plain stack of the same
    cells with the norm before the head."""
    model, params, _ = _model(dataclasses.replace(TINY, total_ut_steps=1))
    params = _perturbed(params)
    assert len(model.cells) == 5 and model.tied == ()
    plain = CellModel(
        [model.cells[0], model.cells[1].cell, model.cells[2].cell,
         lfm2.head_cell(VOCAB, TINY.hidden_size, TINY.rms_norm_eps)],
        model.in_shape, VOCAB)
    plain_params = [*params[:3], {"norm": params[3]["norm"], **params[4]}]
    x, _ = _ids()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: model.apply(p, x, CTX))(params)
        want = jax.jit(lambda p: plain.apply(p, x, CTX))(plain_params)
    _close(got, want, tol=1e-6)


@pytest.mark.parametrize("name", [*(f"layer00.{n}" for n in LAYER_NAMES
                                    if n.endswith("norm")), "final_norm"])
def test_the_loss_changes_with_each_norm_of_a_layer_and_with_the_final_norm(name):
    """Each of a layer's four scales and ``RMS_f``'s moves the loss; and
    ``RMS_f``'s acts between passes too, not only before the head: with the
    fourth pass's application of it left as it was (a norm of scale one
    there), a change of its scale still moves the loss."""
    model, params, _ = _model()
    x, y = _ids()
    loss = jax.jit(_loss(model, x, y))
    base = float(loss(params))

    def scaled(p, cell, key):
        p = [dict(q) for q in p]
        p[cell] = {**p[cell], key: {"scale": p[cell][key]["scale"] * 1.5}}
        return p

    if name == "final_norm":
        moved = scaled(params, 3, "norm")
        assert abs(float(loss(moved)) - base) > 1e-4 * base
        # RMS_f between the passes alone: the model untied, the last pass's
        # application of it on the scale it had
        untied = jax.jit(_loss(
            CellModel(model.cells, model.in_shape, model.num_classes), x, y))
        between = model.per_cell(moved)
        between[-2] = model.per_cell(params)[-2]
        assert abs(float(untied(between)) - base) > 1e-4 * base
    else:
        moved = scaled(params, 1, name.split(".")[1])
        assert abs(float(loss(moved)) - base) > 1e-4 * base


def test_the_recorder_counts_four_applications_of_each_layer(rec):
    """``ut_loop``: the held layers by the number of cells that apply them,
    traced; ``{"1": 2}`` with one pass, the loop not engaged."""
    model, params, _ = _model()
    x, _ = _ids()
    jax.eval_shape(lambda p: model.apply(p, x, CTX), params)
    assert rec.site_paths("ut_loop") == {"4": LAYERS}
    assert rec.summary()["ut_loop"] == {"4": LAYERS}
    one, p1, _ = _model(dataclasses.replace(TINY, total_ut_steps=1))
    jax.eval_shape(lambda p: one.apply(p, x, CTX), p1)
    assert rec.site_paths("ut_loop") == {"1": LAYERS, "4": LAYERS}


def test_every_application_carries_its_pass_scope():
    """Each layer's products, in every pass, under ``ut_loop`` and the
    pass's ``ut_step{t}``; the embedding and the head under neither."""
    model, params, _ = _model()
    x, y = _ids()
    text = jax.jit(jax.grad(_loss(model, x, y))).lower(params).as_text(
        debug_info=True)
    for t in range(4):
        assert f"ut_loop/ut_step{t}/" in text, t
    assert "ut_step4" not in text


# --- through the entry point's builders -----------------------------------------

ARGV = ["--model", "ouro", "--num-layers", "2", "--vocab-size", "256",
        "--seq-len", "16", "--batch-size", "4", "--lr", "0.5", "--app", "3"]


def test_one_chip_trains_through_build_train_and_run_supervised(tiny):
    result, losses, steps, run = _first_losses(ARGV, "lp", jax.devices()[:1], steps=3)
    assert result.anomalies == 0 and len(losses) == 3
    assert abs(losses[0] - np.log(VOCAB)) < 1.0 and losses[2] < losses[0]
    assert all(np.isfinite(losses))
    assert run.attrs["global_batch"] == 4
    from mpi4dl_tpu.obs.spans import recorder

    summary = recorder().summary()
    assert summary["ut_loop"].get("4", 0) >= LAYERS
    assert summary["attention_paths"].get("einsum", 0) >= 1


def test_eval_params_are_per_cell(tiny):
    from benchmarks.common import build_train

    cfg = config_from_args(get_parser().parse_args(ARGV))
    _, state, eval_params_fn, _ = build_train(cfg, "lp", None)
    per_cell = eval_params_fn(state)
    assert state.params[4] == {} and len(per_cell) == 14
    assert per_cell[4]["op"] is state.params[1]["op"]
    assert per_cell[12]["norm"] is state.params[3]["norm"]


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


def test_gpipe_over_two_stages_gives_the_one_chip_loss_and_update(tiny):
    """Fourteen cells over two stages of seven: passes 0 and 1 (with pass 1's
    norm) on stage 0, passes 2 and 3 on stage 1, so every held leaf has
    copies on both stages.  The GPipe schedule sums all four uses'
    gradients at once over the stage axis: every copy takes the one-chip
    update."""
    argv = ARGV + ["--precision", "fp_32"]
    loss_one, before_one, after_one = _one_step(argv, jax.devices()[:1])
    loss, before, after = _one_step(
        argv + ["--split-size", "2", "--parts", "2"], jax.devices()[:2])
    assert loss == pytest.approx(loss_one, rel=2e-5)
    stride = LAYERS + 1
    for j in range(stride):
        for t in range(4):
            i = 1 + t * stride + j
            for (path, b), a, a_one in zip(
                    jax.tree_util.tree_flatten_with_path(before[i])[0],
                    jax.tree.leaves(after[i]), jax.tree.leaves(after_one[i])):
                step_one = a_one - b
                assert np.abs(step_one).max() > 0, (i, path)
                assert np.abs((a - b) - step_one).max() <= (
                    2e-4 * np.abs(step_one).max()), (i, path)
    # the copies stay one value
    for t in range(1, 4):
        for j in range(stride):
            jax.tree.map(np.testing.assert_array_equal,
                         after[1 + t * stride + j], after[1 + j])
    for cell in (0, -1):
        for a, b in zip(jax.tree.leaves(after[cell]), jax.tree.leaves(after_one[cell])):
            assert np.abs(a - b).max() <= 2e-4 * max(np.abs(b).max(), 1e-6)


def test_the_partition_groups_each_leaf_over_its_four_uses():
    from mpi4dl_tpu.parallel.partition import StagePartition

    model, params, _ = _model()
    part = StagePartition.build(model, params, 2, (2, SEQ), sums_tied_grads=True)
    # a layer's 11 leaves (four norms' scales, four attention and three MLP
    # kernels) in each of two layers, and RMS_f's scale
    assert len(part.tied_slots) == 2 * 11 + 1
    for size, uses in part.tied_slots:
        assert len(uses) == 4 and size > 0
        assert {s for s, _ in uses} == {0, 1}  # across the stage boundary
        assert len(set(uses)) == 4


@pytest.mark.parametrize("family, extra", [
    ("sp", []),
    ("gems", ["--split-size", "2"]),
    ("gems_sp", ["--split-size", "2"]),
    ("lp", ["--split-size", "2", "--schedule", "1f1b"]),
])
def test_other_families_refuse_this_token_model(tiny, family, extra):
    from benchmarks.common import build_train

    cfg = config_from_args(get_parser().parse_args(ARGV + extra))
    with pytest.raises(ValueError, match="token model"):
        build_train(cfg, family, None)


def test_the_other_pipelined_engines_refuse_the_tie_by_the_leafs_name():
    from mpi4dl_tpu.parallel.partition import StagePartition
    from mpi4dl_tpu.parallel.pipeline import make_pipeline_train_step

    model, params, _ = _model()
    with pytest.raises(ValueError, match=r"cell 4 \(ut1_layer00\) reads the leaf"):
        StagePartition.build(model, params, 2, (2, SEQ))
    part = StagePartition.build(model, params, 2, (2, SEQ), sums_tied_grads=True)
    with pytest.raises(ValueError, match=r"the 1f1b schedule"):
        make_pipeline_train_step(part, Optimizer("sgd"), None, 2, schedule="1f1b")


def test_build_model_states_the_cut_in_flags_only():
    cfg = config_from_args(get_parser().parse_args(
        ["--model", "ouro", "--num-layers", "8", "--vocab-size", "49152",
         "--seq-len", "8192", "--batch-size", "1", "--precision", "bf_16",
         "--experts-held", "7"]))  # a flag of routed models: ignored
    assert cfg.is_token_model
    model = build_model(cfg)
    assert len(model.cells) == 38 and model.in_shape == (1, 8192)
    assert [c.name for c in model.cells[1:10]] == [
        *(f"ut0_layer{i:02d}" for i in range(8)), "ut0_norm"]
    assert model.cells[28].name == "ut3_layer00" and model.cells[-1].name == "head"
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.key(0))
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    # attention 16,777,216 + MLP 34,603,008 + four norms 8,192
    assert count(shapes[1]) == 51_388_416
    assert count(shapes) == 612_435_968
    assert shapes[1]["op"]["k_proj"]["kernel"].shape == (2048, 2048)
    assert shapes[1]["ffn"]["w1"]["kernel"].shape == (2048, 5632)
    assert "q_norm" not in shapes[1]["op"]
    assert shapes[-1]["head"]["kernel"].shape == (2048, 49152)
    with pytest.raises(ValueError, match="--vocab-size 65536 of 49152"):
        build_model(config_from_args(get_parser().parse_args(
            ["--model", "ouro", "--num-layers", "8"])))
