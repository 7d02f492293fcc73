"""LFM2-MoE as a token ``CellModel``: the program against the benchmark's plain
reference (perfbench/references/lfm2_moe.py, which shares no code with it) at
small widths on the CPU, the routed expert layer's share of the experts, and
the path through ``build_train`` and ``run_supervised``."""

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
from mpi4dl_tpu.layers import CausalConv1d
from mpi4dl_tpu.models import build_model, lfm2
from mpi4dl_tpu.ops import moe
from mpi4dl_tpu.train import cross_entropy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dataclasses.replace(
    lfm2.PUBLISHED, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=2,
    num_experts=16)
CTX = ApplyCtx(train=True)


def _reference():
    path = os.path.join(ROOT, "perfbench", "references", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("reference_lfm2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def _sizes(config, num_layers, vocab, held, first=0):
    """What the configuration's file states for the reference, for ``config``."""
    run = lfm2.layers_run(config, num_layers)
    return {
        "num_layers": num_layers,
        "layer_types": [config.layer_types[i] for i in run],
        "dense_layers": sum(i < config.num_dense_layers for i in run),
        "hidden_size": config.hidden_size,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "head_dim": config.head_dim, "norm_eps": config.norm_eps,
        "rope_theta": config.rope_parameters["rope_theta"],
        "conv_L_cache": config.conv_L_cache,
        "num_experts": held, "num_experts_published": config.num_experts,
        "expert_first": first,
        "num_experts_per_tok": config.num_experts_per_tok,
        "routed_scaling_factor": config.routed_scaling_factor,
        "vocab_size": vocab,
    }


@pytest.fixture
def tiny(monkeypatch):
    """The published config at toy widths, for what builds from flags."""
    monkeypatch.setattr(lfm2, "PUBLISHED", TINY)
    return TINY


def _model(num_layers=5, vocab=50, held=4, first=4, batch=2, seq=24, config=TINY):
    model = lfm2.lfm2_moe((batch, seq), num_layers=num_layers, vocab_size=vocab,
                          experts_held=held, expert_first=first, config=config)
    params, _ = model.init(jax.random.key(3))
    return model, params, _sizes(config, num_layers, vocab, held, first)


def _batch(vocab=50, batch=2, seq=24, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


# --- the program against the reference, float32 -------------------------------


def test_cut_keeps_the_leading_dense_layer_once_and_whole_periods():
    run = lfm2.layers_run(lfm2.PUBLISHED, 9)
    assert run == tuple(range(1, 10))
    kinds = [lfm2.PUBLISHED.layer_types[i] for i in run]
    assert kinds == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    assert lfm2.layers_run(lfm2.PUBLISHED, 40) == tuple(range(40))
    assert lfm2.PUBLISHED.layer_types.count("full_attention") == 10
    with pytest.raises(ValueError):
        lfm2.layers_run(lfm2.PUBLISHED, 41)
    model, _, _ = _model()
    assert [c.name for c in model.cells] == [
        "embed", "layer01_conv", "layer02_full_attention", "layer03_conv",
        "layer04_conv", "layer05_conv", "norm_head"]
    assert isinstance(model.cells[1].ffn, lfm2.SwiGLU)
    assert all(isinstance(c.ffn, moe.RoutedExperts) for c in model.cells[2:6])


@pytest.mark.parametrize("cell", [0, 1, 2, 3, 6], ids=[
    "embedding", "conv+dense", "attention+experts", "conv+experts", "norm+head"])
def test_each_cell_kind_matches_the_reference(cell):
    model, params, sizes = _model()
    ref_cells = REF.cells(params, sizes)
    x, _ = _batch()
    act = x if cell == 0 else jnp.asarray(np.random.default_rng(cell).standard_normal(
        (2, 24, TINY.hidden_size), np.float32)) * 0.3
    with jax.default_matmul_precision("highest"):
        got = model.cells[cell].apply(params[cell], act, CTX)
        want = ref_cells[cell](act)
    assert got.shape == want.shape and got.dtype == jnp.float32
    _close(got, want)


def test_whole_model_loss_and_every_gradient_match_the_reference():
    model, params, sizes = _model()
    x, y = _batch()

    def program(p):
        return cross_entropy(model.apply(p, x, CTX), y)

    def reference(p):
        act = x
        for fn in REF.cells(p, sizes):
            act = fn(act)
        logp = jax.nn.log_softmax(act, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(program)(params)
        want, g_want = jax.value_and_grad(reference)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    paths = jax.tree_util.tree_flatten_with_path(g_want)[0]
    assert len(paths) == len(jax.tree.leaves(g_got)) > 40
    for (path, want_leaf), got_leaf in zip(paths, jax.tree.leaves(g_got)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']") or name.endswith("['load']"):
            # the bias enters the choice only; the load is a statistic
            assert not np.any(np.asarray(got_leaf)), name
            continue
        assert float(jnp.max(jnp.abs(want_leaf))) > 0, name
        _close(got_leaf, want_leaf, tol=2e-4)


# --- the routed expert layer ---------------------------------------------------


def _expert_layer(held, first, total=64, seed=0, d=32, f=16, n=96):
    layer = moe.RoutedExperts(d, f, total, 4, held, first)
    full = moe.RoutedExperts(d, f, total, 4, total, 0)
    p_full, _ = full.init(jax.random.key(seed), (1, n, d))
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (1, n, d), np.float32))
    return layer, p_full, x


def _share(p_full, first, held):
    return {"router": p_full["router"], "load": p_full["load"][:held],
            "experts": {k: v[first:first + held]
                        for k, v in p_full["experts"].items()}}


def _ref_sizes(held, first, total=64):
    return {"num_experts": held, "num_experts_published": total,
            "expert_first": first, "num_experts_per_tok": 4,
            "routed_scaling_factor": 1}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """64 experts at a small width: what the eight chips of a layer compute,
    each its 8 experts' part, adds up to the uncut reference's layer (the
    residual is outside the layer: counted once by whoever adds it)."""
    _, p_full, x = _expert_layer(8, 0)
    with jax.default_matmul_precision("highest"):
        uncut = REF.experts(x, p_full, _ref_sizes(64, 0), None)
        parts, rows = [], 0.0
        for chip in range(8):
            layer = moe.RoutedExperts(32, 16, 64, 4, 8, 8 * chip)
            sink = {}
            share = _share(p_full, 8 * chip, 8)
            parts.append(layer.apply(
                share, x, dataclasses.replace(CTX, bn_sink=sink)))
            rows += float(jnp.sum(sink[id(share["load"])]))
            _close(parts[-1], REF.experts(x, share, _ref_sizes(8, 8 * chip), None))
    assert rows == pytest.approx(1.0, abs=1e-6)  # every assignment on one chip
    assert float(jnp.max(jnp.abs(parts[0]))) > 0
    _close(sum(parts), uncut)


@pytest.mark.parametrize("tile", [512, 8])
def test_no_token_is_dropped_when_all_go_to_the_held_experts(tile, monkeypatch):
    """A bias that sends every token's four experts to this chip: eight times
    the balanced load.  With rounds of 8 rows the rows run through many
    rounds; the output and the gradients still match the reference."""
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    layer, p_full, x = _expert_layer(8, 16)
    share = _share(p_full, 16, 8)
    share["router"] = {"kernel": share["router"]["kernel"],
                       "bias": jnp.zeros((64,)).at[18:22].set(10.0)}
    n_rounds = -(-x.shape[1] * 4 // moe.round_capacity(x.shape[1] * 4, 8, 64))
    assert n_rounds == (1 if tile == 512 else 6)
    sizes = _ref_sizes(8, 16)

    def program(p, x):
        sink = {}
        y = layer.apply(p, x, dataclasses.replace(CTX, bn_sink=sink))
        return jnp.sum(y * jnp.cos(y)), (y, sink[id(p["load"])])

    def reference(p, x):
        y = REF.experts(x, p, sizes, None)
        return jnp.sum(y * jnp.cos(y)), y

    with jax.default_matmul_precision("highest"):
        (_, (y, load)), g = jax.value_and_grad(program, (0, 1), has_aux=True)(share, x)
        (_, want), g_want = jax.value_and_grad(reference, (0, 1), has_aux=True)(share, x)
    assert float(jnp.sum(load)) == pytest.approx(1.0)  # all 4 x N rows are here
    np.testing.assert_allclose(np.asarray(load)[2:6], 0.25, atol=1e-6)
    _close(y, want)
    _close(g[1], g_want[1], tol=1e-4)
    for name in ("w1", "w3", "w2"):
        _close(g[0]["experts"][name], g_want[0]["experts"][name], tol=1e-4)
    _close(g[0]["router"]["kernel"], g_want[0]["router"]["kernel"], tol=1e-4)
    assert not np.any(np.asarray(g[0]["router"]["bias"]))


def test_a_bias_changes_the_choice_and_not_the_weights():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((40, 32), np.float32))
    kernel = jnp.asarray(rng.standard_normal((32, 64), np.float32)) * 0.2
    bias = jnp.asarray(rng.standard_normal((64,), np.float32)) * 0.3
    with jax.default_matmul_precision("highest"):
        plain_choice, _ = moe.route(x, kernel, jnp.zeros((64,)), 4)
        chosen, w = moe.route(x, kernel, bias, 4)
        scores = jax.nn.sigmoid(x @ kernel)
    assert np.any(np.sort(np.asarray(chosen)) != np.sort(np.asarray(plain_choice)))
    np.testing.assert_array_equal(
        np.sort(np.asarray(chosen)),
        np.sort(np.argsort(-np.asarray(scores + bias), axis=-1)[:, :4]))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    ref_chosen, ref_w = REF.route(x, {"kernel": kernel, "bias": bias}, _ref_sizes(8, 0))
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(ref_chosen))
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref_w), rtol=1e-6)


def test_round_capacity_is_the_balanced_load_and_a_quarter():
    assert moe.round_capacity(32768 * 4, 8, 64) == 20480  # 16,384 balanced
    assert moe.round_capacity(32768 * 4, 64, 64) == 32768 * 4  # uncut: one round
    assert moe.round_capacity(96, 8, 64) == 512
    with pytest.raises(ValueError):
        moe.RoutedExperts(32, 16, 64, 4, 8, 60)


def test_the_causal_convolution_does_not_see_the_next_token():
    conv = CausalConv1d(6, 3)
    p, _ = conv.init(jax.random.key(0), (1, 10, 6))
    x = np.random.default_rng(0).standard_normal((1, 10, 6)).astype(np.float32)
    y = np.asarray(conv.apply(p, jnp.asarray(x), CTX))
    bumped = x.copy()
    bumped[0, 5] += 1.0
    y2 = np.asarray(conv.apply(p, jnp.asarray(bumped), CTX))
    changed = np.any(y2 != y, axis=-1)[0]
    assert list(np.flatnonzero(changed)) == [5, 6, 7]  # t, t+1, t+2: never t-1
    w = np.asarray(p["kernel"])
    by_hand = sum(w[j] * np.pad(x, ((0, 0), (2, 0), (0, 0)))[:, j:j + 10]
                  for j in range(3))
    np.testing.assert_allclose(y, by_hand, rtol=1e-6, atol=1e-7)


# --- data, loss and the path through the trainer -------------------------------


def test_synthetic_tokens_are_ids_and_their_successors():
    from mpi4dl_tpu.data import SyntheticTokens, make_dataset

    cfg = config_from_args(get_parser().parse_args(
        ["--model", "lfm2_moe", "--seq-len", "64", "--vocab-size", "8192",
         "--seed", "7"]))
    data = make_dataset(cfg)
    assert isinstance(data, SyntheticTokens)
    x, y = data.batch(3, 4)
    assert x.shape == y.shape == (4, 64) and x.dtype == y.dtype == np.int32
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    assert 256 <= x.max() < 8192 and x.min() >= 0
    again, _ = make_dataset(cfg).batch(3, 4)
    np.testing.assert_array_equal(x, again)
    assert np.any(data.batch(4, 4)[0] != x)
    with pytest.raises(ValueError, match="synthetic"):
        make_dataset(dataclasses.replace(cfg, app=1))


@pytest.mark.parametrize("shape", [(5,), (2, 3)])
def test_cross_entropy_takes_labels_of_the_logits_leading_shape(shape):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((*shape, 7), np.float32))
    labels = jnp.asarray(rng.integers(0, 7, shape, dtype=np.int32))
    got = float(cross_entropy(logits, labels))
    flat, flat_labels = logits.reshape(-1, 7), labels.reshape(-1)
    was = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(flat, axis=-1), flat_labels[:, None], axis=-1))
    if len(shape) == 1:
        assert got == float(was)  # [B] labels: today's number to the bit
    else:
        assert got == pytest.approx(float(was), rel=1e-6)


def test_ids_are_not_cast_with_the_floating_input():
    from mpi4dl_tpu.train import cast_input

    ids = np.array([[0, 255, 257, 8191]], np.int32)
    assert cast_input(ids, jnp.bfloat16).dtype == np.int32
    assert cast_input(np.ones((1, 2), np.float32), jnp.bfloat16).dtype == jnp.bfloat16


ARGV = ["--model", "lfm2_moe", "--num-layers", "3", "--vocab-size", "300",
        "--experts-held", "4", "--expert-first", "8", "--seq-len", "16",
        "--batch-size", "4", "--app", "3", "--seed", "5", "--lr", "0.05"]


def _first_losses(argv, family, devices, steps=2):
    from benchmarks.common import build_train
    from mpi4dl_tpu.data import make_dataset
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh
    from mpi4dl_tpu.obs.spans import recorder
    from mpi4dl_tpu.resilience import run_supervised

    cfg = config_from_args(get_parser().parse_args(argv))
    mesh = build_mesh(MeshSpec(data=1, stage=max(cfg.split_size, 1)), devices)
    step, state, _, global_batch = build_train(cfg, family, mesh)
    lines = []
    result = run_supervised(
        step, state, make_dataset(cfg), global_batch=global_batch,
        steps_per_epoch=steps, print_fn=lines.append)
    run = recorder().closed("run")[-1]
    losses = [float(l.split(" loss ")[1].split()[0]) for l in lines
              if l.startswith("epoch ")]
    return result, losses, recorder().closed("step", within=run), run


def test_one_chip_trains_through_build_train_and_run_supervised(tiny):
    result, losses, steps, run = _first_losses(ARGV, "lp", jax.devices()[:1], steps=3)
    assert result.anomalies == 0 and len(losses) == 3
    assert abs(losses[0] - np.log(300)) < 0.5 and losses[2] < losses[0]
    assert run.attrs["global_batch"] == 4
    # two expert layers of 4 x 16 tokens, four experts a token
    for s in steps:
        assert s.attrs["expert_assignments"] == 2 * 64 * 4
        assert 0 < s.attrs["expert_rows"] <= s.attrs["expert_assignments"]
        assert s.attrs["expert_rows"] == int(s.attrs["expert_rows"])
        assert s.attrs["expert_load_max_over_mean"] >= 1.0
    from mpi4dl_tpu.obs.spans import recorder

    summary = recorder().summary()
    assert summary["attention_paths"].get("einsum", 0) >= 1
    assert summary["expert_paths"].get("ragged_dot", 0) >= 2


@pytest.mark.parametrize("seq,pct", [(8192, "78"), (300, "0")])
def test_the_attention_site_counts_the_forward_kernels_whole_tiles(
        monkeypatch, seq, pct):
    """``flash_whole_tile_pct``, at trace time on the Pallas path alone: the
    share in percent of the forward kernel's live tiles that fold whole, 28
    of 36 at 8,192 tokens in tiles of 1,024; at 300 the one tile is on the
    diagonal.  The einsum path notes nothing."""
    import mpi4dl_tpu.config as config
    from mpi4dl_tpu.obs.spans import recorder

    layer = lfm2.Attention(64, 4, 2, 16, 10000.0, 1e-5)
    x = jax.ShapeDtypeStruct((1, seq, 64), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.key(0), x.shape)[0])
    noted = lambda: {path for kind, i, path in recorder()._sites
                     if kind == "flash_whole_tile_pct" and i == id(layer)}
    apply = lambda: jax.eval_shape(
        lambda p, x: layer.apply(p, x, ApplyCtx(train=True)), params, x)
    apply()
    assert noted() == set()
    monkeypatch.setattr(config, "is_tpu_backend", lambda: True)
    apply()
    assert noted() == {pct}
    assert recorder().site_paths("flash_whole_tile_pct")[pct] >= 1
    assert recorder().summary()["flash_whole_tile_pct"][pct] >= 1


def test_gpipe_over_two_stages_gives_the_one_chip_loss(tiny):
    assert len(jax.devices()) >= 2
    _, one, _, _ = _first_losses(ARGV, "lp", jax.devices()[:1])
    _, two, _, _ = _first_losses(
        ARGV + ["--split-size", "2", "--parts", "2"], "lp", jax.devices()[:2])
    assert two == pytest.approx(one, rel=2e-5)


@pytest.mark.parametrize("family, extra, why", [
    ("sp", [], "token model"),
    ("gems", ["--split-size", "2"], "token model"),
    ("gems_sp", ["--split-size", "2"], "token model"),
    ("lp", ["--split-size", "2", "--schedule", "1f1b"], "token model"),
    ("lp", ["--split-size", "2", "--precision", "bf_16"], "fp_32"),
])
def test_other_families_refuse_a_token_model(tiny, family, extra, why):
    from benchmarks.common import build_train

    cfg = config_from_args(get_parser().parse_args(ARGV + extra))
    with pytest.raises(ValueError, match=why):
        build_train(cfg, family, None)


def test_build_model_states_the_cut_in_flags_only():
    cfg = config_from_args(get_parser().parse_args(
        ["--model", "lfm2_moe", "--num-layers", "9", "--vocab-size", "8192",
         "--experts-held", "8", "--seq-len", "8192", "--batch-size", "4",
         "--precision", "bf_16"]))
    model = build_model(cfg)
    assert len(model.cells) == 11 and model.in_shape == (4, 8192)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 849_429_312
    moe_layer = shapes[2]["ffn"]
    assert moe_layer["router"]["kernel"].shape == (2048, 64)
    assert moe_layer["experts"]["w1"].shape == (8, 2048, 1536)
    assert shapes[1]["ffn"]["w1"]["kernel"].shape == (2048, 11776)
    assert shapes[2]["op"]["k_proj"]["kernel"].shape == (2048, 8 * 64)
    assert shapes[0]["table"].shape == shapes[-1]["head"]["kernel"].shape[::-1]
