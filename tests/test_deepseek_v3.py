"""``deepseek_v3`` (Kanana-2-30B-A3B) as a token ``CellModel``: the program
against the benchmark's plain reference (perfbench/references/deepseek_v3.py,
which shares no code with it) at small widths on the CPU, the attention kernel
at a value width other than the key width, the shares of the expert layer with
the shared expert counted once, and the path through ``build_train`` and
``run_supervised``."""

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
from mpi4dl_tpu.ops import moe
from mpi4dl_tpu.ops.pallas_attention import (
    _reference_mlo, block_flash, flash_attention_local)
from mpi4dl_tpu.train import cross_entropy

import mpi4dl_tpu.models.deepseek_v3 as dsv3
from test_lfm2 import _batch, _close, _first_losses

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# hidden 64, 4 heads of 16 + 8 with values of 16, latent rank 32, 16 experts
# of 24, three a token, a shared expert of 2 x 24, a dense width of 96
TINY = dataclasses.replace(
    dsv3.PUBLISHED, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=24, num_attention_heads=4, num_key_value_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
    kv_lora_rank=32, n_routed_experts=16, num_experts_per_tok=3)
CTX = ApplyCtx(train=True)


def _reference():
    path = os.path.join(ROOT, "perfbench", "references", "deepseek_v3.py")
    spec = importlib.util.spec_from_file_location("reference_deepseek_v3", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def _sizes(config, num_layers, vocab, held, first=0):
    """What the configuration's file states for the reference, for ``config``."""
    run = lfm2.layers_run(config, num_layers)
    return {
        "num_layers": num_layers,
        "dense_layers": sum(i < config.first_k_dense_replace for i in run),
        "hidden_size": config.hidden_size,
        "num_attention_heads": config.num_attention_heads,
        "qk_nope_head_dim": config.qk_nope_head_dim,
        "qk_rope_head_dim": config.qk_rope_head_dim,
        "v_head_dim": config.v_head_dim, "kv_lora_rank": config.kv_lora_rank,
        "rms_norm_eps": config.rms_norm_eps, "rope_theta": config.rope_theta,
        "moe_intermediate_size": config.moe_intermediate_size,
        "n_shared_experts": config.n_shared_experts,
        "n_routed_experts": held,
        "n_routed_experts_published": config.n_routed_experts,
        "expert_first": first,
        "num_experts_per_tok": config.num_experts_per_tok,
        "routed_scaling_factor": config.routed_scaling_factor,
        "vocab_size": vocab,
    }


@pytest.fixture
def tiny(monkeypatch):
    """The published config at toy widths, for what builds from flags."""
    monkeypatch.setattr(dsv3, "PUBLISHED", TINY)
    return TINY


def _model(num_layers=3, vocab=50, held=4, first=4, batch=2, seq=24, config=TINY):
    model = dsv3.deepseek_v3((batch, seq), num_layers=num_layers,
                             vocab_size=vocab, experts_held=held,
                             expert_first=first, config=config)
    params, _ = model.init(jax.random.key(3))
    return model, params, _sizes(config, num_layers, vocab, held, first)


# --- the program against the reference, float32 -------------------------------


def test_the_cut_keeps_layer_0_once():
    assert lfm2.layers_run(dsv3.PUBLISHED, 5) == (0, 1, 2, 3, 4)
    assert lfm2.layers_run(dsv3.PUBLISHED, 48) == tuple(range(48))
    with pytest.raises(ValueError):
        lfm2.layers_run(dsv3.PUBLISHED, 49)
    model, _, _ = _model(num_layers=5)
    assert [c.name for c in model.cells] == [
        "embed", "layer00_mla", "layer01_mla", "layer02_mla", "layer03_mla",
        "layer04_mla", "norm_head"]
    assert isinstance(model.cells[1].ffn, lfm2.SwiGLU)
    assert all(isinstance(c.ffn, dsv3.SharedAndRoutedExperts)
               and isinstance(c.ffn.routed, moe.RoutedExperts)
               and isinstance(c.ffn.shared, lfm2.SwiGLU)
               and isinstance(c.op, dsv3.LatentAttention)
               for c in model.cells[2:6])
    assert model.cells[2].ffn.shared.ffn == 2 * TINY.moe_intermediate_size
    assert model.cells[2].ffn.routed.sum_eps == 1e-20


@pytest.mark.parametrize("name, bad", [
    ("q_lora_rank", {"q_lora_rank": 1536}),
    ("n_group", {"n_group": 8, "topk_group": 4}),
    ("rope_scaling", {"rope_scaling": {"type": "yarn", "factor": 40}}),
    ("attention_bias", {"attention_bias": True}),
])
def test_what_the_model_does_not_compute_is_refused(name, bad):
    with pytest.raises(ValueError, match=name):
        _model(config=dataclasses.replace(TINY, **bad))


@pytest.mark.parametrize("cell", [0, 1, 2, 4], ids=[
    "embedding", "latent+dense", "latent+experts", "norm+head"])
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


# --- latent attention ------------------------------------------------------------


def test_the_rotary_embedding_turns_pairs():
    """Column pair ``(2i, 2i+1)`` as a complex number, times
    ``exp(j pos theta^(-2i/hd))``; the result lies evens first, then odds."""
    theta, hd = 100.0, 8
    x = np.random.default_rng(0).standard_normal((1, 5, 2, hd)).astype(np.float32)
    got = np.asarray(dsv3.rotary_interleaved(jnp.asarray(x), theta))
    pos = np.arange(5)[:, None]
    turn = np.exp(1j * pos * theta ** (-np.arange(0, hd, 2) / hd))[None, :, None, :]
    want = (x[..., 0::2] + 1j * x[..., 1::2]) * turn
    np.testing.assert_allclose(got[..., :hd // 2], want.real, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[..., hd // 2:], want.imag, rtol=1e-5, atol=1e-6)
    # the same scores as the reference's pairs turned in place
    in_place = np.asarray(REF.rotate_pairs(jnp.asarray(x), theta))
    np.testing.assert_allclose(
        np.einsum("bqhd,bkhd->bhqk", got, got),
        np.einsum("bqhd,bkhd->bhqk", in_place, in_place), rtol=1e-4, atol=1e-5)


def test_the_rotary_key_is_one_for_all_heads():
    """``kv_a_proj_with_mqa`` gives the compressed row and ONE rotary key: a
    change to that key's columns of the kernel moves every head's output, and
    the parts carry the config's names and shapes."""
    layer = dsv3._block(TINY, 1, 4, 0).op
    params, _ = layer.init(jax.random.key(0), (1, 12, 64))
    assert {n: p["kernel"].shape for n, p in params.items() if "kernel" in p} == {
        "q_proj": (64, 4 * 24), "kv_a_proj_with_mqa": (64, 32 + 8),
        "kv_b_proj": (32, 4 * (16 + 16)), "o_proj": (4 * 16, 64)}
    assert params["kv_a_layernorm"]["scale"].shape == (32,)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 12, 64), np.float32))

    def heads_out(p):
        """The layer's output before ``o_proj``, a head at a time."""
        eye = dict(p, o_proj={"kernel": jnp.eye(64)})
        return layer.apply(eye, x, CTX).reshape(1, 12, 4, 16)

    bumped = jax.tree.map(lambda a: a, params)
    k = bumped["kv_a_proj_with_mqa"]["kernel"]
    bumped["kv_a_proj_with_mqa"] = {"kernel": k.at[:, 32:].multiply(1.5)}
    moved = np.abs(np.asarray(heads_out(bumped) - heads_out(params))).max(axis=(0, 1, 3))
    assert np.all(moved > 1e-6), moved


# --- the attention kernel at a value width of its own ---------------------------


def _qkv(d, dv, bh=3, t=40, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    make = lambda w: jnp.asarray(rng.standard_normal((bh, t, w), np.float32), dtype)
    return make(d), make(d), make(dv)


@pytest.mark.parametrize("causal", [True, False])
def test_block_flash_takes_values_narrower_than_keys(causal):
    """Keys of 24 and values of 16 through the kernel in interpret mode:
    forward and gradients against the einsum reference of the block state."""
    q, k, v = _qkv(24, 16)
    scale = 24 ** -0.5
    zero = jnp.zeros((), jnp.int32)

    def through(fn):
        def loss(q, k, v):
            o, m, l = fn(q, k, v)
            assert o.shape == (3, 40, 16) and m.shape == l.shape == (3, 40)
            return jnp.sum(jnp.sin(o / l[..., None])), (o, m, l)
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)

    with jax.default_matmul_precision("highest"):
        (_, got), g_got = through(lambda q, k, v: block_flash(
            q, k, v, zero, zero, causal, scale, 16, 128, True))
        (_, want), g_want = through(lambda q, k, v: _reference_mlo(
            q, k, v, zero, zero, causal, scale))
    for a, b in zip(got, want):
        _close(a, b, tol=1e-5)
    for a, b in zip(g_got, g_want):
        assert a.shape == b.shape
        _close(a, b, tol=1e-4)


def test_block_flash_at_unequal_widths_is_the_equal_width_kernel_on_padded_values():
    """Values of 16 beside keys of 24 give the first 16 columns of what the
    equal-width kernel (the parent's path) gives for the values padded with
    zeros to 24, state and gradients alike."""
    q, k, v = _qkv(24, 16, seed=1)
    v_wide = jnp.pad(v, ((0, 0), (0, 0), (0, 8)))
    zero = jnp.zeros((), jnp.int32)

    def run(v, cols):
        def loss(q, k, v):
            o, m, l = block_flash(q, k, v, zero, zero, True, 0.2, 16, 128, True)
            return jnp.sum(jnp.cos(o[..., :cols] / l[..., None])), (o, m, l)
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)

    with jax.default_matmul_precision("highest"):
        (_, (o, m, l)), (dq, dk, dv) = run(v, 16)
        (_, (o_w, m_w, l_w)), (dq_w, dk_w, dv_w) = run(v_wide, 16)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m_w))
    np.testing.assert_array_equal(np.asarray(l), np.asarray(l_w))
    _close(o, o_w[..., :16], tol=1e-6)
    assert not np.any(np.asarray(o_w[..., 16:]))
    _close(dq, dq_w, tol=1e-5)
    _close(dk, dk_w, tol=1e-5)
    _close(dv, dv_w[..., :16], tol=1e-5)


def test_flash_attention_local_gives_the_values_width():
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.standard_normal((2, 24, 3, 24), np.float32))
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((2, 24, 3, 16), np.float32))
    from mpi4dl_tpu.ops.ring import ring_attention

    with jax.default_matmul_precision("highest"):
        got = flash_attention_local(q, k, v, causal=True, interpret=True)
        want = ring_attention(q, k, v, None, 1, causal=True, use_flash=False)
    assert got.shape == want.shape == (2, 24, 3, 16)
    _close(got, want, tol=1e-5)


@pytest.mark.parametrize("batch, seq", [(2, 40), (1, 24)])
def test_latent_attention_by_the_pallas_kernel_is_the_einsum_path(
        monkeypatch, rec, batch, seq):
    """``LatentAttention`` as a TPU backend takes it (``latent_flash`` on the
    projections' layout, here in interpret mode: the test steers, the program
    has no switch) against the einsum path on the same parameters: output and
    gradients with respect to every parameter and the input.  The flash path
    projects the ``rope`` columns apart, evens before odds, and turns halves;
    the einsum path gathers every second column of the activation."""
    import functools

    import mpi4dl_tpu.config as config
    from mpi4dl_tpu.ops import pallas_latent_attention

    layer = dsv3._block(TINY, 1, 4, 0).op
    params, _ = layer.init(jax.random.key(0), (batch, seq, TINY.hidden_size))
    x = jax.random.normal(jax.random.key(1), (batch, seq, TINY.hidden_size))

    def run():
        def loss(p, x):
            y = layer.apply(p, x, CTX)
            return jnp.sum(jnp.sin(y)), y
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, (0, 1), has_aux=True)(params, x)

    (_, want), g_want = run()
    assert rec.site_paths("attention") == {"latent_einsum": 1}
    monkeypatch.setattr(config, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(
        pallas_latent_attention, "latent_flash",
        functools.partial(pallas_latent_attention.latent_flash, interpret=True))
    (_, got), g_got = run()
    assert rec.site_paths("attention") == {"latent_einsum": 1,
                                           "latent_block_flash": 1}
    assert got.shape == want.shape == (batch, seq, TINY.hidden_size)
    _close(got, want, tol=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert a.shape == b.shape
        _close(a, b, tol=1e-5)


# --- the expert layer and its shares --------------------------------------------


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """16 experts at a small width over 8 chips of 2: the routed parts that the
    eight chips compute, and the shared expert counted ONCE (every chip
    computes it alike, for its own tokens), add up to the uncut reference's
    layer; and each chip's whole layer is the reference's for its share."""
    uncut = dsv3._block(TINY, 1, 16, 0).ffn
    p_full, _ = uncut.init(jax.random.key(0), (1, 96, 64))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 96, 64), np.float32))

    def share(first, held):
        return {"router": p_full["router"], "load": p_full["load"][:held],
                "shared_experts": p_full["shared_experts"],
                "experts": {k: v[first:first + held]
                            for k, v in p_full["experts"].items()}}

    with jax.default_matmul_precision("highest"):
        want = REF.experts(x, p_full, _sizes(TINY, 3, 50, 16, 0), None)
        parts, rows = [], 0.0
        for chip in range(8):
            layer = dsv3._block(TINY, 1, 2, 2 * chip).ffn
            p, sink = share(2 * chip, 2), {}
            ctx = dataclasses.replace(CTX, bn_sink=sink)
            parts.append(layer.routed.apply(p, x, ctx))
            rows += float(jnp.sum(sink[id(p["load"])]))
            _close(layer.apply(p, x, ctx),
                   REF.experts(x, p, _sizes(TINY, 3, 50, 2, 2 * chip), None))
        once = uncut.shared.apply(p_full["shared_experts"], x, CTX)
    assert rows == pytest.approx(1.0, abs=1e-6)  # every assignment on one chip
    assert float(jnp.max(jnp.abs(parts[0]))) > 0 and float(jnp.max(jnp.abs(once))) > 0
    _close(sum(parts) + once, want)
    assert float(jnp.max(jnp.abs(sum(parts) + 8 * once - want))) > 1e-3


def test_the_weights_are_the_scores_over_their_sum_times_the_scaling():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((40, 32), np.float32))
    kernel = jnp.asarray(rng.standard_normal((32, 16), np.float32)) * 0.2
    bias = jnp.asarray(rng.standard_normal((16,), np.float32)) * 0.3
    sizes = {"num_experts_per_tok": 3, "routed_scaling_factor": 2.448}
    with jax.default_matmul_precision("highest"):
        chosen, w = moe.route(x, kernel, bias, 3, 2.448, 1e-20)
        ref_chosen, ref_w = REF.route(x, {"kernel": kernel, "bias": bias}, sizes)
        lfm2s, _ = moe.route(x, kernel, bias, 3, 2.448)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(ref_chosen))
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(lfm2s))
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref_w), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.448, rtol=1e-6)


def test_round_capacity_at_the_published_shape():
    # 32,768 tokens, six experts each, 16 of 128 held: 24,576 balanced
    assert moe.round_capacity(32768 * 6, 16, 128) == 30720


# --- the path through the trainer ------------------------------------------------


ARGV = ["--model", "deepseek_v3", "--num-layers", "3", "--vocab-size", "300",
        "--experts-held", "4", "--expert-first", "8", "--seq-len", "16",
        "--batch-size", "4", "--app", "3", "--seed", "5", "--lr", "0.05"]


def test_one_chip_trains_through_build_train_and_run_supervised(tiny):
    result, losses, steps, run = _first_losses(ARGV, "lp", jax.devices()[:1], steps=3)
    assert result.anomalies == 0 and len(losses) == 3
    assert abs(losses[0] - np.log(300)) < 0.5 and len(set(losses)) == 3
    assert all(np.isfinite(losses))
    assert run.attrs["global_batch"] == 4
    # two expert layers of 4 x 16 tokens, three experts a token
    for s in steps:
        assert s.attrs["expert_assignments"] == 2 * 64 * 3
        assert 0 < s.attrs["expert_rows"] <= s.attrs["expert_assignments"]
        assert s.attrs["expert_rows"] == int(s.attrs["expert_rows"])
        assert s.attrs["expert_load_max_over_mean"] >= 1.0
    from mpi4dl_tpu.obs.spans import recorder

    summary = recorder().summary()
    assert summary["attention_paths"].get("latent_einsum", 0) >= 3
    assert summary["expert_paths"].get("ragged_dot", 0) >= 2
    assert summary["shared_expert_paths"].get("swiglu", 0) >= 2


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
def test_other_families_refuse_this_token_model_too(tiny, family, extra, why):
    from benchmarks.common import build_train

    cfg = config_from_args(get_parser().parse_args(ARGV + extra))
    with pytest.raises(ValueError, match=why):
        build_train(cfg, family, None)


def test_build_model_states_the_cut_in_flags_only():
    cfg = config_from_args(get_parser().parse_args(
        ["--model", "deepseek_v3", "--num-layers", "5", "--vocab-size", "16032",
         "--experts-held", "16", "--seq-len", "8192", "--batch-size", "4",
         "--precision", "bf_16"]))
    assert cfg.is_token_model
    model = build_model(cfg)
    assert len(model.cells) == 7 and model.in_shape == (4, 8192)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.key(0))
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    # q 12,582,912 + kv_a 1,179,648 + its norm 512 + kv_b 4,194,304 + o 8,388,608
    assert count(shapes[1]["op"]) == 26_345_984
    assert count(shapes[1]) == 26_345_984 + 2 * 2048 + 3 * 2048 * 6144
    assert count(shapes[2]) == (26_345_984 + 2 * 2048 + 3 * 2048 * 1536
                                + 2048 * 128 + 128 + 16 + 16 * 3 * 2048 * 768)
    assert count(shapes) == 575_956_032
    ffn = shapes[2]["ffn"]
    assert ffn["router"]["kernel"].shape == (2048, 128)
    assert ffn["experts"]["w1"].shape == (16, 2048, 768)
    assert ffn["shared_experts"]["w1"]["kernel"].shape == (2048, 1536)
    assert shapes[1]["ffn"]["w1"]["kernel"].shape == (2048, 6144)
    assert shapes[2]["op"]["kv_a_proj_with_mqa"]["kernel"].shape == (2048, 576)
    assert shapes[2]["op"]["kv_b_proj"]["kernel"].shape == (512, 32 * 256)
    assert shapes[0]["table"].shape == shapes[-1]["head"]["kernel"].shape[::-1]
