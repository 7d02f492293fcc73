"""``keye_vl2`` (Keye-VL-2.0-30B-A3B's language model) as a token
``CellModel``: the program against the benchmark's plain reference
(perfbench/references/keye_vl2.py, which shares no code with it) at small
widths on the CPU, the exact top-k selection and its tie rule, the Pallas
kernels in interpret mode against XLA's products (the attention under a
selection, forward and backward; the selection; the indexer loss's
gradient), the softmax router, the shares of the expert layer, and the path
through ``build_train`` and ``run_supervised``."""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.config import config_from_args, get_parser
from mpi4dl_tpu.layer_ctx import ApplyCtx
from mpi4dl_tpu.models import build_model, lfm2
from mpi4dl_tpu.ops import moe, pallas_attention, sparse_indexer
from mpi4dl_tpu.train import cross_entropy

import mpi4dl_tpu.models.keye_vl2 as keye
from test_lfm2 import _batch, _close, _first_losses

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# hidden 64, 4/2 heads of 16, an indexer of 4 heads of 8 choosing 8 keys,
# 8 experts of 24, two a token
TINY = dataclasses.replace(
    keye.PUBLISHED, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2,
    rope_scaling={"mrope_section": (2, 3, 3), "rope_type": "default",
                  "type": "default"},
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 8})
CTX = ApplyCtx(train=True)
SEQ = 64


def _reference():
    path = os.path.join(ROOT, "perfbench", "references", "keye_vl2.py")
    spec = importlib.util.spec_from_file_location("reference_keye_vl2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def _sizes(config, num_layers, vocab, held, first=0):
    """What the configuration's file states for the reference, for ``config``."""
    sa = config.sa_config
    return {
        "num_layers": num_layers, "hidden_size": config.hidden_size,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "head_dim": config.head_dim, "rms_norm_eps": config.rms_norm_eps,
        "rope_theta": config.rope_theta,
        "indexer_num_heads": sa["indexer_num_heads"],
        "indexer_head_dim": sa["indexer_head_dim"], "topk": sa["topk"],
        "moe_intermediate_size": config.moe_intermediate_size,
        "num_experts": held, "num_experts_published": config.num_experts,
        "expert_first": first, "num_experts_per_tok": config.num_experts_per_tok,
        "vocab_size": vocab,
    }


@pytest.fixture
def tiny(monkeypatch):
    """The published config at toy widths, for what builds from flags."""
    monkeypatch.setattr(keye, "PUBLISHED", TINY)
    return TINY


def _model(num_layers=2, vocab=50, held=4, first=2, batch=2, seq=SEQ,
           config=TINY):
    model = keye.keye_vl2((batch, seq), num_layers=num_layers, vocab_size=vocab,
                          experts_held=held, expert_first=first, config=config)
    params, _ = model.init(jax.random.key(3))
    return model, params, _sizes(config, num_layers, vocab, held, first)


def _pallas(monkeypatch):
    """The path a TPU backend takes, its kernels in interpret mode (the
    test steers; the program has no switch)."""
    import mpi4dl_tpu.config as config

    monkeypatch.setattr(config, "is_tpu_backend", lambda: True)
    for module, name in ((pallas_attention, "sparse_flash_forward"),
                         (pallas_attention, "sparse_flash_backward"),
                         (sparse_indexer, "indexer_select"),
                         (sparse_indexer, "indexer_backward")):
        monkeypatch.setattr(module, name, functools.partial(
            getattr(module, name), interpret=True))


# --- the model -----------------------------------------------------------------


def test_the_cut_runs_published_layers_0_to_3():
    assert lfm2.layers_run(keye.PUBLISHED, 4) == (0, 1, 2, 3)
    assert lfm2.layers_run(keye.PUBLISHED, 48) == tuple(range(48))
    model, _, _ = _model(num_layers=4)
    assert [c.name for c in model.cells] == [
        "embed", "layer00_dsa", "layer01_dsa", "layer02_dsa", "layer03_dsa",
        "norm_head"]
    for cell in model.cells[1:5]:
        assert isinstance(cell.op, keye.SparseAttention)
        assert isinstance(cell.ffn, moe.RoutedExperts)
        assert cell.ffn.scoring == "softmax" and cell.ffn.sum_eps == 0.0
        assert cell.op.topk == 8 and cell.op.attention.kv_heads == 2


@pytest.mark.parametrize("name, bad", [
    ("mlp_only_layers", {"mlp_only_layers": (0,)}),
    ("decoder_sparse_step", {"decoder_sparse_step": 2}),
    ("use_sliding_window", {"use_sliding_window": True}),
    ("indexer_num_kv_heads", {"sa_config": {**TINY.sa_config,
                                            "indexer_num_kv_heads": 2}}),
])
def test_what_the_model_does_not_compute_is_refused(name, bad):
    with pytest.raises(ValueError, match=name):
        _model(config=dataclasses.replace(TINY, **bad))


@pytest.mark.parametrize("cell", [0, 1, 3], ids=["embedding", "layer", "norm+head"])
def test_each_cell_kind_matches_the_reference(cell):
    model, params, sizes = _model()
    ref_cells = REF.cells(params, sizes)
    x, _ = _batch(seq=SEQ)
    act = x if cell == 0 else jnp.asarray(np.random.default_rng(cell).standard_normal(
        (2, SEQ, TINY.hidden_size), np.float32)) * 0.5
    with jax.default_matmul_precision("highest"):
        got = model.cells[cell].apply(params[cell], act, CTX)
        want = ref_cells[cell](act)
    assert got.shape == want.shape and got.dtype == jnp.float32
    _close(got, want)


def _grads(model, params, sizes, x, y):
    def program(p):
        return cross_entropy(model.apply(p, x, CTX), y)

    def lm_only(p):
        act = x
        for fn in REF.cells(p, sizes):
            act = fn(act)
        return cross_entropy(act, y)

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(program)(params)
        want = lm_only(params)
        g_want = jax.grad(lambda p: REF.loss_with_indexer(p, sizes, x, y))(params)
        g_lm = jax.grad(lm_only)(params)
    return got, want, g_got, g_want, g_lm


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_the_loss_and_every_gradient_match_the_reference(path, monkeypatch):
    """The step's loss is the LM cross-entropy; every parameter's gradient is
    the reference's of the LM loss plus the indexer loss, the indexer's
    being the indexer loss's alone (the LM loss gives it none).  ``pallas``:
    the TPU path, its kernels in interpret mode."""
    if path == "pallas":
        _pallas(monkeypatch)
    model, params, sizes = _model()
    x, y = _batch(seq=SEQ)
    got, want, g_got, g_want, g_lm = _grads(model, params, sizes, x, y)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    paths = jax.tree_util.tree_flatten_with_path(g_want)[0]
    assert len(paths) == len(jax.tree.leaves(g_got)) > 30
    indexer = 0
    for (path_, want_leaf), got_leaf, lm_leaf in zip(
            paths, jax.tree.leaves(g_got), jax.tree.leaves(g_lm)):
        name = jax.tree_util.keystr(path_)
        if name.endswith("['load']") or name.endswith("['sparse_kl']"):
            assert not np.any(np.asarray(got_leaf)), name  # statistics
            continue
        if "indexer" in name:
            indexer += 1
            assert not np.any(np.asarray(lm_leaf)), name
        assert float(jnp.max(jnp.abs(want_leaf))) > 0, name
        _close(got_leaf, want_leaf, tol=2e-4)
    assert indexer == 2 * 5  # wq, wk, k_norm (scale, bias), weights_proj


def test_removing_the_selection_fails_the_comparison():
    """The reference with every causal key selected (dense causal attention)
    is far from the program, where the reference as published agrees with
    it: the selection is what the layer computes."""
    model, params, sizes = _model()
    x, y = _batch(seq=SEQ)
    with jax.default_matmul_precision("highest"):
        act = jnp.asarray(np.random.default_rng(1).standard_normal(
            (2, SEQ, TINY.hidden_size), np.float32)) * 0.5
        got = model.cells[1].apply(params[1], act, CTX)
        want = REF.cells(params, sizes)[1](act)
        dense = REF.cells(params, {**sizes, "topk": SEQ})[1](act)
    err = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    assert err(got, want) < 1e-5
    assert err(got, dense) > 100 * err(got, want) and err(got, dense) > 1e-3


# --- the selection ----------------------------------------------------------------


def _indexer_inputs(seed, ints, b=2, t=SEQ, heads=4, dim=8):
    rng = np.random.default_rng(seed)
    if ints:  # exact scores, with many ties
        make = lambda *s: rng.integers(-2, 3, s).astype(np.float32)
    else:
        make = lambda *s: rng.standard_normal(s).astype(np.float32)
    return (jnp.asarray(make(b, t, heads, dim), jnp.bfloat16),
            jnp.asarray(make(b, t, dim), jnp.bfloat16),
            jnp.asarray(make(b, t, heads)))


def _top_k_reference(iq, ik, w, topk):
    """``lax.top_k`` on the causal scores, the first ``min(topk, t + 1)``."""
    scores = np.asarray(sparse_indexer.scores_dense(iq, ik, w))
    t = scores.shape[1]
    masked = np.where(np.tril(np.ones((t, t), bool)), scores, -np.inf)
    _, top = jax.lax.top_k(jnp.asarray(masked), min(topk, t))
    sel = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for q in range(t):
            sel[b, q, np.asarray(top[b, q])[:min(topk, q + 1)]] = True
    return sel


@pytest.mark.parametrize("ints", [False, True], ids=["floats", "ties"])
@pytest.mark.parametrize("topk", [8, 24])
def test_the_selection_is_lax_top_k_exactly(ints, topk):
    """Rows before ``topk`` keep every key; after it, exactly ``topk``, the
    same as ``lax.top_k`` (a tie to the lower position); the log-sum-exp is
    the selected scores'."""
    iq, ik, w = _indexer_inputs(0, ints)
    words_t, lse = sparse_indexer.select_dense(iq, ik, w, topk)
    sel = np.asarray(sparse_indexer.unpack_selection(
        jnp.swapaxes(words_t, 1, 2), SEQ))
    np.testing.assert_array_equal(sel, _top_k_reference(iq, ik, w, topk))
    np.testing.assert_array_equal(
        sel.sum(-1), np.minimum(topk, np.arange(SEQ) + 1)[None].repeat(2, 0))
    scores = np.asarray(sparse_indexer.scores_dense(iq, ik, w), np.float64)
    want = np.log(np.sum(np.where(sel, np.exp(scores), 0.0), axis=-1))
    np.testing.assert_allclose(np.asarray(lse), want, rtol=1e-5, atol=1e-5)


def test_a_planted_tie_goes_to_the_lower_position():
    """Every score of a row equal: the row keeps its first ``topk`` keys."""
    b, t, topk = 1, 40, 8
    iq = jnp.zeros((b, t, 2, 4), jnp.bfloat16)
    ik = jnp.zeros((b, t, 4), jnp.bfloat16)
    w = jnp.ones((b, t, 2))
    for select in (sparse_indexer.select_dense,
                   functools.partial(sparse_indexer.indexer_select,
                                     interpret=True)):
        words_t, _ = select(iq, ik, w, topk)
        sel = np.asarray(sparse_indexer.unpack_selection(
            jnp.swapaxes(words_t, 1, 2), t))[0]
        for q in range(t):
            np.testing.assert_array_equal(
                np.flatnonzero(sel[q]), np.arange(min(topk, q + 1)))


@pytest.mark.parametrize("ints", [False, True], ids=["floats", "ties"])
def test_the_selection_kernel_is_the_dense_selection(ints):
    """``sparse_indexer_select`` in interpret mode: the same words as XLA's
    products, the same log-sum-exp, and its own scores those of the dense
    form where a key is seen, at a length that leaves a block of padding."""
    iq, ik, w = _indexer_inputs(1, ints, t=200)
    want_words, want_lse = sparse_indexer.select_dense(iq, ik, w, 16)
    words_t, lse, scores = sparse_indexer.indexer_select(
        iq, ik, w, 16, interpret=True, with_scores=True)
    np.testing.assert_array_equal(np.asarray(words_t), np.asarray(want_words))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), rtol=1e-6)
    dense = np.swapaxes(np.asarray(sparse_indexer.scores_dense(iq, ik, w)), 1, 2)
    seen = np.isfinite(np.asarray(scores))
    assert seen.sum() == 2 * 200 * 201 // 2
    np.testing.assert_allclose(np.asarray(scores)[seen], dense[seen], rtol=1e-6,
                               atol=1e-6)


def test_the_words_hold_a_key_at_bit_s_over_w_of_word_s_mod_w():
    rng = np.random.default_rng(0)
    sel = rng.random((2, 5, 300)) < 0.3
    words = sparse_indexer.pack_selection(jnp.asarray(sel))
    width = pallas_attention.selection_width(300)
    assert words.shape == (2, 5, width) and width == 128
    raw = np.asarray(words).view(np.uint32)
    for s in (0, 127, 128, 299):
        np.testing.assert_array_equal((raw[:, :, s % width] >> (s // width)) & 1,
                                      sel[:, :, s])
    np.testing.assert_array_equal(
        np.asarray(sparse_indexer.unpack_selection(words, 300)), sel)


# --- the kernels under a selection ---------------------------------------------------


def _selection(b, t, seed, density=0.3):
    rng = np.random.default_rng(seed)
    sel = (rng.random((b, t, t)) < density) & np.tril(np.ones((t, t), bool))
    sel[:, np.arange(t), np.arange(t)] = True
    sel[:, t // 3:t // 2, :t // 4] = False  # tiles no query chose
    return sel


@pytest.mark.parametrize("rep", [1, 3])
@pytest.mark.parametrize("t, tk", [(300, 128), (300, 256), (64, 128)])
def test_the_attention_kernels_under_a_selection_match_the_dense_masked_einsum(
        t, tk, rep):
    """``sparse_flash_fwd`` and ``sparse_flash_bwd`` in interpret mode
    against einsums over the whole masked score matrix, three heads a
    sequence sharing its selection: the output and the row maxima, and every
    cotangent of the output, with a tile that spans one bit and one that
    spans two.  Both kernels take the key-value heads as they are, ``rep`` query
    heads a group (3: one group a sequence); the einsums take them repeated,
    so their k and v cotangents are the group's sums."""
    b, heads, d = 2, 3, 32
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.standard_normal((b * heads, t, d)), jnp.float32)
    k_kv, v_kv = (jnp.asarray(rng.standard_normal((b * heads // rep, t, d)),
                              jnp.float32) for _ in range(2))
    sel = _selection(b, t, 0)
    words = sparse_indexer.pack_selection(jnp.asarray(sel))
    mask = jnp.repeat(jnp.asarray(sel), heads, axis=0)
    scale = d ** -0.5

    def dense(q, k_kv, v_kv):
        k, v = (jnp.repeat(x, rep, axis=0) for x in (k_kv, v_kv))
        s = jnp.where(mask, jnp.einsum("bqd,bkd->bqk", q, k) * scale, -1e30)
        m = jax.lax.stop_gradient(jnp.max(s, -1))
        p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
        return jnp.einsum("bqk,bkd->bqd", p, v), m, p.sum(-1)

    with jax.default_matmul_precision("highest"):
        o, m, l = pallas_attention.sparse_flash_forward(
            q, k_kv, v_kv, words, heads=heads, scale=scale, tq=128, tk=tk,
            interpret=True)
        o_d, m_d, l_d = dense(q, k_kv, v_kv)
        do = jnp.asarray(rng.standard_normal(o.shape), jnp.float32)
        want = jax.vjp(lambda *a: (lambda o, _, l: o / l[..., None])(
            *dense(*a)), q, k_kv, v_kv)[1](do)
        got = pallas_attention.sparse_flash_backward(
            q, k_kv, v_kv, o, m, l, do,
            jnp.swapaxes(words, 1, 2), heads=heads, scale=scale,
            interpret=True)
    _close(o, o_d / l_d[..., None], tol=1e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_d), rtol=1e-5, atol=1e-5)
    for a, w_ in zip(got, want):
        assert a.shape == w_.shape
        _close(a, w_, tol=1e-5)


def test_the_whole_causal_selection_is_block_flash():
    """Every causal key selected: ``sparse_flash_fwd`` and
    ``sparse_flash_bwd`` (one key-value group of three heads a sequence)
    give what ``block_flash`` and its backward give under ``causal`` on the
    key-value heads repeated, the dense kernels whose tile code they follow:
    the backward handed ``o``'s cotangent gives what ``block_flash``'s gives
    from the cotangents of ``o_hat`` and ``l`` that it makes (dô / l and
    −Σ dô·o / l), its dk and dv the sums of the group's; the forward's
    output is ``block_flash``'s ``o_hat / l``."""
    b, heads, t, d = 2, 3, 300, 32
    rng = np.random.default_rng(2)
    q, k_kv, v_kv = (jnp.asarray(rng.standard_normal((n, t, d)), jnp.float32)
                     for n in (b * heads, b, b))
    k, v = (jnp.repeat(x, heads, axis=0) for x in (k_kv, v_kv))
    words = sparse_indexer.pack_selection(
        jnp.asarray(np.tril(np.ones((b, t, t), bool))))
    scale, zero = d ** -0.5, jnp.int32(0)
    do = jnp.asarray(rng.standard_normal((b * heads, t, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = pallas_attention.sparse_flash_forward(
            q, k_kv, v_kv, words, heads=heads, scale=scale, tq=128, tk=128,
            interpret=True)
        want = pallas_attention.block_flash(q, k, v, zero, zero, True, scale,
                                            128, 128, True)
        o, m, l = got
        g_got = pallas_attention.sparse_flash_backward(
            q, k_kv, v_kv, o, m, l, do, jnp.swapaxes(words, 1, 2),
            heads=heads, scale=scale, tq=128, tk=128, interpret=True)
        dq, dk, dv = pallas_attention.block_flash_backward(
            q, k, v, zero, zero, want[1], do / l[..., None],
            -jnp.sum(do * o, -1) / l, True, scale, 128, 128, True)
    group = lambda x: x.reshape(b, heads, t, d).sum(1)
    want = (want[0] / want[2][..., None], *want[1:])
    for a, w_ in zip((*got, *g_got), (*want, dq, group(dk), group(dv))):
        _close(a, w_, tol=1e-5)


@pytest.mark.parametrize("causal", [False, True], ids=["sparse", "causal"])
@pytest.mark.parametrize("rep", [1, 2, 8])
def test_the_grouped_backward_is_the_backward_on_repeated_heads_summed(
        rep, causal):
    """``sparse_flash_bwd`` over key-value groups of ``rep`` query heads
    against two things: what it gives on k and v repeated to one head a
    group (``rep`` 1: a head a grid step), its dk and dv then summed over
    each group (dq to the bit, dk and dv, summed in float32 before their one
    rounding, to float32's rounding), and the dense masked einsum's
    cotangents of the normalized output.  At a length that is no multiple
    of the tiles (300 tokens, q tiles of 128 and k tiles of 256), under a
    selection with a q tile that sees nothing in k tile 0 and tiles no
    query chose, and under ``causal``'s: every causal key."""
    b, heads, t, d = 2, 8, 300, 32
    kv = heads // rep
    rng = np.random.default_rng(10 + rep)
    q, k, v = (jnp.asarray(rng.standard_normal((n, t, d)), jnp.float32)
               for n in (b * heads, b * kv, b * kv))
    if causal:
        sel = np.tril(np.ones((b, t, t), bool))
    else:
        sel = _selection(b, t, 1)
        sel[:, 256:, :256] = False  # q tile 2 sees nothing in k tile 0
    words = sparse_indexer.pack_selection(jnp.asarray(sel))
    scale = d ** -0.5
    o, m, l = pallas_attention.sparse_flash_forward(
        q, k, v, words, heads=heads, scale=scale, tq=128, tk=256,
        interpret=True)
    do = jnp.asarray(rng.standard_normal(o.shape), jnp.float32)

    def backward(k, v):
        return pallas_attention.sparse_flash_backward(
            q, k, v, o, m, l, do, jnp.swapaxes(words, 1, 2), heads=heads,
            scale=scale, tq=128, tk=256, interpret=True)

    dq, dk, dv = backward(k, v)
    dq_1, dk_1, dv_1 = backward(*(jnp.repeat(x, rep, axis=0) for x in (k, v)))
    np.testing.assert_array_equal(np.asarray(dq), np.asarray(dq_1))
    group = lambda x: x.reshape(b * kv, rep, t, d).sum(1)
    for a, w_ in ((dk, group(dk_1)), (dv, group(dv_1))):
        assert a.shape == (b * kv, t, d)
        np.testing.assert_allclose(np.asarray(a), np.asarray(w_),
                                   rtol=1e-5, atol=1e-5)
    mask = jnp.repeat(jnp.asarray(sel), heads, axis=0)

    def dense(q, k, v):
        k, v = (jnp.repeat(x, rep, axis=0) for x in (k, v))
        s = jnp.where(mask, jnp.einsum("bqd,bkd->bqk", q, k) * scale, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)

    with jax.default_matmul_precision("highest"):
        want = jax.vjp(dense, q, k, v)[1](do)
    for a, w_ in zip((dq, dk, dv), want):
        _close(a, w_, tol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("rep", [1, 2, 8])
def test_the_grouped_forward_is_the_forward_on_repeated_heads_to_the_bit(
        rep, causal):
    """``sparse_flash_fwd`` over key-value groups of ``rep`` query heads
    gives, bit for bit, what it gives on k and v repeated to one head a group
    (``rep`` 1: a head a grid step), in bf16 at a length that is no multiple
    of the tiles, under a selection with a tile no query of its q tile chose
    a key in, whose queries' first k tile holds none of their keys (the rows
    the exp's guard is for), and under ``causal``'s: every causal key."""
    b, heads, t, d = 2, 8, 300, 32
    rng = np.random.default_rng(rep)
    q, k, v = (jnp.asarray(rng.standard_normal((n, t, d)), jnp.bfloat16)
               for n in (b * heads, b * heads // rep, b * heads // rep))
    if causal:
        sel = np.tril(np.ones((b, t, t), bool))
    else:
        sel = _selection(b, t, 1)
        sel[:, 256:, :256] = False  # q tile 2 (of 128) sees nothing in k tile 0
    words = sparse_indexer.pack_selection(jnp.asarray(sel))

    def forward(k, v):
        return pallas_attention.sparse_flash_forward(
            q, k, v, words, heads=heads, scale=d ** -0.5, tq=128, tk=256,
            interpret=True)

    got = forward(k, v)
    want = forward(*(jnp.repeat(x, rep, axis=0) for x in (k, v)))
    for a, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(w_))
    assert np.all(np.asarray(got[2]) >= 1)  # every query sees its own key


def test_the_indexer_gradient_kernel_is_the_indexer_loss_gradient():
    """``sparse_indexer_bwd`` in interpret mode, the attention's
    probabilities made in it from q, k and the row statistics, against XLA's
    products, and both against autodiff of the KL loss itself."""
    b, t, heads, kv, d = 2, 300, 4, 2, 16
    rng = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k = f(b, t, heads, d), f(b, t, kv, d)
    iq, ik, w = f(b, t, 4, 8), f(b, t, 8), f(b, t, 4)
    scale, inv_n = d ** -0.5, 1.0 / (b * t)
    with jax.default_matmul_precision("highest"):
        words_t, lse = sparse_indexer.select_dense(iq, ik, w, 24)
        sel = sparse_indexer.unpack_selection(jnp.swapaxes(words_t, 1, 2), t)
        p = sparse_indexer.head_mean_probs(q, k, sel, scale)
        want = sparse_indexer.indexer_grads_dense(p, iq, ik, w, sel, lse, inv_n)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)) * scale
        s = jnp.where(sel[:, None], s, -jnp.inf)
        m = jnp.max(s, -1)
        c = m + jnp.log(jnp.sum(jnp.exp(s - m[..., None]), -1))
        got = sparse_indexer.indexer_backward(
            q, k, c, iq, ik, w, words_t, lse, scale=scale, inv_n=inv_n,
            interpret=True)

        def kl(iq, ik, w):
            scores = sparse_indexer.scores_dense(iq, ik, w)
            log_soft = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), -1)
            keep = sel & (p > 0)
            return jnp.sum(jnp.where(keep, p * (jnp.log(jnp.where(keep, p, 1.0))
                                                - log_soft), 0.0)) * inv_n

        autodiff = jax.grad(kl, (0, 1, 2))(iq, ik, w)
    for a, b_, c_ in zip(got, want, autodiff):
        _close(a, b_, tol=1e-5)
        _close(b_, c_, tol=1e-5)


def test_the_step_reports_the_sampled_indexer_loss(monkeypatch):
    """What the layer writes through ``bn_sink``: the indexer loss over
    sampled queries, on both paths alike."""
    layer = keye._block(TINY, 0, 4, 0).op
    params, _ = layer.init(jax.random.key(0), (2, 300, 64))
    x = jax.random.normal(jax.random.key(1), (2, 300, 64))

    def kl():
        sink = {}
        layer.apply(params, x, dataclasses.replace(CTX, bn_sink=sink))
        return float(sink[id(params["sparse_kl"])])

    from mpi4dl_tpu.obs.spans import recorder

    kinds = ("sparse_plane_heads", "sparse_bwd_plane_heads")
    noted = lambda: {(kind, path) for kind, i, path in recorder()._sites
                     if kind in kinds and i == id(layer)}
    xla = kl()
    assert noted() == set()  # no plane on the einsum path
    _pallas(monkeypatch)
    assert 0 < xla and kl() == pytest.approx(xla, rel=1e-4)
    # TINY's 4 query heads over 2 key-value heads share a plane two by two,
    # forward and backward
    assert noted() == {(kind, "2") for kind in kinds}
    for kind in kinds:
        assert recorder().site_paths(kind)["2"] >= 1


# --- the expert layer --------------------------------------------------------------


def test_the_softmax_router_is_the_references():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((40, 32), np.float32))
    kernel = jnp.asarray(rng.standard_normal((32, 16), np.float32)) * 0.5
    with jax.default_matmul_precision("highest"):
        chosen, w = moe.route(x, kernel, None, 3, 1.0, 0.0, "softmax")
        ref_chosen, ref_w = REF.route(x, {"kernel": kernel},
                                      {"num_experts_per_tok": 3})
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(ref_chosen))
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref_w), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    probs = jax.nn.softmax(x @ kernel, axis=-1)
    assert np.all(np.asarray(jnp.take_along_axis(probs, chosen, -1))[:, -1]
                  >= np.sort(np.asarray(probs), -1)[:, -3] - 1e-7)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(x, kernel, None, 3, scoring="relu")


def test_the_two_shares_of_four_experts_add_up_to_the_uncut_layer():
    """Eight experts over two chips of four: the routed parts the two chips
    compute add up to the uncut reference's layer, each chip's part is the
    reference's for its share, and every assignment falls on one chip."""
    uncut = keye._block(TINY, 0, 8, 0).ffn
    p_full, _ = uncut.init(jax.random.key(0), (1, 96, 64))
    assert set(p_full["router"]) == {"kernel"}  # no bias under softmax scores
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 96, 64), np.float32))

    def share(first, held):
        return {"router": p_full["router"], "load": p_full["load"][:held],
                "experts": {k: v[first:first + held]
                            for k, v in p_full["experts"].items()}}

    with jax.default_matmul_precision("highest"):
        want = REF.experts(x, p_full, _sizes(TINY, 2, 50, 8, 0), None)
        parts, rows = [], 0.0
        for chip in range(2):
            layer = keye._block(TINY, 0, 4, 4 * chip).ffn
            p, sink = share(4 * chip, 4), {}
            parts.append(layer.apply(p, x, dataclasses.replace(CTX, bn_sink=sink)))
            rows += float(jnp.sum(sink[id(p["load"])]))
            _close(parts[-1], REF.experts(x, p, _sizes(TINY, 2, 50, 4, 4 * chip),
                                          None))
    assert rows == pytest.approx(1.0, abs=1e-6)
    assert all(float(jnp.max(jnp.abs(part))) > 0 for part in parts)
    _close(sum(parts), want)


# --- the path through the trainer ------------------------------------------------


ARGV = ["--model", "keye_vl2", "--num-layers", "2", "--vocab-size", "300",
        "--experts-held", "4", "--expert-first", "2", "--seq-len", "32",
        "--batch-size", "2", "--app", "3", "--seed", "5", "--lr", "0.05"]


def test_one_chip_trains_through_build_train_and_run_supervised(tiny):
    result, losses, steps, run = _first_losses(ARGV, "lp", jax.devices()[:1], steps=3)
    assert result.anomalies == 0 and len(losses) == 3
    assert abs(losses[0] - np.log(300)) < 0.5 and len(set(losses)) == 3
    assert run.attrs["global_batch"] == 2
    for s in steps:
        assert s.attrs["expert_assignments"] == 2 * 64 * 2
        assert 0 < s.attrs["expert_rows"] <= s.attrs["expert_assignments"]
        assert s.attrs["sparse_kl"] > 0
    from mpi4dl_tpu.obs.spans import recorder

    summary = recorder().summary()
    assert summary["attention_paths"].get("sparse_einsum", 0) >= 2
    assert summary["sparse_indexer_paths"].get("xla", 0) >= 2
    assert summary["expert_paths"].get("ragged_dot", 0) >= 2


def test_build_model_states_the_cut_in_flags_only():
    cfg = config_from_args(get_parser().parse_args(
        ["--model", "keye_vl2", "--num-layers", "4", "--vocab-size", "18992",
         "--experts-held", "16", "--seq-len", "16384", "--batch-size", "1",
         "--precision", "bf_16"]))
    assert cfg.is_token_model
    model = build_model(cfg)
    assert len(model.cells) == 6 and model.in_shape == (1, 16384)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.key(0))
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    layer = shapes[1]
    assert count(layer["op"]["indexer"]) == 2048 * (1024 + 64 + 16) + 128
    assert count(layer["op"]) - 1 == 18_874_624 + 2_261_120
    assert count(layer) - 1 - 16 == 96_899_456
    assert count(shapes) - 4 * (1 + 16) == 465_391_104
    assert layer["ffn"]["router"]["kernel"].shape == (2048, 128)
    assert layer["ffn"]["experts"]["w1"].shape == (16, 2048, 768)
    assert layer["op"]["k_proj"]["kernel"].shape == (2048, 512)
    assert shapes[0]["table"].shape == shapes[-1]["head"]["kernel"].shape[::-1]
