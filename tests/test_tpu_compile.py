"""The Pallas kernel and the convolution paths compiled by the chip's own
compiler, without the chip.

Interpret mode (every other kernel test here) cannot show a block shape
Mosaic refuses, a slice off the tiling, or a VMEM overrun; the TPU compiler
installed in this container can, for a chip that is described and not
attached.  The kernel's shapes are the ones ``chip_smoke.py`` runs on the
chip: a 4096-token, 128-wide head.  Each of ``Conv2d.apply``'s paths that
is not XLA's own convolution as it stands compiles at a shape of the
benchmark's ResNet cell, and one normal cell of its AmoebaNet-D whole, for
the copies the compiler puts round its pointwise convolutions.

The one-chip train step is compiled the same way, tiny, to see that the
program's scope names (``cellNN``, ``loss``, ``optimizer_update``) reach the
``op_name`` metadata of the instructions the chip would run: a device trace
names an op by its HLO instruction and nothing else, so that metadata is the
only road from a trace event back to the model.  The token models' layers
are compiled at their published widths to hold each scope a benchmark metric
reads (``attention_core``, ``expert_route``, ``expert_dispatch``,
``shared_expert``, ``ssm_mixer``) to the instructions of its mechanism.

All in ONE file and the topology in a fixture, never at import: only one
process may load libtpu, and under xdist every worker imports every file.
Nothing runs here, so these say nothing about results or times.
"""

import functools
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chip_smoke import KERNEL_SHAPES, flash_attention  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; turn the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _lower_fwd_bwd(layer, shape, one_chip):
    """``layer`` forward and backward in bf16 (float32 parameters, train
    mode) on shapes placed on the described chip, lowered."""
    from mpi4dl_tpu.layer_ctx import ApplyCtx

    params = jax.eval_shape(lambda: layer.init(jax.random.key(0), shape)[0])
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        return jnp.sum(layer.apply(p, x, ApplyCtx(train=True))
                       .astype(jnp.float32))

    return jax.jit(jax.grad(loss, (0, 1))).lower(params, x)


def test_stem_convolution_at_1024_compiles_in_h_stripes_for_v5e(
        one_chip, no_persistent_cache, rec):
    """The ResNet cell's stem, 3 -> 16 channels on 1 x 1024 x 1024 x 3: 42
    does not divide the row, so the fold leaves it to the H stripes
    (ops/hstripe_conv.py), the one ``hstripe`` site of the cell's
    ``conv_paths``; at this size it is one stripe, so no loop.  Temporaries
    under a bound taken from this compile (536,935,424 B on jax 0.9.0) with
    20 % room.  Fails if the stem leaves the path, the striped form stops
    compiling for the chip, or its flat-row padding starts to cost a copy of
    the image."""
    from mpi4dl_tpu.layers import Conv2d

    compiled = _lower_fwd_bwd(Conv2d(3, 16, 3), (1, 1024, 1024, 3),
                              one_chip).compile()
    assert rec.conv_paths() == {"hstripe": 1}
    text = compiled.as_text()
    assert " convolution(" in text and not re.search(r" while\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2 * 536_935_424


@pytest.mark.parametrize("conv,shape", [
    ((64, 64, 3), (1, 1024, 1024, 64)),
    ((128, 256, 1), (1, 512, 512, 128)),
], ids=["3x3_64_at_1024", "1x1_128_to_256_at_512"])
def test_strided_convolution_compiles_in_phase_form_for_v5e(
        one_chip, no_persistent_cache, rec, conv, shape):
    """Two of the ResNet cell's four stride-2 convolutions at their own
    shapes: the ``phase`` path (ops/conv_phase.py), whose input gradient is
    a sum of stride-1 convolutions over the phases of the cotangent.  Fails
    if a strided convolution leaves the path, or its backward goes back to a
    convolution over a dilated cotangent (``lhs_dilate`` in the chip's HLO),
    which is what the form exists to avoid."""
    from mpi4dl_tpu.layers import Conv2d

    cin, cout, k = conv
    compiled = _lower_fwd_bwd(Conv2d(cin, cout, k, stride=2), shape,
                              one_chip).compile()
    assert rec.conv_paths() == {"phase": 1}
    text = compiled.as_text()
    assert " convolution(" in text and "lhs_dilate" not in text


def test_factorized_reduce_keeps_its_barrier_in_the_program_for_v5e(
        one_chip, no_persistent_cache, rec):
    """AmoebaNet-D(18, 416)'s first FactorizedReduce at 1024² (104 -> 416
    channels on 1 x 512 x 512 x 104), forward and backward in bf16:
    its two halves are ``phase`` sites, and the ``optimization_barrier`` on
    its output (libtpu 0.0.34 miscompiled the bf16 backward across that
    boundary: NaN gradients at step 1, PR 22; tests/test_models.py holds it
    in the forward's jaxpr) is in what the chip's compiler is handed, which
    it compiles.  The compiled text cannot be asked for it: XLA expands its
    barriers away after fusion.  Fails if the barrier is dropped from
    ``apply``, a half leaves the phase form, or the cell's backward stops
    compiling for the chip at this width."""
    from mpi4dl_tpu.models.amoebanet import FactorizedReduce

    lowered = _lower_fwd_bwd(FactorizedReduce(104, 416), (1, 512, 512, 104),
                             one_chip)
    assert rec.conv_paths() == {"phase": 2}
    assert "stablehlo.optimization_barrier" in lowered.as_text()
    lowered.compile()


def test_block_flash_fwd_bwd_compiles_for_v5e(one_chip, no_persistent_cache):
    qkv = jax.ShapeDtypeStruct(
        (KERNEL_SHAPES["heads"], KERNEL_SHAPES["seq"],
         KERNEL_SHAPES["head_dim"]), jnp.bfloat16, sharding=one_chip)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(flash_attention, q, k, v)
        return out, vjp(jnp.ones_like(out))

    _assert_mosaic(jax.jit(fwd_bwd).lower(qkv, qkv, qkv).compile())


@pytest.mark.parametrize("t,d,dv,causal", [
    (2000, 64, 64, True), (1536, 192, 128, False)],
    ids=["ring_hop_ragged", "wide_keys_not_causal"])
def test_block_flash_backward_compiles_for_v5e_with_traced_offsets(
        one_chip, no_persistent_cache, t, d, dv, causal):
    """``block_flash`` forward and backward as a hop of ring attention calls
    it: the GLOBAL offsets traced (scalar prefetch), the forward's tiles
    (256, 512), a cotangent for each of ``(o_hat, m, l)``; a length no tile
    divides (2,000 tokens: the backward's two tiles of 1,024), or keys wider
    than values.  Mosaic accepts the backward kernel, and it leaves no loop."""
    from mpi4dl_tpu.ops.pallas_attention import block_flash

    def struct(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fwd_bwd(q, k, v, q_off, k_off):
        out, vjp = jax.vjp(lambda q, k, v: block_flash(
            q, k, v, q_off, k_off, causal, d ** -0.5, 256, 512, False), q, k, v)
        return vjp(jax.tree.map(jnp.ones_like, out))

    text = jax.jit(fwd_bwd).lower(
        struct(8, t, d), struct(8, t, d), struct(8, t, dv),
        struct(dtype=jnp.int32), struct(dtype=jnp.int32)).compile().as_text()
    assert "block_flash_bwd" in text and not re.search(r" while\(", text)


def test_one_chip_step_names_its_scopes_in_op_name_metadata(
        one_chip, no_persistent_cache):
    """The smallest model with every scope of the one-chip step (a ResNet v2
    of depth 11, 32 x 32, batch 2, per-cell remat as the entry point defaults
    it), shapes placed on the described chip, nothing put on a device."""
    from mpi4dl_tpu.models.resnet import get_resnet_v2
    from mpi4dl_tpu.obs.scopes import scopes_enabled
    from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step

    assert scopes_enabled()
    model = get_resnet_v2((2, 32, 32, 3), depth=11, num_classes=10)
    opt = Optimizer("sgd", lr=0.001)
    step = make_train_step(model, opt, None, compute_dtype=jnp.bfloat16,
                           remat=True, donate=True)
    state = jax.eval_shape(lambda: TrainState.create(
        model.init(jax.random.key(0))[0], opt))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), state)
    x = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    text = step.lower(state, x, y).compile().as_text()

    names = re.findall(r'op_name="([^"]+)"', text)
    want = [f"cell{i:02d}" for i in range(len(model.cells))] + [
        "loss", "optimizer_update"]
    for scope in want:
        assert any(re.search(rf"[/(]{scope}[/)]", n) for n in names), scope
    # forward, recompute and backward of a cell are told apart by jax's own
    # name stack round the scope
    cell = [n for n in names if "cell01" in n]
    assert any(n.startswith("jit(step)/jvp(cell01)") for n in cell)
    assert any("transpose(jvp(cell01))" in n for n in cell)
    assert any("rematted_computation" in n for n in cell)
    # most instructions that do work carry one of the program's scopes (the
    # rest are the compiler's own: converts, copies between memory spaces)
    work = [l for l in text.split("\n")
            if re.match(r"^\s+(ROOT )?%[\w.\-]+ = ", l) and not re.search(
                r" (parameter|constant|get-tuple-element|tuple|bitcast)\(", l)]
    scoped = [l for l in work if re.search(
        r'op_name="[^"]*[/(](cell\d+|loss|optimizer_update)[/)]', l)]
    assert len(scoped) > 0.5 * len(work), (len(scoped), len(work))


def test_narrow_resblock_at_1024_folds_lane_dense_for_v5e(
        one_chip, no_persistent_cache):
    """A block of ResNet-110 v2's 16-channel stage at its real size
    (1 x 1024 x 1024 x 64 in bf16; 3x3 64→16, 3x3 16→16, 1x1 16→64), forward
    and backward under ``jax.checkpoint``: every convolution W-folded
    (ops/wfold_conv.py), so no loop, no tensor in the narrow ``T(2,128)``
    tiling on a convolution or anywhere else, and temporaries under a bound
    taken from this compile (763,265,536 B on jax 0.9.0; the striped block
    before the fold: 4 loops, 32 such tensors, 1,077,160,960 B) with 20 %
    room.  The block is one folded run (``layers.run_fold``): BatchNorm takes
    its sums on ``[N, H, W/8, 8·C]``, so the step writes no float32 tensor
    the size of an activation in the convolutions' tiling (with each layer
    folded alone the compiled block had four ``copy f32[1024,8,17,128]``:
    x and x² of two BatchNorms, re-tiled for the reduction)."""
    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from mpi4dl_tpu.models.resnet import ResBlockV2

    blk = ResBlockV2(in_f=64, f1=16, f2=64, stride=1, first_block=False,
                     pre_activation=True)
    shape = (1, 1024, 1024, 64)
    params = jax.eval_shape(lambda: blk.init(jax.random.key(0), shape)[0])
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        y = jax.checkpoint(
            lambda p, x: blk.apply(p, x, ApplyCtx(train=True)))(p, x)
        return jnp.sum(y.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile()
    text = compiled.as_text()
    assert not re.search(r" while\(", text)
    folded = re.findall(r"= bf16\[1024,8,1[67],(?:128|512)\]\S* convolution\(",
                        text)
    assert len(folded) >= 5    # forward, recomputed and dx, on [N,H,W/8,8·C]
    narrow = [l for l in text.split("\n") if "T(2,128)" in l]
    assert not narrow, narrow[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2 * 763_265_536
    entry = text[text.index("ENTRY "):]
    f32_activations = re.findall(r"= f32\[1024,8,1[67],\d+\]\S* \S+\(", entry)
    assert not f32_activations, f32_activations[:4]


_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}


def _entry_copies(compiled):
    """``(dtype, dims, bytes)`` of every ``copy`` instruction of the compiled
    program's entry computation (what it writes; fusions' insides are not
    the entry's)."""
    text = compiled.as_text()
    out = []
    for m in re.finditer(r"^\s+(?:ROOT )?%copy(?:\.\d+)* = (\w+)\[([\d,]*)\]",
                         text[text.index("ENTRY "):], re.M):
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append((m.group(1), dims, _BYTES[m.group(1)] * math.prod(dims)))
    return out


def test_amoebanet_normal_cell_keeps_no_transposes_round_its_1x1_for_v5e(
        one_chip, no_persistent_cache, rec):
    """One normal cell of AmoebaNet-D(18,416)'s first group at its real size
    (``AmoebaCell(1664, 1664, 416)`` on two 1 x 256 x 256 x 1664 states in
    bf16), forward and backward under ``jax.checkpoint``.  Nine of its
    thirteen convolutions are pointwise at stride 1 and go to XLA as matrix
    products (``Conv2d.apply``'s ``dot`` form, PR 32).  As convolutions (the
    parent tree, counted by this function: 50 ``copy`` instructions writing
    4,634,707,584 B) XLA:TPU ran each as a batch of 8 splits of W in a
    channel-minor layout, and transposed to and from the H-minor layout of
    the fusions round it: 33 copies of a ``[256,8,32,416]`` or
    ``[256,8,32,416,1]`` view and 12 of the 1664-wide forms.  Fails if such a
    copy comes back into the entry computation, or if the copies together
    write more than half of what the parent's did (this tree: 59 writing
    1,429,390,976 B, those round the 1x7 and 7x1 at 104 channels and the
    probe's own ends)."""
    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from mpi4dl_tpu.models.amoebanet import AmoebaCell

    c, shape = 416, (1, 256, 256, 1664)
    cell = AmoebaCell(4 * c, 4 * c, c, reduction=False, reduction_prev=False)
    params = jax.eval_shape(
        lambda: cell.init(jax.random.key(0), (shape, shape))[0])
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(p, x, skip):
        y = jax.checkpoint(lambda p, x, skip: cell.apply(
            p, (x, skip), ApplyCtx(train=True)))(p, x, skip)
        return sum(jnp.sum(t.astype(jnp.float32))
                   for t in jax.tree.leaves(y))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(params, x, x).compile()
    assert rec.conv_paths() == {"xla": 4, "dot": 9}
    copies = _entry_copies(compiled)
    split = [(dt, dims) for dt, dims, _ in copies
             if dims[:4] == [256, 8, 32, c]]
    assert not split, split[:4]
    assert sum(size for _, _, size in copies) < 4_634_707_584 / 2


def _compile_routed_experts(struct, *, held, total, top_k, ffn, **route):
    """``ops.moe.routed_experts`` forward and backward over 32,768 tokens of
    2,048 in bf16 (float32 parameters), under a ``"highest"`` default,
    compiled for the chip ``struct`` places its shapes on."""
    from mpi4dl_tpu.ops import moe

    def experts(x, router, weights):
        def loss(x, router, weights):
            y, _ = moe.routed_experts(x, router, weights, first=0, held=held,
                                      total=total, top_k=top_k, **route)
            return jnp.sum(y.astype(jnp.float32))

        with jax.default_matmul_precision("highest"):
            return jax.grad(loss, (0, 1, 2))(x, router, weights)

    f32 = jnp.float32
    compiled = jax.jit(experts).lower(
        struct((32768, 2048)),
        {"kernel": struct((2048, total), f32), "bias": struct((total,), f32)},
        {"w1": struct((held, 2048, ffn), f32), "w3": struct((held, 2048, ffn), f32),
         "w2": struct((held, ffn, 2048), f32)}).compile()
    _assert_mosaic(compiled)
    return compiled


def test_lfm2_kernels_compile_for_v5e_under_a_highest_default(
        one_chip, no_persistent_cache, monkeypatch):
    """The token model's two kernels at its published widths, bf16, forward
    and backward, traced as the benchmark's check traces the program's cells:
    under ``jax.default_matmul_precision("highest")``, where a bf16 product
    left to the default is asked for in float32 and Mosaic refuses it ("Bad
    lhs type", PR 29's first chip run).  The grouped expert product is
    ``lax.ragged_dot`` over one round's rows (32,768 tokens, 8 of 64
    experts), which XLA:TPU compiles to instructions named ``ragged-dot-*``:
    the names by which the benchmark's ``expert_ffn_ms`` picks them from the
    trace.  Attention is one sequence of 8,192 tokens, 32 heads of 64."""
    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile_routed_experts(struct, held=8, total=64, top_k=4, ffn=1536)
    products = re.findall(
        r"%ragged-dot-\w+(?:\.\d+)? = bf16\[(20480,1536|20480,2048|8,2048,1536|"
        r"8,1536,2048)\]", compiled.as_text())
    # W1, W3, W2 forward, by the rows and by the weights backward, in the
    # first round and in the overflow rounds' branch
    assert len(products) >= 9 and len(set(products)) == 4, products

    qkv = struct((32, 8192, 64))

    def attention(q, k, v):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(flash_attention, q, k, v)
        return out, vjp(jnp.ones_like(out))

    _assert_mosaic(jax.jit(attention).lower(qkv, qkv, qkv).compile())


def _executed_keys(text):
    """``name:type[shape]`` of every instruction of a compiled module that
    runs on its own (not inside a fusion), as ``perfbench.trace.op_key``
    names a trace event."""
    return [key for key, _ in _executed_with_scopes(text)]


def _latent_layer_on(one_chip, monkeypatch, batch):
    """Kanana-2's ``LatentAttention`` at its published widths with the Pallas
    path asked for as on a TPU backend, and the shapes of its float32
    parameters and of ``batch`` sequences of 8,192 tokens in bf16 placed on
    the described chip."""
    import mpi4dl_tpu.config as config
    from mpi4dl_tpu.models import deepseek_v3

    monkeypatch.setattr(config, "is_tpu_backend", lambda: True)
    layer = deepseek_v3._block(deepseek_v3.PUBLISHED, 1, 16, 0).op

    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: struct(a.shape, a.dtype),
        jax.eval_shape(lambda: layer.init(jax.random.key(0), (batch, 8192, 2048))[0]))
    return layer, params, struct((batch, 8192, 2048)), struct


def test_deepseek_v3_kernels_compile_for_v5e_under_the_names_the_metrics_pick(
        one_chip, no_persistent_cache, monkeypatch):
    """The second token model's kernels at its published widths, bf16,
    forward and backward, under a ``"highest"`` default as the benchmark's
    check traces them.  Latent attention whole (four sequences of 8,192
    tokens through ``LatentAttention`` under per-cell remat: 32 heads of 128 +
    64, values of 128, the Pallas path asked for as on a TPU backend): Mosaic
    accepts the forward and the backward kernel on the projections' layout;
    every Mosaic kernel of the compiled layer carries the scope
    ``attention_core``, by which the four attention metrics pick
    (``perfbench/optable.py``), forward, recomputed and backward; beside the
    kernels the scope holds only Δ's row sums and the sum of the rotary key's
    partials, no product and nothing as large as a projection's, a norm's or
    the rotary embedding's result.  The backward is the kernel: no loop, no
    heads-first array (which the broadcast of the rotary key to the heads
    was), no tile of the einsum backward, no accumulator of tiles.  The
    grouped product at 32,768 tokens, 16 of 128 experts of 768, six a token:
    ``ragged-dot-*`` at this configuration's shapes."""
    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from perfbench import optable

    layer, params, x, struct = _latent_layer_on(one_chip, monkeypatch, batch=4)

    def attention(p, x):
        def loss(p, x):
            # under per-cell remat, as the step runs it: the forward kernel is
            # then there twice (the loss is returned, so the first stays)
            y = jax.checkpoint(
                lambda p, x: layer.apply(p, x, ApplyCtx(train=True)))(p, x)
            return jnp.sum(y.astype(jnp.float32))

        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, (0, 1))(p, x)

    compiled = jax.jit(attention).lower(params, x).compile()
    _assert_mosaic(compiled)
    text = compiled.as_text()
    rows = optable.parse(text)
    kernels = [r for r in rows.values() if r["target"] == "tpu_custom_call"]
    assert all("attention_core" in optable.scopes_of(r["op_name"])
               for r in kernels), [r["op_name"] for r in kernels]
    passes = [(r["name"].split(".")[0], optable.pass_of(r["op_name"]))
              for r in kernels]
    assert sorted(passes) == [("block_flash_fwd", "forward"),
                              ("block_flash_fwd", "recompute"),
                              ("latent_flash_bwd", "backward")], passes
    # what else the scope holds does no product and is small
    scoped = [r for r in rows.values() if r not in kernels
              and "attention_core" in optable.scopes_of(r["op_name"])]
    assert not [r["name"] for r in scoped
                if r["opcode"] in ("dot", "convolution", "ragged-dot")]
    elements = lambda t: math.prod(
        int(d) for d in re.search(r"\[([\d,]*)\]", t).group(1).split(",") if d)
    executed = _executed_names(text)
    large = [(r["name"], r["types"]) for r in scoped
             if r["name"] in executed and r["opcode"] != "get-tuple-element"
             and max(map(elements, r["types"])) > 4 * 8192 * 64]
    assert not large, large
    # the backward is the kernel
    assert not re.search(r" while\(", text)
    results = re.findall(r" = \(?\w+\[([\d,]+)\]", text)
    gone = [r for r in results if re.fullmatch(
        r"(\d+,)?32,8192,\d+|(\d+,)?32,(1024|512),\d+|8,32,1024,192", r)]
    assert not gone, sorted(set(gone))

    compiled = _compile_routed_experts(struct, held=16, total=128, top_k=6,
                                       ffn=768, scaling=2.448, sum_eps=1e-20)
    products = re.findall(
        r"%ragged-dot-\w+(?:\.\d+)? = bf16\[(30720,768|30720,2048|16,2048,768|"
        r"16,768,2048)\]", compiled.as_text())
    assert len(products) >= 9 and len(set(products)) == 4, products


def test_latent_attention_forward_writes_no_heads_first_or_padded_operand_for_v5e(
        one_chip, no_persistent_cache, monkeypatch):
    """The forward alone, four sequences: between the projections and the
    kernel nothing is written heads-first (``[*, 32, 8192, 192]``), a head's
    ``nope`` and ``rope`` columns are not concatenated (``[*, 8192, 32,
    192]``), nothing is padded to 256 lanes (``[32, 8192, 256]``), the rotary
    key is not broadcast to the heads, and there is no loop over the
    sequences: what ``block_flash`` needed and ``latent_flash`` reads in
    place (PR 34).  The kernel is there once, for all four sequences."""
    from mpi4dl_tpu.layer_ctx import ApplyCtx

    layer, params, x, _ = _latent_layer_on(one_chip, monkeypatch, batch=4)

    def forward(p, x):
        with jax.default_matmul_precision("highest"):
            return layer.apply(p, x, ApplyCtx(train=True))

    text = jax.jit(forward).lower(params, x).compile().as_text()
    results = re.findall(r" = \(?\w+\[([\d,]+)\]", text)
    gone = [r for r in results if re.fullmatch(
        r"(\d+,)?32,8192,192|(\d+,)?8192,32,192|32,8192,256", r)]
    assert not gone, sorted(set(gone))
    assert not re.search(r" while\(", text)
    assert [k for k in _executed_keys(text) if k.startswith("block_flash_fwd")
            ] == ["block_flash_fwd:bf16[4,8192,4096]"]


def _executed_lines(text):
    """``(line, op_name)`` of every instruction of a compiled module that
    runs on its own (not inside a fusion); ``op_name`` is the scope path in
    the instruction's metadata ("" where the compiler made it and gave it
    none)."""
    fused = False
    for line in text.splitlines():
        if line and not line.startswith(" "):  # a computation's header, or "}"
            fused = line.startswith(("%fused_", "fused_"))
        elif not fused and " = " in line:
            scope = re.search(r'op_name="([^"]*)"', line)
            yield (line.strip().removeprefix("ROOT "),
                   scope.group(1) if scope else "")


def _executed_names(text):
    """The instruction names of :func:`_executed_lines`."""
    return {line.split(" = ")[0].lstrip("%") for line, _ in _executed_lines(text)}


def _executed_with_scopes(text):
    """``(key, op_name)`` of :func:`_executed_lines`, the key as
    ``perfbench.trace.op_key`` names a trace event."""
    from perfbench.trace import op_key

    return [(op_key(line), scope) for line, scope in _executed_lines(text)]


def test_granitemoehybrid_layers_compile_for_v5e_under_the_names_the_metrics_pick(
        one_chip, no_persistent_cache, monkeypatch):
    """The third token model's two kinds of layer at the published widths and
    the cell's batch (two sequences of 8,192 tokens), bf16, under per-cell
    remat and a ``"highest"`` default as the step and the benchmark's check
    trace them.  The state-space layer (``Mamba2Mixer`` with its MLP and
    norms): XLA's compiler accepts the chunked scan, every large instruction
    that the scan's scope made is among the names ``ssm_scan_ms`` picks, the
    pattern picks nothing at the widths of the projections, the gate, the
    norms or the MLP, and ``attention_ms``'s picks nothing there.  The
    attention layer (no rotary embedding, no head norms, scale 1/64): Mosaic
    accepts the forward and the backward kernel at the LFM2 cell's shape,
    ``attention_ms``'s pattern picks the forward kernel (the metric reads the
    scope first, which holds both: the last test of this file), and
    ``ssm_scan_ms`` picks nothing."""
    import json

    import mpi4dl_tpu.config as config
    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from mpi4dl_tpu.models import granitemoehybrid as gmh

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def pattern(metric):
        with open(os.path.join(root, "perfbench", "layer_metrics",
                               metric + ".json")) as f:
            return re.compile(json.load(f)["params"]["pattern"])

    scan_ms, attention_ms = pattern("ssm_scan_ms"), pattern("attention_ms")
    monkeypatch.setattr(config, "is_tpu_backend", lambda: True)

    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def executed(layer_index):
        cell = gmh._block(gmh.PUBLISHED, layer_index)
        params = jax.tree.map(
            lambda a: struct(a.shape, a.dtype),
            jax.eval_shape(lambda: cell.init(jax.random.key(0), (2, 8192, 2048))[0]))

        def grads(p, x):
            def loss(p, x):
                y = jax.checkpoint(
                    lambda p, x: cell.apply(p, x, ApplyCtx(train=True)))(p, x)
                return jnp.sum(y.astype(jnp.float32))

            with jax.default_matmul_precision("highest"):
                return jax.grad(loss, (0, 1))(p, x)

        compiled = jax.jit(grads).lower(params, struct((2, 8192, 2048))).compile()
        return compiled, _executed_with_scopes(compiled.as_text())

    # a state-space layer
    assert gmh.PUBLISHED.layer_types[0] == "mamba"
    _, ops = executed(0)
    elements = lambda key: math.prod(
        int(d) for d in re.search(r"\[([\d,]*)\]", key).group(1).split(",") if d)
    in_scope = {k for k, scope in ops if "ssm_scan" in scope
                and not k.startswith(("while:", "conditional:", "call:",
                                      # no work of their own
                                      "get-tuple-element:", "tuple:",
                                      "parameter:", "constant:"))
                and re.search(r"\[[\d,]+\]", k) and elements(k) >= 1 << 20}
    assert len(in_scope) >= 10, sorted(in_scope)
    assert not [k for k in in_scope if not scan_ms.search(k)], sorted(in_scope)
    picked = {k for k, _ in ops if scan_ms.search(k)}
    shapes = {k.split(":")[1] for k in picked}
    for block in ("[2,32,256,256]", "[2,32,256,64,64]", "[32,2,64,64,128]",
                  "[2,64,64,128]", "[2,32,256,64]"):
        assert any(s.endswith(block) for s in shapes), (block, sorted(shapes))
    # nothing at the widths of the projections (2048, 8512, 4352), of the MLP
    # (8192 wide) or of the gate and the norm (bf16 or float32 [2,8192,4096]
    # other than x on its way in and y on its way out)
    assert not [k for k in picked if re.search(r"2048|8512|4352|8192,8192", k)], sorted(picked)
    assert {k for k in picked if "4096" in k} <= {
        "slice_convert_fusion:f32[2,8192,4096]", "reshape:bf16[2,8192,4096]",
        "multiply_reduce_fusion:f32[4096]"}, sorted(picked)
    assert "multiply_reduce_fusion:f32[4096]" in picked  # D's gradient
    assert not [k for k, _ in ops if attention_ms.search(k)]

    # the attention layer
    assert gmh.PUBLISHED.layer_types[5] == "attention"
    compiled, ops = executed(5)
    _assert_mosaic(compiled)
    keys = [k for k, _ in ops]
    assert not [k for k in keys if scan_ms.search(k)]
    picked = {k for k in keys if attention_ms.search(k)}
    assert any(k.startswith("block_flash_fwd:") for k in picked), sorted(picked)
    assert {k.split(":")[0] for k in keys if "block_flash" in k} == {
        "block_flash_fwd", "block_flash_bwd"}, sorted(set(keys))


@functools.lru_cache(maxsize=None)
def _compiled_layer(model, layer, batch, one_chip, part="block", seq=8192):
    """One layer of a token model at its published widths (``lfm2``,
    ``deepseek_v3``, ``keye_vl2``: experts held as in the cells, 8 of 64 and
    16 of 128; ``granitemoehybrid``; ``ouro``: its second pass's application
    of the layer), ``batch`` sequences of ``seq`` tokens in bf16 with
    float32 parameters, forward under ``jax.checkpoint`` (per-cell remat, as
    the step runs it) and backward with the loss returned, under a
    ``"highest"`` default and the Pallas path asked for as on a TPU backend;
    ``part`` ``"ffn"`` compiles the layer's feed-forward alone.  Returns
    ``(row, optable.describe(row))`` of every instruction that runs on its own
    and does work, a custom call's row with the types of its operands
    (``operands``, from ``operand_layout_constraints``).  Compiled once for
    the tests that read it."""
    from unittest import mock

    import mpi4dl_tpu.config as config
    from mpi4dl_tpu.layer_ctx import ApplyCtx
    from mpi4dl_tpu.models import deepseek_v3, granitemoehybrid, keye_vl2, lfm2, ouro
    from perfbench import optable

    cell = {"lfm2": lambda: lfm2._block(lfm2.PUBLISHED, layer, 8, 0),
            "deepseek_v3": lambda: deepseek_v3._block(
                deepseek_v3.PUBLISHED, layer, 16, 0),
            "keye_vl2": lambda: keye_vl2._block(keye_vl2.PUBLISHED, layer, 16, 0),
            "granitemoehybrid": lambda: granitemoehybrid._block(
                granitemoehybrid.PUBLISHED, layer),
            # pass 1's application, handed the parameters here
            "ouro": lambda: ouro.LoopCell(
                ouro._block(ouro.PUBLISHED, layer), 1, 4, True,
                f"ut1_layer{layer:02d}")}[model]()
    if part == "ffn":
        cell = cell.ffn
    shape = (batch, seq, 2048)  # every published hidden size

    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: struct(a.shape, a.dtype), jax.eval_shape(
        lambda: cell.init(jax.random.key(0), shape)[0]))

    def grads(p, x):
        def loss(p, x):
            y = jax.checkpoint(
                lambda p, x: cell.apply(p, x, ApplyCtx(train=True)))(p, x)
            return jnp.sum(y.astype(jnp.float32))

        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss, (0, 1))(p, x)

    with mock.patch.object(config, "is_tpu_backend", lambda: True):
        text = jax.jit(grads).lower(params, struct(shape)).compile().as_text()
    rows = optable.parse(text)
    for line in text.splitlines():
        head, sep, rest = line.partition(" custom-call(")
        if sep:
            row = rows[head.strip().split(" ", 1)[0].lstrip("%")]
            # the scoped VMEM a Mosaic kernel is given: none, the compiler's
            # own where another kernel of the step names a limit, or the
            # limit it names itself
            scoped = re.search(r'"scoped_memory_configs":\[[^\]]*\]', rest)
            row["vmem_bytes"] = [int(n) for n in re.findall(
                r'"size":"(\d+)"', scoped.group(0) if scoped else "")]
        if sep and "operand_layout_constraints={" in rest:
            constraints = rest.split("operand_layout_constraints={", 1)[1]
            row["operands"] = (
                re.findall(r"\w+\[[\d,]*\]", constraints.split("}}", 1)[0]))
    said = []
    for name in sorted(_executed_names(text)):
        row = rows.get(name)
        if row is None or row["opcode"] in ("parameter", "constant", "tuple",
                                            "get-tuple-element"):
            continue
        d = optable.describe(row)
        if d["cls"] != "container":
            said.append((row, d))
    return said


def _leaf_opcodes(row):
    from perfbench import optable

    return {optable._base(r["opcode"]) for r in optable._leaves(row)}


def _own_scopes(row):
    """The scopes in the instruction's OWN ``op_name`` (a fusion's own
    metadata), beside what ``optable.describe`` reads from its fused ones."""
    from perfbench import optable

    return optable.scopes_of(row["op_name"])


def _assert_attention_core_is_the_kernel(said):
    """``attention_core`` holds the two Mosaic kernels, as ``optable`` reads
    it: ``block_flash_fwd`` forward and recomputed, ``block_flash_bwd`` in
    the backward pass.  No product of the projections (nor anything of the
    experts, the MLP or the scan) carries it, and the backward leaves no loop,
    conditional or dynamic-update-slice of the rule it replaced (a scan of
    einsum tiles) in the step.  The forward names no VMEM limit (it is given
    the compiler's own 16 MiB where the backward names one): one would give
    every instruction of the step a scoped reservation of HBM."""
    kernels = [(r["name"].split(".")[0], d) for r, d in said
               if r["name"].startswith("block_flash_")]
    assert sorted((n, d["pass"]) for n, d in kernels) == [
        ("block_flash_bwd", "backward"), ("block_flash_fwd", "forward"),
        ("block_flash_fwd", "recompute")], [(n, d["pass"]) for n, d in kernels]
    assert all("attention_core" in d["scopes"] for _, d in kernels)
    from mpi4dl_tpu.ops.pallas_attention import _DEFAULT_VMEM

    assert not [r["name"] for r, _ in said if r["name"].startswith(
        "block_flash_fwd") and set(r["vmem_bytes"]) - {_DEFAULT_VMEM}]
    assert not [d["key"] for _, d in said
                if d["cls"] == "product" and "attention_core" in d["scopes"]]
    scoped = [r for r, d in said if "attention_core" in d["scopes"]]
    assert not [r["name"] for r in scoped
                if "dynamic-update-slice" in _leaf_opcodes(r)]
    assert not [r["op_name"] for r in scoped if re.search(
        r"attention_core/(.*/)?(while|cond)/", r["op_name"])]


def _assert_the_routed_layer_carries_route_and_dispatch(said, width=2048):
    """``ops/moe.routed_experts``' two scopes: no grouped product
    (``ragged-dot-*``) carries either; the sorts (the router's top-k, the
    assignments' argsort) and the cumulative counts (``reduce-window``) carry
    ``expert_route`` as ``optable`` reads them, where the compiler left them
    an ``op_name``; every gather and scatter carries one of the two, and those
    that move rows (``width`` wide) carry ``expert_dispatch``, forward,
    recomputed and backward.  A gather fusion's fused instructions carry the
    bare ``op_name`` ``gather`` (XLA:TPU's expander), so for the rows the
    fusion's own ``op_name`` is what carries the scope (PERF.md section 7)."""
    from perfbench import optable

    both = {"expert_route", "expert_dispatch"}
    ragged = [(r, d) for r, d in said if r["name"].startswith("ragged-dot")]
    assert len(ragged) >= 9
    assert not [d["key"] for r, d in ragged
                if both & (set(d["scopes"]) | _own_scopes(r))]
    named = lambda r: r["op_name"] or any(
        leaf["op_name"] for leaf in r["fused"])
    counted = [(r, d) for r, d in said
               if _leaf_opcodes(r) & {"sort", "reduce-window"} and named(r)]
    assert {"sort", "reduce-window"} <= set().union(
        *(_leaf_opcodes(r) for r, _ in counted))
    assert all("expert_route" in d["scopes"] for _, d in counted), [
        d["key"] for _, d in counted]
    moved = [(r, d) for r, d in said
             if _leaf_opcodes(r) & {"gather", "scatter"} and named(r)]
    assert all(both & (set(d["scopes"]) | _own_scopes(r)) for r, d in moved)
    rows = [r for r, _ in moved if r["types"][0].endswith(f",{width}]")]
    assert all("expert_dispatch" in _own_scopes(r) for r in rows)
    assert {optable.pass_of(r["op_name"]) for r in rows} == {
        "forward", "recompute", "backward"}


def test_lfm2_attention_and_expert_layer_carry_the_scopes_the_metrics_read_for_v5e(
        one_chip, no_persistent_cache):
    """LFM2's layer 2 (attention, then the routed experts) as the cell runs it,
    four sequences: ``attention_core`` round ``block_flash`` and inside its
    backward rule, ``expert_route`` and ``expert_dispatch`` round the routed
    layer's routing and row traffic, which ``attention_ms``,
    ``attention_roofline_pct``, ``expert_route_ms`` and ``expert_dispatch_ms``
    read (``perfbench/optable.py``)."""
    said = _compiled_layer("lfm2", 2, 4, one_chip)
    _assert_attention_core_is_the_kernel(said)
    _assert_the_routed_layer_carries_route_and_dispatch(said)


def test_kanana_2_expert_layer_carries_its_shared_and_routed_scopes_for_v5e(
        one_chip, no_persistent_cache):
    """Kanana-2's expert layer (layer 1's ``SharedAndRoutedExperts``) over
    the cell's 32,768 tokens: the shared SwiGLU's products (1,536 wide: two
    shared experts of 768) carry ``shared_expert``, which ``shared_expert_ms``
    reads; no instruction of the routed layer does (no ``ragged-dot-*``,
    nothing under ``expert_route`` or ``expert_dispatch``), and the routed
    layer carries its own two scopes as in LFM2's."""
    said = _compiled_layer("deepseek_v3", 1, 4, one_chip, part="ffn")
    shared = [(r, d) for r, d in said if d["cls"] == "product"
              and not r["name"].startswith("ragged-dot")
              and any("1536" in t for t in r["types"])]
    assert len(shared) >= 6
    assert all("shared_expert" in d["scopes"] for _, d in shared)
    routed = {"expert_route", "expert_dispatch"}
    for r, d in said:
        scopes = set(d["scopes"]) | _own_scopes(r)
        if r["name"].startswith("ragged-dot") or scopes & routed:
            assert "shared_expert" not in scopes, d["key"]
    _assert_the_routed_layer_carries_route_and_dispatch(said)


def test_granitemoehybrid_mixer_and_attention_carry_their_scopes_for_v5e(
        one_chip, no_persistent_cache):
    """granite's two kinds of layer, the cell's two sequences: in a
    state-space layer every instruction that carries ``ssm_scan`` also
    carries ``ssm_mixer`` (the whole of ``Mamba2Mixer.apply``, which
    ``ssm_mixer_ms`` reads), as ``optable`` reads it and in its own
    ``op_name``, and the mixer's projections carry it; the attention layer
    carries ``attention_core`` as LFM2's does."""
    said = _compiled_layer("granitemoehybrid", 0, 2, one_chip)
    for r, d in said:
        if "ssm_scan" in d["scopes"]:
            assert "ssm_mixer" in d["scopes"], d["key"]
        if "ssm_scan" in _own_scopes(r):
            assert "ssm_mixer" in _own_scopes(r), d["key"]
    mixer = [d for _, d in said if "ssm_mixer" in d["scopes"]]
    assert len(mixer) > len([d for d in mixer if "ssm_scan" in d["scopes"]])
    # in_proj's product, 8,512 wide, and out_proj's, under the mixer's scope
    assert any("8512" in d["key"] and d["cls"] == "product" for d in mixer)
    _assert_attention_core_is_the_kernel(
        _compiled_layer("granitemoehybrid", 5, 2, one_chip))


def test_keye_vl2_layer_compiles_for_v5e_with_its_kernels_in_their_scopes(
        one_chip, no_persistent_cache):
    """Keye-VL-2.0's layer (sparse attention, then 16 of 128 softmax-routed
    experts) as its cell runs it, one sequence of 16,384 tokens: the four
    Mosaic kernels compile for the chip, each in the scope its metric reads
    (``sparse_flash_fwd`` forward and recomputed and ``sparse_flash_bwd`` in
    ``attention_core``; ``sparse_indexer_select`` forward and recomputed and
    ``sparse_indexer_bwd`` in ``sparse_indexer``), no product of the
    projections in ``attention_core``, the indexer's own projections in
    ``sparse_indexer``, and the routed layer's scopes as in LFM2's.
    ``sparse_flash_fwd`` takes the 4 key-value heads as they are (a grid
    step a group of 8 query heads): no broadcast or copy of k or v to the 32
    query heads feeds it.  So does ``sparse_flash_bwd``, one custom call
    under the VMEM limit it names: q, dô and dq feature-major for the 32
    heads, k and v (and dk and dv) for the 4, o as the forward wrote it, and
    no float32 ``[32, 16384, 128]`` array (a copy of dô) in the attention's
    backward."""
    from mpi4dl_tpu.ops.pallas_attention import _sparse_bwd_vmem_bytes

    said = _compiled_layer("keye_vl2", 0, 1, one_chip, seq=16384)
    forward = [r["operands"] for r in (r for r, _ in said)
               if r["name"].startswith("sparse_flash_fwd")]
    assert len(forward) == 2 and all(ops == [
        "bf16[32,16384,128]", "bf16[4,16384,128]", "bf16[4,16384,128]",
        "s32[1,16384,512]"] for ops in forward), forward
    backward = [r for r, _ in said if r["name"].startswith("sparse_flash_bwd")]
    assert [r["operands"] for r in backward] == [[
        "bf16[32,128,16384]", "bf16[4,128,16384]", "bf16[4,128,16384]",
        "f32[32,16384,128]", "bf16[32,128,16384]", "f32[32,1,16384]",
        "f32[32,1,16384]", "s32[1,512,16384]"]], [r["operands"] for r in backward]
    assert backward[0]["vmem_bytes"] == [
        _sparse_bwd_vmem_bytes(8, 1024, 1024, 16384, 128, 128, 512, 2) + 2 ** 21]
    assert not [d["key"] for r, d in said if d["pass"] == "backward"
                and "attention_core" in d["scopes"]
                and "f32[32,16384,128]" in r["types"]]
    kernels = [(r["name"].split(".")[0], d) for r, d in said
               if d["cls"] == "kernel" and not r["name"].startswith("ragged-dot")]
    assert sorted((n, d["pass"]) for n, d in kernels) == [
        ("sparse_flash_bwd", "backward"), ("sparse_flash_fwd", "forward"),
        ("sparse_flash_fwd", "recompute"), ("sparse_indexer_bwd", "backward"),
        ("sparse_indexer_select", "forward"),
        ("sparse_indexer_select", "recompute")], [(n, d["pass"]) for n, d in kernels]
    for name, d in kernels:
        want = "attention_core" if name.startswith("sparse_flash") else "sparse_indexer"
        assert want in d["scopes"], (name, d["scopes"])
        assert {"attention_core", "sparse_indexer"} - {want} - set(d["scopes"])
    assert not [d["key"] for _, d in said
                if d["cls"] == "product" and "attention_core" in d["scopes"]]
    # W_q^I [2048, 1024] forward and its weight gradient carry the indexer's scope
    indexer_products = [d["key"] for _, d in said if d["cls"] == "product"
                        and "sparse_indexer" in d["scopes"]]
    assert any("1024" in k for k in indexer_products), indexer_products
    _assert_the_routed_layer_carries_route_and_dispatch(said)


def test_ouro_layer_compiles_for_v5e_in_its_loop_and_attention_scopes(
        one_chip, no_persistent_cache):
    """Ouro's layer as its cell applies it in the second pass, one sequence
    of 8,192 tokens: ``block_flash`` forward, recomputed and backward compile
    for the chip in ``attention_core`` on 16 heads of 128 whose keys and
    values are not repeated (as many key-value heads as query heads), and
    every product of the layer (the attention's four projections, the MLP's
    three, forward, recomputed and backward) carries ``ut_loop`` and the
    pass's ``ut_step1``, which ``ut_loop_ms`` reads."""
    said = _compiled_layer("ouro", 0, 1, one_chip)
    _assert_attention_core_is_the_kernel(said)
    forward = [r["operands"] for r, _ in said
               if r["name"].startswith("block_flash_fwd")]
    # the scalar-prefetched offsets, then q, k and v
    assert len(forward) == 2 and all(
        ops == ["s32[2]"] + ["bf16[16,8192,128]"] * 3 for ops in forward), forward
    products = [d for _, d in said if d["cls"] == "product"]
    assert len(products) >= 3 * 7, [d["key"] for d in products]
    assert all({"ut_loop", "ut_step1"} <= set(d["scopes"]) for d in products), [
        (d["key"], d["scopes"]) for d in products
        if not {"ut_loop", "ut_step1"} <= set(d["scopes"])]
    assert any("5632" in d["key"] for d in products)
    kernels = [d for r, d in said if r["name"].startswith("block_flash_")]
    assert all("ut_loop" in d["scopes"] for d in kernels)
