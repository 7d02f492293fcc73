"""A folded run (``layers.run_fold`` / ``apply_run``): BatchNorm, ReLU, the
convolutions and the residual add of ResNet v2's narrow stage on the
lane-dense ``[N, H, W/p, p·C]`` form, from one W-folded convolution to the
next.

The run's gate is the W-fold's own (a million pixels and more), which no
shape of the suite reaches, so these tests force
``layers._HSTRIPE_MIN_PIXELS`` down and hold the run to the path the layers
take one by one (each convolution folded and unfolded alone, each BatchNorm
on ``[N, H, W, C]``): values, every gradient, the running statistics, the
fall-throughs and the recorder's ``norm_paths`` count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mpi4dl_tpu import cells as C
from mpi4dl_tpu import layers as L
from mpi4dl_tpu.compat import shard_map
from mpi4dl_tpu.layer_ctx import ApplyCtx, SpatialCtx
from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.models.resnet import ResBlockV2, _resnet_layer
from mpi4dl_tpu.obs import spans
from mpi4dl_tpu.ops.wfold_conv import fold, unfold

# perfbench/configs/resnet110_v2.json, tolerances.cell (as tests/test_wfold.py)
CELL_TOLERANCE = 0.015
DTYPES = pytest.mark.parametrize(
    "dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, CELL_TOLERANCE)],
    ids=["f32", "bf16"])


@pytest.fixture
def gate_down(monkeypatch):
    monkeypatch.setattr(L, "_HSTRIPE_MIN_PIXELS", 1)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _stir(params, key):
    """Scales and biases away from (1, 0) and running statistics away from
    (0, 1), so that every gradient and every deposit says something."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.2 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1
        else leaf for leaf, k in zip(leaves, keys)])


def _stats(params, sink):
    """The running statistics a trace left in its sink, in leaf order."""
    return [sink[id(leaf)] for leaf in jax.tree.leaves(params)
            if id(leaf) in sink]


def _one_by_one(layers, params, x, ctx):
    """Today's path: every layer alone, on ``[N, H, W, C]``."""
    for p, layer in zip(params, layers):
        x = layer.apply(p, x, ctx)
    return x


def _value_grads_stats(forward, params, x, t):
    """``forward(params, x, ctx) -> y``: y, the running statistics, and the
    gradients of ``sum(y * t)`` by every parameter and by x."""
    def loss(params, x):
        sink = {}
        y = forward(params, x, ApplyCtx(train=True, bn_sink=sink))
        return jnp.sum((y * t).astype(jnp.float32)), (y, _stats(params, sink))

    (_, (y, stats)), (gp, gx) = jax.value_and_grad(
        loss, (0, 1), has_aux=True)(params, x)
    return y, stats, gp, gx


def _compare(forward, today, params, x, t, tol):
    """``forward`` against the layers one by one (``today``) on the same
    parameters: output, dx, running statistics and every gradient, as
    relative L2 distances.  In float32 directly.  In bf16 both against
    today's path in float32, as the benchmark holds a cell to its float32
    reference: a gradient of a scale or a bias is there a sum of bf16 terms
    over every pixel, and three layers deep the order of the sums alone
    leaves today's own bf16 path 0.02–0.11 from the float32 one on this
    backend, so the folded run is held to ``tol`` or to 1.5 times what
    today's path reads, whichever is larger.  Returns the number of
    gradients compared."""
    got = _value_grads_stats(forward, params, x, t)
    f32 = jnp.float32
    want = _value_grads_stats(today, params, x.astype(f32), t.astype(f32))
    base = (_value_grads_stats(today, params, x, t)
            if x.dtype != f32 else None)

    def flat(r):
        y, stats, gp, gx = r
        out = {"y": y, "dx": gx}
        out.update({f"stat{i}": s for i, s in enumerate(stats)})
        out.update({jax.tree_util.keystr(path): g for path, g
                    in jax.tree_util.tree_leaves_with_path(gp)})
        return out

    got, want = flat(got), flat(want)
    base = flat(base) if base is not None else None
    assert got["y"].dtype == x.dtype and got["dx"].dtype == x.dtype
    assert set(got) == set(want) and "stat0" in got
    compared = 0
    for name, w in want.items():
        g = got[name]
        assert g.shape == w.shape, name
        if name.endswith(("['mean']", "['var']")):   # no gradient in train mode
            assert not np.any(np.asarray(g)), name
            continue
        kernel = name.replace("['bias']", "['kernel']")
        if kernel != name and kernel in want and (
                np.linalg.norm(w) < 1e-6 * np.linalg.norm(want[kernel])):
            # a bias that a BatchNorm follows: zero but for rounding
            assert (np.linalg.norm(np.asarray(g, np.float32))
                    <= 0.01 * np.linalg.norm(want[kernel])), name
            continue
        limit = tol if base is None else max(tol, 1.5 * _rel(base[name], w))
        assert _rel(g, w) <= limit, (name, _rel(g, w), limit)
        compared += name.startswith("[")
    return compared


RUNS = {
    "bn64_relu_3x3_64_16": (64, 16, 3),
    "bn16_relu_3x3_16_16": (16, 16, 3),
    "bn16_relu_1x1_16_64": (16, 64, 1),
}


@DTYPES
@pytest.mark.parametrize("run", list(RUNS))
def test_a_folded_run_is_the_layers_one_by_one(gate_down, rec, run, dtype,
                                               tol):
    """BatchNorm → ReLU → convolution as ResNet v2 builds them, folded by
    the convolution's 8 (the 64-channel BatchNorm too, not by its own 2):
    output, dx, the gradients of kernel, bias, scale and bias, and the
    running statistics left in ``ctx.bn_sink``."""
    cin, cout, k = RUNS[run]
    layers = _resnet_layer(cin, cout, kernel=k, conv_first=False)
    shape = (2, 12, 32, cin)
    k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
    params = _stir(C.LayerCell(layers).init(k1, shape)[0], k2)
    x = jax.random.normal(k3, shape, dtype)
    t = jax.random.normal(k4, shape[:3] + (cout,), dtype)
    assert L.run_fold(layers, shape, ApplyCtx(train=True)) == 8

    def folded(params, x, ctx):
        p = L.run_fold(layers, x.shape, ctx)
        return unfold(L.apply_run(layers, params, fold(x, p), ctx, p), p)

    n = _compare(folded, lambda p, x, ctx: _one_by_one(layers, p, x, ctx),
                 params, x, t, tol)
    assert n == 4
    assert rec.site_paths("norm") == {"folded": 1, "plain": 1}
    assert rec.conv_paths() == {"wfold": 1}


def _block(first):
    """A block of the 16-channel stage: 64 → 16 → 16 → 64 with the identity
    beside it, or the stage's first, 16 → 16 → 16 → 64 with no BatchNorm
    before its first convolution and a 1×1 convolution as the shortcut."""
    if first:
        return ResBlockV2(16, 16, 64, 1, first_block=True,
                          pre_activation=False), 16
    return ResBlockV2(64, 16, 64, 1, first_block=False,
                      pre_activation=True), 64


def _block_one_by_one(blk, params, x, ctx):
    y = x
    for name in ("r1", "r2", "r3"):
        y = _one_by_one(getattr(blk, name).layers, params[name], y, ctx)
    if blk.r4 is not None:
        x = _one_by_one(blk.r4.layers, params["r4"], x, ctx)
    return x + y


@DTYPES
@pytest.mark.parametrize("first", [False, True], ids=["block", "first_block"])
@pytest.mark.parametrize("checkpointed", [False, True],
                         ids=["plain", "checkpointed_packed"])
def test_a_resblock_stays_folded_to_its_residual_add(
        gate_down, rec, monkeypatch, checkpointed, first, dtype, tol):
    """``ResBlockV2.apply`` folds its input once, runs the branch and the
    add (and the first block's shortcut convolution) on the folded form and
    unfolds the sum; as it stands, and under the per-cell checkpoint with
    its packed boundary, where the statistics cross the checkpoint."""
    blk, cin = _block(first)
    shape = (2, 12, 32, cin)
    k1, k2, k3, k4 = jax.random.split(jax.random.key(1), 4)
    params = _stir(blk.init(k1, shape)[0], k2)
    x = jax.random.normal(k3, shape, dtype)
    t = jax.random.normal(k4, shape[:3] + (64,), dtype)

    def forward(params, x, ctx):
        if not checkpointed:
            return blk.apply(params, x, ctx)
        monkeypatch.setattr(C, "_PACK_MIN_ELEMS", 1)
        xp, meta = C._pack_act(x)
        y, out_meta = C.checkpointed_apply(blk.apply, params, xp, ctx,
                                           in_meta=meta, pack=True)
        assert meta is not None and out_meta is not None
        return C._unpack_act(y, out_meta)

    n = _compare(forward,
                 lambda p, x, ctx: _block_one_by_one(blk, p, x, ctx),
                 params, x, t, tol)
    # kernel and bias of each convolution, scale and bias of each BatchNorm,
    # less the two biases that a BatchNorm follows
    n_norm = 2 if first else 3
    assert n == 2 * (4 if first else 3) + 2 * n_norm - 2
    assert rec.site_paths("norm") == {"folded": n_norm, "plain": n_norm}
    assert rec.conv_paths() == {"wfold": 4 if first else 3}


@DTYPES
def test_the_block_after_the_narrow_stage_takes_the_stream_as_it_comes(
        gate_down, rec, dtype, tol):
    """Stage 1's first block: its 3×3 is strided and does not fold, but the
    BatchNorm(64) and ReLU before it work on the form the narrow stage's
    runs left (``layers.stream_fold``: by 8, not by the layer's own 2)."""
    blk = ResBlockV2(64, 64, 128, 2, first_block=True, pre_activation=True)
    shape = (2, 12, 32, 64)
    assert L.stream_fold(shape) == 8
    k1, k2, k3, k4 = jax.random.split(jax.random.key(8), 4)
    params = _stir(blk.init(k1, shape)[0], k2)
    x = jax.random.normal(k3, shape, dtype)
    t = jax.random.normal(k4, (2, 6, 16, 128), dtype)
    n = _compare(blk.apply,
                 lambda p, x, ctx: _block_one_by_one(blk, p, x, ctx),
                 params, x, t, tol)
    assert n == 2 * 4 + 2 * 3 - 2
    # the strided convolutions take the phase form as before; after them
    # the tensors are outside the narrow gate (128 channels) or fold alone
    assert rec.site_paths("norm") == {"folded": 1, "plain": 3}
    assert rec.conv_paths()["phase"] == 2


@pytest.mark.parametrize(
    "shape,p",
    [
        ((1, 1024, 1024, 64), 8),
        ((1, 1024, 1024, 16), 8),
        ((1, 1024, 1024, 32), 8),
        ((1, 1024, 1024, 3), 0),      # 42 does not divide the row
        ((1, 1024, 1020, 64), 0),     # 8 does not divide the row
        ((1, 1024, 1024, 128), 0),    # lane-dense as it stands
        ((1, 512, 512, 64), 0),       # under a million pixels
        ((1, 2048, 2048, 64), 0),     # the fold stops under 2²² pixels
        ((4, 8192), 0),
    ],
)
def test_stream_fold_and_the_packed_boundary(monkeypatch, shape, p):
    """The form in which the narrow stage hands its activation on is decided
    from the tensor's shape by the W-fold's own gate, and the packed
    checkpoint boundary has its lanes: ``[N, H, W/8, 512]`` for the
    64-channel boundaries at 1024², 128 lanes everywhere else, as before."""
    assert L.stream_fold(shape) == p
    if len(shape) != 4:
        return
    n, h, w, c = shape
    assert C._pack_lanes(shape) == (p * c if p else 128)
    meta = C._pack_meta(shape)
    if meta is not None:
        assert meta == (w, c)
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        packed = jax.eval_shape(lambda x: C._pack_one(x)[0], x)
        assert packed.shape == (n, h, w * c // C._pack_lanes(shape),
                                C._pack_lanes(shape))
        back = jax.eval_shape(lambda y: C._unpack_one(y, meta), packed)
        assert back.shape == shape
    monkeypatch.setenv("MPI4DL_NO_HSTRIPE", "1")
    assert L.stream_fold(shape) == 0 and C._pack_lanes(shape) == 128


def _margin_ctx():
    return ApplyCtx(train=True, spatial=SpatialCtx(
        axis_h="sph", grid_h=2, bn_cross_tile=False, stat_local=True,
        halo_pre_exchanged=True, pre_margin_h=1))


FALL_THROUGHS = {
    # why: (channels in, f1, shape of x, context, gate forced down, env)
    "208_channels": (208, 52, (1, 8, 32, 208), None, True, {}),
    "w_not_a_multiple_of_p": (64, 16, (1, 8, 20, 64), None, True, {}),
    "eval_mode": (64, 16, (1, 8, 32, 64), ApplyCtx(train=False), True, {}),
    "pre_exchanged_margin": (64, 16, (1, 8, 32, 64), _margin_ctx(), True, {}),
    "under_a_million_pixels": (64, 16, (1, 8, 32, 64), None, False, {}),
    "no_hstripe": (64, 16, (1, 8, 32, 64), None, True,
                   {"MPI4DL_NO_HSTRIPE": "1"}),
    "remat_ops": (64, 16, (1, 8, 32, 64),
                  ApplyCtx(train=True, remat_ops=True), True, {}),
}


@pytest.mark.parametrize("why", list(FALL_THROUGHS))
def test_outside_the_gate_every_layer_takes_todays_path(monkeypatch, rec,
                                                        why):
    """Where the run's gate says no the block traces what it traced before:
    no BatchNorm folded, and the layers' own result to the bit."""
    cin, f1, shape, ctx, gate, env = FALL_THROUGHS[why]
    if gate:
        monkeypatch.setattr(L, "_HSTRIPE_MIN_PIXELS", 1)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    ctx = ctx or ApplyCtx(train=True)
    blk = ResBlockV2(cin, f1, cin, 1, first_block=False, pre_activation=True)
    layers = (list(blk.r1.layers) + list(blk.r2.layers)
              + list(blk.r3.layers))
    params = _stir(blk.init(jax.random.key(2), shape)[0], jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), shape)
    if why != "remat_ops":   # the gate is the run's; remat_ops is the block's
        assert L.run_fold(layers, shape, ctx) == 0
    if why == "pre_exchanged_margin":
        # each 3×3 eats a row of margin top and bottom: no residual add
        flat = params["r1"] + params["r2"] + params["r3"]
        y = L.apply_run(layers, flat, x, ctx)
        y_want = _one_by_one(layers, flat, x, ctx)
        assert y.shape == (1, 4, 32, 64)
    else:
        y = blk.apply(params, x, ctx)
        y_want = _block_one_by_one(blk, params, x, ctx)
    assert rec.site_paths("norm") == {"plain": 3}
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_want))


@pytest.mark.parametrize(
    "why,layers",
    [
        ("convolutions disagree", [L.BatchNorm(64), L.ReLU(),
                                   L.Conv2d(64, 64, 3), L.BatchNorm(64),
                                   L.ReLU(), L.Conv2d(64, 16, 3)]),
        ("one convolution strided", [L.BatchNorm(64), L.ReLU(),
                                     L.Conv2d(64, 16, 3, stride=2)]),
        ("one left to the stripes", [L.BatchNorm(64), L.ReLU(),
                                     L.Conv2d(64, 16, 3, padding=(1, 0))]),
        ("no convolution", [L.BatchNorm(64), L.ReLU()]),
        ("a pool", [L.BatchNorm(16), L.Pool2d("avg", 2),
                    L.Conv2d(16, 16, 3)]),
        ("a lane-padded BatchNorm", [L.BatchNorm(16, lane_pad=64),
                                     L.Conv2d(64, 16, 3)]),
    ],
    ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None,
)
def test_the_fold_is_the_runs_or_nobodys(gate_down, why, layers):
    """p is decided once a run, from its convolutions: where they disagree,
    or one takes another path, or a layer is none of BatchNorm, ReLU and
    convolution, nothing is folded."""
    assert L.run_fold(layers, (1, 8, 32, 64 if "pool" not in why else 16),
                      ApplyCtx(train=True)) == 0


def test_a_tile_sharded_on_h_folds_and_sums_across_tiles(gate_down, rec,
                                                         devices8):
    """An SP tile sharded on H alone takes its H margin from a halo exchange
    and is SAME on W, so its run folds; the statistics are the image's
    (``psum`` of the ``[C]`` sums), and the tiles together are the unsharded
    block."""
    blk, cin = _block(False)
    shape = (1, 16, 32, cin)
    params = _stir(blk.init(jax.random.key(5), shape)[0], jax.random.key(6))
    x = jax.random.normal(jax.random.key(7), shape)
    want = _block_one_by_one(blk, params, x, ApplyCtx(train=True))
    assert rec.site_paths("norm") == {"plain": 3}

    sp = SpatialCtx(axis_h="sph", grid_h=2)
    mesh = build_mesh(MeshSpec(sph=2), devices8[:2])
    spec = P(None, "sph", None, None)
    got = jax.jit(shard_map(
        lambda p, t: blk.apply(p, t, ApplyCtx(train=True, spatial=sp)),
        mesh=mesh, in_specs=(P(), spec), out_specs=spec, check_vma=False,
    ))(params, x)
    assert rec.site_paths("norm") == {"folded": 3, "plain": 3}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    # sharded on W the convolutions are VALID on W: the stripes, no run
    sp_w = SpatialCtx(axis_w="spw", grid_w=2)
    layers = list(blk.r1.layers)
    assert L.run_fold(layers, (1, 16, 16, cin),
                      ApplyCtx(train=True, spatial=sp_w)) == 0


def test_norm_sites_are_not_counted_with_the_recorder_off():
    rec = spans.Recorder(enabled=False)
    rec.note_site("norm", object(), "folded")
    assert rec.site_paths("norm") == {}
