"""W-folded convolution (ops/wfold_conv.py) and its place in Conv2d.apply.

The fold is gated to narrow-channel convolutions at a million pixels and
more, which no shape of the suite reaches, so these tests call it directly
or force ``layers._HSTRIPE_MIN_PIXELS`` down, and pin values, gradients,
the dispatch's fall-throughs and the recorder's ``conv_paths`` count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mpi4dl_tpu import layers as L
from mpi4dl_tpu.layer_ctx import ApplyCtx, SpatialCtx
from mpi4dl_tpu.obs import spans
from mpi4dl_tpu.ops import hstripe_conv as hc
from mpi4dl_tpu.ops import wfold_conv as wf

# perfbench/configs/resnet110_v2.json, tolerances.cell: what a cell in bf16
# may differ from the float32 reference's by, as a relative L2 norm
CELL_TOLERANCE = 0.015


def _ref(x, w, ph, pw, stride=1, groups=1):
    return lax.conv_general_dilated(
        x, w, (stride, stride), (ph, pw),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture
def gate_down(monkeypatch):
    """Suite-sized shapes pass the million-pixel gate, and a stripe's patch
    budget is small enough that the striped path would loop."""
    monkeypatch.setattr(L, "_HSTRIPE_MIN_PIXELS", 1)
    monkeypatch.setattr(hc, "_PATCH_BUDGET", 4000)


@pytest.mark.parametrize("h_pad", ["same", "none"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, CELL_TOLERANCE)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("cin,cout,k", [(16, 16, 3), (64, 16, 3),
                                        (16, 64, 1), (32, 32, 3)])
def test_wfold_matches_lax(cin, cout, k, dtype, tol, h_pad):
    """Forward, dx and dw against lax.conv_general_dilated, with H padded
    SAME and not at all (a pre-margined run)."""
    n, h, wid = 2, 12, 32
    pw = ((k - 1) // 2,) * 2
    ph = pw if h_pad == "same" else (0, 0)
    p = wf.wfold_factor(wid, k, cin, cout, pw)
    assert p == 128 // min(cin, cout) and p > 1
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(k1, (n, h, wid, cin), dtype)
    w = (jax.random.normal(k2, (k, k, cin, cout)) / (k * cin ** 0.5)
         ).astype(dtype)

    y = wf.wfold_conv2d(x, w, ph, p)
    y_ref = _ref(x, w, ph, pw)
    assert y.shape == y_ref.shape and y.dtype == dtype
    assert _rel(y, y_ref) <= tol

    t = jax.random.normal(k3, y.shape, dtype)

    def loss(conv):
        return lambda x, w: jnp.sum((conv(x, w) * t).astype(jnp.float32))

    gx, gw = jax.grad(loss(lambda x, w: wf.wfold_conv2d(x, w, ph, p)),
                      (0, 1))(x, w)
    gx_r, gw_r = jax.grad(loss(lambda x, w: _ref(x, w, ph, pw)), (0, 1))(x, w)
    assert gx.dtype == dtype and gw.dtype == dtype
    assert _rel(gx, gx_r) <= tol
    assert _rel(gw, gw_r) <= tol


def test_folded_kernel_is_the_true_one_on_a_block_band():
    """Every element of the folded kernel is a tap of the true one or an
    exact zero, and each tap appears p times (once for each pixel of a
    folded column) but for those that leave the row's two neighbours."""
    p, k, cin, cout = 4, 3, 2, 3
    w = jax.random.normal(jax.random.key(1), (k, k, cin, cout))
    wfd = np.asarray(wf.fold_kernel(w, p)).reshape(k, 3, p, cin, p, cout)
    w = np.asarray(w)
    for j in range(3):
        for a in range(p):
            for b in range(p):
                x = a + p * (j - 1) - b + 1
                want = w[:, x] if 0 <= x < k else np.zeros_like(w[:, 0])
                np.testing.assert_array_equal(wfd[:, j, a, :, b, :], want)


@pytest.mark.parametrize(
    "wid,kw,cin,cout,pad_w,p",
    [
        (1024, 3, 16, 16, (1, 1), 8),
        (1024, 3, 64, 16, (1, 1), 8),
        (1024, 1, 16, 64, (0, 0), 8),
        (512, 3, 64, 64, (1, 1), 2),
        (1024, 3, 3, 16, (1, 1), 0),      # 42 does not divide the row
        (1024, 3, 128, 128, (1, 1), 0),   # lane-dense as it stands
        (1024, 3, 16, 16, (0, 0), 0),     # the margin came from an exchange
        (1024, 3, 16, 16, (1, 2), 0),     # not symmetric
        (1024, 2, 16, 16, (1, 1), 0),     # even kernel
        (1020, 3, 16, 16, (1, 1), 0),     # 8 does not divide the row
        (1024, 7, 64, 64, (3, 3), 0),     # reaches beyond one folded pixel
    ],
)
def test_wfold_factor(wid, kw, cin, cout, pad_w, p):
    assert wf.wfold_factor(wid, kw, cin, cout, pad_w) == p


def _rows(*rows):
    """A row may end in a dict that moves a threshold of ``layers`` to the
    row's own size (the suite has no image of a million pixels) or asks for
    eval mode; most do not."""
    return [(*row, {})[:5] for row in rows]


@pytest.mark.parametrize(
    "why,conv,shape,path,knobs",
    _rows(
        ("Cin 3: W % 42", L.Conv2d(3, 16, 3), (1, 16, 32, 3), "hstripe"),
        ("W % p", L.Conv2d(16, 16, 3), (1, 16, 20, 16), "hstripe"),
        ("VALID W", L.Conv2d(16, 16, 3, padding=(1, 0)), (1, 16, 32, 16),
         "hstripe"),
        ("stride 2", L.Conv2d(16, 16, 3, stride=2), (1, 16, 32, 16), "phase"),
        ("groups", L.Conv2d(16, 16, 3, feature_group_count=2),
         (1, 16, 32, 16), "xla"),
        # the rows between which the opted-in kernel's gate used to sit
        # (128 -> 128, 3x3, stride 1 was its home): each fails if a branch
        # is put back between the stripes and the phase form, or if a
        # threshold turns from >= to > (or < to <=)
        ("65 channels", L.Conv2d(65, 65, 3), (1, 16, 32, 65), "xla"),
        ("128 channels", L.Conv2d(128, 128, 3), (1, 16, 32, 128), "xla"),
        ("1x1 at 64 channels", L.Conv2d(64, 64, 1), (1, 16, 32, 64), "wfold"),
        ("at the fold's ceiling", L.Conv2d(16, 16, 3), (1, 16, 32, 16),
         "hstripe", {"_WFOLD_MAX_PIXELS": 16 * 32}),
        ("one pixel under the gate", L.Conv2d(16, 16, 3), (1, 16, 32, 16),
         "xla", {"_HSTRIPE_MIN_PIXELS": 16 * 32 + 1}),
        ("stride 2 at 64 channels", L.Conv2d(64, 64, 3, stride=2),
         (1, 16, 32, 64), "phase"),
        ("groups and stride 2",
         L.Conv2d(16, 16, 3, stride=2, feature_group_count=2),
         (1, 16, 32, 16), "xla"),
        ("eval mode", L.Conv2d(16, 16, 3), (1, 16, 32, 16), "wfold",
         {"train": False}),
    ),
    ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None,
)
def test_fall_throughs_take_todays_path(gate_down, rec, monkeypatch, why,
                                        conv, shape, path, knobs):
    """Where the fold is not exact the dispatch goes where it went before,
    decided from shapes and padding alone, and gives that path's result."""
    knobs = dict(knobs)
    ctx = ApplyCtx(train=knobs.pop("train", True))
    for name, value in knobs.items():
        monkeypatch.setattr(L, name, value)
    params, _ = conv.init(jax.random.key(2), shape)
    x = jax.random.normal(jax.random.key(3), shape)
    y = conv.apply(params, x, ctx)
    assert rec.conv_paths() == {path: 1}, why

    kh, kw, sh, sw, ph, pw = conv._geometry()
    y_ref = _ref(x, params["kernel"], (ph, ph), (pw, pw), stride=sh,
                 groups=conv.feature_group_count) + params["bias"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)


def _lowered(conv, params, x):
    return jax.jit(
        lambda p, x: conv.apply(p, x, ApplyCtx(train=True))
    ).lower(params, x).as_text()


def test_conv2d_takes_the_fold_before_the_stripes(gate_down, rec,
                                                  monkeypatch):
    """At a qualifying shape Conv2d.apply lowers to one folded convolution
    and no loop; the striped function at the same shape does loop; and
    MPI4DL_NO_HSTRIPE=1 means the plain convolution: neither."""
    conv = L.Conv2d(16, 16, 3)
    shape = (1, 16, 32, 16)
    params, _ = conv.init(jax.random.key(4), shape)
    x = jax.random.normal(jax.random.key(5), shape)

    text = _lowered(conv, params, x)
    assert "stablehlo.while" not in text
    assert "tensor<3x3x128x128xf32>" in text          # the folded kernel
    assert rec.conv_paths() == {"wfold": 1}
    y = conv.apply(params, x, ApplyCtx(train=True))

    striped = jax.jit(
        lambda x, w: hc.hstripe_conv2d(x, w, (1, 1), (1, 1))
    ).lower(x, params["kernel"]).as_text()
    assert "stablehlo.while" in striped

    monkeypatch.setenv("MPI4DL_NO_HSTRIPE", "1")
    plain = _lowered(conv, params, x)
    assert "stablehlo.while" not in plain
    assert "x128x128x" not in plain
    assert rec.conv_paths() == {"wfold": 1, "xla": 1}
    y_plain = conv.apply(params, x, ApplyCtx(train=True))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_plain), atol=1e-5)


def test_fold_under_an_h_sharded_context_with_the_margin_pre_exchanged(
        gate_down, rec):
    """The context ``hstripe_layer_run`` builds (and an SP tile sharded on
    H alone): H margin already in the activation, so no H padding; W SAME.
    Each tile takes the fold and together they are the unsharded result."""
    conv = L.Conv2d(16, 16, 3)
    shape = (1, 16, 32, 16)
    params, _ = conv.init(jax.random.key(6), shape)
    x = jax.random.normal(jax.random.key(7), shape)
    whole = conv.apply(params, x, ApplyCtx(train=True))

    tiles, rows = 2, shape[1] // 2
    sp = SpatialCtx(axis_h="sph", grid_h=tiles, bn_cross_tile=False,
                    stat_local=True, halo_pre_exchanged=True, pre_margin_h=1)
    ctx = ApplyCtx(train=True, spatial=sp)
    xp = jnp.pad(x, ((0, 0), (1, 1), (0, 0), (0, 0)))
    parts = [conv.apply(params, xp[:, i * rows:(i + 1) * rows + 2], ctx)
             for i in range(tiles)]
    assert rec.conv_paths() == {"wfold": 1}
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(parts, axis=1)), np.asarray(whole),
        atol=1e-5)

    # a tile whose W margin came from an exchange is VALID on W: the stripes
    sp_w = SpatialCtx(axis_w="spw", grid_w=2, bn_cross_tile=False,
                      stat_local=True, halo_pre_exchanged=True,
                      pre_margin_w=1)
    conv.apply(params, jnp.pad(x, ((0, 0), (0, 0), (1, 1), (0, 0))),
               ApplyCtx(train=True, spatial=sp_w))
    assert rec.conv_paths() == {"wfold": 1, "hstripe": 1}


# One convolution for each way through Conv2d.apply, on a 16 x 32 image:
# name, layer, input channels, and the path it takes on a whole image.
_FIVE = [
    ("stem", lambda: L.Conv2d(3, 16, 3), 3, "hstripe"),       # W % 42
    ("narrow3x3", lambda: L.Conv2d(16, 16, 3), 16, "wfold"),
    ("narrow1x1", lambda: L.Conv2d(16, 64, 1), 16, "wfold"),
    ("wide", lambda: L.Conv2d(80, 80, 3), 80, "xla"),
    ("strided", lambda: L.Conv2d(80, 80, 3, stride=2), 80, "phase"),
]


@pytest.mark.parametrize(
    "sp_kw,narrow3x3",
    [
        pytest.param(dict(axis_h="sph", grid_h=2), "wfold",
                     id="H_sharded_margin_by_the_layer"),
        pytest.param(dict(axis_w="spw", grid_w=2), "hstripe",
                     id="W_sharded_margin_by_the_layer"),
        pytest.param(dict(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2),
                     "hstripe", id="both_sharded_margin_by_the_layer"),
        pytest.param(dict(axis_h="sph", grid_h=2, d2_mode=True), "wfold",
                     id="H_sharded_margin_by_a_D2_run"),
        pytest.param(dict(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2,
                          d2_mode=True), "hstripe",
                     id="both_sharded_margin_by_a_D2_run"),
        pytest.param(dict(axis_h="sph", axis_w="spw", grid_h=1, grid_w=1,
                          rep_h=2, rep_w=2), "wfold",
                     id="degenerate_level_axes_named_grid_1"),
    ],
)
def test_the_four_paths_under_a_spatial_context(devices8, gate_down, rec,
                                                monkeypatch, sp_kw,
                                                narrow3x3):
    """Each of five convolutions as a cell of its own on a 2x2 mesh: the path
    is chosen from the tile's shape and from which padding a halo exchange
    replaced, and the tiles together are the convolution of the whole image.
    A narrow 3x3 folds while its W padding is SAME (H sharded, or a level
    whose grid is 1) and takes the stripes once W's margin came from the
    neighbours; a 1x1 has no margin and folds on every tile; the stem, the
    wide and the strided one go where they go unsharded.  Fails if the
    dispatch reads anything of the context but its padding (the retired
    kernel's gate read the context's axes and a flag on it), or if a tile's
    margin is exchanged twice or not at all under `d2_mode`."""
    from jax.sharding import PartitionSpec as P

    from mpi4dl_tpu.cells import LayerCell
    from mpi4dl_tpu.compat import shard_map
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh
    from mpi4dl_tpu.ops import d2

    d2_runs = []
    monkeypatch.setattr(
        d2, "run_layers_d2",
        lambda *a, run=d2.run_layers_d2: d2_runs.append(1) or run(*a))
    sp = SpatialCtx(**sp_kw)
    ctx = ApplyCtx(train=True, spatial=sp)
    mesh = build_mesh(MeshSpec(data=1, stage=1, sph=2, spw=2), devices8[:4])
    spec = (P(None, sp.axis_h, sp.axis_w, None) if sp.active else P())
    want = {}
    for i, (name, make, cin, alone) in enumerate(_FIVE):
        conv = make()
        cell = LayerCell([conv])
        shape = (1, 16, 32, cin)
        params, _ = cell.init(jax.random.key(10 + i), shape)
        x = jax.random.normal(jax.random.key(20 + i), shape)
        got = jax.jit(shard_map(
            lambda p, t, cell=cell: cell.apply(p, t, ctx), mesh=mesh,
            in_specs=(P(), spec), out_specs=spec))(params, x)
        kh, kw, sh, sw, ph, pw = conv._geometry()
        ref = _ref(x, params[0]["kernel"], (ph, ph), (pw, pw),
                   stride=sh) + params[0]["bias"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, err_msg=name)
        path = narrow3x3 if name == "narrow3x3" else alone
        want[path] = want.get(path, 0) + 1
    assert rec.conv_paths() == want
    # under d2_mode every convolution with a margin got it from the run
    assert len(d2_runs) == (4 if sp.d2_mode else 0)


def _trace_model(model, dtype=jnp.float32):
    """Trace init and apply on shapes alone: nothing is allocated."""
    params = jax.eval_shape(lambda: model.init(jax.random.key(0))[0])
    jax.eval_shape(
        lambda p, x: model.apply(p, x, ApplyCtx(train=True)),
        params, jax.ShapeDtypeStruct(model.in_shape, dtype))


def test_conv_paths_of_a_resnet_v2(monkeypatch, rec):
    """Depth 11 (one block a stage) at 64² with the gate at 64²: the
    16-channel stage's two 3×3 and two 1×1 convolutions fold, the stem (Cin
    3) keeps the stripes, the strided ones take the phase form, the 1×1
    64→128 that closes stage 1's block is a matrix product, the rest (the
    3×3, and the 1×1 128→256 whose widths both fill the lanes) are XLA's; a
    site counts once however often it is traced."""
    from mpi4dl_tpu.models.resnet import get_resnet_v2

    monkeypatch.setattr(L, "_HSTRIPE_MIN_PIXELS", 64 * 64)
    model = get_resnet_v2((1, 64, 64, 3), depth=11, num_classes=10)
    _trace_model(model)
    want = {"wfold": 4, "hstripe": 1, "phase": 4, "xla": 3, "dot": 1}
    assert rec.conv_paths() == want
    # the stage's one block is a folded run (layers.run_fold): its two
    # BatchNorms work on [N, H, W/8, 8·C], and so does the one that opens
    # the next stage, before its strided convolution; the stem's, the six
    # others of the two other blocks and the head's do not
    norms = {"folded": 3, "plain": 7}
    assert rec.site_paths("norm") == norms
    _trace_model(model)
    assert rec.conv_paths() == want
    assert rec.summary()["conv_paths"] == want
    assert rec.summary()["norm_paths"] == norms


def test_conv_paths_of_the_resnet_cell(rec):
    """``resnet110_v2.1024.bs1`` as it is: 13 3×3 16→16, 11 3×3 64→16 and 13
    1×1 16→64 fold; the stem alone is left to the stripes; the 1×1 that
    closes each block of stage 1 (12 of 64→128 at 512²) is a matrix product;
    stage 2's (12 of 128→256 at 256²: both widths fill the lanes) and the 46
    3×3 of both stages are XLA's."""
    from mpi4dl_tpu.models.resnet import get_resnet_v2

    _trace_model(get_resnet_v2((1, 1024, 1024, 3), depth=110,
                               num_classes=1000), jnp.bfloat16)
    assert rec.conv_paths() == {"wfold": 37, "hstripe": 1, "phase": 4,
                                "xla": 58, "dot": 12}
    # stage 0 is twelve folded runs: block 0 has two BatchNorms, blocks 1–11
    # three each, and the BatchNorm(64) at 1024² that opens stage 1 takes the
    # stream as they left it; the stem's, the other 71 of stages 1 and 2 and
    # the head's are plain
    assert rec.site_paths("norm") == {"folded": 36, "plain": 73}


def test_conv_paths_of_resnet_at_2048(rec):
    """At 2048² the 16-channel stage runs block by block in H stripes
    (``hstripe_layer_run``: each stripe is under the gate, so its 3×3 are
    XLA's own and its 12 1×1 16→64 matrix products, on the stripe), the
    shortcut conv left beside it is at ``_WFOLD_MAX_PIXELS`` and keeps the
    striped path with the stem, the 64-channel stage at 1024² folds by 2 (12
    3×3 64→64 and 12 1×1 64→128), and the 12 1×1 128→256 at 512² stay
    XLA's (both widths fill the lanes)."""
    from mpi4dl_tpu.models.resnet import get_resnet_v2

    _trace_model(get_resnet_v2((1, 2048, 2048, 3), depth=110,
                               num_classes=1000), jnp.bfloat16)
    assert rec.conv_paths() == {"wfold": 24, "hstripe": 2, "phase": 4,
                                "xla": 70, "dot": 12}
    # no run is folded: stage 0 is over the fold's 2²² pixels, and a block
    # of stage 1 opens with a 128-channel convolution that does not fold
    assert rec.site_paths("norm") == {"plain": 109}


def test_conv_paths_of_the_amoebanet_cell(rec):
    """``amoebanet_d.2048.bs1`` as it is: the stem is strided and every
    other convolution at a million pixels is wider than 64 channels, so the
    traffic bypasses the fold and the stripes.  Of its 186 pointwise
    convolutions at stride 1 (the cells' ``reduce1``/``reduce2``, their
    ``conv_1x1`` and both ends of each ``conv_1x7_7x1`` bottleneck) the 154
    with a width of 52, 104, 208, 416 or 832 are matrix products; XLA's own
    are the 32 between multiples of 128 (1664, 3328, 4992, 6656: the third
    group's, and the reduction into it) and the 80 1×7 and 7×1; the strided
    13 (stem, 3×3 and the factorized reductions' 1×1 at stride 2) take the
    phase form."""
    from mpi4dl_tpu.models.amoebanet import amoebanetd

    _trace_model(amoebanetd((1, 2048, 2048, 3), num_classes=1000,
                            num_layers=18, num_filters=416), jnp.bfloat16)
    assert rec.conv_paths() == {"phase": 13, "xla": 112, "dot": 154}
    assert set(rec.site_paths("norm")) == {"plain"}


def test_conv_paths_are_not_counted_with_the_recorder_off(monkeypatch):
    rec = spans.Recorder(enabled=False)
    rec.note_conv(object(), "xla")
    assert rec.conv_paths() == {}
