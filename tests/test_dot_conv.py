"""A pointwise convolution as a matrix product (``Conv2d.apply``'s ``dot``
form): a 1×1 kernel at stride 1 with no padding and one group, one of whose
widths is no multiple of the 128 lanes, is ``lax.dot_general`` over the
channel axis of the ``[N, H, W, C]`` activation as it stands.

Held to ``lax.conv_general_dilated`` on the same operands: the forward and
both gradients, with and without bias, with the kernel's lane pads, on the
tiles of a 2×2 spatial context; and the convolutions next to the rule that
keep the path they had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from mpi4dl_tpu import layers as L
from mpi4dl_tpu.cells import LayerCell
from mpi4dl_tpu.compat import shard_map
from mpi4dl_tpu.layer_ctx import ApplyCtx, SpatialCtx
from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.ops.wfold_conv import fold, unfold

# (dtype, limit).  float32: relative to the largest entry of the result.
# bf16: in units in the last place of each entry of the result (``_ulps``).
# Both forms sum the same bf16 products in float32 and round the sum once,
# so they are the same number wherever the float32 sums agree; what XLA does
# not promise is the order of that sum (its convolution and its product are
# different contractions), so two things keep it from being bit-equal by
# contract: a sum that lands on the other side of a bf16 rounding boundary
# (one ulp), and a sum that cancels (a kernel gradient over 480 pixels of
# terms near 1 that comes to 1e-4 differs by float32's rounding of the TERMS,
# which is 3 ulp of so small a result: ``_ulps`` gives such an entry
# float32's rounding at the size of the tensor's largest entry instead).
# On this container the forward and dx read 0 ulp in every case.
DTYPES = pytest.mark.parametrize(
    "dtype,limit", [(jnp.float32, 1e-6), (jnp.bfloat16, 1)],
    ids=["f32", "bf16"])


def _conv_form(x, kernel, bias):
    y = lax.conv_general_dilated(
        x, kernel.astype(x.dtype), (1, 1), ((0, 0), (0, 0)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y if bias is None else y + bias.astype(y.dtype)


def _ulp_size(want):
    """One unit in the last place of each bf16 entry of ``want``, never less
    than 16 float32 roundings of the tensor's largest entry (a sum that
    cancels)."""
    want = np.asarray(want, np.float32)
    bits = jnp.finfo(jnp.bfloat16).nmant
    exponent = np.floor(np.log2(np.maximum(np.abs(want), 1e-30)))
    floor = 16 * np.finfo(np.float32).eps * np.max(np.abs(want))
    return np.maximum(2.0 ** (exponent - bits), floor)


def _ulps(got, want):
    """The largest difference in units in the last place of ``want``'s
    entry (`_ulp_size`)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want) / _ulp_size(want)))


def _assert_close(got, want, dtype, limit, what):
    assert got.dtype == want.dtype and got.shape == want.shape, what
    if dtype == jnp.bfloat16:
        assert _ulps(got, want) <= limit, what
    else:
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=limit * scale, err_msg=what)


def _operands(conv, shape, dtype, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    params, out_shape = conv.init(k1, shape)
    x = jax.random.normal(k2, shape, dtype)
    t = jax.random.normal(k3, out_shape, dtype)
    return params, x, t


def _padded(conv, params):
    """The kernel and bias as ``Conv2d.apply`` pads them to the lane widths."""
    cin = conv.lane_pad_in or conv.in_channels
    cout = conv.lane_pad_out or conv.out_channels
    kernel = jnp.pad(params["kernel"], (
        (0, 0), (0, 0), (0, cin - conv.in_channels),
        (0, cout - conv.out_channels)))
    bias = params.get("bias")
    if bias is not None:
        bias = jnp.pad(bias, (0, cout - conv.out_channels))
    return kernel, bias


@DTYPES
@pytest.mark.parametrize(
    "conv",
    [
        L.Conv2d(80, 48, 1),
        L.Conv2d(80, 48, 1, bias=False),
        L.Conv2d(52, 104, 1, lane_pad_in=128),
        L.Conv2d(104, 52, 1, lane_pad_out=128, bias=False),
        L.Conv2d(128, 104, 1),
        L.Conv2d(104, 256, 1),
        L.Conv2d(416, 104, (1, 1), stride=(1, 1), padding=(0, 0)),
    ],
    ids=["bias", "no_bias", "lane_pad_in", "lane_pad_out_no_bias",
         "input_fills_the_lanes", "output_fills_the_lanes",
         "geometry_as_pairs"])
def test_dot_form_matches_the_convolution(rec, conv, dtype, limit):
    """Forward, dx, and the gradients of kernel and bias."""
    shape = (2, 12, 20, conv.lane_pad_in or conv.in_channels)
    params, x, t = _operands(conv, shape, dtype)

    def by_layer(params, x):
        y = conv.apply(params, x, ApplyCtx(train=True))
        return jnp.sum((y * t).astype(jnp.float32)), y

    def by_conv(params, x):
        y = _conv_form(x, *_padded(conv, params))
        return jnp.sum((y * t).astype(jnp.float32)), y

    (_, y), (gp, gx) = jax.value_and_grad(by_layer, (0, 1), has_aux=True)(
        params, x)
    (_, y_ref), (gp_ref, gx_ref) = jax.value_and_grad(
        by_conv, (0, 1), has_aux=True)(params, x)
    assert rec.conv_paths() == {"dot": 1}
    _assert_close(y, y_ref, dtype, limit, "y")
    _assert_close(gx, gx_ref, dtype, limit, "dx")
    assert set(gp) == set(gp_ref) == set(params)
    for name in params:
        # the parameters are float32 whatever the compute dtype, and their
        # gradients come through the cast: bf16 values in float32
        _assert_close(gp[name], gp_ref[name], dtype, limit, name)


def test_dot_form_is_one_product_on_the_activation_as_it_stands(rec):
    """What XLA is handed: one ``dot_general`` contracting the activation's
    last axis, no convolution, and no reshape or transpose of the
    activation; with the compute dtype's operands and result, and no
    accumulation type the convolution did not ask for either."""
    conv = L.Conv2d(80, 48, 1)
    params, x, _ = _operands(conv, (2, 12, 20, 80), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda p, x: conv.apply(p, x, ApplyCtx(train=True)))(params, x)
    names = [e.primitive.name for e in jaxpr.eqns]
    assert names.count("dot_general") == 1
    assert not {"conv_general_dilated", "reshape", "transpose"} & set(names)
    (dot,) = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dot.params["dimension_numbers"] == (((3,), (0,)), ((), ()))
    assert dot.params["preferred_element_type"] in (None, jnp.bfloat16)
    assert [v.aval.shape for v in dot.invars] == [(2, 12, 20, 80), (80, 48)]
    assert dot.outvars[0].aval.dtype == jnp.bfloat16


@pytest.mark.parametrize(
    "sp_kw",
    [
        pytest.param(dict(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2),
                     id="margin_by_the_layer"),
        pytest.param(dict(axis_h="sph", axis_w="spw", grid_h=2, grid_w=2,
                          d2_mode=True), id="d2_mode"),
    ])
@DTYPES
def test_dot_form_on_the_tiles_of_a_2x2_spatial_context(devices8, rec, sp_kw,
                                                        dtype, limit):
    """A pointwise convolution needs no halo: each of four tiles takes the
    product on what it holds, and forward and gradients are the whole
    image's convolution."""
    conv = L.Conv2d(80, 48, 1)
    cell = LayerCell([conv])
    shape = (2, 16, 32, 80)
    params, x, t = _operands(cell, shape, dtype, seed=1)
    ctx = ApplyCtx(train=True, spatial=SpatialCtx(**sp_kw))
    mesh = build_mesh(MeshSpec(data=1, stage=1, sph=2, spw=2), devices8[:4])
    spec = P(None, "sph", "spw", None)
    tiled = shard_map(lambda p, x: cell.apply(p, x, ctx), mesh=mesh,
                      in_specs=(P(), spec), out_specs=spec)

    def sharded(params, x):
        y = tiled(params, x)
        return jnp.sum((y * t).astype(jnp.float32)), y

    def whole(params, x):
        y = _conv_form(x, params[0]["kernel"], params[0]["bias"])
        return jnp.sum((y * t).astype(jnp.float32)), y

    (_, y), (gp, gx) = jax.jit(jax.value_and_grad(
        sharded, (0, 1), has_aux=True))(params, x)
    (_, y_ref), (gp_ref, gx_ref) = jax.value_and_grad(
        whole, (0, 1), has_aux=True)(params, x)
    assert rec.conv_paths() == {"dot": 1}
    _assert_close(y, y_ref, dtype, limit, "y")
    _assert_close(gx, gx_ref, dtype, limit, "dx")

    # The parameters' gradients are sums over pixels, and under `sp` a sum
    # of four tiles' shares, each rounded to the compute dtype before the
    # shares are added in float32: another sum than the whole image's, as
    # for any layer under `sp`.  So they are held to the convolution's own
    # shares of the same four tiles, added the same way: in bf16 each share
    # may differ by its one ulp (`_ulp_size`); in float32 the sum to 1e-6,
    # which the whole image's gradient is within too.
    def share(xt, tt):
        return jax.grad(lambda p: jnp.sum(
            (_conv_form(xt, p["kernel"], p["bias"]) * tt).astype(jnp.float32)
        ))(params[0])

    shares = [share(x[:, h:h + 8, w:w + 16], t[:, h:h + 8, w:w + 16])
              for h in (0, 8) for w in (0, 16)]
    for name in ("kernel", "bias"):
        parts = np.stack([np.asarray(s[name], np.float32) for s in shares])
        want = parts.sum(0)
        got = np.asarray(gp[0][name])
        assert got.dtype == want.dtype and got.shape == want.shape
        if dtype == jnp.bfloat16:
            room = sum(_ulp_size(part) for part in parts)
            assert np.all(np.abs(got - want) <= limit * room), name
        else:
            _assert_close(gp[0][name], jnp.asarray(want), dtype, limit, name)
            _assert_close(gp[0][name], gp_ref[0][name], dtype, limit,
                          name + " of the whole image")


def _in_a_folded_run(conv, shape):
    """BatchNorm → ReLU → ``conv`` as ResNet v2's narrow stage runs them."""
    layers = [L.BatchNorm(conv.in_channels), L.ReLU(), conv]
    p = L.run_fold(layers, shape, ApplyCtx(train=True))
    assert p == 8

    def apply(params, x, ctx):
        return unfold(L.apply_run(layers, params, fold(x, p), ctx, p), p)

    return LayerCell(layers), apply


@pytest.mark.parametrize(
    "why,conv,shape,path,knobs",
    [
        ("stride 2", L.Conv2d(80, 48, 1, stride=2), (1, 16, 32, 80), "phase",
         {}),
        ("stride 2 on W alone", L.Conv2d(80, 48, 1, stride=(1, 2)),
         (1, 16, 32, 80), "phase", {}),
        ("inside a folded run", L.Conv2d(16, 64, 1), (1, 16, 32, 16), "wfold",
         {"gate": 1, "run": True}),
        ("narrow and huge: the fold", L.Conv2d(64, 64, 1), (1, 16, 32, 64),
         "wfold", {"gate": 1}),
        ("narrow and huge, W % p: the stripes", L.Conv2d(16, 64, 1),
         (1, 16, 20, 16), "hstripe", {"gate": 1}),
        ("groups", L.Conv2d(80, 48, 1, feature_group_count=2),
         (1, 16, 32, 80), "xla", {}),
        ("padded", L.Conv2d(80, 48, 1, padding=1), (1, 16, 32, 80), "xla",
         {}),
        ("3x3", L.Conv2d(80, 48, 3), (1, 16, 32, 80), "xla", {}),
        ("1x7", L.Conv2d(80, 48, (1, 7), padding=(0, 3)), (1, 16, 32, 80),
         "xla", {}),
        # XLA keeps channels minor round these: no transpose to save
        ("both widths fill the lanes", L.Conv2d(128, 256, 1),
         (1, 16, 32, 128), "xla", {}),
        ("both widths padded to the lanes",
         L.Conv2d(52, 52, 1, lane_pad_in=128, lane_pad_out=128),
         (1, 16, 32, 128), "xla", {}),
        # the rule's own side of each boundary
        ("65 channels at the gate's size", L.Conv2d(65, 64, 1),
         (1, 16, 32, 65), "dot", {"gate": 1}),
        ("64 channels one pixel under the gate", L.Conv2d(64, 64, 1),
         (1, 16, 32, 64), "dot", {"gate": 16 * 32 + 1}),
        ("narrow and huge with the stripes opted out", L.Conv2d(16, 64, 1),
         (1, 16, 32, 16), "dot", {"gate": 1, "env": "MPI4DL_NO_HSTRIPE"}),
        ("eval mode", L.Conv2d(80, 48, 1), (1, 16, 32, 80), "dot",
         {"train": False}),
    ],
    ids=lambda v: (v.replace(" ", "_").replace(":", "").replace(",", "")
                   if isinstance(v, str) else None))
def test_the_rule_reads_kernel_stride_padding_groups_and_widths_alone(
        rec, monkeypatch, why, conv, shape, path, knobs):
    """The convolutions round the rule keep the path they had, and every
    path gives the convolution's result."""
    if "gate" in knobs:
        monkeypatch.setattr(L, "_HSTRIPE_MIN_PIXELS", knobs["gate"])
    if "env" in knobs:
        monkeypatch.setenv(knobs["env"], "1")
    ctx = ApplyCtx(train=knobs.get("train", True))
    if knobs.get("run"):
        layer, apply = _in_a_folded_run(conv, shape)
    else:
        layer, apply = conv, conv.apply
    params, x, _ = _operands(layer, shape, jnp.float32, seed=2)
    y = apply(params, x, ctx)
    assert rec.conv_paths() == {path: 1}, why

    if knobs.get("run"):
        x = L.ReLU().apply({}, L.BatchNorm(conv.in_channels).apply(
            params[0], x, ctx), ctx)
        params = params[2]
    kh, kw, sh, sw, ph, pw = conv._geometry()
    kernel, bias = _padded(conv, params)
    y_ref = lax.conv_general_dilated(
        x, kernel, (sh, sw), ((ph, ph), (pw, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=conv.feature_group_count) + bias
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
