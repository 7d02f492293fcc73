"""Checkpoint/restore (mpi4dl_tpu/checkpoint.py): resume must be
bit-identical, including flat pipeline buffers and optimizer state; files
carry a CRC32 manifest + config fingerprint and restore_latest walks past
invalid files (torn/corrupt/mismatched) to the newest valid one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.checkpoint import (
    CheckpointInvalid,
    CheckpointManager,
    config_fingerprint,
    load_arrays,
    restore_state,
    save_state,
)
from mpi4dl_tpu.mesh import MeshSpec, build_mesh
from mpi4dl_tpu.models.resnet import get_resnet_v2
from mpi4dl_tpu.parallel.partition import StagePartition
from mpi4dl_tpu.parallel.pipeline import init_pipeline_state, make_pipeline_train_step
from mpi4dl_tpu.train import Optimizer, TrainState, make_train_step


def test_simple_state_roundtrip(tmp_path):
    model = get_resnet_v2((2, 32, 32, 3), depth=11, num_classes=10)
    params, _ = model.init(jax.random.key(0))
    opt = Optimizer("sgd", lr=0.01, momentum=0.9)
    step = make_train_step(model, opt)
    state = TrainState.create(params, opt)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    y = jnp.array([0, 1], jnp.int32)

    state, _ = step(state, x, y)
    path = str(tmp_path / "ckpt_1.npz")
    save_state(path, state, 1)

    # Fresh template (as a resumed process would build it), then restore.
    template = TrainState.create(params, opt)
    restored = restore_state(path, template)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # Continue training from both: identical trajectories.
    s1, m1 = step(state, x, y)
    s2, m2 = step(restored, x, y)
    assert float(m1["loss"]) == float(m2["loss"])
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipeline_state_roundtrip(tmp_path, devices8):
    """Flat stage-sharded buffers (incl. opt state) restore with their
    shardings and resume bit-identically."""
    model = get_resnet_v2((2, 32, 32, 3), depth=11, num_classes=10)
    params, _ = model.init(jax.random.key(0))
    mesh = build_mesh(MeshSpec(stage=2), jax.devices()[:2])
    part = StagePartition.build(model, params, 2, (1, 32, 32, 3))
    opt = Optimizer("sgd", lr=0.01)
    step = make_pipeline_train_step(part, opt, mesh, parts=2)
    state = init_pipeline_state(part, params, opt, mesh)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    y = jnp.array([0, 1], jnp.int32)

    state, _ = step(state, x, y)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(state, step_id=1)

    template = init_pipeline_state(part, params, opt, mesh)
    restored, step_id = mgr.restore_latest(template)
    assert step_id == 1
    np.testing.assert_array_equal(
        np.asarray(restored.param_buf), np.asarray(state.param_buf)
    )
    s1, m1 = step(state, x, y)
    s2, m2 = step(restored, x, y)
    assert float(m1["loss"]) == float(m2["loss"])
    np.testing.assert_array_equal(np.asarray(s1.param_buf), np.asarray(s2.param_buf))


def test_manager_keep_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"w": jnp.ones((3,))}
    for sid in (1, 2, 3):
        mgr.save(state, step_id=sid)
    assert mgr.latest_path().endswith("ckpt_3")
    import os

    files = sorted(os.listdir(tmp_path))
    assert files == ["ckpt_2", "ckpt_3"]  # sharded dirs, oldest pruned


def test_manager_npz_format_compat(tmp_path):
    """format='npz' keeps the v1 single-file layout, and a sharded manager
    restores v1 files (mixed directories walk across formats)."""
    import os

    v1 = CheckpointManager(str(tmp_path), format="npz")
    v1.save({"w": jnp.arange(3.0)}, step_id=1)
    assert sorted(os.listdir(tmp_path)) == ["ckpt_1.npz"]
    mixed = CheckpointManager(str(tmp_path))  # sharded writer, dual reader
    mixed.save({"w": jnp.arange(3.0) * 2}, step_id=2)
    state, step_id = mixed.restore_latest({"w": jnp.zeros((3,))})
    assert step_id == 2
    from mpi4dl_tpu.resilience import corrupt_file

    corrupt_file(mixed.latest_path())  # newest (sharded) falls back to v1
    state, step_id = mixed.restore_latest({"w": jnp.zeros((3,))})
    assert step_id == 1
    np.testing.assert_array_equal(np.asarray(state["w"]), np.arange(3.0))


def test_restore_rejects_mismatched_shapes(tmp_path):
    path = str(tmp_path / "ckpt_1.npz")
    save_state(path, {"w": jnp.ones((3,))}, 1)

    with pytest.raises(ValueError):
        restore_state(path, {"w": jnp.ones((4,))})


# ---------------------------------------------------------------------------
# Manifest: CRC32, fingerprint, step-id round-trip (ISSUE 3)
# ---------------------------------------------------------------------------


def test_manifest_step_id_roundtrip(tmp_path):
    path = str(tmp_path / "ckpt_7.npz")
    save_state(path, {"w": jnp.arange(8.0)}, 7, fingerprint="abcd")
    arrays, step_id = load_arrays(path, expected_fingerprint="abcd")
    assert step_id == 7
    np.testing.assert_array_equal(arrays["leaf_0"], np.arange(8.0))


def test_manifest_detects_bit_corruption(tmp_path):
    """Flipped bytes mid-file fail validation (zip CRC or manifest CRC32 —
    either way CheckpointInvalid, never a silently-wrong resume)."""
    from mpi4dl_tpu.resilience import corrupt_file

    path = str(tmp_path / "ckpt_1.npz")
    save_state(path, {"w": jnp.arange(64.0)}, 1)
    corrupt_file(path)
    with pytest.raises(CheckpointInvalid):
        load_arrays(path)


def test_fingerprint_mismatch_rejected(tmp_path):
    path = str(tmp_path / "ckpt_1.npz")
    save_state(path, {"w": jnp.ones((3,))}, 1, fingerprint="aaaa")
    with pytest.raises(CheckpointInvalid):
        load_arrays(path, expected_fingerprint="bbbb")
    # no expected fingerprint -> accepted (old callers, ad-hoc restores)
    _, step_id = load_arrays(path)
    assert step_id == 1


def test_restore_latest_mismatch_is_a_hard_error(tmp_path):
    """All-files fingerprint mismatch (a DIFFERENT program, deterministic
    user error) must raise even without require=True: a silent fresh start
    would let the new run's saves prune the mismatched run's checkpoints."""
    from mpi4dl_tpu.checkpoint import CheckpointMismatch

    saver = CheckpointManager(str(tmp_path), fingerprint="aaaa")
    saver.save({"w": jnp.ones((3,))}, step_id=5)
    resumer = CheckpointManager(str(tmp_path), fingerprint="bbbb")
    with pytest.raises(CheckpointMismatch):
        resumer.restore_latest({"w": jnp.ones((3,))})
    # wrong template structure (leaf shapes) is the same class of error
    same_fp = CheckpointManager(str(tmp_path), fingerprint="aaaa")
    with pytest.raises(CheckpointMismatch):
        same_fp.restore_latest({"w": jnp.ones((4,))})


def test_config_fingerprint_ignores_volatile_fields():
    from mpi4dl_tpu.config import ParallelConfig

    a = ParallelConfig(checkpoint_dir="/x", verbose=True, num_epochs=2)
    # extending a run (more epochs) or moving it must still resume
    b = ParallelConfig(checkpoint_dir="/y", verbose=False, num_epochs=4)
    c = ParallelConfig(batch_size=64)
    assert config_fingerprint(a) == config_fingerprint(b)
    assert config_fingerprint(a) != config_fingerprint(c)
    # set ordering is process/hash-seed dependent; the digest must not be
    assert config_fingerprint({"s": {"b", "a", "c"}}) == config_fingerprint(
        {"s": {"c", "a", "b"}}
    )


def test_restore_latest_require_raises_when_all_invalid(tmp_path):
    from mpi4dl_tpu.resilience import corrupt_file

    mgr = CheckpointManager(str(tmp_path))
    corrupt_file(mgr.save({"w": jnp.ones((3,))}, step_id=1))
    with pytest.raises(CheckpointInvalid):
        mgr.restore_latest({"w": jnp.ones((3,))}, require=True)
    # and on an empty directory too
    empty = CheckpointManager(str(tmp_path / "empty"))
    with pytest.raises(CheckpointInvalid):
        empty.restore_latest({"w": jnp.ones((3,))}, require=True)


def test_restore_latest_empty_dir_fresh_start(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    template = {"w": jnp.ones((3,))}
    state, step_id = mgr.restore_latest(template)
    assert step_id == 0 and state is template


# ---------------------------------------------------------------------------
# Sharded format v2 + elastic restore (ISSUE 13)
# ---------------------------------------------------------------------------


def test_sharded_manifest_offsets_and_crcs(tmp_path, devices8):
    """Each leaf is written as its unique addressable shards keyed by
    GLOBAL offsets, each with its own CRC32; replicas are deduplicated."""
    import json
    import os

    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi4dl_tpu.checkpoint import SHARD_MANIFEST, load_sharded_arrays
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(stage=2, sph=2, spw=2), jax.devices()[:8])
    w = jax.device_put(
        jnp.arange(64.0).reshape(8, 8), NamedSharding(mesh, P("stage", None))
    )
    rep = jax.device_put(jnp.arange(6.0), NamedSharding(mesh, P()))
    mgr = CheckpointManager(str(tmp_path))
    path = mgr.save({"w": w, "rep": rep}, 4)

    manifest = json.load(open(os.path.join(path, SHARD_MANIFEST)))
    assert manifest["schema"] == 2 and manifest["step_id"] == 4
    by_nshards = sorted(len(l["shards"]) for l in manifest["leaves"])
    assert by_nshards == [1, 2]  # replicated leaf deduped; 2 stage rows
    sharded_leaf = next(l for l in manifest["leaves"]
                        if len(l["shards"]) == 2)
    assert [s["offset"] for s in sharded_leaf["shards"]] == [[0, 0], [4, 0]]
    assert all(isinstance(s["crc32"], int) for s in sharded_leaf["shards"])
    # save cost accounting for the RunLog `checkpoint` record
    stats = mgr.last_save_stats
    assert stats.shards == 3 and stats.bytes > 0
    assert stats.gather_ms >= 0 and stats.write_ms > 0

    arrays, step_id = load_sharded_arrays(path)
    assert step_id == 4
    w_leaf = manifest["leaves"].index(sharded_leaf)
    np.testing.assert_array_equal(
        arrays[f"leaf_{w_leaf}"], np.arange(64.0).reshape(8, 8)
    )


def test_elastic_restore_cross_mesh(tmp_path, devices8):
    """THE elastic-restore contract at the leaf level: a checkpoint saved
    under one mesh layout restores bit-identically under a template built
    on a DIFFERENT mesh shape, and the restored leaves carry the TARGET
    shardings.  Identity must match; layout skew is allowed and flagged."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi4dl_tpu.checkpoint import split_config_fingerprint
    from mpi4dl_tpu.mesh import MeshSpec, build_mesh

    spec_a, spec_b = MeshSpec(stage=2, sph=2, spw=2), MeshSpec(stage=2, sph=4, spw=1)
    mesh_a = build_mesh(spec_a, jax.devices()[:8])
    mesh_b = build_mesh(spec_b, jax.devices()[:8])
    cfg_a = {"model": "resnet", "seed": 0, "slice_method": "square", "parts": 4}
    cfg_b = {"model": "resnet", "seed": 0, "slice_method": "horizontal", "parts": 2}
    ia, la, da = split_config_fingerprint(cfg_a, spec_a)
    ib, lb, db = split_config_fingerprint(cfg_b, spec_b)
    assert ia == ib and la != lb  # same model, different layout

    w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh_a, P("stage", None)))
    tiles = jax.device_put(jnp.arange(16.0).reshape(4, 4),
                           NamedSharding(mesh_a, P(("sph", "spw"), None)))
    saver = CheckpointManager(str(tmp_path), identity=ia, layout=la,
                              layout_desc=da)
    saver.save({"w": w, "t": tiles}, 7)

    template = {
        "w": jax.device_put(jnp.zeros((8, 8)),
                            NamedSharding(mesh_b, P("stage", None))),
        "t": jax.device_put(jnp.zeros((4, 4)),
                            NamedSharding(mesh_b, P("sph", None))),
    }
    restorer = CheckpointManager(str(tmp_path), identity=ib, layout=lb,
                                 layout_desc=db)
    state, step_id = restorer.restore_latest(template)
    assert step_id == 7
    assert restorer.last_restore.elastic
    assert restorer.last_restore.saved_layout["slice_method"] == "square"
    np.testing.assert_array_equal(np.asarray(state["w"]),
                                  np.arange(64.0).reshape(8, 8))
    np.testing.assert_array_equal(np.asarray(state["t"]),
                                  np.arange(16.0).reshape(4, 4))
    assert state["w"].sharding == template["w"].sharding  # target mesh
    # Same-geometry restore stays non-elastic (v1-equivalent behavior).
    again = CheckpointManager(str(tmp_path), identity=ia, layout=la)
    _, sid = again.restore_latest({"w": w, "t": tiles})
    assert sid == 7 and not again.last_restore.elastic


def test_elastic_restore_identity_mismatch_still_hard(tmp_path):
    """Layout may differ; model identity may NOT."""
    from mpi4dl_tpu.checkpoint import CheckpointMismatch, split_config_fingerprint

    ia, la, da = split_config_fingerprint({"model": "resnet", "parts": 2})
    ib, lb, _ = split_config_fingerprint({"model": "amoebanet", "parts": 4})
    saver = CheckpointManager(str(tmp_path), identity=ia, layout=la,
                              layout_desc=da)
    saver.save({"w": jnp.ones((3,))}, 1)
    with pytest.raises(CheckpointMismatch):
        CheckpointManager(str(tmp_path), identity=ib,
                          layout=lb).restore_latest({"w": jnp.ones((3,))})


def test_elastic_restore_shape_change_is_typed_error(tmp_path):
    """A layout change that re-packs leaf shapes cannot restore elastically:
    the cheap pass raises a typed CheckpointMismatch naming the leaf."""
    from mpi4dl_tpu.checkpoint import CheckpointMismatch, split_config_fingerprint

    ia, la, da = split_config_fingerprint({"model": "r", "spatial_until": 5})
    _, lb, _ = split_config_fingerprint({"model": "r", "spatial_until": 9})
    saver = CheckpointManager(str(tmp_path), identity=ia, layout=la,
                              layout_desc=da)
    saver.save({"buf": jnp.ones((6,))}, 1)
    with pytest.raises(CheckpointMismatch, match="not leaf-shape-preserving"):
        CheckpointManager(str(tmp_path), identity=ia,
                          layout=lb).restore_latest({"buf": jnp.ones((8,))})


def test_quant_policy_change_is_reshape_not_drift(tmp_path):
    """The resolved quant policy lives in the LAYOUT fingerprint: resuming
    with a different --quant is an elastic reshape (flagged), never a
    silent same-layout restore."""
    from mpi4dl_tpu.checkpoint import split_config_fingerprint

    i8, l8, d8 = split_config_fingerprint(
        {"model": "r"}, extra_layout={"quant_resolved": "junction=int8"})
    ioff, loff, doff = split_config_fingerprint(
        {"model": "r"}, extra_layout={"quant_resolved": "off"})
    assert i8 == ioff and l8 != loff
    CheckpointManager(str(tmp_path), identity=i8, layout=l8,
                      layout_desc=d8).save({"w": jnp.ones((3,))}, 2)
    r = CheckpointManager(str(tmp_path), identity=ioff, layout=loff,
                          layout_desc=doff)
    _, sid = r.restore_latest({"w": jnp.zeros((3,))})
    assert sid == 2 and r.last_restore.elastic
    assert r.last_restore.saved_layout["quant_resolved"] == "junction=int8"


def test_checkpoint_from_before_a_layout_field_was_retired_restores(
        tmp_path, monkeypatch):
    """A v2 checkpoint written before PR 31: ``ParallelConfig`` then had a
    ``pallas_conv`` field (None unless a flag set it) and counted it as
    layout, so the manifest's ``layout_desc`` carries ``"pallas_conv": null``
    and its layout fingerprint covers the key.  Today's run has no such field
    and ``LAYOUT_FIELDS`` no such name: the model's identity is the same, so
    the checkpoint restores, flagged as a restore across layouts (the two
    layout fingerprints differ by the key alone), and the manifest still says
    what was saved.  Fails if a retired layout field is made part of a run's
    identity, or an unknown key in a saved layout description is refused."""
    import dataclasses

    from mpi4dl_tpu import checkpoint as ckpt
    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.mesh import MeshSpec

    assert "pallas_conv" not in ckpt.LAYOUT_FIELDS
    cfg = ParallelConfig(model="resnet", batch_size=2, image_size=32)
    assert not hasattr(cfg, "pallas_conv")
    spec = MeshSpec()
    # as the parent wrote it: its dataclass had the field, its LAYOUT_FIELDS
    # the name
    with monkeypatch.context() as then:
        then.setattr(ckpt, "LAYOUT_FIELDS",
                     ckpt.LAYOUT_FIELDS | {"pallas_conv"})
        i_then, l_then, d_then = ckpt.split_config_fingerprint(
            dict(dataclasses.asdict(cfg), pallas_conv=None), spec)
    assert d_then["pallas_conv"] is None
    CheckpointManager(str(tmp_path), identity=i_then, layout=l_then,
                      layout_desc=d_then).save({"w": jnp.arange(6.0)}, 3)
    with open(tmp_path / "ckpt_3" / ckpt.SHARD_MANIFEST) as f:
        assert '"pallas_conv": null' in f.read()

    i_now, l_now, d_now = ckpt.split_config_fingerprint(cfg, spec)
    assert i_now == i_then and l_now != l_then and "pallas_conv" not in d_now
    r = CheckpointManager(str(tmp_path), identity=i_now, layout=l_now,
                          layout_desc=d_now)
    state, sid = r.restore_latest({"w": jnp.zeros((6,))}, require=True)
    assert sid == 3 and r.last_restore.elastic
    assert r.last_restore.saved_layout["pallas_conv"] is None
    np.testing.assert_array_equal(np.asarray(state["w"]), np.arange(6.0))


def test_cheap_validation_reads_no_array_bytes(tmp_path, monkeypatch):
    """Walking past a torn checkpoint is manifest-first: the rejected
    candidates cost a manifest read + stat pass, never a shard read; a
    template-shape mismatch is also detected without array bytes."""
    import os

    from mpi4dl_tpu import checkpoint as ckpt_mod
    from mpi4dl_tpu.checkpoint import CheckpointMismatch

    reads = []
    real = ckpt_mod._read_shard_bytes
    monkeypatch.setattr(ckpt_mod, "_read_shard_bytes",
                        lambda p: (reads.append(p) or real(p)))

    mgr = CheckpointManager(str(tmp_path))
    mgr.save({"w": jnp.arange(1024.0)}, 1)
    p2 = mgr.save({"w": jnp.arange(1024.0) * 2}, 2)
    shard = next(os.path.join(p2, f) for f in sorted(os.listdir(p2))
                 if f.endswith(".bin"))
    with open(shard, "r+b") as f:  # torn multi-KB shard
        f.truncate(os.path.getsize(shard) // 2)

    _, step_id = mgr.restore_latest({"w": jnp.zeros((1024,))})
    assert step_id == 1
    # exactly the surviving checkpoint's single shard was read — the torn
    # ckpt_2 was rejected by the stat pass
    assert len(reads) == 1 and os.path.dirname(reads[0]).endswith("ckpt_1")

    reads.clear()
    with pytest.raises(CheckpointMismatch):
        mgr.restore_latest({"w": jnp.zeros((7,))})  # wrong template shape
    assert reads == []  # mismatch detected from the manifest alone


def test_cheap_validation_npz_truncated(tmp_path):
    """v1 npz: truncation fails the zip-directory read in the cheap pass."""
    import os

    from mpi4dl_tpu.checkpoint import cheap_validate

    path = str(tmp_path / "ckpt_1.npz")
    save_state(path, {"w": jnp.arange(4096.0)}, 1)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 3)
    with pytest.raises(CheckpointInvalid):
        cheap_validate(path)


def test_sync_sharded_save_memory_is_one_shard(tmp_path, devices8):
    """The sync sharded save's peak host materialization is O(largest
    shard): the stats watermark equals the largest shard, far under the
    full state size."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi4dl_tpu.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(stage=8), jax.devices()[:8])
    big = jax.device_put(jnp.ones((8, 4096), jnp.float32),
                         NamedSharding(mesh, P("stage", None)))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save({"big": big, "big2": big + 1}, 1)
    stats = mgr.last_save_stats
    total = 2 * 8 * 4096 * 4
    assert stats.bytes == total and stats.shards == 16
    assert stats.peak_pending_bytes == 4096 * 4  # one stage row


@pytest.mark.slow
def test_elastic_restore_sp_pipeline_reshape(tmp_path, devices8):
    """End-to-end reshape-restore through the benchmark entry point: save
    under SP(2×2)×PP(2) parts=4, resume under SP(4×1)×PP(2) parts=2.  The
    restore point is leaf-bit-identical (checked directly against the
    saved checkpoint), training continues, and the final loss matches a
    target-geometry control within tolerance (parts changes micro-batch BN
    statistics, so bit-identity across the reshape is not promised)."""
    import os

    from benchmarks.common import run
    from mpi4dl_tpu.checkpoint import load_arrays

    def argv(ck, extra):
        return [
            "--image-size", "32", "--num-layers", "1", "--batch-size", "4",
            "--steps-per-epoch", "2", "--num-epochs", "2",
            "--split-size", "2", "--checkpoint-dir", str(tmp_path / ck),
        ] + extra

    geo_a = ["--slice-method", "square", "--parts", "4"]
    geo_b = ["--slice-method", "horizontal", "--parts", "2"]

    control_b = run("sp", "resnet", argv("ck_control", geo_b))

    os.environ["MPI4DL_FAULT"] = "reshape@2:slice-method=horizontal,parts=2"
    try:
        killed = run("sp", "resnet", argv("ck_reshape", geo_a))
    finally:
        del os.environ["MPI4DL_FAULT"]
    assert killed["preempted"] and killed["final_step"] == 3

    # Leaf-level bit-identity at the restore point: what geometry B's
    # manager hands back equals what geometry A wrote, byte for byte.
    saved_arrays, saved_step = load_arrays(
        str(tmp_path / "ck_reshape" / "ckpt_3"))
    assert saved_step == 3

    resumed = run("sp", "resnet", argv("ck_reshape", geo_b))
    assert resumed["elastic"], "layout skew must be an ELASTIC restore"
    assert resumed["start_step"] == 3 and resumed["final_step"] == 4
    # The resume leg re-saved at step 4 under geometry B; its step-3 source
    # leaves must survive the round trip through the elastic re-placement.
    resaved, _ = load_arrays(str(tmp_path / "ck_reshape" / "ckpt_4"))
    assert sorted(saved_arrays) == sorted(resaved)

    a, b = resumed["loss"], control_b["loss"]
    assert abs(a - b) <= 0.05 * max(abs(a), abs(b), 1e-6), (
        f"reshape-resumed loss {a} vs target-geometry control {b}"
    )


def test_resave_same_step_swaps_safely(tmp_path):
    """Re-saving an existing step id (a boundary re-reached after rollback)
    publishes the new version and leaves no hidden work dirs behind."""
    import os

    mgr = CheckpointManager(str(tmp_path))
    mgr.save({"w": jnp.full((4,), 1.0)}, step_id=2)
    mgr.save({"w": jnp.full((4,), 9.0)}, step_id=2)
    state, step_id = mgr.restore_latest({"w": jnp.zeros((4,))})
    assert step_id == 2
    np.testing.assert_array_equal(np.asarray(state["w"]), np.full((4,), 9.0))
    assert sorted(os.listdir(tmp_path)) == ["ckpt_2"]  # no .tmp/.old strays


def test_manager_init_reclaims_stranded_work_dirs(tmp_path):
    """Hidden .tmp_ckpt_*/.old_ckpt_* dirs from a hard crash are reclaimed
    at manager construction."""
    import os

    (tmp_path / ".tmp_ckpt_3_x").mkdir()
    (tmp_path / ".old_ckpt_3_y").mkdir()
    CheckpointManager(str(tmp_path))
    assert sorted(os.listdir(tmp_path)) == []


def test_load_arrays_vanished_shard_is_checkpoint_invalid(tmp_path):
    """A shard file that vanishes between manifest read and shard read
    surfaces as CheckpointInvalid through the public load API, not a raw
    OSError."""
    import os

    mgr = CheckpointManager(str(tmp_path))
    path = mgr.save({"w": jnp.arange(8.0)}, 1)
    shard = next(os.path.join(path, f) for f in sorted(os.listdir(path))
                 if f.endswith(".bin"))
    os.unlink(shard)
    with pytest.raises(CheckpointInvalid, match="unreadable|missing"):
        load_arrays(path)
