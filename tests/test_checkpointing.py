"""Fault-tolerance: atomic saves, GC, restore, resharding, corruption safety."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpointing.ckpt import CheckpointManager


@pytest.fixture
def tmp_ckpt(tmp_path):
    return CheckpointManager(str(tmp_path / "ck"), keep_last=2)


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (8, 8)),
                       "b": jnp.zeros((8,))},
            "nested": [jnp.arange(4.0), jnp.int32(7)]}


def test_save_restore_roundtrip(tmp_ckpt):
    s = _state()
    tmp_ckpt.save(10, s, extra={"step": 10})
    out, extra = tmp_ckpt.restore(10, jax.tree_util.tree_map(jnp.zeros_like, s))
    assert extra["step"] == 10
    for a, b in zip(jax.tree_util.tree_leaves(s),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keep_last_gc(tmp_ckpt):
    for step in (1, 2, 3, 4):
        tmp_ckpt.save(step, _state())
    assert tmp_ckpt.all_steps() == [3, 4]


def test_latest_and_resave_noop(tmp_ckpt):
    tmp_ckpt.save(5, _state(1))
    tmp_ckpt.save(5, _state(2))        # re-save same step: no crash, no-op
    assert tmp_ckpt.latest_step() == 5


def test_crash_mid_write_leaves_previous_intact(tmp_ckpt):
    tmp_ckpt.save(1, _state())
    # simulate a crashed writer: stale tmp dir
    os.makedirs(os.path.join(tmp_ckpt.directory, "step_00000002.tmp"))
    assert tmp_ckpt.latest_step() == 1
    tmp_ckpt.save(3, _state())         # next save cleans stale tmp
    assert not any(n.endswith(".tmp")
                   for n in os.listdir(tmp_ckpt.directory))


def test_shape_mismatch_rejected(tmp_ckpt):
    tmp_ckpt.save(1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        tmp_ckpt.restore(1, {"w": jnp.zeros((5,))})


def test_missing_leaf_rejected(tmp_ckpt):
    tmp_ckpt.save(1, {"w": jnp.zeros((4,))})
    with pytest.raises(KeyError):
        tmp_ckpt.restore(1, {"w": jnp.zeros((4,)), "extra": jnp.zeros((1,))})


def test_resharding_restore(tmp_ckpt):
    """Elastic scaling: save unsharded, restore onto a 1x1 mesh sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    s = {"w": jnp.arange(64.0).reshape(8, 8)}
    tmp_ckpt.save(1, s)
    sh = {"w": NamedSharding(mesh, P(None, "model"))}
    out, _ = tmp_ckpt.restore(1, jax.tree_util.tree_map(jnp.zeros_like, s),
                              shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(s["w"]))
    assert out["w"].sharding == sh["w"]


# -- manifest fingerprint + conflicting re-save (ISSUE 7) -------------------
def test_fingerprint_mismatch_rejected(tmp_ckpt):
    """Restoring one model's checkpoint into another's tree fails loudly
    with the differing leaves named — not silently, not deep in jax."""
    from repro.checkpointing.ckpt import CheckpointMismatchError
    tmp_ckpt.save(1, {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))})
    with pytest.raises(CheckpointMismatchError, match="does not fit"):
        tmp_ckpt.restore(1, {"w": jnp.zeros((4, 4)),
                             "b": jnp.zeros((4,), jnp.int32)})  # dtype flip


def test_config_identity_checked(tmp_ckpt):
    from repro.checkpointing.ckpt import CheckpointMismatchError
    s = {"w": jnp.zeros((4,))}
    tmp_ckpt.save(1, s, config="llama3-8b")
    out, _ = tmp_ckpt.restore(1, s, config="llama3-8b")   # match: fine
    with pytest.raises(CheckpointMismatchError, match="whisper"):
        tmp_ckpt.restore(1, s, config="whisper-large")
    # caller not passing a config keeps the old lenient behavior
    tmp_ckpt.restore(1, s)


def test_conflicting_resave_rejected(tmp_ckpt):
    """Same step, DIFFERENT state shape: no more silent no-op."""
    from repro.checkpointing.ckpt import CheckpointMismatchError
    tmp_ckpt.save(5, {"w": jnp.zeros((4,))}, config="arch-a")
    with pytest.raises(CheckpointMismatchError, match="refusing"):
        tmp_ckpt.save(5, {"w": jnp.zeros((8,))}, config="arch-a")
    with pytest.raises(CheckpointMismatchError, match="config"):
        tmp_ckpt.save(5, {"w": jnp.zeros((4,))}, config="other-arch")
    # identical manifest stays an idempotent no-op (crash-resume re-save)
    tmp_ckpt.save(5, {"w": jnp.ones((4,))}, config="arch-a")
    assert tmp_ckpt.latest_step() == 5


def test_tree_fingerprint_ignores_values():
    from repro.checkpointing.ckpt import tree_fingerprint
    a = tree_fingerprint({"w": jnp.zeros((4, 2)), "b": jnp.ones((3,))})
    b = tree_fingerprint({"w": jnp.full((4, 2), 9.0), "b": jnp.ones((3,))})
    c = tree_fingerprint({"w": jnp.zeros((4, 3)), "b": jnp.ones((3,))})
    assert a == b and a != c


# -- cross-mesh resharding round trip (ISSUE 7 satellite) -------------------
multidevice = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


@multidevice
def test_cross_mesh_resharding_roundtrip(tmp_path):
    """Elastic restore, mesh to mesh: save real transformer params under
    the 8-device (4,2) mesh, restore onto 1- and 2-device meshes and
    back onto (4,2) — bit-exact at every hop."""
    from repro import configs
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models import transformer

    cfg = configs.smoke_config("llama3-8b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    ref = jax.tree_util.tree_map(lambda x: np.asarray(x), params)

    def shardings_for(mesh):
        return shd.to_shardings(mesh, shd.param_specs(cfg, params, mesh=mesh))

    mesh8 = make_mesh((4, 2), ("data", "model"))
    placed = jax.device_put(params, shardings_for(mesh8))
    mgr = CheckpointManager(str(tmp_path / "xmesh"))
    mgr.save(1, placed, config=cfg.arch_id)

    state = placed
    for shape in [(1, 1), (2, 1), (4, 2)]:
        mesh = make_mesh(shape, ("data", "model"))
        sh = shardings_for(mesh)
        state, _ = mgr.restore(1, state, shardings=sh, config=cfg.arch_id)
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_leaves_with_path(ref),
                jax.tree_util.tree_leaves_with_path(state)):
            np.testing.assert_array_equal(
                a, np.asarray(b), err_msg=f"{shape}: {jax.tree_util.keystr(pb)}")
        # round-trip through the smaller mesh must also SAVE identically
        mgr2 = CheckpointManager(str(tmp_path / f"xmesh_{shape[0]}x{shape[1]}"))
        mgr2.save(1, state, config=cfg.arch_id)
        back, _ = mgr2.restore(1, state, shardings=shardings_for(mesh8),
                               config=cfg.arch_id)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- shard checksums + intact-fallback (ISSUE 8) ----------------------------
def _corrupt_shard(mgr, step):
    d = mgr._step_dir(step)
    [shard] = [n for n in os.listdir(d) if n.endswith(".npz")]
    path = os.path.join(d, shard)
    # invert one byte in the middle of the archive (array payload): every
    # bit flips, so the bytes the checksum covers always change (a fixed
    # header pattern can equal what is already there, e.g. the ZIP64
    # size placeholders numpy 2 writes as ff ff ff ff)
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0xFF]))
    return path


def test_checksums_in_manifest_and_verify(tmp_ckpt):
    tmp_ckpt.save(1, _state())
    d = tmp_ckpt._step_dir(1)
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["checksums"], "manifest carries shard checksums"
    assert tmp_ckpt.verify(1)
    _corrupt_shard(tmp_ckpt, 1)
    assert not tmp_ckpt.verify(1)


def test_restore_rejects_corrupt_shard(tmp_ckpt):
    from repro.checkpointing.ckpt import CheckpointMismatchError
    s = _state()
    tmp_ckpt.save(1, s)
    _corrupt_shard(tmp_ckpt, 1)
    with pytest.raises(CheckpointMismatchError, match="checksum mismatch"):
        tmp_ckpt.restore(1, jax.tree_util.tree_map(jnp.zeros_like, s))


def test_restore_rejects_missing_shard(tmp_ckpt):
    from repro.checkpointing.ckpt import CheckpointMismatchError
    s = _state()
    tmp_ckpt.save(1, s)
    d = tmp_ckpt._step_dir(1)
    [shard] = [n for n in os.listdir(d) if n.endswith(".npz")]
    os.remove(os.path.join(d, shard))
    with pytest.raises(CheckpointMismatchError, match="missing"):
        tmp_ckpt.restore(1, jax.tree_util.tree_map(jnp.zeros_like, s))


def test_restore_latest_falls_back_past_corruption(tmp_ckpt):
    s = _state()
    tmp_ckpt.save(1, s)
    tmp_ckpt.save(2, _state(seed=2))
    _corrupt_shard(tmp_ckpt, 2)           # newest checkpoint is damaged
    with pytest.warns(UserWarning, match="failed verification"):
        assert tmp_ckpt.latest_intact_step() == 1
    with pytest.warns(UserWarning, match="falling back"):
        step, out, _ = tmp_ckpt.restore_latest(
            jax.tree_util.tree_map(jnp.zeros_like, s))
    assert step == 1                      # one interval lost, not the run
    for a, b in zip(jax.tree_util.tree_leaves(s),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # latest_step (resume-point listing) still sees the damaged one
    assert tmp_ckpt.latest_step() == 2


def test_restore_latest_with_no_intact_checkpoint(tmp_ckpt):
    tmp_ckpt.save(1, _state())
    _corrupt_shard(tmp_ckpt, 1)
    with pytest.warns(UserWarning, match="failed verification"):
        with pytest.raises(FileNotFoundError, match="no intact"):
            tmp_ckpt.restore_latest(
                jax.tree_util.tree_map(jnp.zeros_like, _state()))


def test_pre_checksum_checkpoints_still_verify(tmp_ckpt):
    tmp_ckpt.save(1, _state())
    d = tmp_ckpt._step_dir(1)
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    del manifest["checksums"]             # an older-format checkpoint
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    assert tmp_ckpt.verify(1)             # trusted, not rejected
    assert tmp_ckpt.latest_intact_step() == 1
