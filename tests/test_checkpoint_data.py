"""Checkpoint store (fault tolerance) + deterministic data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.checkpoint.store import wait_for_writes
from repro.configs import get_config
from repro.data import MemmapDataset, SyntheticDataset
from repro.data.pipeline import write_synthetic_corpus
from repro.launch.shapes import ShapeSpec


def _tree():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32),
                  "d": jnp.asarray(2.5, jnp.bfloat16)}}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 7, t, async_write=False)
    assert latest_step(str(tmp_path)) == 7
    back = restore_checkpoint(str(tmp_path), 7, jax.eval_shape(lambda: t))
    for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_async_write_and_latest(tmp_path):
    t = _tree()
    for step in (10, 20, 30):
        save_checkpoint(str(tmp_path), step, t, async_write=True)
    wait_for_writes()
    assert latest_step(str(tmp_path)) == 30


def test_incomplete_checkpoint_ignored(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t, async_write=False)
    # simulate a crash mid-write of step 6: directory without .done marker
    os.makedirs(tmp_path / "step_000000006")
    assert latest_step(str(tmp_path)) == 5


def test_restore_with_shardings(tmp_path):
    """Elastic path: restore re-shards onto the current (1-device) mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    t = {"w": jnp.ones((8, 4), jnp.float32)}
    save_checkpoint(str(tmp_path), 1, t, async_write=False)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    back = restore_checkpoint(str(tmp_path), 1, jax.eval_shape(lambda: t), sh)
    assert back["w"].sharding == sh["w"]


def test_synthetic_data_deterministic():
    cfg = get_config("llama3_8b").scaled_down()
    shape = ShapeSpec("t", "train", 64, 4)
    ds1 = SyntheticDataset(cfg, shape, seed=3)
    ds2 = SyntheticDataset(cfg, shape, seed=3)
    for step in (0, 5, 1000):
        b1, b2 = ds1.batch(step), ds2.batch(step)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(ds1.batch(0)["tokens"], ds1.batch(1)["tokens"])
    # restart-resume: a "new process" at step k sees the same batch
    assert np.array_equal(SyntheticDataset(cfg, shape, seed=3).batch(7)["tokens"],
                          ds1.batch(7)["tokens"])


def test_memmap_dataset(tmp_path):
    cfg = get_config("llama3_8b").scaled_down()
    path = str(tmp_path / "corpus.bin")
    write_synthetic_corpus(path, 100000, cfg.vocab_size, seed=1)
    shape = ShapeSpec("t", "train", 64, 4)
    ds = MemmapDataset(cfg, shape, path)
    b = ds.batch(0)
    assert b["tokens"].shape == (4, 64)
    assert (b["tokens"] >= 0).all() and (b["tokens"] < cfg.vocab_size).all()
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    np.testing.assert_array_equal(ds.batch(3)["tokens"],
                                  MemmapDataset(cfg, shape, path).batch(3)["tokens"])
