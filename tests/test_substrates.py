"""Substrate tests: checkpoint writes and the graph service's straggler
watch (``repro.dist.ft``)."""
import numpy as np

import jax.numpy as jnp

from repro import ckpt
from repro.dist.ft import StragglerWatch


# ---------------------------------------------------------------- checkpoint

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12).reshape(3, 4).astype(jnp.float32),
            "b": {"c": jnp.ones((2,), jnp.bfloat16)}}
    ckpt.save(tmp_path, 7, tree)
    got, step = ckpt.restore_latest(tmp_path, tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(got["b"]["c"], np.float32),
                                  np.asarray(tree["b"]["c"], np.float32))


def test_checkpoint_skips_torn_writes(tmp_path):
    tree = {"a": jnp.ones((2,))}
    ckpt.save(tmp_path, 1, tree)
    # simulate a torn write: directory without manifest
    (tmp_path / "step_00000009").mkdir()
    got, step = ckpt.restore_latest(tmp_path, tree)
    assert step == 1


def test_checkpoint_latest_wins(tmp_path):
    tree = {"a": jnp.zeros((2,))}
    ckpt.save(tmp_path, 1, tree)
    ckpt.save(tmp_path, 5, {"a": jnp.full((2,), 5.0)})
    got, step = ckpt.restore_latest(tmp_path, tree)
    assert step == 5
    assert float(got["a"][0]) == 5.0


# ---------------------------------------------------------------- FT

def test_ft_straggler_detection():
    """A step three times slower than the running median is flagged; the
    median it is judged against excludes the slow step itself."""
    watch = StragglerWatch(factor=3.0)
    times = [0.01, 0.011, 0.009, 0.01, 0.012, 0.01, 0.3, 0.01, 0.011, 0.01]
    flags = [watch.observe(dt) for dt in times]
    assert flags == [i == 6 for i in range(len(times))]
    assert watch.flagged == 1
    assert watch.last_median == 0.01
    # factor 0 disables, and nothing is flagged before the warm-up
    off = StragglerWatch(factor=0.0)
    assert not any(off.observe(dt) for dt in times)
    cold = StragglerWatch(factor=3.0, warmup=3)
    assert [cold.observe(dt) for dt in (1.0, 1.0, 10.0, 10.0)] == [
        False, False, False, True]
