"""``chip_smoke.py``'s logic on the CPU: its phase functions at small
scale with interpret-mode kernels, its refusal to run without a TPU, and
the import hygiene that lets one process own a chip."""
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO

sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def graph():
    return chip_smoke.make_graph(10, seed=0)


@pytest.fixture(scope="module")
def batch(graph):
    return chip_smoke.batch_job(graph, k=8)


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out


def test_batch_job_holds_partition_and_oracles(batch, graph):
    sess = batch["session"]
    assert sess.assign.shape == (graph.num_edges,)
    assert batch["rf"] < batch["rf_hashing"]
    assert batch["pagerank_l1"] <= 1e-4


def test_check_partition_rejects_an_unbalanced_assignment(batch, graph):
    sess = batch["session"]
    k = sess.k
    lopsided = np.zeros(graph.num_edges, np.int32)
    lopsided[: graph.num_edges // 2] = np.arange(graph.num_edges // 2) % k
    sess_bad = chip_smoke._session(k).with_partition(
        graph.src, graph.dst, graph.num_vertices, lopsided)
    with pytest.raises(AssertionError):
        chip_smoke.check_partition(sess_bad, graph, "lopsided")


def test_kernel_twins_agree_in_interpret_mode():
    out = chip_smoke.kernel_twins(chip_smoke.make_graph(9, seed=0), k=8)
    # off the TPU the kernels lower to the interpreter, not Mosaic
    assert out["mosaic"] == {"cluster_scatter": False,
                             "game_bestresponse": False,
                             "greedy_transform": False}


def test_service_replies_before_and_after_ingest(batch):
    out = chip_smoke.service(batch["session"], window=256)
    assert out == {"before ingest": 32, "after ingest": 32}


@pytest.mark.multidevice
def test_four_device_phases(multidevice):
    out = multidevice(f"""
    import sys
    sys.path.insert(0, {str(REPO)!r})
    import chip_smoke as cs
    g = cs.make_graph(11, seed=0)
    # the Pallas kernels in interpret mode, inside shard_map.  The RF
    # gap a 4-way stream split opens is a property of scale: 1.22x the
    # one-device partition at scale 10, 1.04x at scale 20 (the chip's
    # 10% bound) on 4 CPU devices
    sess = cs.sharded_partition(g, rf_within=0.5, kernel="pallas",
                                cluster_kernel="pallas")
    errs = cs.mesh_gas(sess.layout(), g)
    print("FOUR_OK", sorted(errs))
    """, n_devices=4)
    assert "FOUR_OK" in out


def test_importing_repro_initialises_no_backend():
    """A parent that touches a backend holds the chip; importing the
    package (every module the entry points import) must not."""
    code = (
        "import chip_smoke, repro.core, repro.session, repro.serve, "
        "repro.kernels, repro.launch.serve_graph, repro.launch.partition\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env={"PYTHONPATH": f"{REPO / 'src'}:{REPO}",
                               "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """Entry points keep JAX's compilation cache where
    JAX_COMPILATION_CACHE_DIR says (JAX reads it; nothing is set), else
    in ``<checkout>/.jax_cache``.  Run in a child: the test process
    itself never turns the cache on."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch import compile_cache as cc\n"
        "path = cc.enable_compile_cache()\n"
        "print(path)\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    if from_env:
        code += (
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(3)).block_until_ready()\n")
    env = {"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu"}
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    path, configured = proc.stdout.split("\n")[:2]
    if from_env:
        assert path == configured == str(tmp_path)
        assert any(tmp_path.iterdir()), "nothing was cached"
    else:
        assert path == configured == str(REPO / ".jax_cache")
