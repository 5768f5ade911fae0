"""The chip smoke script's phases at tiny sizes on the CPU.

``chip_smoke.py`` runs phases b-e at deployment sizes on a TPU; here the
same functions run small, so a broken phase, oracle comparison or serving
check fails in tier-1 before it costs a chip run.  Where a phase reaches a
Pallas kernel (the distance kernel that ``auto`` picks on TPU), it runs in
interpret mode.  Also pinned: the script refuses to run without a TPU,
and the compile-cache placement its entry point uses.
"""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.api import QueryEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def frame(smoke):
    return smoke.phase_frame(0, 256, 16, 32, 18, n_check=64)


@pytest.fixture(scope="module")
def vector(smoke):
    return smoke.phase_vector(0, 1024, 128, 48, 10, chunk_size=16,
                              n_check=16)


def test_frame_phase(frame, capsys):
    scene, rays = frame
    assert scene.num_triangles == 256 * 16
    assert rays.origin.shape == (32 * 18, 3)


def test_frame_phase_reports_auto_backend(smoke, capsys):
    smoke.phase_frame(1, 256, 16, 24, 16, n_check=32)
    out = capsys.readouterr().out
    assert "frame.build: builder=lbvh triangles=4096" in out
    assert "frame.primary: backend=wavefront rows=384" in out
    assert "frame.shadow: backend=wavefront" in out
    assert "frame.check:" in out and "result=bit-equal" in out


def test_vector_phase(vector):
    index, queries = vector
    assert index.size == 1024 and queries.shape == (48, 128)


def test_vector_phase_through_pallas_kernel(smoke, monkeypatch, capsys):
    """The distance kernel auto picks on TPU, in interpret mode here."""
    monkeypatch.setattr(QueryEngine, "resolve_distance_backend",
                        lambda self: "pallas")
    smoke.phase_vector(2, 512, 128, 24, 10, chunk_size=8, n_check=8)
    out = capsys.readouterr().out
    assert "vector.nearest: backend=pallas rows=24" in out
    assert "vector.check:" in out


def test_cloud_phase(smoke, capsys):
    smoke.phase_cloud(0, 5000, 256, 16, 1e-3, n_check=64)
    out = capsys.readouterr().out
    assert "cloud.nearest: backend=tree_wavefront rows=256" in out
    assert "cloud.count_within: backend=tree_wavefront rows=256" in out
    # the tree held to a brute pass in the leaf's own difference form
    assert "cloud.check:" in out and "knn=leaf form to 2 ulp" in out


def test_serving_phase(smoke, frame, vector, capsys):
    scene, rays = frame
    index, queries = vector
    smoke.phase_serving(0, scene, index, rays, queries, n_requests=12,
                        max_rows=40, k=10, chunk_size=16)
    out = capsys.readouterr().out
    assert "second_pass_compiles=0" in out
    assert "requests=12" in out


def test_check_ranks_rejects_a_wrong_neighbour(smoke):
    import numpy as np

    oracle = np.asarray([[0.0, 1.0, 2.0, 3.0]], np.float32)
    band = np.asarray([1e-6])
    ids = np.asarray([[0, 1]])
    scores = np.asarray([[0.0, 1.0]], np.float32)
    smoke.check_ranks(oracle, scores, ids, scores, ids, band)
    with pytest.raises(AssertionError, match="outside the tie band"):
        smoke.check_ranks(oracle, scores, np.asarray([[0, 2]]), scores, ids,
                          band)
    with pytest.raises(AssertionError, match="scores off the oracle"):
        smoke.check_ranks(oracle, scores + 1.0, ids, scores, ids, band)


def test_script_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300,
                         cwd=tmp_path)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout and "frame" not in out.stdout


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(tmp_path, placed):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and the
    helper overrides nothing; unset, the cache is <checkout>/.jax_cache."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.runtime.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        cache = tmp_path / "cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        code += ("jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0))"
                 ".block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=tmp_path)
    assert out.returncode == 0, out.stderr
    helper, configured = out.stdout.split()
    want = str(cache) if placed else os.path.join(ROOT, ".jax_cache")
    assert helper == configured == want
    if placed:
        assert any(p.name.endswith("-cache") for p in cache.iterdir())
        assert not (tmp_path / ".jax_cache").exists()


def test_sharded_phase_on_four_host_devices(multidev):
    """The ``--chips 4`` comparison on forced host devices: shard="auto"
    spans all four, and every field matches shard=1 bit for bit.  The
    nearest query takes the Pallas distance kernel, as ``auto`` does on
    TPU (interpret mode here): its 128-row tiles make the sharded and
    single-device scores bit-identical by construction."""
    out = multidev(f"""
import importlib.util
from repro.api import QueryEngine
QueryEngine.resolve_distance_backend = lambda self: "pallas"
spec = importlib.util.spec_from_file_location(
    "chip_smoke", {os.path.join(ROOT, "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.phase_sharded(0, (256, 16, 32, 18), (1024, 128, 256, 10, 64))
""", n_devices=4)
    assert "sharded.frame.trace: shard=auto shards=4" in out
    assert "sharded.vector.nearest: shard=auto shards=4" in out
    assert out.count("result=bit-identical") == 2
