import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TESTS = os.path.dirname(os.path.abspath(__file__))
if os.path.abspath(SRC) not in sys.path:
    sys.path.insert(0, os.path.abspath(SRC))


def pytest_addoption(parser):
    parser.addoption(
        "--regen-goldens", action="store_true", default=False,
        help="regenerate tests/golden/*.npz from the wavefront oracle "
             "(then re-run without the flag to verify; see "
             "tests/golden/README.md)")


@pytest.fixture
def regen_goldens(request):
    """Whether this run should rewrite the golden-trace fixtures."""
    return request.config.getoption("--regen-goldens")


def make_test_mesh(axis_shape, axis_names):
    """A mesh over the first ``prod(axis_shape)`` devices, every axis Auto."""
    import jax

    return jax.make_mesh(axis_shape, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run ``code`` in a subprocess with n fake CPU devices.

    Multi-device tests need XLA_FLAGS set before jax import, which cannot
    happen inside an already-initialized test process.  The tests directory
    is on the subprocess path so code strings can import helpers from this
    conftest (``from conftest import make_test_mesh``).  The child is held
    to the CPU: forced host devices are CPU devices, and on a machine with
    an accelerator the parent process may already hold it.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    # prepend (not replace): packages reachable only via the caller's
    # PYTHONPATH (e.g. hypothesis in some setups) stay importable
    inherited = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(SRC), TESTS] + ([inherited] if inherited else []))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], env=env,
        capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.fixture
def multidev():
    return run_with_devices
