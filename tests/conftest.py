import os
import sys

# smoke tests and benches must see ONE device (the dry-run sets its own
# XLA_FLAGS in a separate process; never set device-count flags here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "src")
for _p in (_SRC, _HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# forced-8-device subprocess sessions (the `distributed` marker's substrate):
# the main pytest process keeps its single-device view; mesh tests run their
# snippet in a child process whose backend is forced to 8 CPU host devices.
# ---------------------------------------------------------------------------

_ROOT = os.path.dirname(_HERE)
_N_FORCED = 8
_mesh8_ok = None


def _mesh8_env():
    from repro.launch.mesh import forced_device_env
    env = forced_device_env(_N_FORCED)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _mesh8_available() -> bool:
    global _mesh8_ok
    if _mesh8_ok is None:
        import subprocess
        try:
            r = subprocess.run(
                [sys.executable, "-c",
                 f"import jax; assert len(jax.devices()) == {_N_FORCED}"],
                capture_output=True, env=_mesh8_env(), timeout=300)
            _mesh8_ok = r.returncode == 0
        except Exception:
            _mesh8_ok = False
    return _mesh8_ok


@pytest.fixture(scope="session")
def mesh8():
    """Callable running a python snippet in a subprocess with 8 forced CPU
    host devices; returns its stdout, asserts exit 0, and skips the test
    cleanly when the platform can't force host devices."""
    if not _mesh8_available():
        pytest.skip(f"cannot force {_N_FORCED} CPU host devices")
    import subprocess
    import textwrap

    def run_sub(code: str, timeout: int = 900) -> str:
        r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                           capture_output=True, text=True, env=_mesh8_env(),
                           timeout=timeout)
        assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
        return r.stdout

    return run_sub


@pytest.fixture(scope="session")
def mesh8_start():
    """Like ``mesh8``, but the snippet starts at once in the background:
    returns ``start(code) -> wait``, and ``wait()`` returns its stdout and
    asserts exit 0. A test module overlaps a child's compile with its own
    work this way."""
    if not _mesh8_available():
        pytest.skip(f"cannot force {_N_FORCED} CPU host devices")
    import subprocess
    import textwrap
    children = []

    def start(code: str, timeout: int = 900):
        p = subprocess.Popen([sys.executable, "-c", textwrap.dedent(code)],
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                             text=True, env=_mesh8_env())
        children.append(p)

        def wait() -> str:
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, f"STDOUT:\n{out}\nSTDERR:\n{err}"
            return out

        return wait

    yield start
    for p in children:
        if p.poll() is None:
            p.kill()
            p.communicate()
