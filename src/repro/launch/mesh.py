"""Production mesh construction (spec: MULTI-POD DRY-RUN step 1).

A function — not a module-level constant — so importing this module never
touches jax device state."""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


# ---------------------------------------------------------------------------
# launcher meshes (--mesh / --parallel): CPU host devices or accelerators
# ---------------------------------------------------------------------------

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def parse_mesh_spec(spec):
    """``'8'`` -> (data,), ``'4,2'`` -> (data, model), ``'2,2,2'`` ->
    (data, pp, model) — the 3D training mesh: DP x pipeline stages x
    model(TP/EP) — and ``'2,2,2,2'`` -> (pod, data, pp, model).
    Returns (shape, axis_names)."""
    dims = tuple(int(x) for x in str(spec).split(",") if x.strip())
    if not 1 <= len(dims) <= 4 or any(d < 1 for d in dims):
        raise ValueError(f"bad mesh spec {spec!r} (want e.g. '8', '4,2', "
                         f"'2,2,2', '2,2,2,2')")
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("data", "pp", "model"),
            4: ("pod", "data", "pp", "model")}[len(dims)]
    return dims, axes


def ensure_host_devices(n: int) -> None:
    """Ask the CPU backend for ``n`` host devices by appending
    ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS. Only effective
    if the JAX backend has not initialized yet; respects a count the caller
    already set. Call before the first jax.devices()/PRNGKey in the process.
    """
    import os
    if n <= 1 or _FORCE_FLAG in os.environ.get("XLA_FLAGS", ""):
        return
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FORCE_FLAG}={n}").strip()


def forced_device_env(n: int, env=None) -> dict:
    """Environment for a *child process* whose JAX backend should see ``n``
    CPU host devices. Respects a force-count the caller already set (same
    rule as ensure_host_devices). Used by the bench/test subprocess runners.
    """
    import os
    env = dict(os.environ if env is None else env)
    if _FORCE_FLAG not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" {_FORCE_FLAG}={n}").strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def make_forced_mesh(shape, axes, *, what: str = None):
    """Mesh over the default backend's devices — the one shared constructor
    behind the legacy ``--mesh`` path (make_sim_mesh) and
    ``ParallelPlan.resolve``, so the device contract and its error message
    can never diverge between the two. On the CPU platform it first asks
    for enough host devices (effective only before the backend starts);
    an accelerator has the devices it has, and too few is an error."""
    n = 1
    for d in shape:
        n *= d
    requested = (jax.config.jax_platforms or "").split(",")[0]
    if requested in ("", "cpu"):      # the flag only reaches the CPU client
        ensure_host_devices(n)
    devices = jax.devices()
    if len(devices) < n:
        where = what or f"mesh {tuple(shape)}"
        platform = devices[0].platform
        if platform == "cpu":
            raise RuntimeError(
                f"{where} needs {n} devices but cpu has {len(devices)}; the "
                f"backend initialized before the mesh request — launch with "
                f"XLA_FLAGS='{_FORCE_FLAG}={n}' in the environment")
        raise RuntimeError(f"{where} needs {n} devices, {platform} has "
                           f"{len(devices)}")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_sim_mesh(spec):
    """Mesh from a CLI spec ('4,2') over the default backend's devices."""
    shape, axes = parse_mesh_spec(spec)
    return make_forced_mesh(shape, axes, what=f"mesh {spec}")
