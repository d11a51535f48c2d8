"""Shardlint layer 2 (repro.analysis.lint): the current tree passes
clean, and each AST rule fires on a synthetic violation — including the
acceptance criterion that a file using raw ``shard_map`` exits non-zero.
The lint must stay importable without jax (CI runs it pre-install)."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import lint as L

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(violations):
    return sorted({v[0] for v in violations})


# --- the current tree is clean --------------------------------------------

def test_repo_tree_passes_clean():
    paths = [os.path.join(ROOT, d) for d in ("src", "tests", "benchmarks")]
    vs = L.lint_paths(paths)
    assert vs == [], "\n".join(f"{p}:{ln}: {r} {m}" for r, p, ln, m in vs)


def test_cli_exits_zero_on_tree():
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "src", "tests",
         "benchmarks"],
        cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


def test_lint_importable_without_jax():
    """The CI lint job runs before any jax install — importing the lint
    module must not pull jax in."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.modules['jax'] = None\n"
         "import repro.analysis.lint as L\n"
         "print(len(L.ALLOWLIST))"],
        cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")})
    assert r.returncode == 0, r.stdout + r.stderr


# --- SL001: raw shard_map -------------------------------------------------

@pytest.mark.parametrize("src", [
    "from jax.experimental.shard_map import shard_map",
    "import jax.experimental.shard_map as sm",
    "from jax.experimental import shard_map",
    "def f():\n    return jax.experimental.shard_map.shard_map",
])
def test_sl001_raw_shard_map(src):
    assert _rules(L.lint_source(src, "synthetic/mod.py")) == ["SL001"]


def test_sl001_fires_in_compat():
    """SL001 has no allowlist: the path that once owned the old-jax shims
    is held to the rule like any other."""
    assert L.ALLOWLIST["SL001"] == ()
    src = "from jax.experimental.shard_map import shard_map"
    assert _rules(L.lint_source(src, "src/repro/compat.py")) == ["SL001"]


def test_sl001_cli_exits_nonzero(tmp_path):
    """Acceptance criterion: a synthetic file using raw shard_map makes
    `python -m repro.analysis.lint` exit non-zero."""
    bad = tmp_path / "uses_raw_shard_map.py"
    bad.write_text("from jax.experimental.shard_map import shard_map\n")
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", str(bad)],
        cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")})
    assert r.returncode == 1, r.stdout + r.stderr
    assert "SL001" in r.stdout


# --- SL002: ragged_dot outside the allowlist ------------------------------

def test_sl002_ragged_dot():
    src = "import jax\ny = jax.lax.ragged_dot(a, b, gs)"
    assert _rules(L.lint_source(src, "src/repro/core/new_moe.py")) \
        == ["SL002"]


def test_sl002_allowlisted_in_ref():
    src = "y = jax.lax.ragged_dot(a, b, gs)"
    assert L.lint_source(src, "src/repro/kernels/ref.py") == []


# --- SL003: host transfers in traced step-building modules ----------------

def test_sl003_device_get_and_np_asarray():
    src = textwrap.dedent("""
        import jax
        import numpy as np
        def step(x):
            host = jax.device_get(x)
            arr = np.asarray(x)
            return host, arr
    """)
    vs = L.lint_source(src, "src/repro/train/new_step.py")
    assert _rules(vs) == ["SL003"] and len(vs) == 2


def test_sl003_scoped_to_traced_modules():
    # the same constructs are fine in benches/launch tooling
    src = "import jax\nimport numpy as np\n" \
          "x = np.asarray(jax.device_get(y))"
    assert L.lint_source(src, "benchmarks/bench_new.py") == []


def test_sl003_jnp_asarray_ok():
    src = "import jax.numpy as jnp\nx = jnp.asarray(y)"
    assert L.lint_source(src, "src/repro/train/new_step.py") == []


def test_sl003_traced_override():
    src = "import numpy as np\nx = np.asarray(y)"
    assert L.lint_source(src, "/tmp/elsewhere/f.py") == []
    vs = L.lint_source(src, "/tmp/elsewhere/f.py",
                       traced_dirs=("/tmp/elsewhere/",))
    assert _rules(vs) == ["SL003"]


# --- SL004: retired kernel-knob aliases are tombstoned --------------------

@pytest.mark.parametrize("src", [
    # writes
    "from repro.kernels import ops\nops.KERNEL_CONFIG['tile_m'] = 8",
    "import repro.models.layers as L\nL.ATTN_IMPL = 'pallas'",
    "KERNEL_CONFIG = make_config()",
    # reads are violations too: the symbols no longer exist
    "impl = layers.ATTN_IMPL",
    "tm = ops.KERNEL_CONFIG['tile_m']",
    # and so are imports of the retired names
    "from repro.kernels.ops import KERNEL_CONFIG",
    "from repro.models.layers import ATTN_IMPL as AI",
])
def test_sl004_any_alias_occurrence(src):
    assert _rules(L.lint_source(src, "src/repro/new_tool.py")) == ["SL004"]


def test_sl004_has_no_allowlist():
    """The tombstone is absolute: no path is allowlisted, and string or
    docstring mentions (docs, this test file) stay lint-clean."""
    assert L.ALLOWLIST["SL004"] == ()
    src = 'msg = "KERNEL_CONFIG and ATTN_IMPL are retired"\n' \
          'def f():\n    "replaces ATTN_IMPL"\n'
    assert L.lint_source(src, "src/repro/new_tool.py") == []


# --- robustness -----------------------------------------------------------

def test_syntax_error_is_reported_not_raised():
    vs = L.lint_source("def broken(:\n", "synthetic/x.py")
    assert _rules(vs) == ["SL000"]


def test_allow_extra_suppresses():
    src = "y = jax.lax.ragged_dot(a, b, gs)"
    assert L.lint_source(src, "scratch/probe.py",
                         allow_extra=("scratch/probe.py",)) == []
