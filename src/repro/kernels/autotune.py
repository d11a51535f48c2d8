"""Measured kernel autotuning: tile search, tuning tables, shape buckets.

The paper attributes up to 1.71x of its MoE speedup to hand-tuned expert
kernels; this module closes the same loop mechanically. ``autotune()`` times
candidate tile configs per (kernel, shape-bucket, backend) with the
bench_epso discipline (explicit warmup, ``block_until_ready``, median of N)
and records the winner in a versioned JSON :class:`TuningTable`.
``KernelPlan(tiles='auto')`` then consults the active table at trace time
(``lookup_tiles`` via ``KernelPlan.resolve_tiles``) and falls back to the
plan's explicit tile fields on any miss — an absent or stale table can
never change numerics, only leave performance on the table.

Shape buckets
    Kernels see a continuum of shapes; the table is keyed by *buckets*:
    every dim rounded up to a power of two (``m`` — the token/row dim — is
    dynamic across batch sizes, so lookups that miss on ``m`` fall back to
    the nearest-``m`` entry whose other dims match exactly). Bucket keys
    render as e.g. ``g2_k512_m256_n2048``.

Candidate pruning
    Before anything compiles, candidates whose double-buffered working set
    (``roofline.gmm_working_set_bytes``) exceeds the target
    ``HardwareSpec.vmem_bytes`` are dropped — the same analytic budget the
    ``KernelPlan`` guardrail warns on.

Alignment contract (gmm)
    The MoE dispatch pads group sizes to multiples of ``plan.tile_m``
    (``gmm_align``), and the Pallas gmm requires ``group_sizes % tile_m ==
    0``. A table tile_m is therefore only applied when it divides the
    plan's tile_m (see ``ops._gmm_fwd_impl``); ``autotune`` only measures
    candidates whose tile_m divides the uniform per-group row count.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

TABLE_VERSION = 1

# the committed table `tiles='auto'` resolves from by default (regenerate
# with benchmarks/bench_kernels.py --write-table)
DEFAULT_TABLE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "tuning_table.json")

# dims each kernel is bucketed on, in bucket-key order; "m"-like dims
# (dynamic row counts) get the nearest-match fallback
KERNEL_DIMS = {
    "gmm": ("g", "k", "m", "n"),
    "tgmm": ("g", "k", "m", "n"),
    "fused_swiglu": ("m", "n"),
    "combine": ("d", "k", "t"),
}
_DYNAMIC_DIM = {"gmm": "m", "tgmm": "m", "fused_swiglu": "m", "combine": "t"}


def pow2_bucket(n: int) -> int:
    """Round up to a power of two (bucket boundary)."""
    p = 1
    while p < n:
        p *= 2
    return p


def bucket_dims(kernel: str, dims: Dict[str, int]) -> Dict[str, int]:
    return {k: pow2_bucket(int(dims[k])) for k in KERNEL_DIMS[kernel]}


def bucket_key(kernel: str, dims: Dict[str, int]) -> str:
    b = bucket_dims(kernel, dims)
    return "_".join(f"{k}{b[k]}" for k in KERNEL_DIMS[kernel])


# ----------------------------------------------------------------------------
# tuning table
# ----------------------------------------------------------------------------

@dataclass
class TuningTable:
    """Versioned measured-tile table. ``entries`` rows carry::

        {kernel, backend, bucket: {dim: pow2}, tiles: [..],
         time_ms, default_tiles, default_time_ms, shape: {dim: measured},
         n_iters, hw, gflops, achieved_frac}

    Only ``kernel``/``backend``/``bucket``/``tiles`` are load-bearing for
    lookup; the rest is provenance surfaced by ``dryrun --parallel``.
    """
    hw: str = "tpu-v5e"
    entries: List[dict] = field(default_factory=list)
    version: int = TABLE_VERSION
    path: Optional[str] = None

    def add(self, entry: dict) -> None:
        """Insert/replace the entry for (kernel, backend, bucket)."""
        key = (entry["kernel"], entry["backend"],
               tuple(sorted(entry["bucket"].items())))
        self.entries = [e for e in self.entries
                        if (e["kernel"], e["backend"],
                            tuple(sorted(e["bucket"].items()))) != key]
        self.entries.append(entry)

    def find(self, kernel: str, backend: str,
             dims: Dict[str, int]) -> Optional[dict]:
        """Exact-bucket match, else nearest dynamic-dim (m/t) match with all
        other bucketed dims equal. None on a full miss (including kernels
        with no bucket schema — nothing is ever tuned for those)."""
        if kernel not in KERNEL_DIMS:
            return None
        want = bucket_dims(kernel, dims)
        cands = [e for e in self.entries
                 if e["kernel"] == kernel and e["backend"] == backend]
        for e in cands:
            if e["bucket"] == want:
                return e
        dyn = _DYNAMIC_DIM.get(kernel)
        if dyn is None or dyn not in want:
            return None
        fixed = {k: v for k, v in want.items() if k != dyn}
        near = [e for e in cands
                if {k: v for k, v in e["bucket"].items() if k != dyn} == fixed]
        if not near:
            return None
        return min(near, key=lambda e: abs(e["bucket"].get(dyn, 0)
                                           - want[dyn]))

    def lookup(self, kernel: str, backend: str,
               dims: Dict[str, int]) -> Optional[Tuple[int, ...]]:
        e = self.find(kernel, backend, dims)
        return tuple(e["tiles"]) if e else None

    # ---- persistence ---------------------------------------------------------
    def to_json(self) -> dict:
        return {"version": self.version, "hw": self.hw,
                "entries": self.entries}

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path or DEFAULT_TABLE_PATH
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
            f.write("\n")
        self.path = path
        return path

    @classmethod
    def load(cls, path: str) -> Optional["TuningTable"]:
        """None (with a warning) on a missing/unreadable/version-mismatched
        file — an unusable table must degrade to defaults, never raise."""
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, ValueError) as e:
            warnings.warn(f"tuning table {path!r} unreadable ({e}); "
                          f"falling back to default tiles", stacklevel=2)
            return None
        if raw.get("version") != TABLE_VERSION:
            warnings.warn(f"tuning table {path!r} has version "
                          f"{raw.get('version')!r}, want {TABLE_VERSION}; "
                          f"ignoring it (regenerate with bench_kernels.py)",
                          stacklevel=2)
            return None
        return cls(hw=raw.get("hw", "tpu-v5e"),
                   entries=list(raw.get("entries", [])), path=path)


# ----------------------------------------------------------------------------
# active table + lookup observation
# ----------------------------------------------------------------------------

_UNSET = object()
_ACTIVE: list = [_UNSET]          # _UNSET -> lazily load DEFAULT_TABLE_PATH
_OBSERVER: list = [None]


def active_table() -> Optional[TuningTable]:
    """The table ``tiles='auto'`` resolves from: whatever
    ``set_active_table``/``use_tuning_table`` installed, else the committed
    ``DEFAULT_TABLE_PATH`` (loaded once), else None."""
    if _ACTIVE[0] is _UNSET:
        _ACTIVE[0] = (TuningTable.load(DEFAULT_TABLE_PATH)
                      if os.path.exists(DEFAULT_TABLE_PATH) else None)
    return _ACTIVE[0]


def set_active_table(table: Optional[TuningTable]) -> None:
    """Install ``table`` (None disables auto resolution entirely)."""
    _ACTIVE[0] = table


def reset_active_table() -> None:
    """Forget the installed table; next use lazily reloads the committed
    default."""
    _ACTIVE[0] = _UNSET


@contextlib.contextmanager
def use_tuning_table(table: Optional[TuningTable]):
    prev = _ACTIVE[0]
    _ACTIVE[0] = table
    try:
        yield table
    finally:
        _ACTIVE[0] = prev


@contextlib.contextmanager
def observe_lookups():
    """Record every ``lookup_tiles`` made while the scope is open — trace a
    step under it to learn exactly which (kernel, bucket) entries that step
    consults (the bit-identity test and table-coverage audits use this).
    Yields a list of {kernel, backend, dims, bucket, tiles} dicts."""
    records: List[dict] = []
    prev = _OBSERVER[0]
    _OBSERVER[0] = records
    try:
        yield records
    finally:
        _OBSERVER[0] = prev


def lookup_tiles(kernel: str, backend: str,
                 dims: Dict[str, int]) -> Optional[Tuple[int, ...]]:
    """Tile tuple from the active table, or None (caller falls back to its
    defaults). Every call — hit or miss — is visible to ``observe_lookups``."""
    table = active_table()
    tiles = table.lookup(kernel, backend, dims) if table is not None else None
    if _OBSERVER[0] is not None:
        _OBSERVER[0].append({"kernel": kernel, "backend": backend,
                             "dims": dict(dims),
                             "bucket": bucket_key(kernel, dims),
                             "tiles": tiles})
    return tiles


# ----------------------------------------------------------------------------
# candidate generation + VMEM pruning
# ----------------------------------------------------------------------------

def _divisors_of(n: int, pool: Sequence[int]) -> List[int]:
    return [p for p in pool if p <= n and n % p == 0]


def gmm_candidates(dims: Dict[str, int]) -> List[Tuple[int, int, int]]:
    """(tile_m, tile_k, tile_n) candidates for a gmm measurement shape.
    tile_m is restricted to divisors of the uniform per-group row count
    (the alignment contract); tile_k/tile_n may exceed K/N — the wrapper
    pads — so full-K/full-N single-step configs are always in the pool.
    The plan's 128/512/512 default is always included so "autotuned beats
    default" is decidable from the same run."""
    rows = dims["m"] // max(dims.get("g", 1), 1)
    tms = _divisors_of(rows, (32, 64, 128, 256)) or [rows]
    tks = sorted({min(t, pow2_bucket(dims["k"])) for t in (256, 512, 1024)}
                 | {dims["k"]})
    tns = sorted({min(t, pow2_bucket(dims["n"])) for t in (512, 1024)}
                 | {dims["n"]})
    cands = {(tm, tk, tn) for tm in tms for tk in tks for tn in tns}
    # the plan default, tile_m legalized to the group alignment (a raw
    # 128 on a <128-row group crosses group boundaries = wrong results)
    cands.add(_legalize_gmm(dims, (128, 512, 512)))
    return sorted(cands)


def elementwise_candidates(dims: Dict[str, int]) -> List[Tuple[int, int]]:
    """(tile_rows, tile_cols) candidates for fused_swiglu / combine — both
    tile exact divisors of their dims (no padding in those wrappers)."""
    rows = dims.get("m", dims.get("t"))
    cols = dims.get("n", dims.get("d"))
    tr = _divisors_of(rows, (8, 16, 32, 64, 128, 256)) or [1]
    tc = _divisors_of(cols, (32, 64, 128, 256, 512)) or [1]
    return sorted({(a, b) for a in tr for b in tc})


def prune_candidates(kernel: str, candidates, *, hw=None,
                     in_bytes: int = 2) -> list:
    """Drop candidates whose double-buffered working set exceeds the
    target hardware's fast-memory budget — before anything compiles."""
    from repro.launch.roofline import get_hardware, gmm_working_set_bytes
    hw = get_hardware(hw) if isinstance(hw, str) else \
        (hw or get_hardware("tpu-v5e"))
    kept = []
    for c in candidates:
        if kernel in ("gmm", "tgmm"):
            ws = gmm_working_set_bytes(*c, in_bytes=in_bytes)
        else:    # elementwise: in0 + in1 + out tiles, double-buffered
            ws = 3 * c[0] * c[1] * in_bytes * 2
        if ws <= hw.vmem_bytes:
            kept.append(c)
    return kept


# ----------------------------------------------------------------------------
# measurement
# ----------------------------------------------------------------------------

def _median_time_ms(fn, args, n_iters: int) -> float:
    """bench_epso discipline: explicit warmup (compile + place), then
    median of ``n_iters`` blocked timings."""
    import time

    import jax

    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n_iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2] * 1e3


def _gmm_inputs(dims: Dict[str, int]):
    import jax
    import jax.numpy as jnp
    g, m, k, n = dims["g"], dims["m"], dims["k"], dims["n"]
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k0, (m, k), jnp.bfloat16)
    w = jax.random.normal(k1, (g, k, n), jnp.bfloat16)
    gs = jnp.full((g,), m // g, jnp.int32)
    return x, w, gs


def measure_gmm(dims: Dict[str, int], tiles: Tuple[int, int, int], *,
                n_iters: int = 5, validate: bool = False) -> float:
    """Median ms of one gmm at ``dims`` with an explicit tile triple
    (uniform groups: m/g rows each). ``validate`` checks the candidate
    against the pure-JAX reference once before timing."""
    import jax

    from repro.kernels import ops, ref
    from repro.parallel.plan import KernelPlan, use_kernel_plan

    x, w, gs = _gmm_inputs(dims)
    tm, tk, tn = tiles
    plan = KernelPlan(backend="pallas", tile_m=tm, tile_k=tk, tile_n=tn)
    with use_kernel_plan(plan):
        fn = jax.jit(lambda a, b, c: ops.gmm(a, b, c))
        if validate:
            import numpy as np
            got = np.asarray(fn(x, w, gs), dtype=np.float32)
            want = np.asarray(ref.gmm_ref(x, w, gs), dtype=np.float32)
            np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
        return _median_time_ms(fn, (x, w, gs), n_iters)


def gmm_flops(dims: Dict[str, int]) -> float:
    return 2.0 * dims["m"] * dims["k"] * dims["n"]


def _legalize_gmm(dims: Dict[str, int],
                  tiles: Tuple[int, ...]) -> Tuple[int, ...]:
    """Clamp a tile triple to something measurable at ``dims``: tile_m must
    divide the uniform per-group row count (the wrapper clamps tile_k/tile_n
    itself). Keeps the plan-default timing well-defined on shapes smaller
    than the default tile_m."""
    rows = dims["m"] // max(dims.get("g", 1), 1)
    tm = tiles[0]
    while rows % tm:
        tm //= 2
    return (max(tm, 1), tiles[1], tiles[2])


def _tgmm_inputs(dims: Dict[str, int]):
    import jax
    import jax.numpy as jnp
    g, m, k, n = dims["g"], dims["m"], dims["k"], dims["n"]
    k0, k1 = jax.random.split(jax.random.PRNGKey(1))
    lhs = jax.random.normal(k0, (m, k), jnp.bfloat16)
    rhs = jax.random.normal(k1, (m, n), jnp.bfloat16)
    gs = jnp.full((g,), m // g, jnp.int32)
    return lhs, rhs, gs


def measure_tgmm(dims: Dict[str, int], tiles: Tuple[int, int, int], *,
                 n_iters: int = 5, validate: bool = False) -> float:
    """Median ms of one tgmm (transposed grouped matmul — the gmm weight
    gradient: out[g] = lhs[rows of g]^T @ rhs[rows of g]) at ``dims`` with
    an explicit tile triple. Mirrors ``ops._gmm_bwd``'s invocation exactly
    (pad K/N, tile->group scalar prefetch, empty-group zero-fill) so the
    table rows that ``_gmm_bwd`` resolves under ``tiles='auto'`` are
    measured on the same program it traces."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.gmm import tgmm_pallas

    lhs, rhs, gs = _tgmm_inputs(dims)
    G, M, K, N = dims["g"], dims["m"], dims["k"], dims["n"]
    tm, tk, tn = tiles
    tk = min(tk, K)
    tn = min(tn, N)

    def fn(x, dy, group_sizes):
        xp = ops._pad_to(x, tk, 1)
        dyp = ops._pad_to(dy, tn, 1)
        gids, n_active = ops._tile_group_ids(group_sizes, M // tm, tm)
        out = tgmm_pallas(xp, dyp, gids, n_active, G, tile_m=tm, tile_k=tk,
                          tile_n=tn, interpret=ops._interpret())
        out = jnp.where((group_sizes > 0)[:, None, None], out, 0)
        return out[:, :K, :N]

    jitted = jax.jit(fn)
    if validate:
        import numpy as np
        got = np.asarray(jitted(lhs, rhs, gs), dtype=np.float32)
        want = np.asarray(ref.tgmm_ref(lhs, rhs, gs, G), dtype=np.float32)
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
    return _median_time_ms(jitted, (lhs, rhs, gs), n_iters)


def tgmm_flops(dims: Dict[str, int]) -> float:
    # sum_g rows_g x K x N multiply-adds == M x K x N total
    return 2.0 * dims["m"] * dims["k"] * dims["n"]


def tgmm_candidates(dims: Dict[str, int]) -> List[Tuple[int, int, int]]:
    """(tile_m, tile_k, tile_n) candidates for a tgmm measurement shape:
    tile_m under the same group-alignment contract as gmm (it tiles the
    shared row dim); tile_k/tile_n tile the *output* (G, K, N) block and
    may exceed K/N (the wrapper pads). ``_gmm_bwd``'s built-in 512/512
    defaults are always included so parity is decidable in-run."""
    rows = dims["m"] // max(dims.get("g", 1), 1)
    tms = _divisors_of(rows, (32, 64, 128, 256)) or [rows]
    tks = sorted({min(t, pow2_bucket(dims["k"])) for t in (256, 512)}
                 | {dims["k"]})
    tns = sorted({min(t, pow2_bucket(dims["n"])) for t in (256, 512)}
                 | {dims["n"]})
    cands = {(tm, tk, tn) for tm in tms for tk in tks for tn in tns}
    cands.add(_legalize_gmm(dims, (128, 512, 512)))
    return sorted(cands)


_MEASURE = {"gmm": measure_gmm, "tgmm": measure_tgmm}
_CANDIDATES = {"gmm": gmm_candidates, "tgmm": tgmm_candidates}
_FLOPS = {"gmm": gmm_flops, "tgmm": tgmm_flops}
_LEGALIZE = {"gmm": _legalize_gmm, "tgmm": _legalize_gmm}


def autotune(kernel: str, shapes: Sequence[Dict[str, int]],
             candidates=None, *, backend: str = "pallas", n_iters: int = 5,
             hw: str = "tpu-v5e", measured_hw: Optional[object] = None,
             validate: bool = True, table: Optional[TuningTable] = None,
             default_tiles: Tuple[int, ...] = (128, 512, 512),
             log=None) -> TuningTable:
    """Measured tile search over ``shapes`` (dim dicts, e.g.
    ``{"g": 2, "m": 256, "k": 512, "n": 1792}`` for gmm).

    For each shape: generate candidates (or use ``candidates``), prune
    against ``hw``'s VMEM budget analytically, time each survivor
    (median-of-``n_iters``), and record the winner next to the
    ``default_tiles`` timing in ``table``. ``measured_hw`` (a HardwareSpec,
    e.g. ``calibrate_sim_cpu()``) stamps the achieved-vs-peak fraction.
    Returns the (new or updated) table.
    """
    if kernel not in _MEASURE:
        raise ValueError(f"no measurement adapter for kernel {kernel!r} "
                         f"(have: {', '.join(sorted(_MEASURE))})")
    table = table if table is not None else TuningTable(hw=hw)
    measure = _MEASURE[kernel]
    for dims in shapes:
        cands = list(candidates) if candidates is not None \
            else _CANDIDATES[kernel](dims)
        kept = prune_candidates(kernel, cands, hw=hw)
        if log:
            log(f"{kernel} {bucket_key(kernel, dims)}: "
                f"{len(cands)} candidates, {len(kept)} after VMEM prune")
        results = []
        for c in kept:
            try:
                t = measure(dims, c, n_iters=n_iters, validate=validate)
            except Exception as e:        # invalid config: skip, keep going
                if log:
                    log(f"  {c}: skipped ({type(e).__name__}: {e})")
                continue
            results.append((t, c))
            if log:
                log(f"  {c}: {t:.1f}ms")
        if not results:
            continue
        best_t, best_c = min(results, key=lambda r: r[0])
        legalize = _LEGALIZE.get(kernel, lambda d, t: tuple(t))
        dflt = tuple(legalize(dims, tuple(default_tiles)))
        dflt_t = dict((tuple(c), t) for t, c in results).get(dflt)
        if dflt_t is None:
            dflt_t = measure(dims, dflt, n_iters=n_iters, validate=False)
        entry = {
            "kernel": kernel, "backend": backend,
            "bucket": bucket_dims(kernel, dims), "shape": dict(dims),
            "tiles": list(best_c), "time_ms": best_t,
            "default_tiles": list(dflt), "default_time_ms": dflt_t,
            "n_iters": n_iters, "hw": hw,
        }
        flops = _FLOPS.get(kernel)
        if flops:
            gf = flops(dims) / 1e9
            entry["gflops"] = gf
            if measured_hw is not None:
                entry["measured_hw"] = measured_hw.name
                entry["achieved_frac"] = (gf * 1e9 / (best_t / 1e3)
                                          / measured_hw.peak_flops)
        table.add(entry)
    return table
