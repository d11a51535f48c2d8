"""The system under test: the repository's training step, built through its
normal entry points (``ParallelPlan.resolve``, ``make_train_step`` jitted
with the state donated, ``ShardedDataLoader``), from a configuration file
and a cell file of this benchmark. This is the only module of the
benchmark that imports the program."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig, TrainConfig
from repro.data.loader import ShardedDataLoader
from repro.launch import compile_cache
from repro.models.model import init_params
from repro.optim.adamw import AdamWState
from repro.parallel.plan import KernelPlan, ParallelPlan
from repro.parallel.sharding import batch_sharding
from repro.train.trainer import (TrainState, make_train_step,
                                 train_state_shardings)

from bench import configs, weights

__all__ = ["ShardedDataLoader", "Program", "enable_compile_cache"]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``), with every program cached,
    however short its compile; returns its directory."""
    d = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return d


# keys that document the configuration and configure nothing
DOC_KEYS = frozenset({"reference", "deployment", "assumed"})
DOC_PREFIXES = ("source", "published_")
MODEL_KEYS = frozenset(f.name for f in dataclasses.fields(ModelConfig)) - {
    "moe", "ssm"}
MOE_KEYS = frozenset(f.name for f in dataclasses.fields(MoEConfig))
# seq_len and global_batch are the traffic's
RECIPE_KEYS = frozenset(f.name for f in dataclasses.fields(TrainConfig)) - {
    "seq_len", "global_batch", "seed"}
def _pick(c: dict, keys) -> dict:
    return {k: v for k, v in c.items() if k in keys}


def check_keys(c: dict) -> None:
    """Every key of the configuration file reaches something that reads
    it: the program's ``ModelConfig``, ``MoEConfig`` or ``TrainConfig`` by
    field name, or the plain reference's ``REFERENCE_KEYS``; or it
    documents the configuration; or it is ``master_dtype``, which has to
    be float32, the master weights and AdamW moments of ``init_state``.
    Any other key, or another master, would build a different model than
    the file states, so it stops the run with its name."""
    known = (MODEL_KEYS | MOE_KEYS | RECIPE_KEYS | {"master_dtype"} | DOC_KEYS
             | set(getattr(configs.reference(c), "REFERENCE_KEYS", ())))
    unknown = sorted(k for k in c
                     if k not in known and not k.startswith(DOC_PREFIXES))
    if unknown:
        raise KeyError(f"configuration {c.get('name')!r}: no part of the "
                       f"program or of its reference reads {unknown}")
    if c.get("master_dtype", "float32") != "float32":
        raise ValueError(f"configuration {c.get('name')!r}: master_dtype "
                         f"{c['master_dtype']!r}, but the state is float32")


def model_config(c: dict) -> ModelConfig:
    """The program's ``ModelConfig``: each key of the file that names one
    of its fields, and for ``arch_type`` moe a ``MoEConfig`` of each key
    that names one of its fields (``moe_impl`` fsmoe unless the file says
    otherwise)."""
    check_keys(c)
    moe = None
    if c["arch_type"] == "moe":
        moe = MoEConfig(**{"moe_impl": "fsmoe", **_pick(c, MOE_KEYS)})
    return ModelConfig(**_pick(c, MODEL_KEYS), moe=moe)


def train_config(c: dict, seq_len: int, global_batch: int) -> TrainConfig:
    """The program's ``TrainConfig``: the traffic's sequence length and
    batch, and each key of the file that names one of its fields."""
    return TrainConfig(seq_len=seq_len, global_batch=global_batch,
                       **_pick(c, RECIPE_KEYS))


class Program:
    """The program's train step and state for one cell."""

    def __init__(self, c: dict, cell: dict, seq_len: int, global_batch: int):
        self.c = c
        self.cfg = model_config(c)
        self.train = train_config(c, seq_len, global_batch)
        self.plan = ParallelPlan(
            kernel=KernelPlan(**cell["kernel"]), **cell["plan"]
        ).resolve(self.cfg, self.train)
        self._check_layout()
        shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), self.cfg))
        self.state_sharding = train_state_shardings(
            shapes, self.plan.rules, self.plan.opt_shard)
        self.batch_sharding = batch_sharding(self.plan.rules)
        self.devices = (list(self.plan.mesh.devices.flat)
                        if self.plan.mesh is not None
                        else [jax.devices()[0]])

    def _check_layout(self):
        """The benchmark's weight layout is the program's parameter tree."""
        got = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), self.cfg))
        want = weights.nest(self.c, [
            jax.ShapeDtypeStruct(s, jnp.float32)
            for _, s, _ in weights.layout(self.c)])
        g = jax.tree.map(lambda x: (x.shape, str(x.dtype)), got)
        w = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
        if g != w:
            raise RuntimeError(f"the program's parameter tree {g} is not "
                               f"the benchmark's weight layout {w}")

    def init_state(self, seed: int, step: int) -> TrainState:
        """The state from the benchmark's weights for ``seed``, made on the
        device in one jitted call, in the dtypes the configuration states
        (params in ``param_dtype`` over a float32 master, zero moments),
        with the optimizer's step counter at ``step``."""
        c = self.c
        pd = jnp.dtype(c["param_dtype"])

        def make(words):
            w = weights.make(c, words)
            zeros = jax.tree.map(jnp.zeros_like, w)
            return TrainState(jax.tree.map(lambda x: x.astype(pd), w),
                              AdamWState(jnp.full((), step, jnp.int32), w, zeros,
                                         jax.tree.map(jnp.zeros_like, w)))

        out = self.state_sharding
        if out is None:
            out = jax.sharding.SingleDeviceSharding(self.devices[0])
        return jax.jit(make, out_shardings=out)(weights.seed_words(seed))

    def step_fn(self):
        """The program's train step, as ``make_train_step`` returns it."""
        return make_train_step(self.cfg, None, self.train, plan=self.plan)

    def compile(self, step_fn, state, batch):
        kw = {}
        if self.state_sharding is not None:
            kw = dict(out_shardings=(self.state_sharding, None))
        return jax.jit(step_fn, donate_argnums=0, **kw).lower(
            state, batch).compile()

    def put(self, batch: dict):
        if self.batch_sharding is not None:
            return jax.device_put(batch, self.batch_sharding)
        return jax.device_put(batch, self.devices[0])
