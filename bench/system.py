"""The system under test: the repository's training step, built through its
normal entry points (``ParallelPlan.resolve``, ``make_train_step`` jitted
with the state donated, ``ShardedDataLoader``), from a configuration file
and a cell file of this benchmark. This is the only module of the
benchmark that imports the program."""
from __future__ import annotations

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

from bench import weights

__all__ = ["ShardedDataLoader", "Program", "enable_compile_cache"]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``), with every program cached,
    however short its compile; returns its directory."""
    d = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return d


def model_config(c: dict) -> ModelConfig:
    moe = None
    if c["arch_type"] == "moe":
        moe = MoEConfig(num_experts=c["num_experts"],
                        experts_per_token=c["experts_per_token"],
                        d_ff_expert=c["d_ff_expert"],
                        router_aux_coef=c["router_aux_coef"],
                        router_z_coef=c["router_z_coef"],
                        moe_impl="fsmoe")
    return ModelConfig(
        name=c["name"], arch_type=c["arch_type"], num_layers=c["num_layers"],
        d_model=c["d_model"], num_heads=c["num_heads"],
        num_kv_heads=c["num_kv_heads"], head_dim=c["head_dim"],
        d_ff=c["d_ff"], vocab_size=c["vocab_size"], moe=moe,
        rope_theta=c["rope_theta"], norm=c["norm"],
        tie_embeddings=c["tie_embeddings"])


def train_config(c: dict, seq_len: int, global_batch: int) -> TrainConfig:
    return TrainConfig(
        seq_len=seq_len, global_batch=global_batch, lr_peak=c["lr_peak"],
        lr_min=c["lr_min"], warmup_steps=c["warmup_steps"],
        total_steps=c["total_steps"], weight_decay=c["weight_decay"],
        beta1=c["beta1"], beta2=c["beta2"], eps=c["eps"],
        grad_clip=c["grad_clip"],
        clip_after_warmup_only=c["clip_after_warmup_only"],
        grad_reduce_dtype=c["grad_reduce_dtype"],
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"])


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
