"""Training loop: jit'd step + checkpoint/restart + failure handling.

The Trainer owns: the sharded train state, the deterministic data cursor,
an async CheckpointManager, and a restart path that (a) resumes from the
latest complete checkpoint, (b) re-shards onto the *current* mesh (elastic
— chip loss between runs changes the mesh shape, not the code path), and
(c) resumes the exact batch stream from the stored cursor.

Failure handling is exercised by tests via ``FailureInjector`` — a hook
that raises at a chosen step; the driver catches, constructs a fresh
Trainer (as a restarted job would), and verifies bit-exact continuation.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import numpy as np

from ..checkpoint.ckpt import CheckpointManager
from ..data.pipeline import make_train_batches
from ..models.config import ArchConfig
from ..models.model import Model, train_inputs
from ..optim.optimizer import AdamWConfig, adamw_init, opt_state_axes
from ..parallel.sharding import DEFAULT_RULES, tree_shardings_sized
from .step import make_train_step


class InjectedFailure(RuntimeError):
    """A deliberately injected failure (chaos tests, restart drills).

    Subclasses RuntimeError for backward compatibility, but restart
    harnesses catch *this* type: a genuine RuntimeError from the train
    step (NaN loss, OOM, shape bug) must propagate, not be retried into
    a restart loop that masks it.
    """


@dataclasses.dataclass
class FailureInjector:
    """Raises InjectedFailure right after ``at_step`` completes (tests)."""

    at_step: int = -1

    def check(self, step: int):
        if step == self.at_step:
            raise InjectedFailure(f"injected failure at step {step}")


@dataclasses.dataclass
class Trainer:
    cfg: ArchConfig
    mesh: object
    global_batch: int
    seq_len: int
    opt_cfg: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    microbatches: int = 1
    ckpt_dir: str | None = None
    ckpt_every: int = 50
    seed: int = 0
    log_every: int = 10
    on_metrics: Callable[[int, dict], None] | None = None

    def __post_init__(self):
        self.model = Model(self.cfg)
        self.step_fn = None
        self.ckpt = CheckpointManager(self.ckpt_dir) if self.ckpt_dir \
            else None
        self._compiled = None

    # -- state ----------------------------------------------------------------

    def _shardings(self):
        p_spec = self.model.param_specs()
        pa = self.model.param_axes()
        p_sh = tree_shardings_sized(pa, p_spec, DEFAULT_RULES, self.mesh)
        o_spec = {"mu": p_spec, "nu": p_spec,
                  "step": jax.ShapeDtypeStruct((), np.int32)}
        o_sh = tree_shardings_sized(opt_state_axes(pa), o_spec,
                                    DEFAULT_RULES, self.mesh)
        b_spec = train_inputs(self.cfg, self.global_batch, self.seq_len)
        b_sh = tree_shardings_sized(
            train_inputs(self.cfg, self.global_batch, self.seq_len, "axes"),
            b_spec, DEFAULT_RULES, self.mesh)
        return p_sh, o_sh, b_sh

    def init_state(self, rng=None):
        rng = rng if rng is not None else jax.random.PRNGKey(self.seed)
        p_sh, o_sh, _ = self._shardings()
        with jax.set_mesh(self.mesh):
            params = jax.jit(self.model.init, out_shardings=p_sh)(rng)
            opt = jax.jit(adamw_init, out_shardings=o_sh)(params)
        return params, opt

    def restore_or_init(self):
        """Restart path: latest checkpoint if present, else fresh init."""
        if self.ckpt and self.ckpt.latest_step() is not None:
            p_sh, o_sh, _ = self._shardings()
            like_p = self.model.param_specs()
            like_o = {"mu": like_p, "nu": like_p,
                      "step": jax.ShapeDtypeStruct((), np.int32)}
            (params, opt), step, extra = self.ckpt.restore(
                (like_p, like_o), shardings=(p_sh, o_sh))
            return params, opt, step + 1
        params, opt = self.init_state()
        return params, opt, 0

    # -- loop -----------------------------------------------------------------

    def run(self, num_steps: int, *, params=None, opt_state=None,
            start_step: int | None = None,
            failure: FailureInjector | None = None) -> dict:
        if params is None:
            params, opt_state, start_step = self.restore_or_init()
        elif start_step is None:
            start_step = 0
        p_sh, o_sh, b_sh = self._shardings()
        step_fn = make_train_step(self.cfg, self.opt_cfg, self.microbatches)
        jstep = jax.jit(step_fn, in_shardings=(p_sh, o_sh, b_sh),
                        donate_argnums=(0, 1))
        batches = make_train_batches(self.cfg, self.global_batch,
                                     self.seq_len, seed=self.seed)
        # fast-forward the deterministic stream to the resume point
        history = []
        t0 = time.time()
        for step, batch in batches:
            if step < start_step:
                continue
            if step >= num_steps:       # num_steps = TOTAL training steps
                break
            with jax.set_mesh(self.mesh):
                batch = {k: jax.device_put(v, b_sh[k])
                         for k, v in batch.items()}
                params, opt_state, metrics = jstep(params, opt_state, batch)
            if step % self.log_every == 0 or step == start_step:
                m = {k: float(v) for k, v in metrics.items()
                     if np.ndim(v) == 0}
                m["step"] = step
                history.append(m)
                if self.on_metrics:
                    self.on_metrics(step, m)
            if self.ckpt and step > 0 and step % self.ckpt_every == 0:
                self.ckpt.save_async(step, (params, opt_state),
                                     extra={"seed": self.seed})
            if failure:
                failure.check(step)
        if self.ckpt:
            self.ckpt.wait()
        return {"params": params, "opt_state": opt_state,
                "history": history, "steps_per_s":
                (num_steps / max(time.time() - t0, 1e-9))}
