"""The Trainer: Keras-``compile``/``fit`` surface over one jitted SPMD core.

Replaces the reference's orchestration layer (``keras.Model.compile`` +
``model.fit`` + callbacks, ``/root/reference/imagenet-resnet50.py:62-67``)
with a custom loop:

- ``train_step``/``eval_step`` are pure functions jitted **once** with
  ``NamedSharding``-annotated inputs/outputs over the strategy's mesh. All
  cross-device traffic (gradient all-reduce, sharded-state gather/scatter,
  cross-replica BN) is inserted by XLA's SPMD partitioner at compile time —
  the collectives ride ICI/DCN with zero framework code in the hot loop.
- State buffers are donated: params/optimizer state update in place in HBM.
- The epoch driver is host-side Python: data feeding, callbacks, History —
  deliberately outside jit (dynamic control flow stays off the device).

TPU-first details: metrics are computed from the same forward pass as the
loss (no second pass), device->host sync happens once per epoch (metric
fetch), and augmentation runs on-device inside the step (the reference puts
augmentation in the model graph for the same reason,
``imagenet-resnet50.py:53-55``).
"""

from __future__ import annotations

import logging
import sys
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pddl_tpu.obs.trace import NULL_TRACER
from pddl_tpu.parallel.base import Strategy
from pddl_tpu.parallel.single import SingleDeviceStrategy
from pddl_tpu.train import metrics as metrics_lib
from pddl_tpu.train.callbacks import Callback
from pddl_tpu.train.faults import (
    InjectedResourceExhausted,
    InjectedTransientError,
    TrainStateLost,
    classify,
)
from pddl_tpu.train.history import History
from pddl_tpu.train.state import TrainState, make_optimizer

PyTree = Any
log = logging.getLogger(__name__)


class Trainer:
    """Strategy-agnostic training orchestrator.

    Args mirror ``model.compile`` (``imagenet-resnet50.py:62``):

    >>> trainer = Trainer(model, optimizer="adam", loss="sparse_categorical_crossentropy",
    ...                   metrics=["accuracy"], strategy=MirroredStrategy())
    >>> history = trainer.fit(train_ds, epochs=50, validation_data=val_ds,
    ...                       callbacks=[ReduceLROnPlateau(), EarlyStopping()])
    """

    def __init__(
        self,
        model,
        optimizer: str | Any = "adam",
        learning_rate: float = 1e-3,
        loss: str | Callable = "sparse_categorical_crossentropy",
        metrics: Sequence[str | Callable] = ("accuracy",),
        strategy: Optional[Strategy] = None,
        seed: int = 0,
        augment: Optional[Callable] = None,  # fn(rng, images) -> images, on-device
        eval_transform: Optional[Callable] = None,  # fn(images) -> images, deterministic
        donate_state: bool = True,
        input_key: str = "image",   # batch keys; the GPT family uses
        target_key: str = "label",  # tokens/targets (models/gpt.py)
        lr_schedule: Optional[str | Callable] = None,
        lr_schedule_options: Optional[Dict[str, Any]] = None,
        ema_decay: Optional[float] = None,
        # Evaluate on the EMA weights when ema_decay is set. BN models
        # evaluate against the EMA-shadowed batch_stats (TrainState.
        # ema_batch_stats), averaged on the same cadence as the params.
        eval_with_ema: bool = True,
        gradient_accumulation_steps: Optional[int] = None,
        # Add the global gradient L2 norm to the train logs — cheap (one
        # fused reduction in the compiled step) and the observable the
        # multichip equivalence gate compares: unlike per-leaf gradients
        # (ill-conditioned through BN backward), the norm separates fp
        # reduction noise (~1e-3 relative) from semantic errors like a
        # psum-where-pmean-belongs (device_count x).
        log_grad_norm: bool = False,
        # Low-precision parameter-update rule for bf16 param storage:
        # "plain" | "stochastic_round" | "f32_master"
        # (train/mixed_precision.py). No-op for f32 params.
        param_update: str = "plain",
        # -- crash resilience (train/faults.py, docs/OPERATIONS.md
        # § "Failure modes & recovery (training)") --------------------
        # Seeded deterministic fault injection over the compiled-program
        # sites ("train_step"/"eval_step") — the chaos handle.
        fault_plan=None,
        # Transient-device-error retry budget per dispatch; past it the
        # state is declared lost and the in-process restore+replay path
        # runs (needs a CheckpointEveryN callback attached).
        max_retries: int = 3,
        retry_backoff_s: float = 0.02,
        # Restore+replay attempts per failed step before giving up (a
        # persistently failing site must surface, not crash-loop).
        max_recoveries: int = 8,
        # Training fault/recovery/checkpoint events flow through the
        # same tracer surface the serving engine uses (obs/trace.py).
        tracer=None,
        # How retry backoff waits (tests pass a no-op).
        retry_sleep=time.sleep,
    ):
        self.model = model
        self.input_key = input_key
        self.target_key = target_key
        self.strategy = strategy or SingleDeviceStrategy()
        self.tx = make_optimizer(
            optimizer, learning_rate,
            schedule=lr_schedule, schedule_options=lr_schedule_options,
            accumulate_steps=gradient_accumulation_steps,
            param_update=param_update, update_seed=seed,
        )
        self.ema_decay = ema_decay
        self.eval_with_ema = eval_with_ema
        self.eval_transform = eval_transform
        self.loss_fn = metrics_lib.resolve_loss(loss)
        self.metric_fns = dict(metrics_lib.resolve_metric(m) for m in metrics)
        self.seed = seed
        self.augment = augment
        self.donate_state = donate_state
        self.log_grad_norm = log_grad_norm

        self.state: Optional[TrainState] = None
        self.stop_training = False
        self.steps_per_epoch: Optional[int] = None
        self._train_step = None
        self._eval_step = None
        self._state_shardings = None

        # -- crash-resilience state ------------------------------------
        self._faults = fault_plan
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.max_recoveries = int(max_recoveries)
        self._tracer = tracer if tracer is not None else NULL_TRACER
        self._retry_sleep = retry_sleep
        if self._faults is not None and self._faults.on_inject is None:
            # Every injection — LATENCY included, which raises nothing —
            # lands in the trace at its exact (step, site) coordinate.
            self._faults.on_inject = self._tracer.on_fault_injected
        # Host-side dispatch wall time per site (obs exposition).
        self._site_wall: Dict[str, float] = {}
        # Lifetime fault/recovery counters (obs/export.train_exposition
        # renders every key — keep in sync with TRAIN_COUNTER_KEYS).
        self.fault_stats: Dict[str, float] = {
            "retries": 0, "recoveries": 0, "replayed_steps": 0,
            "checkpoints_saved": 0, "checkpoint_wall_s": 0.0,
        }
        # In-process recovery plumbing: the CheckpointEveryN callback
        # registers itself here (attach_recovery) and the bounded batch
        # replay buffer covers the gap back to its last verified save.
        self._recovery_cb = None
        self._replay_buffer: Optional[deque] = None
        # Python mirror of state.step (no per-step device sync) — the
        # (step, site) fault coordinate and the replay-buffer key.
        self._opt_step = 0
        # Data-pipeline position, refreshed after every step; saved into
        # checkpoint metadata so a restart resumes MID-epoch, bit-exact.
        self._loader_state: Optional[Dict[str, int]] = None
        self._batches_consumed = 0

    # ------------------------------------------------------------------ init
    def init_state(self, sample_batch: Dict[str, np.ndarray]) -> TrainState:
        """Create the (sharded) TrainState from a sample batch.

        Initialization is itself jitted with the strategy's output shardings,
        so parameters materialize directly in their final layout — no host
        round-trip, no replicated staging (matters for PS-sharded state).
        """
        mesh = self.strategy.setup()
        sample = np.asarray(sample_batch[self.input_key])
        image = jnp.zeros((1,) + tuple(sample.shape[1:]), sample.dtype)
        rng = jax.random.key(self.seed)

        def _init(rng):
            variables = self.model.init(rng, image, train=False)
            params = variables["params"]
            batch_stats = variables.get("batch_stats", {})
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                batch_stats=batch_stats,
                opt_state=self.tx.init(params),
                ema_params=params if self.ema_decay else None,
                ema_batch_stats=batch_stats if self.ema_decay else None,
            )

        abstract = jax.eval_shape(_init, rng)
        self._state_shardings = self.strategy.state_sharding(abstract)
        with jax.set_mesh(mesh):
            self.state = jax.jit(_init, out_shardings=self._state_shardings)(rng)
        self._build_steps()
        return self.state

    # ----------------------------------------------------------------- steps
    def _apply(self, params, batch_stats, images, train: bool, rngs=None, mutable=False):
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        kwargs = dict(train=train)
        if rngs:
            kwargs["rngs"] = rngs
        if mutable:
            # "losses" collects model-internal auxiliary losses sown via
            # self.sow("losses", ...) — e.g. the MoE load-balancing loss
            # (pddl_tpu/ops/moe.py); train AND eval steps add them to the
            # task loss (Keras add_loss semantics: evaluate() includes
            # add_loss terms, so train loss and val_loss stay comparable).
            # "metrics" collects model-internal observables (e.g. the MoE
            # capacity drop rate) — logged, never added to the loss.
            collections = (["batch_stats", "losses", "metrics"] if train
                           else ["losses", "metrics"])
            return self.model.apply(
                variables, images, mutable=collections, **kwargs
            )
        return self.model.apply(variables, images, **kwargs), {}

    @staticmethod
    def _sown_metrics(updates) -> Dict[str, jnp.ndarray]:
        """Aggregate model-internal observables sown into "metrics".

        Leaves sharing a name (one per MoE block, say) are averaged into
        one log entry — e.g. ``moe_drop_rate`` = mean fraction of routed
        token-slots dropped at capacity, across routed blocks.
        """
        groups: Dict[str, list] = {}
        flat = jax.tree_util.tree_flatten_with_path(
            updates.get("metrics", {}))[0]
        for path, leaf in flat:
            names = [p.key for p in path
                     if isinstance(p, jax.tree_util.DictKey)]
            if names:
                groups.setdefault(str(names[-1]), []).append(leaf)
        return {name: sum(vals) / len(vals)
                for name, vals in groups.items()}

    def _build_steps(self) -> None:
        batch_sh = self.strategy.batch_sharding()
        state_sh = self._state_shardings
        base_rng = jax.random.key(self.seed + 1)

        def train_step(state: TrainState, batch):
            images, labels = batch[self.input_key], batch[self.target_key]
            rng = jax.random.fold_in(base_rng, state.step)
            if self.augment is not None:
                aug_rng, rng = jax.random.split(rng)
                images = self.augment(aug_rng, images)

            def loss_of(params):
                (logits, updates) = self._apply(
                    params, state.batch_stats, images, train=True,
                    rngs={"dropout": rng}, mutable=True,
                )
                loss = self.loss_fn(logits, labels)
                # Model-internal auxiliary losses (sown into "losses").
                for aux in jax.tree.leaves(updates.get("losses", {})):
                    loss = loss + jnp.sum(aux)
                return loss, (logits, updates)

            (loss, (logits, updates)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(state.params)
            new_state = state.apply_gradients(
                self.tx, grads, updates.get("batch_stats", state.batch_stats),
                ema_decay=self.ema_decay,
            )
            logs = {"loss": loss}
            if self.log_grad_norm:
                import optax

                logs["grad_norm"] = optax.global_norm(grads)
            for name, fn in self.metric_fns.items():
                logs[name] = fn(logits, labels)
            logs.update(self._sown_metrics(updates))
            return new_state, logs

        def eval_step(state: TrainState, batch):
            images, labels = batch[self.input_key], batch[self.target_key]
            if self.eval_transform is not None:
                images = self.eval_transform(images)
            # Structural (trace-time) choice: EMA weights when enabled —
            # and the EMA-shadowed batch_stats with them, so BN models
            # see statistics averaged on the same cadence as the params.
            use_ema = self.eval_with_ema and state.ema_params is not None
            eval_params = state.ema_params if use_ema else state.params
            eval_stats = (
                state.ema_batch_stats
                if use_ema and state.ema_batch_stats is not None
                else state.batch_stats
            )
            (logits, updates) = self._apply(
                eval_params, eval_stats, images, train=False,
                mutable=True,
            )
            loss = self.loss_fn(logits, labels)
            for aux in jax.tree.leaves(updates.get("losses", {})):
                loss = loss + jnp.sum(aux)
            logs = {"loss": loss}
            for name, fn in self.metric_fns.items():
                logs[name] = fn(logits, labels)
            logs.update(self._sown_metrics(updates))
            return logs

        batch_shardings = {self.input_key: batch_sh, self.target_key: batch_sh}
        self._train_step = jax.jit(
            train_step,
            in_shardings=(state_sh, batch_shardings),
            out_shardings=(state_sh, None),
            donate_argnums=(0,) if self.donate_state else (),
        )
        self._eval_step = jax.jit(
            eval_step,
            in_shardings=(state_sh, batch_shardings),
            out_shardings=None,
        )

    # -------------------------------------------------- fault handling
    def compile_counts(self) -> Dict[str, int]:
        """Compiled-executable count per resident program — the
        training analogue of ``ServeEngine.compile_counts()`` (and the
        vocabulary of :class:`~pddl_tpu.train.faults.TrainFaultPlan`
        sites). Any value above 1 is a recompile; the chaos suite pins
        exactly 1 across every recovery transition."""
        counts: Dict[str, int] = {}
        for name, fn in (("train_step", self._train_step),
                         ("eval_step", self._eval_step)):
            if fn is not None:
                n = fn._cache_size()
                if n:
                    counts[name] = n
        return counts

    def attach_recovery(self, checkpoint_cb) -> None:
        """Wire a ``CheckpointEveryN`` callback as the in-process
        restore source (called automatically by its ``set_trainer``).
        The batch replay buffer is sized to TWO save intervals: the
        newest save can be torn/corrupt, and recovery must still reach
        back to the previous verified one."""
        self._recovery_cb = checkpoint_cb
        self._replay_buffer = deque(
            maxlen=2 * int(checkpoint_cb.every_n_steps))

    def on_checkpoint_saved(self, step: int, wall_s: float) -> None:
        """``CheckpointEveryN`` save hook: telemetry only."""
        self.fault_stats["checkpoints_saved"] += 1
        self.fault_stats["checkpoint_wall_s"] += wall_s
        self._tracer.on_checkpoint_saved(step, wall_s)

    def loader_state(self) -> Optional[Dict[str, int]]:
        """Data-pipeline position after the latest completed step:
        ``{"epoch", "step_in_epoch", "batches_consumed"}`` — what a
        step-granular save embeds so ``fit(resume=...)`` repositions
        the stream exactly. ``None`` before the first step."""
        return dict(self._loader_state) if self._loader_state else None

    def fault_snapshot(self) -> Dict[str, object]:
        """Flat export dict (``ServeMetrics.snapshot()`` discipline:
        every key always present) for the Prometheus exposition —
        rendered whole by ``obs.export.train_exposition``."""
        injected = ({k.value: v for k, v in self._faults.injected.items()}
                    if self._faults is not None else {})
        return {
            **{k: self.fault_stats[k] for k in sorted(self.fault_stats)},
            "faults_injected": injected,
            "site_wall_s": {k: round(v, 6)
                            for k, v in sorted(self._site_wall.items())},
            "compile_counts": self.compile_counts(),
            "opt_step": self._opt_step,
        }

    def _device_call(self, site: str, fn, *args):
        """The ONE guarded device-dispatch boundary (the serving
        engine's ``_device_call`` ported to training): consult the
        fault plan, classify failures, retry transients with bounded
        exponential backoff, and escalate to
        :class:`~pddl_tpu.train.faults.TrainStateLost` when the budget
        runs out. ``KillPoint`` is a BaseException — it passes through
        everything here, like the SIGKILL it stands for. Injected
        faults fire BEFORE ``fn`` runs, so retrying never touches a
        half-consumed donated buffer; a REAL error from the donated
        train step is never re-dispatched (its donated state may
        already be deleted) — it escalates immediately, as does any
        OOM (an allocation that just failed won't pass until the
        restore path rebuilds the state)."""
        attempt = 0
        while True:
            try:
                if self._faults is not None:
                    self._faults.check(site)
                t0 = time.perf_counter()
                out = fn(*args)
                self._site_wall[site] = (self._site_wall.get(site, 0.0)
                                         + time.perf_counter() - t0)
                return out
            except Exception as e:
                kind = classify(e)
                if kind is None:
                    raise  # not a device fault: bugs stay loud
                injected = isinstance(e, (InjectedTransientError,
                                          InjectedResourceExhausted))
                consumed = (not injected and site == "train_step"
                            and self.donate_state)
                if kind == "oom" or consumed:
                    raise TrainStateLost(site, e) from e
                attempt += 1
                if attempt > self.max_retries:
                    raise TrainStateLost(site, e) from e
                self.fault_stats["retries"] += 1
                self._tracer.on_retry(self._opt_step, site, attempt)
                self._retry_sleep(self.retry_backoff_s * (2 ** (attempt - 1)))

    def _guarded_train_step(self, batch) -> Dict[str, jnp.ndarray]:
        """One optimizer step through the guarded boundary. On
        escalation, restore the last verified checkpoint IN-PROCESS,
        replay forward from the batch buffer to the failed step, then
        retry the failed step itself — CheckFreq-style recovery without
        a process restart. Bit-exact: the step is a pure function of
        (state, batch) and the per-step PRNG folds in ``state.step``."""
        while True:
            try:
                if self._faults is not None:
                    self._faults.on_step(self._opt_step)
                out = self._device_call("train_step", self._train_step,
                                        self.state, batch)
                break
            except TrainStateLost as lost:
                self._restore_and_replay(lost)
        self.state, logs = out
        if self._replay_buffer is not None:
            self._replay_buffer.append((self._opt_step, batch))
        self._opt_step += 1
        return logs

    def _restore_and_replay(self, lost: TrainStateLost) -> None:
        """Roll the live state back to the newest VERIFIED checkpoint
        and replay buffered batches forward to the step that failed.
        Leaves ``self.state`` at exactly ``self._opt_step`` (the failed
        step re-dispatches in the caller's loop)."""
        cb = self._recovery_cb
        if cb is None or cb.ckpt is None:
            raise lost
        target = self._opt_step
        for _ in range(self.max_recoveries):
            self.fault_stats["recoveries"] += 1
            cb.ckpt.wait()  # an in-flight async save may be the newest good
            restored = cb.ckpt.restore(self.state)
            restored_step = int(jax.device_get(restored.step))
            if restored_step > target:
                raise RuntimeError(
                    f"newest checkpoint (step {restored_step}) is AHEAD "
                    f"of the failed step {target}; cannot replay "
                    "backwards — is another run writing this directory?"
                ) from lost
            buffered = dict(self._replay_buffer or ())
            missing = [s for s in range(restored_step, target)
                       if s not in buffered]
            if missing:
                raise RuntimeError(
                    f"replay buffer does not cover steps {missing} "
                    f"between the restored checkpoint ({restored_step}) "
                    f"and the failed step ({target}) — checkpoint "
                    "cadence outran the buffer") from lost
            self._tracer.on_restore(target, restored_step, lost.site)
            self.state = restored
            try:
                for s in range(restored_step, target):
                    if self._faults is not None:
                        self._faults.on_step(s)
                    self.state, _ = self._device_call(
                        "train_step", self._train_step, self.state,
                        buffered[s])
                    self.fault_stats["replayed_steps"] += 1
            except TrainStateLost as again:
                lost = again
                continue
            self._tracer.on_recovery(target, restored_step,
                                     target - restored_step)
            log.warning(
                "recovered in-process from %s at step %d: restored "
                "step %d and replayed %d step(s)", lost.site, target,
                restored_step, target - restored_step)
            return
        raise RuntimeError(
            f"recovery budget exhausted ({self.max_recoveries} "
            f"restore+replay attempts) at step {target}") from lost

    # --------------------------------------------------------------- prefetch
    def _prefetch_distributed(self, it: Iterator, depth: int) -> Iterator:
        """Yield already-distributed global batches, ``depth`` ahead.

        ``device_put``/``make_array_from_process_local_data`` dispatch
        asynchronously, so queuing the next batches while the device chews
        on the current step overlaps host-side data work with compute —
        the ``.prefetch(AUTOTUNE)`` moment (``imagenet-resnet50.py:47``)
        at the host→HBM boundary.
        """
        from collections import deque

        q: deque = deque()

        def fill():
            while len(q) < depth:
                try:
                    q.append(self.strategy.distribute_batch(next(it)))
                except StopIteration:
                    return

        fill()
        while q:
            batch = q.popleft()
            yield batch
            fill()

    # ------------------------------------------------------------------- fit
    def fit(
        self,
        train_data: Iterable[Dict[str, np.ndarray]],
        epochs: int = 1,
        steps_per_epoch: Optional[int] = None,
        validation_data: Optional[Iterable[Dict[str, np.ndarray]]] = None,
        validation_steps: Optional[int] = None,
        callbacks: Sequence[Callback] = (),
        verbose: int = 2,  # reference uses verbose=2 (imagenet-resnet50.py:67)
        initial_epoch: int = 0,
        prefetch: int = 2,  # device-feed lookahead; 0/1 disables
        # Crash-resume: a checkpoint directory (or Checkpointer). The
        # newest VERIFIED save restores (a torn/corrupt latest falls
        # back to the previous good step), the data stream repositions
        # from the saved loader state, and training continues MID-epoch
        # — bit-exact with an uninterrupted run. Overrides
        # ``initial_epoch``. An empty directory starts fresh (so the
        # same command line works for the first launch and every
        # restart). See docs/OPERATIONS.md § "Failure modes & recovery
        # (training)".
        resume=None,
    ) -> History:
        if validation_data is not None and isinstance(validation_data, Iterator):
            raise ValueError(
                "validation_data is a one-shot iterator; fit() evaluates it "
                "once per epoch, so pass a re-iterable dataset"
            )
        self.steps_per_epoch = steps_per_epoch
        history = History()
        history.trainer = self
        self.stop_training = False
        self.global_step = 0
        self._batches_consumed = 0
        self._loader_state = None
        if self._replay_buffer is not None:
            # Stale batches from a previous fit would alias step indices.
            self._replay_buffer.clear()

        resume_offset = 0  # steps already done inside the resumed epoch
        host_skip = 0      # batches to drop from the fresh iterator
        if resume is not None:
            prepared = self._prepare_resume(resume, train_data,
                                            steps_per_epoch)
            if prepared is not None:
                train_data, initial_epoch, resume_offset, host_skip = prepared

        train_iter = self._ensure_iterator(train_data)
        if self.state is None:
            first = next(train_iter)
            self.init_state(first)
            train_iter = _chain_first(first, train_iter)
        self._opt_step = int(jax.device_get(self.state.step))
        if host_skip:
            train_iter = self._skip_consumed(train_iter, host_skip,
                                             train_data, steps_per_epoch)

        for cb in callbacks:
            cb.set_trainer(self)

        final_logs: Dict[str, float] = {}
        stopped_mid_epoch = False
        continuous_feed = None
        # on_train_begin is INSIDE the try: if a later callback's
        # on_train_begin raises (corrupt restore, ...), earlier callbacks
        # that already acquired resources (signal handlers, checkpoint
        # managers — utils/preemption.py) still get their on_train_end
        # cleanup from the finally.
        try:
            self._run_hooks(callbacks, "on_train_begin")
            for epoch in range(initial_epoch, epochs):
                if self.stop_training:
                    break
                self._run_hooks(callbacks, "on_epoch_begin", epoch)
                t0 = time.perf_counter()
                step_logs = []
                steps = 0
                samples = 0
                # Mid-epoch resume: the restored epoch already ran this
                # many steps before the crash — run only the remainder.
                offset = resume_offset if epoch == initial_epoch else 0
                def make_feed(it):
                    if prefetch and prefetch > 1:
                        return self._prefetch_distributed(it, prefetch)
                    return (self.strategy.distribute_batch(b) for b in it)

                if steps_per_epoch is not None:
                    # Continuous stream: ONE persistent feed across epochs
                    # (recreating it each epoch would drop the batches the
                    # prefetcher already pulled from the shared iterator).
                    # A finite RE-ITERABLE dataset repeats when it drains —
                    # the reference's own `.repeat()` + fixed steps_per_epoch
                    # pattern (imagenet-resnet50-ps.py:118-119,143) without
                    # the caller spelling it; each re-pass is a fresh
                    # __iter__ (so per-epoch reshuffles apply). One-shot
                    # iterators still just end.
                    if continuous_feed is None:
                        def _repeating(first_iter, data=train_data):
                            it = first_iter
                            batches = 0
                            repassed = False
                            while True:
                                yielded = False
                                for b in it:
                                    yielded = True
                                    batches += 1
                                    yield b
                                if isinstance(data, Iterator) or not yielded:
                                    return
                                if not repassed:
                                    # Loud once: a mis-sized pipeline (e.g. a
                                    # glob matching too few files) would
                                    # otherwise repeat data silently.
                                    repassed = True
                                    log.warning(
                                        "steps_per_epoch outlives the "
                                        "dataset (%d batches/pass); "
                                        "re-iterating (reference .repeat() "
                                        "semantics)", batches,
                                    )
                                it = iter(data)

                        continuous_feed = make_feed(_repeating(train_iter))
                    feed = continuous_feed
                elif epoch == initial_epoch:
                    # First epoch must include the batch consumed by
                    # init_state via _chain_first; finite data drains the
                    # feed fully so nothing is lost between epochs.
                    feed = make_feed(train_iter)
                else:
                    if isinstance(train_data, Iterator):
                        raise ValueError(
                            "train_data is a one-shot iterator but steps_per_epoch "
                            "is None; pass a re-iterable dataset or set steps_per_epoch"
                        )
                    feed = make_feed(iter(train_data))
                while steps_per_epoch is None or offset + steps < steps_per_epoch:
                    try:
                        global_batch = next(feed)
                    except StopIteration:
                        break
                    # Global batch size (leading dim of the global array).
                    samples += int(global_batch[self.target_key].shape[0])
                    logs = self._guarded_train_step(global_batch)
                    step_logs.append(logs)
                    steps += 1
                    # Loader position settles BEFORE batch-end hooks run,
                    # so a step-granular save records exactly this step's
                    # stream position (normalized to the next epoch's
                    # start at the boundary).
                    self._batches_consumed += 1
                    in_ep = offset + steps
                    if steps_per_epoch is not None and in_ep >= steps_per_epoch:
                        self._loader_state = {
                            "epoch": epoch + 1, "step_in_epoch": 0,
                            "batches_consumed": self._batches_consumed}
                    else:
                        self._loader_state = {
                            "epoch": epoch, "step_in_epoch": in_ep,
                            "batches_consumed": self._batches_consumed}
                    self._run_hooks(
                        callbacks, "on_train_batch_end", self.global_step, logs=logs
                    )
                    self.global_step += 1
                    if self.stop_training:
                        # Honored mid-epoch (Keras semantics) — e.g. preemption
                        # checkpointing stops at the next batch boundary.
                        stopped_mid_epoch = True
                        break
                if steps == 0:
                    if offset:
                        # The resumed epoch was already fully trained
                        # before the crash (the save landed on its last
                        # batch): nothing to re-run HERE, but the later
                        # epochs still must run — fall through to them.
                        # (Only the first resumed epoch can carry an
                        # offset, so a genuinely empty dataset still
                        # raises on the next iteration.)
                        continue
                    raise ValueError("empty training dataset/epoch")
                if stopped_mid_epoch:
                    # A mid-epoch stop means "exit NOW" (preemption grace
                    # window): no validation pass, no epoch-end hooks (whose
                    # checkpoint saves could also collide with the preemption
                    # save), no partial-epoch History entry that would mislead
                    # plateau/early-stop logic on resume.
                    break
                # Epoch boundary reached (finite stream drained): saves
                # from here resume at the NEXT epoch's start.
                self._loader_state = {
                    "epoch": epoch + 1, "step_in_epoch": 0,
                    "batches_consumed": self._batches_consumed}

                # Training throughput: window closes before validation runs.
                dt = time.perf_counter() - t0
                epoch_logs = _mean_logs(step_logs)
                if validation_data is not None:
                    val_logs = self.evaluate(validation_data, steps=validation_steps,
                                             verbose=0, _prefix="val_")
                    epoch_logs.update(val_logs)

                epoch_logs["images_per_sec"] = samples / dt if dt > 0 else 0.0
                history.append(epoch, epoch_logs)
                if verbose and self.strategy.is_coordinator:
                    line = " - ".join(
                        [f"Epoch {epoch + 1}/{epochs}", f"{dt:.1f}s"]
                        + [f"{k}: {v:.4f}" for k, v in epoch_logs.items()
                           if k != "images_per_sec"]
                        + [f"{epoch_logs['images_per_sec']:.0f} img/s"]
                    )
                    print(line, file=sys.stderr)
                self._run_hooks(callbacks, "on_epoch_end", epoch, logs=epoch_logs)
                final_logs = epoch_logs

        finally:
            self._run_hooks(callbacks, "on_train_end", logs=final_logs)
        self.history = history
        return history

    # --------------------------------------------------------------- resume
    @staticmethod
    def _skip_consumed(it, n: int, data, steps_per_epoch) -> Iterator:
        """Drain ``n`` already-consumed batches from the stream. With
        ``steps_per_epoch`` set, a finite re-iterable that drains is
        RE-ITERATED — exactly the ``_repeating`` wrap-around the
        original run's continuous feed applied — so the skip follows
        the same batch sequence the crashed run consumed. Without it,
        the skip stays within the resumed epoch's single pass."""
        skipped = 0
        while skipped < n:
            advanced = False
            for _ in it:
                advanced = True
                skipped += 1
                if skipped == n:
                    return it
            if (steps_per_epoch is None or isinstance(data, Iterator)
                    or not advanced):
                raise ValueError(
                    f"resume: dataset ended after {skipped} of {n} "
                    "already-consumed batches — the stream is shorter "
                    "than it was before the crash")
            it = iter(data)
        return it

    def _prepare_resume(self, resume, train_data, steps_per_epoch):
        """Restore the newest verified checkpoint and work out where the
        data stream must restart. Returns ``(train_data, initial_epoch,
        step_offset, host_skip)`` or ``None`` when the directory holds
        no checkpoint yet (fresh start — same CLI for launch and
        restart).

        Stream repositioning, in preference order: a dataset exposing
        ``with_offset(n)`` (the synthetic families) is shifted by the
        saved ``batches_consumed`` — free; otherwise ``host_skip``
        batches are drained from the fresh iterator before training
        (exact for any deterministic re-iterable). Without
        ``steps_per_epoch`` the feed is rebuilt per epoch, so only the
        resumed epoch's ``step_in_epoch`` batches are skipped. Legacy
        saves (no loader metadata) keep the old semantics: restart at
        the epoch after the recorded one, stream from the top.
        """
        if isinstance(train_data, Iterator):
            raise ValueError(
                "fit(resume=...) needs a re-iterable dataset — a one-shot "
                "iterator cannot be repositioned to the saved offset"
            )
        from pddl_tpu.ckpt.checkpoint import Checkpointer

        own = isinstance(resume, str)
        ckpt = Checkpointer(resume, async_save=False) if own else resume
        try:
            if ckpt.latest_step() is None:
                log.info("resume: no checkpoint under %s yet — fresh run",
                         getattr(ckpt, "directory", resume))
                return None
            if self.state is None:
                self.init_state(next(iter(train_data)))
            self.state = ckpt.restore(self.state)
            step = int(jax.device_get(self.state.step))
            try:
                meta = ckpt.metadata(step)
            except Exception:  # noqa: BLE001 - meta is advisory here
                meta = {}
        finally:
            if own:
                ckpt.close()
        loader = meta.get("loader") or None
        if loader:
            initial_epoch = int(loader.get("epoch", 0))
            offset = int(loader.get("step_in_epoch", 0))
            consumed = int(loader.get("batches_consumed", 0))
        else:
            saved = meta.get("epoch")
            initial_epoch = int(saved) + 1 if saved is not None else 0
            offset = consumed = 0
        self._batches_consumed = consumed
        skip = consumed if steps_per_epoch is not None else offset
        host_skip = 0
        if skip:
            if (steps_per_epoch is not None
                    and hasattr(train_data, "with_offset")):
                train_data = train_data.with_offset(skip)
            else:
                host_skip = skip
        log.info(
            "resume: restored verified step %d (epoch %d, step_in_epoch "
            "%d, %d batches consumed)", step, initial_epoch, offset,
            consumed)
        return train_data, initial_epoch, offset, host_skip

    # -------------------------------------------------------------- evaluate
    def evaluate(
        self,
        data: Iterable[Dict[str, np.ndarray]],
        steps: Optional[int] = None,
        verbose: int = 0,
        _prefix: str = "",
    ) -> Dict[str, float]:
        if self.state is None:
            raise RuntimeError("call fit() or init_state() before evaluate()")
        it = self._ensure_iterator(data, fresh=True)
        logs_list = []
        n = 0
        while steps is None or n < steps:
            try:
                batch = next(it)
            except StopIteration:
                break
            global_batch = self.strategy.distribute_batch(batch)
            try:
                if self._faults is not None:
                    self._faults.on_step(self._opt_step)
                logs_list.append(self._device_call(
                    "eval_step", self._eval_step, self.state, global_batch))
            except TrainStateLost as lost:
                # Eval mutates nothing — there is no state to restore;
                # an exhausted retry budget surfaces the device error.
                raise lost.err
            n += 1
        if not logs_list:
            raise ValueError("empty evaluation dataset")
        out = {_prefix + k: v for k, v in _mean_logs(logs_list).items()}
        if verbose and self.strategy.is_coordinator:
            print(" - ".join(f"{k}: {v:.4f}" for k, v in out.items()), file=sys.stderr)
        return out

    # --------------------------------------------------------------- predict
    def predict(self, images: np.ndarray) -> np.ndarray:
        """Forward pass (inference mode) on a batch of images."""
        if self.state is None:
            raise RuntimeError("call fit() or init_state() before predict()")
        x = self.strategy.distribute_batch(
            {self.input_key: np.asarray(images)})[self.input_key]
        if self.eval_transform is not None:
            x = self.eval_transform(x)
        logits, _ = self._apply(self.state.params, self.state.batch_stats, x, train=False)
        return np.asarray(jax.device_get(logits))

    # --------------------------------------------------------------- helpers
    def _ensure_iterator(self, data, fresh: bool = False) -> Iterator:
        # A bare iterator cannot be restarted; fit() rejects one-shot
        # iterators for train (multi-epoch) and validation data up front.
        if isinstance(data, Iterator):
            return data
        return iter(data)

    def _run_hooks(self, callbacks, hook: str, *args, logs=None) -> None:
        # on_train_end is CLEANUP: every callback must get its turn
        # (checkpoint flush, signal-handler restore) even when an
        # earlier one raises — e.g. HeartbeatCallback re-raising
        # WorkerLost for the supervisor. The first error re-raises
        # after the sweep, so it still reaches the caller.
        deferred: Optional[Exception] = None
        for cb in callbacks:
            fn = getattr(cb, hook)
            if hook in ("on_train_begin",):
                result = fn(self.state)
            elif hook in ("on_train_end",):
                try:
                    result = fn(self.state, logs or {})
                except Exception as e:  # noqa: BLE001 - swept, re-raised
                    if deferred is None:
                        deferred = e
                    else:
                        # Only the first propagates; later failures must
                        # not vanish without a trace.
                        log.error(
                            "on_train_end of %s also failed (suppressed "
                            "in favor of the first error): %s",
                            type(cb).__name__, e)
                    continue
            elif hook == "on_epoch_begin":
                result = fn(args[0], self.state)
            elif hook == "on_epoch_end":
                result = fn(args[0], self.state, logs or {})
            elif hook == "on_train_batch_end":
                result = fn(args[0], self.state, logs or {})
            else:  # pragma: no cover
                raise ValueError(hook)
            if result is not None:
                self.state = result
        if deferred is not None:
            raise deferred


def _mean_logs(logs_list) -> Dict[str, float]:
    """Fetch once, average on host (one device sync per epoch).

    Perplexity keys are logged per batch in log space (mean CE — see
    ``metrics.log_perplexity``); exponentiating AFTER the average yields
    exactly exp(mean CE) over all tokens (the standard corpus number),
    where a mean of per-batch exponentials would be Jensen-biased high
    and could overflow.
    """
    fetched = jax.device_get(logs_list)
    keys = fetched[0].keys()
    out = {}
    for k in keys:
        vals = np.asarray([d[k] for d in fetched], np.float64)
        # Exact key only (evaluate() adds its val_ prefix after this
        # aggregation): user metrics with "perplexity" in their name are
        # not assumed to be log-space.
        if k == "perplexity":
            out[k] = float(np.exp(np.mean(vals)))
        else:
            out[k] = float(np.mean(vals))
    return out


def _chain_first(first, rest: Iterator) -> Iterator:
    yield first
    yield from rest
