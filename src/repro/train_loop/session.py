"""Resumable training sessions — the Theano-MPI/Caffe-style evolution of
the paper's training *script* into a restartable *session*.

A session owns the train loop that ``launch/train.py`` used to inline:

    while step < total:
        batch   -> jitted param-avg step        (mesh or reference engine)
        every eval_every:  jitted eval on a held-out stream
                           -> plateau controller (may divide LR by 10)
        every ckpt_every:  atomic checkpoint (arrays + session meta)

and makes the whole thing deterministic under kill/resume:

* **State** — the TrainState is checkpointed with its step counter and
  restored via ``checkpoint.restore(..., sharding=...)`` onto the SAME
  mesh layout a fresh run would use (``sharding`` is the NamedSharding
  tree the engine device_puts with), so the resumed compiled step is the
  same program over the same device placement.
* **Data** — synthetic streams are seeded iterators; the manifest records
  how many batches the train stream yielded, and resume rebuilds the
  stream and fast-forwards host-side past exactly that many draws (this
  also replays the preprocess RNG, which advances per batch).  The
  prefetch queue needs no persistence: batches a killed run staged but
  never trained on are re-drawn identically.
* **Schedule** — the LR controller's decision state (current LR, best
  metric, bad-eval count) rides in the manifest meta, so a resumed
  session drops the LR at the same step the uninterrupted one does.
* **Eval** — stateless by construction: each eval rebuilds a freshly
  seeded held-out stream (train_loop.eval), so it adds no resume state.

Together: an interrupted-and-resumed run reproduces the uninterrupted
loss trace bit-exactly (asserted per-step in tests/train_loop/ and the CI
``resume-smoke`` job).

Throughput is recorded per step and rolled up into the paper's Table 1
format (images/sec + step-time percentiles) as JSONL via
``train_loop.metrics``; ``benchmarks/session_throughput.py`` consumes it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax

from repro import checkpoint, obs
from repro.optim import schedules
from repro.train_loop.eval import run_eval, take
from repro.train_loop.metrics import MetricsWriter


@dataclasses.dataclass
class SessionResult:
    start_step: int              # 0 for fresh runs, N when resumed at N
    final_step: int
    state: Any
    losses: list                 # [(step, loss), ...] one per executed step
    evals: list                  # [(step, {metric: float}), ...]
    lr_drops: list               # steps whose eval dropped the LR
    summary: dict                # Table-1 rollup (also last JSONL line)


class TrainSession:
    """See module docstring.  All engine specifics stay with the caller:

    Args:
      state: freshly-initialized TrainState (step 0); doubles as the
        restore template on resume.
      build_step: ``schedule -> jitted step(state, batch)`` factory; called
        once at start and again after every plateau LR drop (the LR is a
        compile-time constant, so each segment runs fully compiled).
      make_stream: zero-arg factory for the host-batch iterator from step
        0 (preprocess + replica reshape included, NO device_put) — must be
        re-creatable so resume can fast-forward a fresh copy.
      controller: LR controller (``schedules.as_controller`` accepts plain
        compiled schedules too).
      device_put: stages a host batch onto the engine's layout.
      sharding: NamedSharding pytree for ``state`` (None = default device).
      eval_step / make_eval_batches / eval_every: validation loop; the
        controller is fed ``plateau_metric`` from each eval's averages.
      images_per_step: global batch items per step (Table 1 throughput
        unit; sequences for the LM zoo).
    """

    def __init__(self, *, state, build_step: Callable, make_stream: Callable,
                 controller=None, steps: int, device_put=None, sharding=None,
                 eval_step=None, make_eval_batches=None, eval_every: int = 0,
                 eval_batches: int = 2, plateau_metric: str = "loss",
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 resume: bool = False, prefetch: int = 2,
                 staging: str = "queue", log_every: int = 10,
                 images_per_step: int = 0, metrics_path: Optional[str] = None,
                 run_meta: Optional[dict] = None):
        self.state = state
        self.build_step = build_step
        self.step_fn = None           # the jitted step run() last built
        self.make_stream = make_stream
        self.controller = schedules.as_controller(
            controller if controller is not None
            else schedules.constant(0.01))
        self.steps = steps
        self.device_put = device_put or jax.device_put
        self.sharding = sharding
        self.eval_step = jax.jit(eval_step) if eval_step is not None else None
        self.make_eval_batches = make_eval_batches
        self.eval_every = eval_every if eval_step is not None else 0
        self.eval_batches = eval_batches
        self.plateau_metric = plateau_metric
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.resume = resume
        self.prefetch = prefetch
        self.staging = staging
        self.log_every = log_every
        self.images_per_step = images_per_step
        self.metrics_path = metrics_path
        # run_meta rides in the checkpoint manifest: resume compares it so
        # a run restarted under a different kernel policy/backend gets a
        # loud warning — cross-backend numerics differ in the last bits,
        # which silently breaks the bit-exact-resume guarantee
        self.run_meta = run_meta or {}
        self._ff_batches = 0          # train batches to skip on resume
        self._eval_cache = None       # eval streams are freshly-seeded and
        # deterministic (train_loop.eval), so the batches are identical on
        # every pass — materialize once instead of re-running the source's
        # table build each eval
        if resume and not ckpt_dir:
            raise ValueError("--resume needs a checkpoint directory")

    # ------------------------------------------------------------------
    def _try_restore(self) -> int:
        """Restore the latest complete checkpoint; returns the start step."""
        step = checkpoint.latest_step(self.ckpt_dir) if self.resume else None
        if step is None:
            return 0
        self.state = checkpoint.restore(self.ckpt_dir, step, self.state,
                                        sharding=self.sharding)
        meta = checkpoint.load_meta(self.ckpt_dir, step) or {}
        if "controller" in meta:
            self.controller.load_state_dict(meta["controller"])
        # reuse the checkpoint's autotuned block sizes: re-measuring under
        # timing noise could pick different winners, whose different fp
        # reduction order would silently break bit-exact resume
        from repro.kernels import common as _kernels_common
        _kernels_common.load_cache_state(meta.get("autotune_cache"))
        saved = meta.get("run_meta") or {}
        drift = {k: (saved.get(k), v) for k, v in self.run_meta.items()
                 if k in saved and saved.get(k) != v}
        if drift:
            print("WARNING: resuming under a different configuration than "
                  "the checkpoint was written with — the continued loss "
                  "trace will NOT be bit-exact: "
                  + ", ".join(f"{k}: {a!r} -> {b!r}"
                              for k, (a, b) in sorted(drift.items())),
                  flush=True)
        # the manifest is authoritative for the stream position (== step
        # today, but decoupled so a future loop drawing !=1 batch/step
        # keeps resuming correctly)
        self._ff_batches = meta.get("batches_consumed", step)
        return step

    def _save(self, step: int):
        from repro.kernels import common as _kernels_common
        checkpoint.save(
            self.ckpt_dir, step, self.state,
            meta={"controller": self.controller.state_dict(),
                  "batches_consumed": step,
                  "plateau_metric": self.plateau_metric,
                  "run_meta": self.run_meta,
                  "autotune_cache": _kernels_common.cache_state()})

    def _run_eval(self, step: int, writer, result: SessionResult) -> bool:
        """One validation pass; returns True iff the LR dropped."""
        if self._eval_cache is None:
            self._eval_cache = take(self.make_eval_batches(),
                                    self.eval_batches)
        avg = run_eval(self.eval_step, self.state.params, self._eval_cache,
                       self.device_put)
        dropped = self.controller.update(avg[self.plateau_metric])
        writer.eval(step, avg, dropped)
        result.evals.append((step, avg))
        if dropped:
            result.lr_drops.append(step)
            print(f"step {step:5d} eval "
                  f"{self.plateau_metric}={avg[self.plateau_metric]:.4f} "
                  f"plateaued -> lr {self.controller.lr:.2e}", flush=True)
        return dropped

    # ------------------------------------------------------------------
    def run(self) -> SessionResult:
        from repro.data import make_loader        # local: keeps import light

        start = self._try_restore() if self.ckpt_dir else 0
        result = SessionResult(start, start, self.state, [], [], [], {})
        if start >= self.steps:
            print(f"checkpoint at step {start} >= --steps {self.steps}; "
                  "nothing to do", flush=True)
            return result

        writer = MetricsWriter(
            self.metrics_path, images_per_step=self.images_per_step,
            resume_step=start if start else None)
        loader = None
        compiling = True                  # first call of each jit compiles
        t_session = time.perf_counter()
        spans0 = obs.snapshot()
        try:
            stream = self.make_stream()
            for _ in range(self._ff_batches):   # deterministic fast-forward
                next(stream)
            # loader construction starts the worker thread, so everything
            # from here on runs under the finally that closes it
            loader = make_loader(stream, prefetch=self.prefetch,
                                 staging=self.staging,
                                 device_put=self.device_put)
            sched_fn = self.controller.schedule()
            step_fn = self.step_fn = self.build_step(sched_fn)
            # a metrics trace needs the loss + honest wall time every step,
            # which costs a host sync per step; without it, sync only at
            # log/eval boundaries and keep jax's async dispatch pipelined
            per_step_sync = self.metrics_path is not None
            for i in range(start, self.steps):
                t0 = time.perf_counter()
                batch = next(loader)
                stage_wait_ms = loader.last_wait_ms
                self.state, loss = step_fn(self.state, batch)
                # pinned staging: the slot this batch occupies may be
                # overwritten only after this step's reads finish — hand
                # the loader a fence token of the dispatched step
                loader.fence(loss)
                at_log = (i + 1) % self.log_every == 0 or i == start
                if per_step_sync or at_log:
                    loss_f = float(loss)          # blocks on the device
                    result.losses.append((i + 1, loss_f))
                if per_step_sync:
                    # loss-scaling state (NumericsPolicy) rides the
                    # TrainState as replica-identical scalars; trace them
                    # so a run's scale trajectory and skip count are
                    # auditable from the JSONL alone
                    ns = getattr(self.state, "numerics", None)
                    # compile steps are logged, excluded from percentiles
                    writer.train(i + 1, loss_f, float(sched_fn(i)),
                                 time.perf_counter() - t0,
                                 timed=not compiling,
                                 stage_wait_ms=stage_wait_ms,
                                 loss_scale=float(ns["scale"])
                                 if ns is not None else None,
                                 skipped_steps=int(ns["skipped"])
                                 if ns is not None else None)
                compiling = False
                if at_log:
                    print(f"step {i + 1:5d} loss {loss_f:.4f} "
                          f"({(time.perf_counter() - t_session) / (i + 1 - start):.3f}"
                          "s/step)", flush=True)
                if self.eval_every and (i + 1) % self.eval_every == 0:
                    if self._run_eval(i + 1, writer, result):
                        sched_fn = self.controller.schedule()
                        step_fn = self.step_fn = self.build_step(sched_fn)
                        compiling = True
                if self.ckpt_dir and self.ckpt_every and \
                        (i + 1) % self.ckpt_every == 0:
                    self._save(i + 1)
                result.final_step = i + 1
        finally:
            if loader is not None:
                loader.close()            # never leak the worker thread
            result.state = self.state     # original buffer was donated
            result.summary = writer.summary(
                result.final_step,
                loader=obs.mean_ms(spans0, obs.snapshot(), "repro.data."))
            writer.close()
        return result
