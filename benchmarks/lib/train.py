"""A training cell's run: the program's compiled train step (one chip) or
its hybrid step on the fleet mesh (the layout the configuration file
states), the comparison of its first loss with the plain reference, and
the window of back-to-back steps."""
from __future__ import annotations

import time

import numpy as np

from . import build
from .common import (Cell, SetupClock, import_object, note, peaks_for,
                     rehearsed, start_jax)
from .tracing import TraceSlice

#: |first step's loss - reference loss on the same batch|, in nats. The
#: step computes in bf16 through the kernels (or, on the mesh, the XLA
#: composites), the reference in float32 from the same bf16 weights.
#: Measured on the v5e (my chip runs, PR 25): 0.00001-0.00013 at a loss of
#: 11.9 on one chip; PR 24 measured 0.001 between the hybrid and the
#: one-chip step. 0.01 is ten times that; an 8-bit float step (3 mantissa
#: bits against bf16's 7) would move a mean over 8192 tokens by far more.
LOSS_TOL = 0.01


class TrainRun:
    def __init__(self, cell: Cell, args, t_start: float):
        self.cell, self.args = cell, args
        self.rehearse = bool(args.rehearse)
        self.clock = SetupClock(t_start)
        self.cfg = rehearsed(cell.config, self.rehearse)
        self.job = rehearsed(cell.traffic, self.rehearse)
        self.batch, self.seq = int(self.job["batch"]), int(self.job["seq_len"])
        self.tokens_per_step = self.batch * self.seq
        self.reference = cell.reference()
        self.spec = self.reference.Spec.from_config(self.cfg)
        self.compared = {}

    # ---- set-up ---------------------------------------------------------
    def setup(self) -> None:
        cell, recipe = self.cell, self.cfg["recipe"]
        self.device, self.meter, cache_dir = start_jax(cell, self.rehearse)
        self.peaks = None if self.rehearse else peaks_for(
            self.device["kind"])
        self.clock.lap("backend_s")
        import paddle_tpu as paddle

        self.paddle = paddle
        opt = recipe["optimizer"]

        def make_optimizer(model):
            return import_object(opt["class"])(
                opt["learning_rate"], parameters=model.parameters(),
                **opt.get("args", {}))

        def loss_fn(m, x, y):
            return m(x, labels=y)[0]

        layout = recipe.get("layout")
        if layout:
            import paddle_tpu.distributed as dist
            from paddle_tpu.distributed.engine import parallelize

            strategy = dist.DistributedStrategy()
            strategy.hybrid_configs = dict(layout["fleet"])
            strategy.sharding_configs = {"stage": layout["sharding_stage"]}
            dist.fleet.init(is_collective=True, strategy=strategy)
            self.model = dist.fleet.distributed_model(
                build.build_model(self.cfg, self.args.seed))
            optimizer = dist.fleet.distributed_optimizer(
                make_optimizer(self.model))
            self.step = parallelize(self.model, loss_fn, optimizer)
        else:
            self.model = build.build_model(self.cfg, self.args.seed)
            self.step = paddle.jit.train_step(self.model, loss_fn,
                                              make_optimizer(self.model))
        self.clock.lap("weights_s")
        note("setup", cell=cell.name, compile_cache=cache_dir,
             device=self.device, layout=layout,
             parameters=int(sum(int(np.prod(p.shape))
                                for p in self.model.parameters())))

    def make_batch(self, i: int):
        """Step ``i``'s batch: drawn on the host from one of a cycle of
        per-batch seeds, then transferred (the input path runs; the model
        can memorise the cycle, so the loss must fall)."""
        cycle = int(self.job["batch_cycle"])
        rng = np.random.RandomState(
            (self.args.seed * 1000003 + i % cycle) % (2 ** 31))
        ids = rng.randint(0, int(self.cfg["vocab_size"]),
                          (self.batch, self.seq + 1))
        return ids

    def _tensors(self, ids):
        return (self.paddle.to_tensor(ids[:, :-1]),
                self.paddle.to_tensor(ids[:, 1:]))

    def check(self) -> bool:
        """The loss the FIRST step returns (the forward loss at the seeded
        weights, from the very program the window measures) against the
        plain float32 reference's loss on the same batch; then the
        warm-up steps."""
        import jax

        ids = self.make_batch(0)
        dev0 = jax.devices()[0]
        state = {k: jax.device_put(v, dev0)
                 for k, v in build.plain_state(self.model).items()}
        ref = float(np.mean([
            self.reference.next_token_loss(self.spec, state, row)
            for row in ids]))
        del state
        self.clock.lap("reference_check_s")
        first = float(self.step(*self._tensors(ids)).numpy())
        for i in range(1, int(self.job["warmup_steps"])):
            last = float(self.step(*self._tensors(self.make_batch(i)))
                         .numpy())
        self.next_step = int(self.job["warmup_steps"])
        self.clock.lap("warmup_s")
        err = abs(first - ref)
        ok = bool(np.isfinite(first) and err <= LOSS_TOL)
        self.compared = {"first_loss_abs_err": {"value": err,
                                                "limit": LOSS_TOL}}
        note("reference_check", reference=self.reference.__name__,
             tolerance=LOSS_TOL, step_loss=first,
             reference_loss=ref, abs_err=err, ok=ok,
             ln_vocab=float(np.log(self.cfg["vocab_size"])))
        note("warmup", steps=int(self.job["warmup_steps"]),
             last_loss=last if self.next_step > 1 else first,
             **self.meter.report())
        from paddle_tpu.ops.pallas import backend

        note("kernels", paths=backend.paths(), refusals=backend.refusals())
        return ok

    # ---- the window -----------------------------------------------------
    def run_window(self) -> dict:
        import jax

        args, seconds = self.args, float(self.args.seconds)
        tracer = TraceSlice(self.cell.name) if args.trace else None
        trace_steps = int(self.job.get("trace_steps", 10))
        traced_from = traced_steps = None
        losses, done_at = [], []
        mark = self.meter.mark()
        self.setup_s = time.time() - self.clock.t_start
        t0 = time.perf_counter()
        pending, i = None, self.next_step

        def settle():
            nonlocal pending
            if pending is not None:
                losses.append(float(pending.numpy()))
                done_at.append(time.perf_counter())
                pending = None

        with jax.profiler.TraceAnnotation("bench/window"):
            while time.perf_counter() - t0 < seconds:
                if tracer is not None and traced_from is None and \
                        time.perf_counter() - t0 >= seconds / 4.0:
                    settle()
                    tracer.start()
                    traced_from = i
                ids = self.make_batch(i)
                with jax.profiler.StepTraceAnnotation("bench/train_step",
                                                      step_num=i):
                    x, y = self._tensors(ids)
                    loss = self.step(x, y)
                settle()                       # the step BEFORE this one
                pending = loss
                i += 1
                if traced_from is not None and tracer.t_stop is None and \
                        i - traced_from >= trace_steps:
                    settle()
                    tracer.stop()
                    traced_steps = i - traced_from
            settle()
        elapsed = time.perf_counter() - t0
        if tracer is not None:
            if tracer.t_start is not None and tracer.t_stop is None:
                tracer.stop()
                traced_steps = i - traced_from
            if tracer.t_start is not None:
                tracer.reduce()
        after = self.meter.mark()
        steps = len(losses)
        step_s = list(np.diff([t0] + done_at))
        note("setup_split", setup_s=self.setup_s, **self.clock.parts,
             **self.meter.report())
        return {
            "cell": self.cell.name, "kind": "train_job", "seconds": seconds,
            "elapsed_s": elapsed, "chips": self.cell.chips,
            "config": self.cfg, "traffic": self.job, "peaks": self.peaks,
            "reference": self.reference, "spec": self.spec,
            "compared": self.compared,
            "batch": self.batch, "seq_len": self.seq,
            "tokens_per_step": self.tokens_per_step,
            "tokens_per_s": steps * self.tokens_per_step / elapsed,
            "losses": losses, "step_s": step_s,
            "compiles_in_window": (after[0] - mark[0]) + (after[1] - mark[1]),
            "trace": tracer.reduced if tracer else None,
            "traced_steps": traced_steps,
        }
