"""``Trainer.fit``'s loop over one-epoch spans.

A span IS an epoch: one dispatch of the fused train+eval program
(``steps.make_epoch_train_eval_step``, key ``EPOCH_PROGRAM_KEY``) on the
scan path, or one Python loop over batches on the eager path. ``EpochLoop``
owns what the phases share (the live state, the early-stop monitor, the step
counter, the span in flight, the prefetch future, the history, the open
trace spans the crash sweep must close) and has one method a phase:
``assemble`` / ``scan_epoch`` (dispatch) / ``join`` / ``finish`` /
``bookkeep`` / ``checkpoint``, plus ``eager_epoch``. Three modes, all kept:

- pipelined (scan, ``prefetch_spans >= 1``, no armed fault plan): what every
  benchmark cell runs. Epoch e+1 is dispatched BEFORE epoch e is bookkept.
- serial (scan): join and bookkeep each epoch before the next dispatch. The
  fault-injection drills assert its exact crash / checkpoint order, and
  ``fit_setup.prepare_fit`` selects it from ``plan.enabled``.
- eager (``use_scan=False``): step-granular fault and health drills run it,
  and ``tests/test_scan_path.py`` / ``test_grad_accum.py`` use it as the
  reference for the scan path.

The pure decisions of the loop are module functions so tests reach them
without a fit: ``early_stop_update``, ``must_consume_pending``,
``may_prefetch_next``, ``span_bill``. This module knows ``steps.py``, the
checkpointers and the telemetry object; it does not import ``trainer.py``.
"""

from __future__ import annotations

import gc
import math
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from dct_tpu.checkpoint.manager import to_host
from dct_tpu.observability.health import HealthMonitor
from dct_tpu.ops.losses import precision_recall_f1
from dct_tpu.parallel.mesh import make_global_batch, make_global_epoch
from dct_tpu.parallel.sharding_rules import layout_mismatches
from dct_tpu.resilience.preempt import PreemptedError
from dct_tpu.train.steps import EPOCH_PROGRAM_KEY, counter_metrics


def early_stop_update(
    val_loss: float,
    best: float | None,
    stale: int,
    *,
    patience: int,
    min_delta: float,
) -> tuple[float | None, int, bool]:
    """One early-stopping step (monitor val_loss, min mode): returns the
    updated ``(best, stale, stop)``. A NaN val_loss never counts as an
    improvement — in particular a NaN on the FIRST monitored epoch must
    not seed ``best`` (nothing compares below NaN, which would turn every
    later finite epoch 'stale' and force a spurious stop)."""
    improved = not math.isnan(val_loss) and (
        best is None or val_loss < best - min_delta
    )
    if improved:
        return val_loss, 0, False
    return best, stale + 1, stale + 1 >= patience


def must_consume_pending(patience: int, es_stale: int) -> bool:
    """Pipelined early-stop guard, asked while a dispatched epoch is still
    un-bookkept: could THAT epoch trip the stop? Then it is consumed
    BEFORE more work is dispatched (serial fallback for exactly this
    epoch), so the stop decision is never speculated past."""
    return patience > 0 and es_stale + 1 >= patience


def may_prefetch_next(patience: int, es_stale: int, pending: bool) -> bool:
    """May the epoch after the one just dispatched be assembled and staged
    ahead? Not when early stopping is armed and could trigger within the
    epoch just dispatched or the still-unbookkept previous one
    (``pending``): the next epoch may never run, and a speculative H2D
    would sit in HBM through checkpointing / upload for nothing."""
    return not (
        patience > 0
        and es_stale + (1 if pending else 0) + 1 >= patience
    )


def span_bill(
    pipelined: bool, *, dispatch_elapsed: float, join_seconds: float,
    t_dispatch: float, join_t1: float,
) -> float:
    """Seconds one epoch's fused dispatch bills to the goodput ledger.
    Serial: one window, dispatch -> results joined (the historical
    accounting). Pipelined: the wall interval dispatch(e) -> consume(e)
    CONTAINS other billed windows (the previous epoch's checkpoint, the
    next one's data_wait), so billing it whole would double-count and
    push goodput_fraction past 1 — bill only the two main-thread-blocking
    windows instead: the dispatch call itself (trace + compile + enqueue)
    plus the join. Device time overlapped by host bookkeeping is exactly
    the overlap the mode buys; it surfaces as the other categories'
    windows, never twice. (The join precedes the successor's dispatch
    call, so the two windows stay disjoint.)"""
    if pipelined:
        return dispatch_elapsed + join_seconds
    return join_t1 - t_dispatch


def device_ids(tree) -> list:
    """Sorted ids of every device holding a shard of any leaf of ``tree``."""
    ids: set = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            ids.update(d.id for d in leaf.sharding.device_set)
    return sorted(ids)


def preempt_exit(
    guard,
    events,
    ckptr,
    *,
    epochs_completed: int,
    state=None,
    target_epochs: int | None = None,
    opt_identity: dict | None = None,
):
    """Honor a SIGTERM: make the resume checkpoint durable, put the
    preemption on the record, raise :class:`PreemptedError` (the
    entry point maps it to ``EXIT_PREEMPTED``).

    ``state=None`` means the span boundary just submitted the right
    snapshot asynchronously — joining it is the synchronous save;
    the eager path passes the live state for an explicit save.
    """
    if state is not None:
        ckptr.save(
            state,
            meta={
                "epochs_completed": int(epochs_completed),
                "target_epochs": int(target_epochs),
                "optimizer": opt_identity,
            },
        )
    else:
        ckptr.wait()
    events.emit(
        "trainer", "preempt.signal_received",
        signal_time=guard.signal_time,
    )
    events.emit(
        "trainer", "preempt.checkpoint_saved",
        epochs_completed=int(epochs_completed), dir=ckptr.dirpath,
    )
    raise PreemptedError(
        f"SIGTERM honored: resume checkpoint durable at "
        f"epochs_completed={int(epochs_completed)}"
    )


@dataclass
class _SpanInFlight:
    """One dispatched epoch awaiting host bookkeeping (the pipelined
    loop's unit of deferral): its device result futures, the output
    state both checkpoint tiers will read, and the open trace spans the
    crash sweep must be able to close."""

    epoch0: int
    n_steps: int
    state: object
    losses: object = None
    val_sums: object = None
    gnorms: object = None
    # The model's sown counters, summed over the epoch (steps.py).
    counters: object = None
    t_dispatch: float = 0.0
    # Host seconds the dispatch call itself blocked (jit tracing + XLA
    # compile on the program's first span, ~enqueue cost after).
    # Pipelined billing uses it: see span_bill.
    dispatch_elapsed: float = 0.0
    dispatch_span: object = None
    epoch_span: object = None


class EpochLoop:
    """The loop's shared state and its phases. Read after ``run``:
    ``state``, ``history``, ``global_step``, ``batch_devices``."""

    def __init__(self, cfg, mesh, setup, tel, *, tracker, coordinator):
        self.cfg, self.mesh, self.setup, self.tel = cfg, mesh, setup, tel
        self.tracker, self.coordinator = tracker, coordinator
        # The live train state moves here: the setup must not keep the
        # initial one alive beside the loop's two (the span in flight
        # and its successor).
        self.state, setup.state = setup.state, None
        self.history: list[dict] = []
        self.global_step = int(jax.device_get(self.state.step))
        self.es_best: float | None = None
        self.es_stale = 0
        self.batch_devices: list = []
        # Epoch-ahead input pipeline (scan path): the next epoch's host
        # batch assembly + H2D staging runs on a worker thread WHILE the
        # current one computes on device — shuffle/stack/device_put leave
        # the step critical path (device_put is async; the transfer itself
        # also overlaps compute). One epoch deep: bounded host memory, and
        # the device queue never sees stale epochs after an early stop.
        self.prefetch_pool = None
        self.prefetched = None
        if setup.use_scan and cfg.train.prefetch_spans >= 1:
            self.prefetch_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="epoch-prefetch"
            )
        # In-flight phase spans, tracked so a crash mid-epoch still
        # records them (Span.end is idempotent: the success path's own
        # end() wins and the crash-path sweep becomes a no-op).
        self.epoch_span = self.dispatch_span = self.bookkeep_bracket = None
        # The program's first dispatch_call is its trace + AOT load or
        # compile (attr first=true).
        self.dispatched = False
        # Pipelined mode: the one dispatched-but-unbookkept epoch. Its
        # results are consumed one iteration late, while the NEXT epoch
        # computes on device; the crash sweep also closes its spans.
        self.pending: _SpanInFlight | None = None
        self.consumed_through = setup.start_epoch
        self.timer_running = False
        self.layout_checked = False

    # ------------------------------------------------------------------
    def in_flight_spans(self) -> list:
        """What the crash sweep must close: the spans of the epoch being
        bookkept and, pipelined, of the un-bookkept successor in
        ``pending``."""
        spans = [self.bookkeep_bracket, self.dispatch_span, self.epoch_span]
        if self.pending is not None:
            spans += [self.pending.dispatch_span, self.pending.epoch_span]
        return spans

    def shutdown(self) -> None:
        """Crash-path hygiene: never leave the prefetch thread running."""
        if self.prefetch_pool is not None:
            self.prefetch_pool.shutdown(wait=True)

    def _preempt_exit(self, **kw):
        tel, setup = self.tel, self.setup
        preempt_exit(tel.guard, tel.events, setup.state_ckptr, **kw)

    # ------------------------------------------------------------------
    def run(self) -> None:
        """Epochs ``[start_epoch, target_epochs)``, or up to an early
        stop; raises ``PreemptedError`` / ``TrainingHealthError``."""
        cfg, setup, tel = self.cfg, self.setup, self.tel
        patience = cfg.train.early_stop_patience
        epoch = setup.start_epoch
        stop_early = False
        while epoch < setup.target_epochs:
            if self.pending is not None and must_consume_pending(
                patience, self.es_stale
            ):
                sp, self.pending = self.pending, None
                stop_early = self.consume(sp)
                if tel.guard.requested:
                    self._preempt_exit(
                        epochs_completed=self.consumed_through
                    )
                if stop_early:
                    break
            # Trainer fault hook at the epoch boundary (`crash` /
            # `hang` / `slow_epoch` clauses). A crash first joins
            # any in-flight resume-snapshot write so the death
            # leaves a deterministic resume point — torn-write
            # recovery has its own injector (`crash_save`).
            # (Pipelining is auto-disabled while a plan is armed,
            # so the hook always sees fully-bookkept prior epochs.)
            if tel.plan.enabled:
                tel.plan.maybe_fire(
                    "epoch", epoch=epoch, pre_exit=setup.state_ckptr.wait
                )
            # Span boundary = the flight recorder's poll point: an
            # operator trigger starts (or a passed deadline stops)
            # a capture here, between dispatches, never inside one.
            tel.flight.poll(epoch=epoch)
            tel.profiler.maybe_start(epoch)
            # One span per dispatch unit: the trace's "trainer
            # epochs" row. Parenting is EXPLICIT (not thread-stack):
            # pipelined, span e is still open when span e+1 starts,
            # so stack-implicit parenting would chain epochs under
            # each other and leak the stack.
            self.epoch_span = tel.tracer.start(
                "trainer.epoch", component="trainer",
                epoch=epoch, k=1, parent_id=tel.fit_span.span_id,
            )
            # Pipelined throughput windows chain consume-to-consume
            # (started once here, re-armed by each consume); serial
            # keeps one window per span, started at the boundary.
            if not (setup.pipelined and self.timer_running):
                tel.timer.start()
                self.timer_running = True
            if setup.use_scan:
                stop_early = self.scan_epoch(epoch)
            else:
                stop_early = self.eager_epoch(epoch)
            epoch += 1
            # Graceful preemption at the span boundary: the last
            # BOOKKEPT epoch's resume snapshot was just submitted —
            # first drain any still-in-flight epoch so its progress
            # is durable too (matching serial semantics: everything
            # dispatched gets consumed), then join the write and
            # exit PREEMPTED. At most one epoch of progress is in
            # flight when SIGTERM lands, so the resume loses at most
            # that epoch.
            if tel.guard.requested:
                if self.pending is not None:
                    sp, self.pending = self.pending, None
                    self.consume(sp)
                self._preempt_exit(epochs_completed=self.consumed_through)
            if stop_early:
                break
        # Pipelined tail: the loop exits on the epoch budget (or an
        # early stop) with the last dispatched epoch's results still
        # on device — bookkeep them now.
        if self.pending is not None:
            sp, self.pending = self.pending, None
            self.consume(sp)
            if tel.guard.requested:
                self._preempt_exit(epochs_completed=self.consumed_through)

    # ------------------------------------------------------------------
    def assemble(self, e: int):
        """Epoch ``e`` as staged device stacks -> ``(n_steps, stacks)``.
        Runs inline or on the prefetch thread."""
        plan, accum = self.tel.plan, self.setup.accum
        # Spanned HERE so it follows the work onto the prefetch
        # thread (the consumer side only joins a future).
        with self.tel.tracer.span("data.assemble", epoch=e, k=1):
            xs, ys, ws = self.setup.train_loader.epoch_stacked(e)
            # Data-pipeline fault hook: a `nan` clause poisons
            # this epoch's staged features, so the non-finite
            # loss arrives through the REAL compute path and the
            # health policy (warn/halt) is exercised end-to-end.
            if plan.enabled and plan.check("data", epoch=e):
                xs = np.array(xs, copy=True)
                xs[0, ...] = np.nan
            if accum > 1:
                # Whole accumulation groups only; the ragged tail
                # (< accum batches) is dropped, like drop_last on
                # the group granularity.
                s_eff = (xs.shape[0] // accum) * accum
                xs, ys, ws = xs[:s_eff], ys[:s_eff], ws[:s_eff]
            return xs.shape[0], make_global_epoch(self.mesh, xs, ys, ws)

    # ------------------------------------------------------------------
    def scan_epoch(self, epoch: int) -> bool:
        """One iteration of the scan path: stage, (pipelined: join the
        epoch in flight), dispatch, start the D2H copies, submit the
        prefetch, then bookkeep — the epoch in flight under pipelining,
        this one in serial mode. Returns ``stop_early``.

        Pipelining (the dispatch-gap work): the loop JOINS epoch e first
        (a wait on one small output that returns when its program ends)
        and dispatches e+1 right after: program e's input state is free
        by then, so TWO states are alive at a dispatch (e's output,
        which e+1 reads and the bookkeeping saves, and e+1's output),
        where dispatching behind a running program held three. The price
        is the host time from the join's return to the enqueue, once an
        epoch (trainer.epoch_gap_ms). Bounded to ONE epoch in flight
        past the bookkeeping (early-stop and health decisions trail the
        device by at most that epoch — see finish)."""
        cfg, setup, tel = self.cfg, self.setup, self.tel
        epoch_span = self.epoch_span
        # Goodput: joining the prefetch future (or assembling
        # inline) is time the DEVICE spends waiting on data.
        with tel.timed(
            "data_wait", "trainer.data_wait",
            epoch=epoch, parent_id=epoch_span.span_id,
        ):
            if self.prefetched is not None:
                n_steps, globs = self.prefetched.result()
            else:
                n_steps, globs = self.assemble(epoch)
        # Train epoch + full eval in ONE dispatch.
        # Beat BEFORE the dispatch: the fused program can
        # legitimately block for minutes (first-span compile), and
        # the monitor must see the rank reached the dispatch rather
        # than ageing the previous span-end beat across the whole
        # gap. (Size DCT_HEARTBEAT_STALL_SECONDS above the longest
        # expected single dispatch.)
        if tel.heartbeat is not None:
            tel.heartbeat.beat(
                step=self.global_step, epoch=epoch, phase="dispatch",
            )
        # Pipelined: wait for the epoch in flight BEFORE
        # dispatching this one (its data is staged already).
        # Its program's input state is then free and the
        # state it bookkept last was let go, so this
        # dispatch finds two states alive. Its read-back and
        # bookkeeping wait until this epoch runs on the device.
        joined = (
            self.join(self.pending)
            if setup.pipelined and self.pending is not None else None
        )
        # dct: begin-no-host-sync — the pipelined dispatch
        # region: from here until the bookkeeping swap,
        # nothing may join device results (device_get,
        # float()/int() on arrays, .block_until_ready()) or
        # the one-span overlap PR 5 bought collapses back to
        # serial. The join belongs in join(), above for
        # the epoch in flight and one iteration later for
        # this one. Enforced by dct-lint rule `span-sync`.
        key = EPOCH_PROGRAM_KEY
        # Dispatch to join: overlaps its successor under
        # pipelining, so JSONL-only (spans.py).
        self.dispatch_span = dispatch_span = tel.tracer.start(
            "trainer.dispatch", component="trainer",
            epoch=epoch, k=1, key=key,
            parent_id=epoch_span.span_id,
        )
        # Host-blocking cost of the dispatch call itself
        # (jit trace + AOT load or XLA compile on the first
        # span of the program; ~enqueue after) — the
        # pipelined ledger bills this window separately
        # from the consume-time join so category windows
        # stay main-thread sequential (never double-counted).
        with tel.timed(
            None, "trainer.dispatch_call", epoch=epoch,
            key=key, first=not self.dispatched,
            parent_id=dispatch_span.span_id,
        ) as dispatch_call:
            self.dispatched = True
            # `key=` threads the goodput dispatch key into
            # the AOT store so cache hit/miss states line up
            # 1:1 with the compile.window accounting.
            if not self.batch_devices:
                self.batch_devices = device_ids(globs)
            self.state, losses, val_sums, gnorms, counters = (
                setup.epoch_fused(
                    self.state, *globs, *setup.val_global, key=key
                )
            )
        # Non-blocking bookkeeping: start the D2H copies of
        # everything finish() will read NOW, so by the time
        # the epoch is bookkept the bytes are already on the
        # host and device_get just unblocks.
        for buf in (
            losses, gnorms, *val_sums, *jax.tree.leaves(counters)
        ):
            try:
                buf.copy_to_host_async()
            except (AttributeError, RuntimeError):
                break
        nxt = epoch + 1
        if (
            self.prefetch_pool is not None
            and nxt < setup.target_epochs
            and may_prefetch_next(
                cfg.train.early_stop_patience, self.es_stale,
                self.pending is not None,
            )
        ):
            self.prefetched = self.prefetch_pool.submit(self.assemble, nxt)
        else:
            self.prefetched = None
        cur = _SpanInFlight(
            epoch0=epoch, n_steps=n_steps, state=self.state,
            losses=losses, val_sums=val_sums, gnorms=gnorms,
            counters=counters,
            t_dispatch=dispatch_call.t0,
            dispatch_elapsed=dispatch_call.seconds,
            dispatch_span=dispatch_span,
            epoch_span=epoch_span,
        )
        # dct: end-no-host-sync — serial mode joins its own
        # epoch here; pipelined joined the PREVIOUS one above,
        # before this dispatch, and bookkeeps it now.
        if not setup.pipelined:
            return self.consume(cur)
        # Swap FIRST: if bookkeeping the previous epoch
        # raises (health halt), the crash sweep still
        # finds the in-flight successor via `pending`.
        sp, self.pending = self.pending, cur
        return self.finish(sp, joined) if sp is not None else False

    # ------------------------------------------------------------------
    def join(self, sp: _SpanInFlight):
        """Wait for epoch ``sp``'s program: returns the join bracket.
        Once it returns the program's input state is no longer held
        by the device. Pipelined, the loop calls it right BEFORE the
        next dispatch, so it waits on ONE small output and reads
        nothing back: every device_get of a result is a round trip
        of its own on the TPU, and what stands between this return
        and the next enqueue is time the device idles."""
        with self.tel.timed(
            None, "trainer.join", epoch=sp.epoch0, k=1
        ) as join:
            # While the program still runs, collect: a full gc, which
            # also has jax drop the Python references of the buffers
            # the bookkeeping let go (the state before this one). Left
            # alone, both happen inside the next dispatch call (159 ms
            # of PythonRefManager::CollectGarbage in the profile) or
            # whenever the allocator's counters say, between this
            # join's return and the enqueue. A program that has ended
            # means the host sets the pace: nothing to hide it under.
            if self.setup.pipelined and not sp.losses.is_ready():
                gc.collect()
            jax.block_until_ready(sp.losses)
        return join

    def consume(self, sp: _SpanInFlight) -> bool:
        """Join, then bookkeep (the serial order)."""
        return self.finish(sp, self.join(sp))

    # ------------------------------------------------------------------
    def finish(self, sp: _SpanInFlight, join) -> bool:
        """All host bookkeeping of the joined epoch ``sp``. Serial
        mode runs it right after the join; pipelined mode after the
        NEXT epoch's dispatch, while that one computes on device (so
        early-stop/health decisions trail the device by at most one
        epoch — the documented trade). Returns ``stop_early``."""
        cfg, setup, tel = self.cfg, self.setup, self.tel
        e0, key = sp.epoch0, EPOCH_PROGRAM_KEY
        # The program has ended and the D2H copies were started right
        # after its dispatch: the bytes are on the host or on their
        # way. losses / gnorms are [S]; val_sums is the 6-tuple of
        # weighted sums (steps._epoch_eval_scan).
        losses_host = np.asarray(jax.device_get(sp.losses)).reshape(-1)
        gnorms_host = np.asarray(jax.device_get(sp.gnorms)).reshape(-1)
        ls, accs, c, tp, fp, fn = (
            float(v) for v in jax.device_get(sp.val_sums)
        )
        counters_host = jax.device_get(sp.counters)
        # Point the crash sweep at the epoch being bookkept: if this
        # dies, THESE are the spans still in flight (a pipelined
        # successor's live in pending).
        self.dispatch_span = sp.dispatch_span
        self.epoch_span = sp.epoch_span
        # Everything between the join and the checkpoint section
        # (tracker, events, health, heartbeat); the ledger leaves
        # it unattributed. bookkeep() closes it.
        self.bookkeep_bracket = tel.timed(
            None, "trainer.bookkeep", epoch=e0
        ).begin()
        # Fused dispatch (train + eval in one program) bills to
        # train_step; its first occurrence is the compile.
        billed = span_bill(
            setup.pipelined,
            dispatch_elapsed=sp.dispatch_elapsed, join_seconds=join.seconds,
            t_dispatch=sp.t_dispatch, join_t1=join.t1,
        )
        billed_cat = tel.ledger.add_dispatch("train_step", key, billed)
        sp.dispatch_span.end()
        # The fused program runs the validation pass inside the
        # timed window; credit it to MFU. Pipelined throughput
        # windows chain consume-to-consume (they tile the loop's
        # wall clock); serial keeps the historical start-to-join
        # window.
        epoch_stats = tel.timer.stop(
            e0, sp.n_steps * setup.global_batch, eval_samples=setup.n_val,
        )
        if setup.pipelined and billed_cat != "compile":
            # Roofline truth-up: the goodput bill above is only the
            # host-BLOCKING part of the window (the overlap the
            # pipelined mode buys); the per-program MFU join needs
            # the wall window the dispatch actually occupied — the
            # consume-to-consume timer window just closed.
            tel.ledger.amend_dispatch_window(
                key, epoch_stats.seconds - billed,
            )
        if setup.pipelined:
            tel.timer.start()
        # log_every_n_steps cadence without one Python iteration
        # per step: visit only the multiples (identical records).
        n_log = max(1, cfg.train.log_every_n_steps)
        for i in range(
            (-(self.global_step + 1)) % n_log, losses_host.size, n_log
        ):
            self.tracker.log_metrics(
                {"train_loss": float(losses_host[i])},
                step=self.global_step + i + 1,
            )
        self.global_step += losses_host.size
        # Step-trigger faults on the scan path fire at the span
        # boundary — steps inside a fused dispatch are not
        # individually interruptible from the host.
        if tel.plan.enabled:
            tel.plan.maybe_fire(
                "step", step=self.global_step,
                pre_exit=setup.state_ckptr.wait,
            )
        # Health pass over the epoch's per-step losses and grad
        # norms BEFORE any epoch bookkeeping: under a halting
        # policy the run stops here — no epoch_end, no checkpoint
        # of the diverged state. (Pipelined: the successor epoch
        # already in flight is abandoned by the raise — at most one
        # extra epoch of device work, never an extra checkpoint.)
        halt_finding = tel.health.observe_span(
            losses_host, gnorms_host,
            start_step=self.global_step - losses_host.size,
            epoch=e0, steps_per_epoch=max(1, losses_host.size),
        )
        if halt_finding is not None:
            # Close the epoch span BEFORE raising: the halted epoch
            # is exactly the one the operator opens the trace to
            # inspect.
            sp.epoch_span.end(halted=halt_finding.kind)
        HealthMonitor.raise_on(halt_finding)
        # Reference parity: the logged train_loss is the
        # EPOCH-AGGREGATED mean (Lightning epoch aggregation of
        # jobs/train_lightning_ddp.py:70), not the last batch.
        epoch_result = (
            float(losses_host.mean()) if losses_host.size else None,
            ls / c if c else float("nan"),
            accs / c if c else float("nan"),
            (tp, fp, fn),
        )
        counted = (
            counter_metrics(counters_host)
            if jax.tree.leaves(counters_host) else None
        )
        return self.bookkeep(
            sp, epoch_result, epoch_stats, losses_host.size, counted
        )

    # ------------------------------------------------------------------
    def bookkeep(self, sp, epoch_result, epoch_stats, span_updates,
                 counted=None) -> bool:
        """Every host-side consequence of a finished epoch: goodput
        report, history/tracker/event records, the early-stop update,
        and (``checkpoint``) BOTH checkpoint tiers. Shared by the scan
        path's finish (where, pipelined, it all overlaps the next
        epoch's device compute) and the eager path. ``epoch_result`` is
        ``(train_loss, val_loss, val_acc, (tp, fp, fn))``; ``counted``
        holds the model's own counters (steps.counter_metrics), which
        join the epoch's tracker metrics and its ``epoch_end`` event.
        Returns ``stop_early``, and lets go of the epoch's state: the
        next dispatch must find two states alive, not three."""
        cfg, tel = self.cfg, self.tel
        e0 = sp.epoch0
        epoch_loss, val_loss, val_acc, (tp, fp, fn) = epoch_result
        # The scan path's finish opened it right after its join;
        # the eager path enters here.
        if self.bookkeep_bracket is None:
            self.bookkeep_bracket = tel.timed(
                None, "trainer.bookkeep", epoch=e0
            ).begin()
        # Declared-vs-actual layout reconciliation, once, on the
        # FIRST epoch the jitted step produced: its output shardings
        # can drift from the declared rule layout (ZeRO-1 keeps the
        # updated params data-sharded), and silently checkpointing
        # whatever layout fell out is how a resume refusal is born.
        # The drift goes on the record LOUDLY; the device_put re-pin
        # in checkpoint() reconciles the checkpoint to the declared
        # layout.
        if not self.layout_checked:
            self.layout_checked = True
            drift = layout_mismatches(
                sp.state, self.setup.declared_shardings
            )
            if drift:
                tel.events.emit(
                    "shard", "shard.layout_mismatch",
                    leaves=len(drift),
                    reconciled=True,
                    examples=drift[:3],
                )
        # Per-epoch goodput: category deltas since the previous
        # report, logged to the tracker next to val_loss so a
        # goodput regression is queryable like an accuracy one.
        span_goodput = tel.ledger.epoch_report()
        if tel.heartbeat is not None:
            tel.heartbeat.beat(
                step=self.global_step, epoch=e0, phase="train"
            )
        epoch_rec = {
            "epoch": e0,
            "train_loss": epoch_loss if epoch_loss is not None else float("nan"),
            "val_loss": val_loss,
            "val_acc": val_acc,
        }
        epoch_metrics = {
            "train_loss_epoch": epoch_rec["train_loss"],
            "val_loss": val_loss,
            "val_acc": val_acc,
            "epoch_time": epoch_stats.seconds,
            "samples_per_sec": epoch_stats.samples_per_sec,
            "samples_per_sec_per_chip": epoch_stats.samples_per_sec_per_chip,
            "goodput_fraction": span_goodput["goodput_fraction"],
        }
        if cfg.model.num_classes == 2:
            # Positive class 1 = "rain" (the reference's label
            # encoding, jobs/preprocess.py:23-25). One-vs-rest
            # counts would mislead for num_classes > 2, so the
            # P/R/F1 surface is binary-only.
            val_precision, val_recall, val_f1 = precision_recall_f1(
                tp, fp, fn
            )
            epoch_rec["val_f1"] = val_f1
            epoch_metrics.update(
                val_precision=val_precision,
                val_recall=val_recall,
                val_f1=val_f1,
            )
        self.history.append(epoch_rec)
        if epoch_stats.mfu is not None:
            epoch_metrics["mfu"] = epoch_stats.mfu
        counted = counted or {}
        epoch_metrics.update(counted)
        self.tracker.log_metrics(epoch_metrics, step=self.global_step)
        tel.events.emit(
            "trainer", "epoch_end",
            epoch=e0,
            train_loss=epoch_rec["train_loss"],
            val_loss=val_loss, val_acc=val_acc,
            goodput_fraction=span_goodput["goodput_fraction"],
            **counted,
        )
        if tel.live_metrics is not None:
            tel.live_metrics.epoch_end(
                val_loss=val_loss,
                goodput_fraction=span_goodput["goodput_fraction"],
                samples_per_sec=epoch_stats.samples_per_sec,
                step_seconds=epoch_stats.seconds / max(1, span_updates),
                grad_norm=tel.health.last_grad_norm,
            )
        # Early stopping (monitor val_loss, min mode — the
        # companion of the reference's ModelCheckpoint
        # policy). val_loss is a globally-reduced scalar, so
        # every SPMD rank takes the same branch; a nan never
        # counts as an improvement (including as the first
        # es_best).
        stop_early = False
        if cfg.train.early_stop_patience > 0:
            self.es_best, self.es_stale, stop_early = early_stop_update(
                val_loss, self.es_best, self.es_stale,
                patience=cfg.train.early_stop_patience,
                min_delta=cfg.train.early_stop_min_delta,
            )
        tel.profiler.maybe_stop(e0)
        self.bookkeep_bracket.end()
        self.bookkeep_bracket = None
        self.checkpoint(sp, epoch_rec, stop_early)
        sp.epoch_span.end(val_loss=val_loss)
        self.consumed_through = e0 + 1
        # The next dispatch must find two states alive, not three.
        sp.state = None
        return stop_early

    # ------------------------------------------------------------------
    def checkpoint(self, sp, epoch_rec: dict, stop_early: bool) -> None:
        """Both checkpoint tiers' synchronous cost (host gather,
        deploy-tier writes, the resume snapshot's device->host copy;
        the npz write itself overlaps on a worker thread). A stack
        span: the checkpoint manager's own spans parent implicitly to
        this thread's stack top, and they belong under the
        trainer.checkpoint window. Safe under pipelining — the whole
        window is synchronous inside this bookkeeping, nothing else
        touches the stack in between."""
        setup, e0 = self.setup, sp.epoch0
        with self.tel.timed(
            "checkpoint", "trainer.checkpoint",
            epoch=e0, parent_id=sp.epoch_span.span_id,
        ):
            # Host-gather BEFORE the coordinator gate: with TP/SP
            # spanning processes this is a collective every rank
            # must join; in the common fully-addressable case only
            # the coordinator pays the device-to-host copy.
            # Pipelined: the gathered state is the NEXT epoch's live
            # input — valid because the fused step does not donate
            # it in that mode.
            if setup.params_cross_process or self.coordinator:
                with self.tel.tracer.span("trainer.gather_params"):
                    host_params = to_host(sp.state.params)
            if self.coordinator:
                ckpt_metrics = {
                    "val_loss": epoch_rec["val_loss"],
                    "val_acc": epoch_rec["val_acc"],
                }
                if "val_f1" in epoch_rec:
                    ckpt_metrics["val_f1"] = epoch_rec["val_f1"]
                setup.ckptr.update(
                    epoch=e0,
                    metrics=ckpt_metrics,
                    params=host_params,
                    meta=setup.meta,
                )

            # Every process keeps its own resume state (host-local
            # disk) plus the run facts the next run's continuation
            # semantics are decided from. The write overlaps the next
            # epoch's compute (device->host snapshot is synchronous;
            # the npz/rotation runs on a worker thread). On an early
            # stop the run is marked COMPLETE at the stop point
            # (target_epochs = epochs_completed) so a resumed run
            # EXTENDS (continuous semantics) instead of "finishing"
            # the abandoned target.
            # Re-pin to the declared layout before snapshotting (a
            # no-op for leaves already there; a collective reshard —
            # every rank calls it — for any the step's output layout
            # drifted, e.g. ZeRO-1 output params).
            setup.state_ckptr.save_async(
                jax.device_put(sp.state, setup.declared_shardings),
                meta={
                    "epochs_completed": e0 + 1,
                    "target_epochs": (
                        e0 + 1 if stop_early else setup.target_epochs
                    ),
                    # Exact resume refusal across optimizer configs
                    # whose state trees are isomorphic (ADVICE r4).
                    "optimizer": setup.opt_identity,
                },
            )

    # ------------------------------------------------------------------
    def eager_epoch(self, epoch: int) -> bool:
        """One epoch as a Python loop over jitted per-batch steps, each
        synced: the path whose faults, health halts and preemption land
        on a STEP. Returns ``stop_early``."""
        cfg, setup, tel = self.cfg, self.setup, self.tel
        accum, plan, epoch_span = setup.accum, tel.plan, self.epoch_span
        loss_sum = 0.0
        n_steps = 0
        n_updates = 0
        # Data-pipeline fault hook (eager path): poison the
        # epoch's first staged group.
        poison = plan.enabled and bool(plan.check("data", epoch=epoch))
        group: list = []
        for batch in setup.train_loader.epoch(epoch):
            group.append(batch)
            if len(group) < accum:
                continue
            with tel.timed("data_wait", "data.stage"):
                if accum > 1:
                    bx = np.concatenate([b.x for b in group])
                    by = np.concatenate([b.y for b in group])
                    bw = np.concatenate([b.weight for b in group])
                else:
                    bx, by, bw = group[0].x, group[0].y, group[0].weight
                if poison:
                    poison = False
                    bx = np.array(bx, copy=True)
                    bx[0, ...] = np.nan
                x, y, w = make_global_batch(self.mesh, bx, by, bw)
                if not self.batch_devices:
                    self.batch_devices = device_ids(x)
            group = []
            # The device_get of the loss is the step's real
            # sync point — include it in the dispatch window.
            with tel.ledger.dispatch("train_step", key="eager_step"):
                self.state, metrics = setup.train_step(self.state, x, y, w)
                m_host = jax.device_get(metrics)
                loss_host = float(m_host["train_loss"])
            self.global_step += 1
            # Step-trigger faults (`crash@...:stepN` /
            # `hang@...:stepN`): fired after the step's sync
            # point, before this step's heartbeat — a hung
            # rank stops beating exactly here, which is what
            # the stall monitor exists to see.
            if plan.enabled:
                plan.maybe_fire(
                    "step", step=self.global_step,
                    pre_exit=setup.state_ckptr.wait,
                )
            # Per-step health: a halting policy stops the
            # run MID-epoch on the eager path (epoch span
            # closed first so the halted epoch is on the
            # trace).
            finding = tel.health.observe_step(
                loss_host,
                grad_norm=float(m_host["grad_norm"]),
                step=self.global_step, epoch=epoch,
            )
            if finding is not None and finding.halt:
                epoch_span.end(halted=finding.kind)
            HealthMonitor.raise_on(finding)
            n_steps += accum
            n_updates += 1
            loss_sum += loss_host
            # Per-step liveness on the eager path (the
            # writer's min_interval throttles the I/O).
            if tel.heartbeat is not None:
                tel.heartbeat.beat(
                    step=self.global_step, epoch=epoch, phase="train",
                )
            if self.global_step % cfg.train.log_every_n_steps == 0:
                self.tracker.log_metrics(
                    {"train_loss": loss_host}, step=self.global_step
                )
            # Graceful preemption (eager path): the in-flight
            # step just finished and synced — save a resume
            # checkpoint NOW (epochs_completed = the last
            # full epoch: resume restarts this one, losing
            # under one epoch of progress) and exit
            # PREEMPTED via the entry point.
            if tel.guard.requested:
                epoch_span.end(preempted=True)
                self._preempt_exit(
                    state=jax.device_put(
                        self.state, setup.declared_shardings
                    ),
                    epochs_completed=epoch,
                    target_epochs=setup.target_epochs,
                    opt_identity=setup.opt_identity,
                )
        # A ragged tail (< accum batches) is dropped, matching
        # the scan path's group-granular drop_last.
        jax.block_until_ready(self.state.params)
        epoch_stats = tel.timer.stop(epoch, n_steps * setup.global_batch)
        epoch_loss = loss_sum / n_updates if n_updates else None

        with tel.ledger.dispatch("eval", key="eager_eval"), \
                tel.tracer.span(
                    "trainer.eval", component="trainer",
                    epoch=epoch,
                    parent_id=epoch_span.span_id,
                ):
            val_loss, val_acc, counts = self.evaluate()
        return self.bookkeep(
            _SpanInFlight(
                epoch0=epoch, n_steps=n_steps,
                state=self.state, epoch_span=epoch_span,
            ),
            (epoch_loss, val_loss, val_acc, counts),
            epoch_stats, 0,
        )

    def evaluate(self):
        """-> (val_loss, val_acc, (tp, fp, fn)) from the global sums."""
        sums = [jnp.zeros(()) for _ in range(6)]
        for batch in self.setup.val_loader.epoch(0):
            x, y, w = make_global_batch(
                self.mesh, batch.x, batch.y, batch.weight
            )
            for i, v in enumerate(self.setup.eval_step(self.state, x, y, w)):
                sums[i] = sums[i] + v
        ls, accs, c, tp, fp, fn = (float(v) for v in jax.device_get(sums))
        if c == 0:
            return float("nan"), float("nan"), (0.0, 0.0, 0.0)
        return ls / c, accs / c, (tp, fp, fn)
