"""The always-on loop: overlapped ETL / train / gate / deploy.

``AlwaysOnLoop.run()`` drives three concurrent actors over shared,
atomically-published artifacts:

- the TRAIN PUMP (this thread): back-to-back rounds of
  ``epochs_per_round`` epochs, each EXTENDING one optimizer trajectory
  (``resume`` semantics — exactly the serial trainer's continuation
  path, so per-step semantics are bit-identical by construction). In
  ``supervised`` mode every round runs under the PR 3 supervisor
  (crash/hang healing, compile-cache continuity); ``inline`` runs
  Trainer.fit in-process (benches/tests).
- the INGEST WATCHER (daemon thread): digest-polls the raw staging CSV
  and feeds the incremental ETL, so a fresh generation is published
  while training computes — the next round picks it up with zero serial
  ETL wait.
- the PROMOTION EVALUATOR (daemon thread): watches the deploy-tier best
  checkpoint and walks each new one through gate + rollout against the
  live champion — promotion happens MID-RUN, overlapped with training.

Freshness: data-arrival -> deployed-model latency is bounded by stage
latencies (round + gate + rollout), not by the episodic cycle sum.

Shutdown: ``request_stop()`` (or SIGTERM via ``jobs/loop.py``) finishes
the round in flight — mid-fit, the trainer's own PreemptionGuard turns
the signal into a durable resume snapshot — then drains both threads,
runs one final evaluator sweep over whatever the last round published,
and emits ``loop.stop``. A relaunch resumes the trajectory and the
deployed champion unchanged.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time

from dct_tpu.config import RunConfig

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def _loop_event_log(cfg: RunConfig, run_id: str):
    from dct_tpu.observability.events import EventLog

    path = (
        os.path.join(cfg.obs.events_dir, "events.jsonl")
        if cfg.obs.enabled and cfg.obs.events_dir
        else None
    )
    return EventLog(path, run_id=run_id)


def _round_config(cfg: RunConfig, epochs: int) -> RunConfig:
    """One training round's config: the loop's epoch quantum with
    resume ALWAYS on (every round extends the same trajectory)."""
    return dataclasses.replace(
        cfg,
        train=dataclasses.replace(cfg.train, epochs=epochs, resume=True),
    )


class AlwaysOnLoop:
    """The loop runtime. Construct with a full :class:`RunConfig`
    (``cfg.loop`` carries the loop knobs); ``client`` defaults to a
    :class:`~dct_tpu.deploy.local.LocalEndpointClient` persisted beside
    the packages dir so a relaunched loop sees its deployed champion."""

    def __init__(
        self,
        cfg: RunConfig,
        *,
        client=None,
        clock=time.time,
        sleep_fn=time.sleep,
        on_promotion=None,
        on_round=None,
        round_gate=None,
        extra_round_env=None,
        launcher_kwargs=None,
    ):
        from dct_tpu.observability.events import current_run_id

        self.cfg = cfg
        self.loop_cfg = cfg.loop
        self._clock = clock
        self._sleep = sleep_fn
        self._on_round = on_round
        # Multi-tenant hooks (dct_tpu.scheduler; docs/SCHEDULER.md):
        # ``round_gate`` is consulted before EVERY round — it blocks
        # until the scheduler grants this loop a round lease (False =
        # the session is draining); ``extra_round_env`` rides into every
        # supervised round's child ranks (per-tenant DCT_* overrides —
        # family, fault drills, world size); ``launcher_kwargs`` lets
        # each tenant's supervised worlds use their own coordinator
        # port. All default to the single-tenant behavior.
        self._round_gate = round_gate
        self._extra_round_env = dict(extra_round_env or {})
        self._launcher_kwargs = dict(launcher_kwargs or {})
        # Scheduler-initiated graceful ROUND preemption: set by
        # preempt_round(); the in-flight round checkpoints and ends
        # early, and the loop returns to the gate instead of draining.
        self._round_preempt = threading.Event()
        self._inline_guard = None
        self.preempted_rounds = 0
        self.run_id = cfg.obs.run_id or current_run_id()
        # Every inline fit (and the checkpoint/tracking layers under it)
        # stamps the SAME run-correlation ID: one grep spans the whole
        # always-on session.
        cfg.obs.run_id = self.run_id
        self.events = _loop_event_log(cfg, self.run_id)
        if client is None:
            from dct_tpu.deploy.local import LocalEndpointClient

            os.makedirs(self.loop_cfg.packages_dir, exist_ok=True)
            client = LocalEndpointClient(
                state_path=os.path.join(
                    self.loop_cfg.packages_dir, "endpoint_state.json"
                )
            )
        self.client = client
        from dct_tpu.continuous.evaluator import PromotionEvaluator
        from dct_tpu.continuous.ingest import (
            IngestWatcher, StreamIngestWatcher,
        )

        if cfg.stream.mode == "stream":
            self.ingest = StreamIngestWatcher(
                cfg.stream, cfg.data.processed_dir,
                poll_s=cfg.stream.poll_s,
                metrics_dir=cfg.obs.metrics_dir,
                emit=self.events.emit, clock=clock,
            )
        else:
            self.ingest = IngestWatcher(
                cfg.data.raw_csv, cfg.data.processed_dir,
                poll_s=self.loop_cfg.poll_s,
                emit=self.events.emit, clock=clock,
            )
        self.evaluator = PromotionEvaluator(
            cfg.data.models_dir, self.loop_cfg.packages_dir,
            client=self.client, endpoint=self.loop_cfg.endpoint,
            processed_dir=cfg.data.processed_dir,
            soak_s=self.loop_cfg.soak_s, poll_s=self.loop_cfg.eval_poll_s,
            run_id=self.run_id, emit=self.events.emit,
            clock=clock, sleep_fn=sleep_fn,
            on_promotion=on_promotion,
        )
        self._stop = threading.Event()
        self.stop_reason: str | None = None
        self.rounds = 0
        self.round_results: list[dict] = []
        self.train_step_wall_s = 0.0
        self.train_samples_per_sec_per_chip: list[float] = []

    # -- control --------------------------------------------------------
    def request_stop(self, reason: str = "requested") -> None:
        if self.stop_reason is None:
            self.stop_reason = reason
        self._stop.set()

    @property
    def stopping(self) -> bool:
        return self._stop.is_set()

    def preempt_round(self) -> None:
        """Gracefully preempt the IN-FLIGHT round (scheduler lease
        revocation): the trainer finishes its step and makes the resume
        snapshot durable — the PR 3 preemption contract — then the loop
        returns to the round gate with the session still alive. A no-op
        when no round is running (the flag is cleared at the next round
        start)."""
        self._round_preempt.set()
        guard = self._inline_guard
        if guard is not None:
            guard.request()

    # -- training rounds ------------------------------------------------
    def _run_round_inline(self) -> dict:
        from dct_tpu.resilience.preempt import PreemptionGuard
        from dct_tpu.train.trainer import Trainer

        cfg = _round_config(self.cfg, self.loop_cfg.epochs_per_round)
        # The loop owns the round's preemption guard so preempt_round()
        # can request a graceful stop from another thread (in the main
        # thread the guard still installs the SIGTERM handler exactly
        # as a trainer-built one would).
        guard = PreemptionGuard(clock=self._clock)
        self._inline_guard = guard
        if self._round_preempt.is_set():
            guard.request()
        try:
            try:
                result = Trainer(cfg, preempt_guard=guard).fit()
            except FileNotFoundError:
                # The ingest thread's full-rebuild swap has a two-rename
                # window with no parquet dir; a round starting inside it
                # must retry, not kill the always-on session (supervised
                # mode heals the same race via the PR 3 relauncher).
                self._sleep(0.2)
                result = Trainer(cfg, preempt_guard=guard).fit()
        finally:
            self._inline_guard = None
        cats = (result.goodput or {}).get("categories") or {}
        train_step_s = float(cats.get("train_step", 0.0))
        self.train_step_wall_s += train_step_s
        if result.steady_samples_per_sec_per_chip:
            self.train_samples_per_sec_per_chip.append(
                result.steady_samples_per_sec_per_chip
            )
        return {
            "mode": "inline",
            "epochs": self.loop_cfg.epochs_per_round,
            "val_loss": result.val_loss,
            "val_acc": result.val_acc,
            # Scheduler quota accounting: useful seconds in the lease
            # (sub-ms dispatches on toy rounds — keep the precision).
            "goodput_s": round(train_step_s, 4),
        }

    def _run_round_supervised(self) -> dict:
        from dct_tpu.launch.launcher import LocalProcessLauncher

        world_size = int(
            self._extra_round_env.get("DCT_WORLD_SIZE")
            or os.environ.get("DCT_WORLD_SIZE", "1") or 1
        )
        # The child ranks rebuild RunConfig.from_env(): every path THIS
        # loop was constructed with must travel, or a programmatic
        # RunConfig would train into env-default dirs while the
        # watcher/evaluator look at the configured ones.
        env = {
            "DCT_EPOCHS": str(self.loop_cfg.epochs_per_round),
            "DCT_RESUME": "1",
            "DCT_RUN_ID": self.run_id,
            "DCT_PROCESSED_DIR": self.cfg.data.processed_dir,
            "DCT_RAW_CSV": self.cfg.data.raw_csv,
            "DCT_MODELS_DIR": self.cfg.data.models_dir,
            "DCT_EVENTS_DIR": self.cfg.obs.events_dir,
            "DCT_HEARTBEAT_DIR": self.cfg.obs.heartbeat_dir,
            # Sharded continuous training: the mesh layout and the
            # partition-rule knobs THIS loop was configured with must
            # travel into every child rank, or a programmatic RunConfig
            # would train data-parallel while the evaluator (and the
            # checkpoints it watches) expect the sharded layout — and a
            # mid-run promotion on a sharded trajectory would judge the
            # wrong model.
            "DCT_MESH_DATA": str(self.cfg.mesh.data),
            "DCT_MESH_MODEL": str(self.cfg.mesh.model),
            "DCT_MESH_SEQ": str(self.cfg.mesh.seq),
            "DCT_MESH_PIPE": str(self.cfg.mesh.pipe),
            "DCT_SHARD_OPT_STATE": (
                "1" if self.cfg.train.shard_opt_state else "0"
            ),
            "DCT_SHARD_PARAMS": "1" if self.cfg.train.shard_params else "0",
            # Stream-mode identity: the child trainer reads etl_state
            # written by THIS loop's stream ETL, and its provenance
            # stamp (stream_offsets → checkpoint meta) must name the
            # same log + group the watcher commits against.
            "DCT_INGEST_MODE": self.cfg.stream.mode,
            "DCT_STREAM_DIR": self.cfg.stream.dir,
            "DCT_STREAM_TOPIC": self.cfg.stream.topic,
            "DCT_STREAM_GROUP": self.cfg.stream.group,
        }
        # Env-only knob: an operator's rule overrides ride along when
        # set (os.environ inheritance covers the CLI path; this covers
        # a launcher given a scrubbed env).
        if os.environ.get("DCT_SHARD_RULES"):
            env["DCT_SHARD_RULES"] = os.environ["DCT_SHARD_RULES"]
        # Per-tenant overrides (scheduler mode) ride UNDER the loop's
        # own cfg-derived keys: the tenant env shaped this loop's cfg in
        # the first place, and the cfg is the operative round contract.
        if self._extra_round_env:
            env = {**self._extra_round_env, **env}
        launcher = LocalProcessLauncher(**self._launcher_kwargs)
        res = launcher.supervise(
            [sys.executable, os.path.join(_REPO_ROOT, "jobs", "train_tpu.py")],
            world_size=world_size,
            env=env,
            max_restarts=self.cfg.resilience.max_restarts,
            backoff_s=self.cfg.resilience.restart_backoff_s,
            backoff_factor=self.cfg.resilience.restart_backoff_factor,
            jitter=self.cfg.resilience.restart_jitter,
            preempt_event=self._round_preempt,
        )
        attempts = getattr(res, "attempts", None)
        if res.restarts and "DCT_FAULT_SPEC" in self._extra_round_env:
            from dct_tpu.resilience.faults import FAULT_CRASH_EXIT

            # Per-session drill semantics, one level above the PR 3
            # supervisor's per-cycle rule: once the tenant's fault plan
            # PROVABLY fired (a rank died with the injected-crash exit
            # code) and was healed inside this round, later rounds run
            # clean — otherwise a resumed trajectory whose epoch index
            # passed the trigger would re-fire the drill every round.
            # A healed restart the drill did NOT cause (evidenced by
            # the exit codes) must not cancel a drill that has yet to
            # reach its trigger.
            fired = any(
                getattr(r, "returncode", None) == FAULT_CRASH_EXIT
                for a in (attempts or [])
                for r in getattr(a, "results", [])
            )
            if fired:
                self._extra_round_env.pop("DCT_FAULT_SPEC", None)
        rec = {
            "mode": "supervised",
            "epochs": self.loop_cfg.epochs_per_round,
            "restarts": res.restarts,
            "classification": res.classification,
        }
        if attempts:
            # Quota accounting: the successful attempt's wall is the
            # round's useful window; everything before it was healing.
            rec["goodput_s"] = round(attempts[-1].wall_seconds, 3)
        if res.classification == "preempted" and not res.success:
            if self._round_preempt.is_set() and not self._stop.is_set():
                # Scheduler lease revocation: the world checkpointed
                # and exited 75 — the round ends early, the loop lives.
                rec["preempted"] = True
                return rec
            # The supervisor itself caught SIGTERM (it forwards our
            # process signals while a round is in flight): the world
            # saved its resume snapshot — drain.
            self.request_stop("preempted")
        elif not res.success:
            self.request_stop(f"train_{res.classification}")
            raise RuntimeError(
                f"supervised round gave up: {res.classification} "
                f"(restarts={res.restarts})"
            )
        return rec

    def _budget_exhausted(self, t0: float) -> str | None:
        lc = self.loop_cfg
        if lc.max_rounds and self.rounds >= lc.max_rounds:
            return "max_rounds"
        if lc.max_wall_s and self._clock() - t0 >= lc.max_wall_s:
            return "max_wall_s"
        if lc.max_promotions and len(
            self.evaluator.promotions
        ) >= lc.max_promotions:
            return "max_promotions"
        return None

    # -- the loop --------------------------------------------------------
    def run(self) -> dict:
        """Run until a stop budget, :meth:`request_stop`, or SIGTERM;
        returns the session summary (also emitted as ``loop.stop``)."""
        from dct_tpu.resilience.preempt import PreemptedError

        lc = self.loop_cfg
        if lc.train_mode == "supervised":
            from dct_tpu import compilecache as _compilecache

            if _compilecache.enabled() and _compilecache.warm_sizes():
                # Packaging-time scorer warm-up compiles in THIS process
                # (score_gen -> warm_package_scorer) while the supervised
                # child trainer holds the chip.
                from dct_tpu.utils.chip import refuse_shared_chip

                refuse_shared_chip(
                    "DCT_LOOP_TRAIN_MODE=supervised with "
                    "DCT_COMPILE_CACHE_WARM_SIZES set (the loop parent "
                    "would compile the scorer beside its child trainer)",
                    {**os.environ, **self._extra_round_env},
                )
        t0 = self._clock()
        self.events.emit(
            "loop", "loop.start",
            train_mode=lc.train_mode,
            epochs_per_round=lc.epochs_per_round,
            endpoint=lc.endpoint,
            poll_s=lc.poll_s, eval_poll_s=lc.eval_poll_s,
            max_rounds=lc.max_rounds, max_wall_s=lc.max_wall_s,
            max_promotions=lc.max_promotions,
        )
        threads = []
        # Stream mode needs no raw_csv — the event log is the source;
        # poll mode keeps the CSV requirement (nothing to watch without
        # a staging file).
        ingest_armed = lc.poll_s > 0 and (
            self.cfg.stream.mode == "stream" or bool(self.cfg.data.raw_csv)
        )
        if ingest_armed:
            # Prime the snapshot BEFORE round 1: a cold start must not
            # race the first fit against an absent parquet.
            self.ingest.check_once()
            t = threading.Thread(
                target=self.ingest.run, args=(self._stop,),
                name="loop-ingest", daemon=True,
            )
            t.start()
            threads.append(t)
        if lc.eval_poll_s > 0:
            t = threading.Thread(
                target=self.evaluator.run, args=(self._stop,),
                name="loop-evaluator", daemon=True,
            )
            t.start()
            threads.append(t)
        if ingest_armed and self.cfg.stream.mode == "stream":
            # Stream cold start: the topic may not exist yet (the
            # producer is its own process and can come up later), so
            # unlike the CSV path there may be NOTHING to prime. Idle
            # at the stream cadence until the first generation
            # publishes instead of crashing round 1 on an absent
            # parquet; the wall/stop budgets still bound the wait.
            from dct_tpu.etl.preprocess import read_etl_state

            while (
                not self._stop.is_set()
                and self._budget_exhausted(t0) is None
                and not read_etl_state(
                    self.cfg.data.processed_dir
                ).get("generation")
            ):
                self._stop.wait(max(self.cfg.stream.poll_s, 0.05))
        error: str | None = None
        try:
            while not self._stop.is_set():
                reason = self._budget_exhausted(t0)
                if reason is not None:
                    self.request_stop(reason)
                    break
                if self._round_gate is not None:
                    # Scheduler mode: block until a round lease is
                    # granted. False = the session is draining (the
                    # scheduler already called request_stop; the
                    # fallback reason covers a gate closing first).
                    try:
                        granted = self._round_gate()
                    except Exception as e:  # noqa: BLE001 — a broken gate stops THIS loop only
                        error = f"{type(e).__name__}: {e}"[:300]
                        self.events.emit(
                            "loop", "loop.error", where="round_gate",
                            error=error,
                        )
                        self.request_stop("gate_error")
                        break
                    if not granted:
                        self.request_stop("gate_closed")
                        break
                self._round_preempt.clear()
                round_t0 = self._clock()
                preempted_round = False
                try:
                    if lc.train_mode == "inline":
                        rec = self._run_round_inline()
                    else:
                        rec = self._run_round_supervised()
                    preempted_round = bool(rec.get("preempted"))
                except PreemptedError:
                    if (
                        self._round_preempt.is_set()
                        and not self._stop.is_set()
                    ):
                        # Scheduler lease revocation (inline round): the
                        # trainer saved a durable resume snapshot — the
                        # round ends early, the loop returns to the
                        # gate. Progress is retained by the resume.
                        rec = {
                            "mode": lc.train_mode,
                            "epochs": lc.epochs_per_round,
                            "preempted": True,
                        }
                        preempted_round = True
                    else:
                        # Inline round honored SIGTERM: resume snapshot
                        # is durable; drain and exit clean.
                        self.request_stop("preempted")
                        break
                except Exception as e:  # noqa: BLE001 — name it, then stop cleanly
                    error = f"{type(e).__name__}: {e}"[:300]
                    self.events.emit(
                        "loop", "loop.error", where="train", error=error
                    )
                    self.request_stop("train_error")
                    if self._on_round is not None:
                        # The scheduler must still release the lease a
                        # failed round was holding.
                        try:
                            self._on_round({"error": error})
                        except Exception:  # noqa: BLE001 — a bad callback must not mask the error
                            pass
                    break
                rec["round_wall_s"] = round(self._clock() - round_t0, 3)
                self.rounds += 1
                if preempted_round:
                    self.preempted_rounds += 1
                rec["round"] = self.rounds
                self.round_results.append(rec)
                self.events.emit("loop", "loop.round", **rec)
                if self._on_round is not None:
                    try:
                        self._on_round(rec)
                    except Exception:  # noqa: BLE001 — a bad callback must not kill the loop
                        pass
        finally:
            self.request_stop("completed")
            for t in threads:
                t.join(timeout=max(60.0, 4 * lc.soak_s + 30.0))
            if error is None and not any(t.is_alive() for t in threads):
                # Drain semantics: whatever the final round published
                # still gets one evaluator pass (bounded: one gate +
                # rollout) — a SIGTERM between checkpoint and promotion
                # must not strand a better model undeployed. Skipped if
                # a join timed out: the evaluator thread may still be
                # mid-pass, and a concurrent second rollout against the
                # same endpoint is worse than a missed final sweep.
                self.evaluator.check_once()
            summary = self.summary(wall_s=self._clock() - t0, error=error)
            self.events.emit("loop", "loop.stop", **summary)
            self.events.close()
        return summary

    def summary(self, *, wall_s: float, error: str | None = None) -> dict:
        promos = self.evaluator.promotions
        fresh = [
            p["freshness_s"] for p in promos
            if p.get("freshness_s") is not None
        ]
        sps = self.train_samples_per_sec_per_chip
        return {
            "reason": self.stop_reason,
            "error": error,
            "rounds": self.rounds,
            "preempted_rounds": self.preempted_rounds,
            "wall_s": round(wall_s, 3),
            "ingested_generations": self.ingest.processed,
            "promotions": len(promos),
            "held": len(self.evaluator.held),
            "evaluator_errors": self.evaluator.errors,
            "ingest_errors": self.ingest.errors,
            "freshness_s": [round(f, 3) for f in fresh],
            "mean_freshness_s": (
                round(sum(fresh) / len(fresh), 3) if fresh else None
            ),
            # Platform goodput: train-step wall as a fraction of loop
            # wall (inline rounds; supervised rounds account in their
            # own rank events).
            "train_step_wall_s": round(self.train_step_wall_s, 3),
            "goodput": (
                round(self.train_step_wall_s / wall_s, 4)
                if wall_s > 0 else None
            ),
            "train_samples_per_sec_per_chip": (
                round(sum(sps) / len(sps), 1) if sps else None
            ),
        }
