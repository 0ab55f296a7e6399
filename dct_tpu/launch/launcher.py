"""SPMD launch: the orchestrator-side process-control machinery.

The reference's launcher is an Airflow BashOperator that ``docker exec``s
the identical training script into both trainer containers, backgrounded,
staggered by ``sleep 5``, then ``wait``s on both PIDs and requires exit 0
from each (dags/2_pytorch_training.py:49-78), preceded by a zombie purge
(``pkill -9 -f train_lightning_ddp.py || true``, :29-38) and an
import-healthcheck (:40-46).

Here the same semantics are generated for any host-access mechanism, so
the training DAG's launch block is one call. :class:`LocalProcessLauncher`
applies identical semantics to local subprocesses, giving the multi-process
CPU rig that replaces the reference's two-container test bed (SURVEY §4).

Exec-template quoting contract: ``{cmd}`` is substituted with ONE
shlex-quoted token holding the full shell command, so the template must
hand it to something that parses a shell command string:

- ``ssh {host} {cmd}``                 — sshd's remote shell re-parses the
  joined argv, recovering the original command (this is why the token must
  be quoted exactly once: ssh flattens one quoting level);
- ``docker exec {host} bash -c {cmd}`` — docker passes argv through
  verbatim, so an explicit ``bash -c`` consumes the token;
- ``bash -c {cmd}``                    — in-place execution (tests).
"""

from __future__ import annotations

import json
import os
import shlex
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field

from dct_tpu.observability.events import (
    EventLog,
    mint_run_id,
    observability_enabled,
)
from dct_tpu.observability.heartbeat import HeartbeatMonitor, heartbeat_path
from dct_tpu.resilience.supervisor import (
    EXIT_INFRA_CLEANUP,
    EXIT_INFRA_HEALTHCHECK,
    RestartPolicy,
    classify_failure,
)
from dct_tpu.observability.spans import (
    SpanRecorder,
    span_file_name,
    spans_dir_from,
)


def _launcher_event_log(env: dict) -> EventLog:
    """The orchestrator-side event log, built from the SAME env the ranks
    will inherit so launcher and rank records land in one file under one
    run-correlation ID (rank=None marks orchestrator records)."""
    events_dir = env.get("DCT_EVENTS_DIR", "logs/events")
    enabled = observability_enabled(env) and bool(events_dir)
    return EventLog(
        os.path.join(events_dir, "events.jsonl") if enabled else None,
        run_id=env["DCT_RUN_ID"],
        rank=None,
    )


def _launcher_metrics_publisher(env: dict, proc: str):
    """Metrics-plane publisher for the orchestrator side (None when the
    plane is unarmed): the launcher/supervisor contributes per-rank
    progress-age gauges and restart counters to the same snapshot dir
    the serving pool and trainer publish into, so one ``/metrics``
    scrape sees them all (docs/OBSERVABILITY.md "Metrics plane")."""
    metrics_dir = env.get("DCT_METRICS_DIR") or ""
    if not metrics_dir or not observability_enabled(env):
        return None
    from dct_tpu.observability.aggregate import SnapshotPublisher
    from dct_tpu.observability.metrics import MetricsRegistry

    try:
        interval = float(env.get("DCT_METRICS_PUBLISH_S") or 2.0)
    except ValueError:
        interval = 2.0
    return SnapshotPublisher(
        MetricsRegistry(), metrics_dir, proc=proc, interval_s=interval
    )


def _launcher_span_recorder(env: dict) -> SpanRecorder:
    """Orchestrator-side span recorder over the same env the ranks
    inherit: the launch span and every rank's trainer spans share one
    trace (trace_id = the run-correlation ID)."""
    directory = (
        spans_dir_from(
            env.get("DCT_EVENTS_DIR", "logs/events"),
            env.get("DCT_SPANS_DIR", ""),
        )
        if observability_enabled(env)
        else None
    )
    rec = SpanRecorder(
        os.path.join(directory, span_file_name(None)) if directory else None,
        trace_id=env["DCT_RUN_ID"],
        rank=None,
    )
    # Parent from the SAME merged env the ranks inherit, not bare
    # os.environ: a caller passing DCT_SPAN_ID through launch(env=...)
    # (a DAG task parenting its launch) must see the launch span attach
    # under it.
    from dct_tpu.observability.spans import env_parent_span_id

    rec.root_parent = env_parent_span_id(env)
    return rec


def remote_command(exec_template: str, host: str, command: str) -> str:
    """Wrap ``command`` for one host per the quoting contract above:
    the raw command becomes a single quoted ``{cmd}`` token."""
    return exec_template.format(host=host, cmd=shlex.quote(command))


def build_zombie_cleanup_script(
    hosts: list[str],
    *,
    exec_template: str = "ssh {host} {cmd}",
    pattern: str = "train_tpu.py",
    settle_seconds: int = 2,
) -> str:
    """Kill stale ranks on every host before relaunch (the reference's
    rendezvous-port hygiene, dags/2_pytorch_training.py:29-38).

    "No zombies matched" is success (the remote ``|| true``), but a dead
    exec TRANSPORT (ssh/docker unreachable) exits ``EXIT_INFRA_CLEANUP``
    — distinct from a training failure, so the supervisor/operator sees
    "the control plane is broken", not "training crashed again".
    """
    lines = ["echo 'Cleaning up zombie training processes...'"]
    # Bracket the first char so the pattern cannot match the shell that
    # carries it (pkill -f would otherwise kill its own wrapping bash).
    safe_pattern = f"[{pattern[0]}]{pattern[1:]}" if pattern else pattern
    for host in hosts:
        kill = f"pkill -9 -f {shlex.quote(safe_pattern)} || true"
        lines.append(
            remote_command(exec_template, host, kill)
            + " || { echo "
            + shlex.quote(f"Cleanup exec transport failed on {host}")
            + f"; exit {EXIT_INFRA_CLEANUP}; }}"
        )
    lines.append(f"sleep {settle_seconds}")
    lines.append("echo 'Cleanup complete'")
    return "\n".join(lines)


def build_healthcheck_script(
    hosts: list[str],
    *,
    exec_template: str = "ssh {host} {cmd}",
    check_command: str = "python3 -c 'import jax; print(jax.devices())'",
) -> str:
    """Verify every host's runtime imports and sees its accelerators
    (analog of the per-node ``import torch`` check,
    dags/2_pytorch_training.py:40-46). ``set -e`` makes any host's failed
    check fail the whole task — without it bash returns the LAST command's
    status and a broken host would slip through to the SPMD launch.

    A failed check exits ``EXIT_INFRA_HEALTHCHECK`` (not the remote
    command's arbitrary status): the supervisor's classifier must see
    "a host is unhealthy" as infra, never as a training crash to burn
    restart budget on.

    Each check takes its host's chips for a moment (``jax.devices()``),
    and a chip belongs to one process at a time: every check runs in the
    FOREGROUND, one after another, so all of them have exited before
    this script returns and the launch block starts rank 0.
    """
    lines = ["set -e"]
    for host in hosts:
        lines.append(f"echo 'Checking {host}...'")
        lines.append(
            remote_command(exec_template, host, check_command)
            + " || { echo "
            + shlex.quote(f"Healthcheck failed on {host}")
            + f"; exit {EXIT_INFRA_HEALTHCHECK}; }}"
        )
    lines.append("echo 'All hosts healthy'")
    return "\n".join(lines)


def build_spmd_launch_script(
    hosts: list[str],
    command: str,
    *,
    exec_template: str = "ssh {host} {cmd}",
    coordinator_port: int = 29500,
    stagger_seconds: int = 5,
    extra_env: dict[str, str] | None = None,
    fail_fast_poll_seconds: int = 2,
    run_id: str | None = None,
) -> str:
    """Generate the launch block: same program on every host, coordinator
    env injected, staggered start, fail-fast join, exit-code conjunction.

    Every rank additionally receives the same ``DCT_RUN_ID``
    run-correlation ID, so one grep over the structured event log
    reconstructs the whole launch. The ID is resolved when the script
    RUNS, not when it is built (``run_id`` arg pins it; otherwise the
    runtime environment's ``DCT_RUN_ID``, else minted by the script) —
    Airflow renders BashOperator commands at DAG-parse time, and a
    parse-time mint would be shared by every run of the parsed script.
    The value is spliced into each rank's env as an unquoted ``$RUN_ID``
    expansion OUTSIDE the shlex-quoted command token, so it expands on
    the LAUNCHER host for every exec template (ssh flattens one quoting
    level; the remote shell must never see the bare variable).

    Host 0 is the coordinator (MASTER_ADDR), mirroring the reference env
    contract (docker-compose.yml:121-124) so the same script works under
    both topologies.

    Fail-fast join: the reference ``wait``s each rank sequentially
    (dags/2_pytorch_training.py:62-75), so a dead worker leaves the
    coordinator blocked in a collective until the 3-hour task timeout.
    Here a polling loop reaps ranks as they exit and, on the first nonzero
    exit, terminates the remaining launch processes — the failure surfaces
    in seconds. (For ssh templates the kill stops the local client; any
    orphaned remote rank is covered by the next run's zombie purge, the
    same hygiene model as the reference.)
    """
    world = len(hosts)
    master = hosts[0]
    # Placeholder protocol: the env prefix carries a token that survives
    # shlex.quote unchanged; after quoting, the token is replaced by
    # '"$RUN_ID"' — closing the single-quoted command token, splicing a
    # double-quoted launcher-side expansion, and reopening it. Every
    # exec template therefore ships the RESOLVED id, never the variable.
    _PH = "__DCT_RUN_ID__"
    lines = [
        f"echo 'Launching SPMD training on {world} hosts...'",
        (
            f"RUN_ID={shlex.quote(run_id)}"
            if run_id
            else 'RUN_ID="${DCT_RUN_ID:-dct-$(date +%s)-$$}"'
        ),
        # The splice below expands $RUN_ID OUTSIDE the quoted command
        # token and the remote shell re-parses the result, so the value
        # MUST be shell-inert: strip to the id alphabet (an operator's
        # 'run 2026' or a $(...) would otherwise split or execute on
        # every host), and re-mint if nothing survives.
        "RUN_ID=\"$(printf %s \"$RUN_ID\" | tr -cd 'A-Za-z0-9._-')\"",
        'RUN_ID="${RUN_ID:-dct-$$}"',
        'echo "run_id=$RUN_ID"',
        "set -m",
    ]
    for rank, host in enumerate(hosts):
        env = {
            "MASTER_ADDR": master,
            "MASTER_PORT": str(coordinator_port),
            "NODE_RANK": str(rank),
            "WORLD_SIZE": str(world),
            "DCT_RUN_ID": _PH,
            **(extra_env or {}),
        }
        env_prefix = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
        full = f"{env_prefix} {command}"
        launch_line = remote_command(exec_template, host, full).replace(
            _PH, "'\"$RUN_ID\"'"
        )
        lines.append(launch_line + " &")
        lines.append(f"PID{rank}=$!")
        lines.append(f"DONE{rank}=0")
        if rank == 0 and world > 1:
            lines.append(f"sleep {stagger_seconds}")
    ranks = range(world)
    # set -m gives each background job its own process group (PGID = leader
    # PID); kill the GROUP — a plain kill of the wrapper shell is deferred
    # by bash until its foreground child finishes, leaving the actual rank
    # running. Reaped ranks are skipped via their DONE flag.
    lines.append("kill_survivors() {")
    for s in ranks:
        lines.append(
            f'  [ "$DONE{s}" -eq 0 ] && kill -- "-$PID{s}" 2>/dev/null'
        )
    lines.append("  :")
    lines.append("}")
    lines.append("FAILED=0")
    lines.append(f"REMAINING={world}")
    lines.append('while [ "$REMAINING" -gt 0 ]; do')
    for r in ranks:
        lines.extend([
            f'  if [ "$DONE{r}" -eq 0 ] && ! kill -0 "$PID{r}" 2>/dev/null; then',
            f'    wait "$PID{r}"; RC{r}=$?; DONE{r}=1; '
            f"REMAINING=$((REMAINING-1))",
            f'    echo "Rank {r} exited with code $RC{r}"',
            f'    if [ "$RC{r}" -ne 0 ] && [ "$FAILED" -eq 0 ]; then',
            "      FAILED=1",
            '      echo "Rank failure detected - terminating remaining ranks (fail-fast)"',
            "      kill_survivors",
            "    fi",
            "  fi",
        ])
    lines.append(
        f'  [ "$REMAINING" -gt 0 ] && sleep {fail_fast_poll_seconds}'
    )
    lines.append("done")
    conj = " && ".join(f'[ "$RC{r}" -eq 0 ]' for r in ranks)
    # Exit-code classification (resilience.supervisor contract): a rank
    # that exited 75 (EXIT_PREEMPTED) was preempted gracefully; 143 is
    # our own fail-fast SIGTERM (kill_survivors) reaping survivors of
    # the first failure. 137 (SIGKILL) is NOT ours — this script never
    # escalates past SIGTERM — so an OOM-killed rank counts as a hard
    # failure. Only when NO rank failed hard does the script itself exit
    # 75, so Airflow retries (the script-level supervisor) see "resume
    # me" distinctly from "training crashed".
    lines.append("HARD=0; PRE=0")
    for r in ranks:
        lines.append(
            f'case "$RC{r}" in 0|143) ;; 75) PRE=1 ;; *) HARD=1 ;; esac'
        )
    lines.append(
        f'if {conj}; then echo "All {world} ranks finished successfully"; '
        f'else echo "Training failed: rank exit codes: '
        + " ".join(f"$RC{r}" for r in ranks)
        + '"; '
        + 'if [ "$HARD" -eq 0 ] && [ "$PRE" -eq 1 ]; '
        + 'then echo "World preempted - resumable"; exit 75; fi; '
        + "exit 1; fi"
    )
    return "\n".join(lines)


def _kill_group(p: "subprocess.Popen") -> None:
    """SIGKILL a rank's whole process group (falls back to the direct
    child if the group is already gone)."""
    try:
        os.killpg(p.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        p.kill()


def _term_group(p: "subprocess.Popen") -> None:
    """SIGTERM a rank's whole process group — the graceful half of the
    SIGTERM -> SIGKILL escalation: a healthy rank's PreemptionGuard gets
    its chance to save a resume checkpoint and exit EXIT_PREEMPTED; a
    wedged one is SIGKILLed when the grace window expires."""
    try:
        os.killpg(p.pid, signal.SIGTERM)
    except (ProcessLookupError, PermissionError, OSError):
        p.terminate()


@dataclass
class RankResult:
    rank: int
    returncode: int


@dataclass
class AttemptRecord:
    """One supervised launch attempt and how it ended."""

    attempt: int
    results: list
    classification: str
    wall_seconds: float


@dataclass
class SuperviseResult:
    """Outcome of :meth:`LocalProcessLauncher.supervise`."""

    results: list
    attempts: list = field(default_factory=list)
    restarts: int = 0
    success: bool = False
    classification: str = "crash"


class SupervisorTerminated(Exception):
    """The supervisor itself received SIGTERM/SIGINT: raised from the
    signal handler so launch()'s finally-block teardown runs — the
    ranks live in their own sessions (start_new_session), so a
    supervisor that dies on the default signal disposition would orphan
    them past any task-level process-group kill."""


class LocalProcessLauncher:
    """The two-container rig, without containers: N local processes running
    the identical SPMD program with coordinator env, staggered start, join,
    and exit-code conjunction.

    Observability duties (the launcher already babysits the ranks, so it
    is the natural monitor): it MINTS the run-correlation ID and passes
    it to every rank via ``DCT_RUN_ID``; it emits launcher events
    (launch_start / rank_exit / rank_stalled / launch_end) into the same
    event log the ranks write; and while joined on the ranks it scans
    their heartbeat files, REPORTING stalled/dead/straggling ranks and
    progress skew instead of waiting silently. Detection never kills: a
    stalled-but-alive rank may be paying a long compile — the operator
    signal is the point, fail-fast on real exits stays the enforcement.
    """

    def __init__(
        self,
        *,
        coordinator_port: int = 29511,
        stagger_seconds: float = 1.0,
        timeout: float = 600.0,
        fail_fast: bool = True,
        poll_seconds: float = 0.2,
        heartbeat_dir: str | None = None,
        heartbeat_stall_seconds: float = 120.0,
        heartbeat_scan_seconds: float = 5.0,
        preempt_grace_s: float = 15.0,
        stall_kill: bool = False,
    ):
        self.coordinator_port = coordinator_port
        self.stagger_seconds = stagger_seconds
        self.timeout = timeout
        self.fail_fast = fail_fast
        self.poll_seconds = poll_seconds
        self.heartbeat_dir = heartbeat_dir
        self.heartbeat_stall_seconds = heartbeat_stall_seconds
        self.heartbeat_scan_seconds = heartbeat_scan_seconds
        # SIGTERM -> SIGKILL escalation window: how long a rank being
        # torn down (fail-fast, stall-kill) gets to honor its
        # PreemptionGuard (finish the step, save, exit 75) before the
        # group is SIGKILLed.
        self.preempt_grace_s = preempt_grace_s
        # Kill the world when a rank's heartbeat goes stalled/missing
        # (supervision mode): a PID-alive rank wedged in a collective
        # blocks every peer; detection-only reporting stays the default.
        self.stall_kill = stall_kill
        # What the last launch() observed, for supervise()'s classifier.
        self._stall_killed = False
        self._timed_out = False

    def cleanup_zombies(self, pattern: str) -> None:
        subprocess.run(["pkill", "-9", "-f", pattern], check=False)
        time.sleep(0.5)

    def launch(
        self,
        argv: list[str],
        *,
        world_size: int,
        env: dict[str, str] | None = None,
        preempt_event=None,
    ) -> list[RankResult]:
        procs: list[subprocess.Popen] = []
        self._stall_killed = False
        self._timed_out = False
        base_env = dict(os.environ)
        base_env.update(env or {})
        # Correlation: one run ID for the whole launch, minted here (the
        # launcher is the minter of record) unless the caller/DAG already
        # chose one — every rank inherits it via env.
        base_env["DCT_RUN_ID"] = base_env.get("DCT_RUN_ID") or mint_run_id()
        if self.heartbeat_dir:
            base_env.setdefault("DCT_HEARTBEAT_DIR", self.heartbeat_dir)
        events = _launcher_event_log(base_env)
        events.emit(
            "launcher", "launch_start",
            world_size=world_size, argv=list(argv),
        )
        # Trace: one span for the whole launch; every rank gets its own
        # child span (spawn -> reap), and DCT_SPAN_ID hands the launch
        # span to the ranks so their trainer.fit spans nest under it
        # across the process boundary.
        tracer = _launcher_span_recorder(base_env)
        launch_span = tracer.open(
            "launcher.launch", component="launcher", world_size=world_size,
        )
        rank_spans: dict[int, object] = {}
        # Default to the SAME dir ObservabilityConfig defaults the ranks
        # to (they inherit this cwd): out of the box the monitor is
        # ARMED, not waiting for an operator to remember a knob.
        hb_dir = (
            base_env.get("DCT_HEARTBEAT_DIR")
            or self.heartbeat_dir
            or "logs/heartbeats"
        )
        # Gated on the SAME observability switch the ranks honor: with
        # DCT_OBSERVABILITY off no rank writes beats, and an ungated
        # monitor would report every healthy rank missing.
        monitor = (
            HeartbeatMonitor(
                hb_dir,
                world_size,
                stall_seconds=self.heartbeat_stall_seconds,
                run_id=base_env["DCT_RUN_ID"],
            )
            if hb_dir and observability_enabled(base_env)
            else None
        )
        # Metrics plane: per-rank PROGRESS age (seconds since step/epoch
        # last advanced — write age alone cannot tell a beating-but-
        # wedged rank from a healthy one) published as a gauge next to
        # the serving pool's snapshots.
        metrics_pub = (
            _launcher_metrics_publisher(
                base_env, f"launcher-{os.getpid()}"
            )
            if monitor is not None else None
        )
        progress_gauge = (
            metrics_pub.registry.gauge(
                "dct_rank_progress_age_seconds",
                "Seconds since each rank's heartbeat (step, epoch) last "
                "advanced (progress age, not write age).",
                agg="max",
            )
            if metrics_pub is not None else None
        )
        # Telemetry history plane (ISSUE 17): the launcher is the
        # training fleet's natural watcher — when DCT_TS_DIR arms the
        # store, it runs the anomaly detector over the ranks' live
        # metric history (loss spikes, step-time regressions, goodput
        # dips) and assembles incident bundles. None when unarmed.
        anomaly_monitor = None
        if metrics_pub is not None:
            from dct_tpu.observability import detect as _detect

            anomaly_monitor = _detect.arm_from_env(
                registry=metrics_pub.registry, emit=events.emit,
            )
        flagged: set[tuple[int, str]] = set()
        last_scan = 0.0
        try:
            for rank in range(world_size):
                rank_env = dict(base_env)
                rank_env.update(
                    MASTER_ADDR="127.0.0.1",
                    MASTER_PORT=str(self.coordinator_port),
                    NODE_RANK=str(rank),
                    WORLD_SIZE=str(world_size),
                    DCT_SPAN_ID=launch_span.span_id,
                )
                rank_spans[rank] = tracer.start(
                    "launcher.rank", component="launcher",
                    parent_id=launch_span.span_id, launched_rank=rank,
                )
                # Own process group per rank so a fail-fast kill reaches the
                # whole rank tree, not just the direct child.
                procs.append(
                    subprocess.Popen(argv, env=rank_env, start_new_session=True)
                )
                if rank == 0 and world_size > 1:
                    time.sleep(self.stagger_seconds)
            # Poll-based join: reap ranks as they exit; with fail_fast, the
            # first nonzero exit kills the survivors immediately instead of
            # leaving them blocked in a collective until the timeout (the
            # reference's sequential wait has exactly that failure mode,
            # dags/2_pytorch_training.py:62-75).
            codes: dict[int, int] = {}
            killed = False
            kill_deadline = None
            escalated = False

            def _teardown_world() -> None:
                """Graceful half of the escalation: SIGTERM every
                surviving group so healthy ranks can save-and-exit-75;
                the poll loop SIGKILLs whatever outlives the grace."""
                nonlocal killed, kill_deadline
                killed = True
                kill_deadline = time.monotonic() + self.preempt_grace_s
                for q in procs:
                    if q.poll() is None:
                        _term_group(q)

            deadline = time.monotonic() + self.timeout
            while len(codes) < world_size and time.monotonic() < deadline:
                progressed = False
                for rank, p in enumerate(procs):
                    if rank in codes:
                        continue
                    rc = p.poll()
                    if rc is None:
                        continue
                    codes[rank] = rc
                    progressed = True
                    rank_spans[rank].end(returncode=rc)
                    events.emit(
                        "launcher", "rank_exit", exited_rank=rank,
                        returncode=rc,
                    )
                    if rc != 0 and self.fail_fast and not killed:
                        _teardown_world()
                if (
                    preempt_event is not None
                    and preempt_event.is_set()
                    and not killed
                ):
                    # Cooperative preemption (the multi-tenant
                    # scheduler's lease revocation): the graceful half
                    # of the escalation — every rank's PreemptionGuard
                    # saves-and-exits-75, classifying the world
                    # "preempted" with checkpointed progress intact.
                    _teardown_world()
                if killed and not escalated and (
                    time.monotonic() >= kill_deadline
                ):
                    escalated = True
                    for q in procs:
                        if q.poll() is None:
                            _kill_group(q)
                # Liveness beyond PIDs: a rank can be alive and wedged in
                # a collective. Scan heartbeats on a slow cadence and
                # NAME stalled/missing ranks while still joined.
                if monitor is not None and (
                    time.monotonic() - last_scan >= self.heartbeat_scan_seconds
                ):
                    last_scan = time.monotonic()
                    wedged = self._flag_heartbeats(
                        monitor, codes, flagged, events,
                        progress_gauge=progress_gauge,
                        metrics_pub=metrics_pub,
                    )
                    if wedged and self.stall_kill and not killed:
                        # Supervision mode: a stalled rank blocks every
                        # peer's collectives — kill the world (escalating)
                        # and let the supervisor relaunch from checkpoint.
                        self._stall_killed = True
                        events.emit(
                            "launcher", "restart.stall_kill",
                            stalled_ranks=wedged,
                            stall_seconds=self.heartbeat_stall_seconds,
                        )
                        print(
                            f"[launcher] stall-kill: ranks {wedged} wedged "
                            "— terminating the world for relaunch",
                            file=sys.stderr, flush=True,
                        )
                        _teardown_world()
                if not progressed and len(codes) < world_size:
                    time.sleep(self.poll_seconds)
            for rank, p in enumerate(procs):
                if rank not in codes:  # deadline hit
                    # Final poll: a rank that finished during the last
                    # sleep window keeps its real exit code (and is NOT
                    # labelled timed-out — trace and event log agree).
                    rc = p.poll()
                    timed_out = rc is None
                    if timed_out:
                        self._timed_out = True
                        _kill_group(p)
                        p.wait()
                        rc = -signal.SIGKILL
                        events.emit(
                            "launcher", "rank_timeout_killed",
                            exited_rank=rank,
                        )
                    codes[rank] = rc
                    rank_spans[rank].end(returncode=rc, timeout=timed_out)
            skew = monitor.report() if monitor is not None else {}
            events.emit(
                "launcher", "launch_end",
                returncodes=[codes[r] for r in range(world_size)],
                success=all(codes[r] == 0 for r in range(world_size)),
                **{k: skew[k] for k in ("epoch_skew", "step_skew") if k in skew},
            )
            launch_span.end(
                success=all(codes[r] == 0 for r in range(world_size)),
            )
            return [
                RankResult(rank=r, returncode=codes[r])
                for r in range(world_size)
            ]
        finally:
            if anomaly_monitor is not None:
                anomaly_monitor.close()
            if metrics_pub is not None:
                # Progress age is a LIVE signal: retire the snapshot so
                # a post-run scrape never reads a frozen age as current.
                metrics_pub.close()
            live = [p for p in procs if p.poll() is None]
            if live:
                # Exception-path teardown (supervisor terminated, monitor
                # error) uses the SAME SIGTERM -> grace -> SIGKILL
                # escalation as fail-fast: a healthy rank's
                # PreemptionGuard gets its chance to save-and-exit-75
                # before the hard kill. On the normal path every rank is
                # already reaped and this costs nothing.
                for p in live:
                    _term_group(p)
                grace_deadline = time.monotonic() + self.preempt_grace_s
                while any(p.poll() is None for p in live) and (
                    time.monotonic() < grace_deadline
                ):
                    time.sleep(0.1)
                for p in live:
                    if p.poll() is None:
                        _kill_group(p)
                    # Reap: nobody polls again after this, and an
                    # unreaped kill leaves a zombie per rank in a
                    # long-lived supervisor.
                    try:
                        p.wait(timeout=5)
                    except (subprocess.TimeoutExpired, OSError):
                        pass
            # A launch that raised (Popen failure, monitor error) must
            # still record its spans — end() is idempotent, so on the
            # success path (everything already ended) this is a no-op.
            for sp in rank_spans.values():
                sp.end(error=True)
            launch_span.end(error=True)

    def _flag_heartbeats(
        self,
        monitor: HeartbeatMonitor,
        codes: dict[int, int],
        flagged: set,
        events: EventLog,
        progress_gauge=None,
        metrics_pub=None,
    ) -> list[int]:
        """One monitor pass: warn (stderr + event) once per (rank, state)
        for stalled/missing ranks that have not exited, and once per new
        epoch-skew level when ranks visibly diverge. Returns the ranks
        currently stalled/missing (alive but not progressing) so a
        stall-kill supervisor can act on them."""
        wedged: list[int] = []
        statuses = monitor.scan()
        if progress_gauge is not None:
            for s in statuses:
                # "done" ranks and reaped ranks stop advancing by
                # design — publishing their ever-growing age would page
                # on a healthy completion (report() excludes them from
                # max_progress_age_seconds for the same reason).
                if (
                    s.progress_age_seconds is not None
                    and s.state != "done"
                    and s.rank not in codes
                ):
                    progress_gauge.set(
                        round(s.progress_age_seconds, 3),
                        {"rank": s.rank},
                    )
            if metrics_pub is not None:
                metrics_pub.maybe_publish()
        for s in statuses:
            if s.rank in codes or s.state not in ("stalled", "missing"):
                continue
            wedged.append(s.rank)
            key = (s.rank, s.state)
            if key in flagged:
                continue
            flagged.add(key)
            age = f" (last beat {s.age_seconds:.0f}s ago)" if s.age_seconds else ""
            print(
                f"[launcher] rank {s.rank} heartbeat {s.state}{age} — "
                "process alive but not progressing"
                if s.state == "stalled"
                else f"[launcher] rank {s.rank} has written no heartbeat",
                file=sys.stderr, flush=True,
            )
            events.emit(
                "launcher", f"rank_{s.state}", flagged_rank=s.rank,
                age_seconds=s.age_seconds, step=s.step, epoch=s.epoch,
            )
        skew = monitor.skew(statuses)
        if skew["epoch_skew"] > 1 and ("skew", skew["epoch_skew"]) not in flagged:
            flagged.add(("skew", skew["epoch_skew"]))
            print(
                f"[launcher] straggler skew: ranks span {skew['epoch_skew']}"
                f" epochs / {skew['step_skew']} steps",
                file=sys.stderr, flush=True,
            )
            events.emit("launcher", "rank_skew", **skew)
        return wedged

    # ------------------------------------------------------------------
    def supervise(
        self,
        argv: list[str],
        *,
        world_size: int,
        env: dict[str, str] | None = None,
        max_restarts: int = 2,
        backoff_s: float = 1.0,
        backoff_factor: float = 2.0,
        jitter: float = 0.1,
        max_attempts: int = 50,
        sleep_fn=time.sleep,
        clock=time.monotonic,
        preempt_event=None,
    ) -> SuperviseResult:
        """Supervised relaunch-and-resume: run :meth:`launch` until the
        world succeeds, classifying every failure
        (:func:`dct_tpu.resilience.supervisor.classify_failure`) and
        relaunching resumable ones with exponential backoff.

        Healing semantics per classification:

        - ``preempted`` — routine (the ranks saved resume checkpoints and
          exited 75): relaunch immediately, no restart budget consumed,
          bounded only by ``max_attempts``;
        - ``crash`` / ``hang`` / ``infra`` — relaunch with backoff, up to
          ``max_restarts`` times;
        - ``health_halt`` — deterministic (a NaN'd trajectory re-diverges
          from the same checkpoint): give up immediately.

        Every relaunch sets ``DCT_RESUME=1`` so the retried world resumes
        from the last published train-state checkpoint
        (:class:`TrainStateCheckpointer` skips torn rotation dirs), and
        exports the wall clock actually LOST so far as
        ``DCT_STARTUP_RECOVERY_DEBT_S`` — the relaunched trainer books it
        as ``startup_recovery`` badput, so the cycle's goodput accounting
        is honest about what the failure cost. "Lost" means the window
        since the attempt's last durable resume checkpoint (read from its
        ``resume_state_saved`` events): checkpointed progress is RETAINED
        by the resume, not lost — in particular a graceful preemption
        after hours of training costs ~nothing. Stale heartbeat files from
        the dead attempt are cleared so the fresh monitor does not
        stall-kill the new world on yesterday's beats.

        The supervisor also forwards its OWN termination: ranks run in
        their own sessions (``start_new_session``), so a supervisor dying
        on the default SIGTERM disposition would orphan them past any
        task-level process-group kill (Airflow ``execution_timeout``).
        SIGTERM/SIGINT raise :class:`SupervisorTerminated` instead, which
        unwinds through launch()'s finally-block world teardown.
        """
        base_env = dict(env or {})
        merged = dict(os.environ)
        merged.update(base_env)
        # One run-correlation ID across every attempt: the relaunches ARE
        # the story of this cycle, and one grep must reconstruct it.
        run_id = merged.get("DCT_RUN_ID") or mint_run_id()
        base_env["DCT_RUN_ID"] = merged["DCT_RUN_ID"] = run_id
        # Compile-cache continuity across attempts: pin ONE resolved
        # cache dir into every rank env, so a relaunch disk-hits the
        # programs its dead predecessor compiled (the relaunch IS the
        # steady-state cache consumer — ROADMAP item 5). No-op unless
        # DCT_COMPILE_CACHE arms the cache.
        from dct_tpu import compilecache as _compilecache

        _compilecache.export_env(base_env, merged)
        events = _launcher_event_log(merged)
        policy = RestartPolicy(
            max_restarts=max_restarts, backoff_s=backoff_s,
            backoff_factor=backoff_factor, jitter=jitter,
        )
        events.emit(
            "launcher", "supervise_start",
            world_size=world_size, max_restarts=max_restarts,
            argv=list(argv),
        )
        # Restart accounting on the metrics plane: relaunch counts by
        # classification + the cumulative lost wall clock, published as
        # a FINAL snapshot when supervision ends (the restart history
        # outlives the supervisor — ROADMAP item 5's restart-debt
        # numbers next to the trainer's compile series).
        metrics_pub = _launcher_metrics_publisher(
            merged, f"supervisor-{os.getpid()}"
        )
        restarts_ctr = lost_gauge = None
        if metrics_pub is not None:
            restarts_ctr = metrics_pub.registry.counter(
                "dct_restarts_total",
                "Supervised world relaunches, by failure classification.",
            )
            lost_gauge = metrics_pub.registry.gauge(
                "dct_restart_lost_wall_seconds",
                "Wall seconds lost to failed attempts and backoff "
                "(handed to the relaunched trainer as startup_recovery "
                "badput).", agg="sum",
            )
        attempts: list[AttemptRecord] = []
        restarts = 0
        debt = 0.0

        def _raise_terminated(signum, frame):
            raise SupervisorTerminated(f"signal {signum}")

        prev_handlers = {}
        if threading.current_thread() is threading.main_thread():
            for sig in (signal.SIGTERM, signal.SIGINT):
                try:
                    prev_handlers[sig] = signal.signal(sig, _raise_terminated)
                except (ValueError, OSError):
                    pass
        try:
            while True:
                t0 = clock()
                t0_wall = time.time()
                results = self.launch(
                    argv, world_size=world_size, env=base_env,
                    preempt_event=preempt_event,
                )
                wall = clock() - t0
                cls = classify_failure(
                    [r.returncode for r in results],
                    stall_killed=self._stall_killed,
                    timed_out=self._timed_out,
                )
                attempts.append(
                    AttemptRecord(len(attempts) + 1, results, cls, wall)
                )
                if cls == "success":
                    events.emit(
                        "launcher", "restart.recovered" if restarts or
                        len(attempts) > 1 else "supervise_end",
                        attempts=len(attempts), restarts_used=restarts,
                        lost_wall_s=round(debt, 3),
                    )
                    return SuperviseResult(
                        results=results, attempts=attempts,
                        restarts=restarts, success=True, classification=cls,
                    )
                if (
                    preempt_event is not None
                    and preempt_event.is_set()
                    and cls == "preempted"
                ):
                    # Scheduler lease revocation, not a failure: the
                    # world checkpointed and exited 75 by contract.
                    # Returning (instead of the free preempted
                    # relaunch) hands the chips back to the grant loop;
                    # the caller's next lease resumes the trajectory.
                    events.emit(
                        "launcher", "supervise_preempted",
                        attempts=len(attempts), restarts_used=restarts,
                    )
                    return SuperviseResult(
                        results=results, attempts=attempts,
                        restarts=restarts, success=False,
                        classification="preempted",
                    )
                if not policy.allows(restarts, cls) or (
                    len(attempts) >= max_attempts
                ):
                    events.emit(
                        "launcher", "restart.gave_up",
                        classification=cls, restarts_used=restarts,
                        attempts=len(attempts),
                        returncodes=[r.returncode for r in results],
                    )
                    return SuperviseResult(
                        results=results, attempts=attempts,
                        restarts=restarts, success=False,
                        classification=cls,
                    )
                consume = cls != "preempted"
                delay = policy.delay(restarts) if consume else 0.0
                if consume:
                    restarts += 1
                debt += self._attempt_lost_seconds(
                    merged, run_id, cls, t0_wall, wall
                ) + delay
                self._clear_heartbeats(merged, world_size)
                if restarts_ctr is not None:
                    restarts_ctr.inc(1, {"classification": cls})
                    lost_gauge.set(round(debt, 3))
                    metrics_pub.publish()
                events.emit(
                    "launcher", "restart.relaunch",
                    attempt=len(attempts) + 1, classification=cls,
                    backoff_s=round(delay, 3), lost_wall_s=round(debt, 3),
                    restarts_used=restarts,
                    returncodes=[r.returncode for r in results],
                )
                # The retried run RESUMES at the last published step
                # rather than epoch 0, and books the lost window as
                # badput.
                base_env["DCT_RESUME"] = "1"
                base_env["DCT_STARTUP_RECOVERY_DEBT_S"] = f"{debt:.3f}"
                # Fault plans are per-CYCLE drills: the spec applies to
                # the first launch, the healed relaunch runs clean —
                # otherwise a resumed world restarting at the trigger
                # epoch re-fires the same fault forever and the drill can
                # never demonstrate recovery.
                base_env["DCT_FAULT_SPEC"] = ""
                if delay > 0:
                    sleep_fn(delay)
        except SupervisorTerminated:
            # launch()'s finally already tore the world down; put the
            # cause on the record and report resumable-not-failed (a
            # task retry with DCT_RESUME=1 picks the cycle back up).
            events.emit(
                "launcher", "supervise_terminated",
                attempts=len(attempts), restarts_used=restarts,
            )
            return SuperviseResult(
                results=attempts[-1].results if attempts else [],
                attempts=attempts, restarts=restarts, success=False,
                classification="preempted",
            )
        finally:
            if metrics_pub is not None:
                metrics_pub.close(final=True)
            for sig, prev in prev_handlers.items():
                try:
                    signal.signal(sig, prev)
                except (ValueError, OSError):
                    pass

    @staticmethod
    def _attempt_lost_seconds(
        env: dict, run_id: str, classification: str,
        t0_wall: float, wall: float,
    ) -> float:
        """Wall clock the failed attempt actually LOST: the window since
        its last durable resume checkpoint (``resume_state_saved``
        events), because checkpointed progress is retained by the
        resume. A graceful preemption saved at the boundary by contract
        — zero. No readable events / no save seen -> the full attempt
        wall (conservative: nothing provably survived)."""
        if classification == "preempted":
            return 0.0
        path = os.path.join(
            env.get("DCT_EVENTS_DIR") or "logs/events", "events.jsonl"
        )
        last_save = None
        try:
            with open(path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if (
                        rec.get("run_id") == run_id
                        and rec.get("event") == "resume_state_saved"
                        and rec.get("ts", 0.0) >= t0_wall
                    ):
                        last_save = max(last_save or 0.0, rec["ts"])
        except OSError:
            return wall
        if last_save is None:
            return wall
        return min(wall, max(0.0, t0_wall + wall - last_save))

    def _clear_heartbeats(self, env: dict, world_size: int) -> None:
        """Drop the dead attempt's heartbeat files: they carry the SAME
        run ID as the relaunch (one cycle, one correlation ID), so the
        fresh monitor would read them as instantly-stalled ranks."""
        hb_dir = (
            env.get("DCT_HEARTBEAT_DIR")
            or self.heartbeat_dir
            or "logs/heartbeats"
        )
        for rank in range(world_size):
            try:
                os.remove(heartbeat_path(hb_dir, rank))
            except OSError:
                pass

    @staticmethod
    def all_succeeded(results: list[RankResult]) -> bool:
        return all(r.returncode == 0 for r in results)
