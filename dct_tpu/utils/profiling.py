"""Tracing / profiling subsystem.

The reference has NO tracing or profiling (SURVEY §5.1): its only
observability knobs are ``log_every_n_steps=5`` cadence control
(jobs/train_lightning_ddp.py:139) and stdout prints; TensorBoard is
installed in the trainer image (Dockerfile.pytorch:16) and a DAG task looks
for a logs directory (dags/pipeline.py:229-240) but nothing ever writes it.
This module fills that gap TPU-natively:

- :class:`Profiler` — a coordinator-gated window around ``jax.profiler``
  device tracing. The trace (XLA ops, fusion boundaries, HBM transfers,
  ICI collectives) lands in a TensorBoard-compatible ``plugins/profile``
  directory, satisfying the DAG's TensorBoard-logs check with real content.
- :class:`EpochTimer` — wall-clock + throughput accounting per epoch
  (samples/sec and samples/sec/chip, the BASELINE.md north-star metric),
  ready to be logged as tracking metrics next to val_loss.

Host-side named spans on the trace timeline (batch assembly, H2D staging,
the checkpoint section) come from the span recorder
(:mod:`dct_tpu.observability.spans`), whose stack spans are
``jax.profiler.TraceAnnotation``s.

Profiling is a window, not a mode: tracing every step of a long run would
produce gigabytes and perturb the steady state, so the profiler arms itself
for one configured epoch and disarms after.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field


class Profiler:
    """Start/stop ``jax.profiler`` tracing around one epoch window.

    Only the coordinator process traces (every process tracing would write
    world_size copies; the device timeline of process 0 is representative
    for SPMD programs). Safe to call when disabled — all methods no-op.
    """

    def __init__(self, trace_dir: str, *, enabled: bool, epoch: int,
                 coordinator: bool = True):
        self.trace_dir = trace_dir
        self.enabled = bool(enabled) and coordinator
        self.epoch = int(epoch)
        self._active = False

    def maybe_start(self, epoch: int) -> None:
        if not self.enabled or self._active or epoch != self.epoch:
            return
        # One jax.profiler session per process: the planned window
        # shares the flight recorder's gate (observability/capture.py).
        # If an on-demand capture is mid-flight when the target epoch
        # arrives, the planned trace is SKIPPED with a note — a second
        # start_trace would raise and fail the run.
        from dct_tpu.observability.capture import _SESSION_LOCK

        if not _SESSION_LOCK.acquire(blocking=False):
            import sys

            print(
                f"[dct_tpu] planned profile of epoch {self.epoch} "
                "skipped: an on-demand capture is already running",
                file=sys.stderr, flush=True,
            )
            return
        try:
            import jax.profiler

            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
        except Exception:
            _SESSION_LOCK.release()
            raise
        self._active = True

    def maybe_stop(self, epoch: int) -> None:
        if not self._active or epoch != self.epoch:
            return
        from dct_tpu.observability.capture import _SESSION_LOCK

        import jax.profiler

        try:
            jax.profiler.stop_trace()
        finally:
            self._active = False
            _SESSION_LOCK.release()

    def close(self) -> None:
        """Stop tracing unconditionally (crash-path hygiene: an abandoned
        trace session would corrupt the output directory)."""
        if self._active:
            from dct_tpu.observability.capture import _SESSION_LOCK

            import jax.profiler

            try:
                jax.profiler.stop_trace()
            finally:
                self._active = False
                _SESSION_LOCK.release()


def chip_peak_flops() -> float | None:
    """bf16 peak FLOPs/sec per chip from the device kind. Override with
    DCT_PEAK_TFLOPS. Off the TPU there is no chip to have a peak: None,
    and whatever divides by it is "not measured". On the TPU a device
    kind the table does not know is an error, not a default."""
    import jax

    env = os.environ.get("DCT_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = dev.device_kind.lower()
    for pat, peak_t in (
        ("v6", 918.0), ("v5p", 459.0), ("v5 lite", 197.0), ("v5e", 197.0),
        ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
    ):
        if pat in kind:
            return peak_t * 1e12
    raise ValueError(
        f"no peak FLOP/s on record for TPU device kind "
        f"{dev.device_kind!r}; add it to the table or set DCT_PEAK_TFLOPS"
    )


def transformer_train_flops(
    *, d_model: int, d_ff: int, seq_len: int, n_heads: int, n_layers: int,
    input_dim: int, batch: int, num_classes: int = 2,
) -> float:
    """Analytic matmul FLOPs for ONE transformer optimizer step
    (fwd + bwd ~ 3x fwd): projection/FFN GEMMs at 2*params*tokens plus
    the attention score/value einsums (4*B*H*S^2*Dh per layer);
    elementwise work excluded. Used for MFU = this / step_time / peak."""
    tokens = batch * seq_len
    proj_params = (
        n_layers * (4 * d_model * d_model + 2 * d_model * d_ff)
        + input_dim * d_model + d_model * num_classes
    )
    fwd = (
        2.0 * proj_params * tokens
        + 4.0 * batch * n_heads * seq_len * seq_len
        * (d_model // n_heads) * n_layers
    )
    return 3.0 * fwd


@dataclass
class EpochStats:
    epoch: int
    seconds: float
    samples: int
    samples_per_sec: float
    samples_per_sec_per_chip: float
    # Model-FLOPs utilization (achieved/peak); None when the analytic
    # FLOPs or the chip peak are unknown (e.g. MLP family, CPU rig).
    mfu: float | None = None


@dataclass
class EpochTimer:
    """Accumulates per-epoch wall time and throughput.

    ``n_chips`` divides throughput into the per-chip north-star metric
    (BASELINE.md): honest accounting means the clock includes host batch
    assembly and H2D staging, not just device execution.
    """

    n_chips: int = 1
    # Analytic train FLOPs per SAMPLE (transformer_train_flops(batch=1));
    # with the chip peak this turns throughput into per-epoch MFU.
    flops_per_sample: float | None = None
    peak_flops: float | None = None
    # Optional goodput ledger (observability.goodput.GoodputLedger): each
    # stop() feeds the epoch's wall seconds to the ledger's per-epoch
    # marks, so goodput reports share the timer's clock windows.
    ledger: object | None = None
    history: list = field(default_factory=list)
    _t0: float = 0.0

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(
        self, epoch: int, samples: int, eval_samples: int = 0
    ) -> EpochStats:
        """``samples`` = TRAIN samples; ``eval_samples`` = validation
        samples whose forward pass ran inside the timed window (the
        fused train+eval epoch program). samples_per_sec stays
        train-samples over the full epoch wall time — the reference's
        per-epoch cadence also includes validation — while MFU credits
        the eval forwards (1/3 of a train sample's FLOPs) so utilization
        is not understated by work the denominator paid for."""
        dt = time.perf_counter() - self._t0
        sps = samples / dt if dt > 0 else 0.0
        mfu = None
        if self.flops_per_sample and self.peak_flops and dt > 0:
            achieved = (
                (samples + eval_samples / 3.0) * self.flops_per_sample / dt
            )
            mfu = achieved / max(self.n_chips, 1) / self.peak_flops
        stats = EpochStats(
            epoch=epoch,
            seconds=dt,
            samples=samples,
            samples_per_sec=sps,
            samples_per_sec_per_chip=sps / max(self.n_chips, 1),
            mfu=mfu,
        )
        self.history.append(stats)
        if self.ledger is not None:
            self.ledger.note_epoch(epoch, dt)
        return stats

    @property
    def total_seconds(self) -> float:
        return sum(s.seconds for s in self.history)

    @property
    def total_samples(self) -> int:
        return sum(s.samples for s in self.history)

    @property
    def samples_per_sec(self) -> float:
        t = self.total_seconds
        return self.total_samples / t if t > 0 else 0.0
