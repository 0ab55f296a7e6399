#!/usr/bin/env python3
"""Record the small trace that ``reduce/host.py`` is checked against.

    python benchmark/tools/record_host_sample.py <output .xplane.pb>

One chip. Two made-up epochs of the trainer's thread, with the program's own
span recorder and the harness's markers, sleeps in place of the work, a jitted
``epoch_fused`` of four matmuls in place of the epoch program, the resume
tier's write on a worker thread, and one stretch under no span at all, so that
every reader has something to read and something to leave out. The profiler is
set as the harness sets it (host spans on, the Python call tracer off).
``benchmark/reduce/sample/README_host_spans.txt`` holds what was read from
the result by hand.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    out = sys.argv[1]
    import jax
    import jax.numpy as jnp

    from benchmark.reduce import trace as tr
    from dct_tpu.observability.spans import SpanRecorder

    @jax.jit
    def epoch_fused(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.full((4096, 4096), 0.01, jnp.bfloat16)
    epoch_fused(x).block_until_ready()  # compiled before the session
    rec = SpanRecorder(None, trace_id="dct-sample")
    mark = jax.profiler.TraceAnnotation
    workers = []

    def publish(n):
        with rec.span("checkpoint.resume_save", epochs_completed=n):
            time.sleep(0.006)

    def checkpoint(epoch, write_s):
        with rec.span("trainer.checkpoint", epoch=epoch):
            with rec.span("trainer.gather_params"):
                time.sleep(0.002)
            with rec.span("checkpoint.deploy_write", epoch=epoch):
                with rec.span("checkpoint.serialize", path="last.ckpt") as sp:
                    time.sleep(0.003)
                    sp.set(bytes=4096)
                with rec.span("checkpoint.file_write", path="last.ckpt",
                              bytes=4096):
                    time.sleep(write_s)
                with rec.span("checkpoint.lineage_hash", path="last.ckpt",
                              bytes=4096):
                    time.sleep(0.001)
            with rec.span("checkpoint.resume_wait_prev"):
                for w in workers:
                    w.join()
            with rec.span("checkpoint.resume_snapshot"):
                time.sleep(0.002)
            workers.append(threading.Thread(target=publish, args=(epoch + 1,)))
            workers[-1].start()

    def epoch(n, write_s, stamp):
        checkpoint(n - 1, write_s)
        with rec.span("trainer.data_wait", epoch=n):
            time.sleep(0.0005)
        with rec.span("trainer.dispatch_call", epoch=n, first=False):
            y = epoch_fused(x)
        with rec.span("trainer.join", epoch=n):
            y.block_until_ready()
        with rec.span("trainer.bookkeep", epoch=n):
            time.sleep(0.001)
            with mark(stamp):
                pass

    trace_dir = tempfile.mkdtemp(prefix="host_sample_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with mark("bench.trace_begin"):
        pass
    fit = rec.open("trainer.fit", epochs=2)
    epoch(1, 0.005, "bench.epoch_end.1")
    time.sleep(0.004)  # under no span: what the timeline cannot name
    epoch(2, 0.008, "bench.epoch_end.2")
    with mark("bench.trace_end"):
        pass
    fit.end()
    for w in workers:
        w.join()
    jax.profiler.stop_trace()
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(tr.find_xplane(trace_dir), out)
    print("wrote", out, os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
