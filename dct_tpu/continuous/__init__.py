"""Always-on overlapped cycles (ROADMAP item 3 / ISSUE 10).

The episodic platform runs ETL -> train -> gate -> deploy strictly
serially, once per DAG trigger — data-to-deployed-model latency is the
SUM of every stage and the chips idle through everything but the train
stage. This package is the Podracer-style restructuring (PAPERS.md):
the same stages as concurrently-running actors over shared, atomically-
published artifacts:

- :class:`~dct_tpu.continuous.ingest.IngestWatcher` — content-digest
  polling of the raw staging CSV; a change triggers the incremental ETL
  (``etl/preprocess.py``) while training keeps running;
- the training pump (:class:`~dct_tpu.continuous.loop.AlwaysOnLoop`) —
  short rounds that EXTEND one optimizer trajectory (``DCT_RESUME``
  semantics), each under the PR 3 supervisor (or inline for benches);
- :class:`~dct_tpu.continuous.evaluator.PromotionEvaluator` — watches
  the deploy-tier best checkpoint, packages each new one, consults the
  PR 4 champion/challenger gate against the LIVE deployed champion, and
  promotes mid-run through the existing
  :class:`~dct_tpu.deploy.rollout.RolloutOrchestrator` — no training
  stop, no cycle boundary.

The train hot path is untouched: per-step semantics are bit-identical
to the serial trainer (pinned by tests/test_continuous.py — loss
trajectories and checkpoint bytes). docs/CONTINUOUS.md has the
architecture, promotion semantics, and failure modes.
"""

from dct_tpu.continuous.evaluator import PromotionEvaluator, package_checkpoint
from dct_tpu.continuous.ingest import IngestWatcher
from dct_tpu.continuous.loop import AlwaysOnLoop

__all__ = [
    "AlwaysOnLoop",
    "IngestWatcher",
    "PromotionEvaluator",
    "package_checkpoint",
]
