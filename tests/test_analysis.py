"""dct-lint (dct_tpu.analysis): framework + every rule, fixture-proven.

Each rule gets a paired good/bad fixture: the bad snippet must produce
the finding, the good one must not — so a rule that silently stops
firing fails CI, not code review. Plus: suppression semantics (line and
def/class `# dct: noqa[...]`), baseline round-trip with justification
hygiene, CLI output/exit codes, and the repo-tree acceptance (the real
tree lints clean with >= 6 active rules).

These tests never import jax — the analyzer is stdlib-only by design.
"""

from __future__ import annotations

import json
import os

import pytest

from dct_tpu.analysis import core
from dct_tpu.analysis import lint as lint_cli


# ----------------------------------------------------------------------
# Mini-repo scaffolding


MINI_CONFIG = '''\
ENV_REGISTRY: dict[str, str] = {
    "DCT_ALPHA": "a documented, used knob",
}
'''

MINI_ENV_EXAMPLE = """\
# DCT_ALPHA=1   # the knob
"""

MINI_DOCS = """\
# Observability

| component | events |
|---|---|
| `trainer` | `fit_start`, `epoch_end`, `fit_end` |
| `checkpoint` | `best_saved`, `last_saved` |
"""

MINI_USER = """\
import os
ALPHA = os.environ.get("DCT_ALPHA")
"""


def make_repo(tmp_path, files: dict[str, str]):
    """A minimal repo root: registry, env example, docs, plus ``files``
    (relpath -> source). Returns the root path."""
    base = {
        "dct_tpu/config.py": MINI_CONFIG,
        "dct_tpu/user.py": MINI_USER,
        ".env.example": MINI_ENV_EXAMPLE,
        "docs/OBSERVABILITY.md": MINI_DOCS,
    }
    base.update(files)
    for rel, src in base.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return str(tmp_path)


def run_rule(tmp_path, files, rule_id, paths=None):
    root = make_repo(tmp_path, files)
    targets = paths or [os.path.join(root, "dct_tpu")]
    report = core.analyze(targets, root=root, select={rule_id})
    return [f for f in report.findings if f.rule == rule_id]


# ----------------------------------------------------------------------
# rank0-io


BAD_RANK0 = """\
import jax

def publish(path, data):
    rank = jax.process_index()
    with open(path, "w") as f:
        f.write(data)
"""

GOOD_RANK0 = """\
import jax

def publish(path, data):
    if jax.process_index() == 0:
        with open(path, "w") as f:
            f.write(data)
"""

GOOD_RANK0_COORD = """\
import jax
from dct_tpu.parallel.distributed import is_coordinator

def publish(self, path, data):
    if self.coordinator:
        with open(path, "w") as f:
            f.write(data)
"""

SINGLE_PROCESS = """\
def publish(path, data):
    with open(path, "w") as f:
        f.write(data)
"""


class TestRank0Io:
    def test_unguarded_write_flagged(self, tmp_path):
        found = run_rule(
            tmp_path, {"dct_tpu/spmd.py": BAD_RANK0}, "rank0-io"
        )
        assert len(found) == 1
        assert found[0].path == "dct_tpu/spmd.py"
        assert "unguarded" in found[0].message

    def test_guarded_write_clean(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/spmd.py": GOOD_RANK0}, "rank0-io"
        )

    def test_coordinator_attribute_guard(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/spmd.py": GOOD_RANK0_COORD}, "rank0-io"
        )

    def test_single_process_module_exempt(self, tmp_path):
        # No rank identity anywhere in the module -> orchestrator-side.
        assert not run_rule(
            tmp_path, {"dct_tpu/tool.py": SINGLE_PROCESS}, "rank0-io"
        )

    def test_publish_api_call_flagged(self, tmp_path):
        src = (
            "import jax\n"
            "def up(tracker, p):\n"
            "    jax.process_count()\n"
            "    tracker.log_artifact(p, artifact_path='best')\n"
        )
        found = run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")
        assert len(found) == 1 and "log_artifact" in found[0].message

    def test_write_in_else_of_guard_flagged(self, tmp_path):
        # The guard selects the coordinator for its BODY; a write in the
        # else branch runs on every non-zero rank.
        src = (
            "import jax\n"
            "def publish(path, data):\n"
            "    if jax.process_index() == 0:\n"
            "        pass\n"
            "    else:\n"
            "        with open(path, 'w') as f:\n"
            "            f.write(data)\n"
        )
        found = run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")
        assert len(found) == 1

    def test_write_under_negated_guard_flagged(self, tmp_path):
        src = (
            "import jax\n"
            "from dct_tpu.parallel.distributed import is_coordinator\n"
            "def publish(path, data):\n"
            "    if not is_coordinator():\n"
            "        with open(path, 'w') as f:\n"
            "            f.write(data)\n"
        )
        found = run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")
        assert len(found) == 1

    def test_write_in_else_of_negated_guard_clean(self, tmp_path):
        src = (
            "import jax\n"
            "from dct_tpu.parallel.distributed import is_coordinator\n"
            "def publish(path, data):\n"
            "    if not is_coordinator():\n"
            "        return\n"
            "    else:\n"
            "        with open(path, 'w') as f:\n"
            "            f.write(data)\n"
        )
        assert not run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")

    def test_write_in_else_of_inverted_comparison_clean(self, tmp_path):
        src = (
            "import jax\n"
            "def publish(path, data):\n"
            "    rank = jax.process_index()\n"
            "    if rank != 0:\n"
            "        return\n"
            "    else:\n"
            "        with open(path, 'w') as f:\n"
            "            f.write(data)\n"
        )
        assert not run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")

    def test_guard_buried_under_compound_not_is_no_guard(self, tmp_path):
        # `busy and not coordinator` selects NON-coordinators; treating
        # it as a guard would launder the exact bug class.
        src = (
            "import jax\n"
            "def publish(self, path, data, busy):\n"
            "    jax.process_count()\n"
            "    if busy and not self.coordinator:\n"
            "        with open(path, 'w') as f:\n"
            "            f.write(data)\n"
        )
        found = run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")
        assert len(found) == 1


# ----------------------------------------------------------------------
# atomic-publish


BAD_PUBLISH = """\
import json, os

def write_manifest(d, obj):
    with open(os.path.join(d, "run_info.json"), "w") as f:
        json.dump(obj, f)
"""

GOOD_PUBLISH = """\
import json, os

def write_manifest(d, obj):
    path = os.path.join(d, "run_info.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)
"""

APPEND_LOG = """\
def append(path, line):
    with open(path, "a") as f:
        f.write(line)
"""

SAVEZ_VIA_TMP_HANDLE = """\
import os
import numpy as np

def save(final, entries):
    tmp = final + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **entries)
    os.replace(tmp, final)
"""


class TestAtomicPublish:
    def test_in_place_write_flagged(self, tmp_path):
        found = run_rule(
            tmp_path, {"dct_tpu/deploy/pkg.py": BAD_PUBLISH}, "atomic-publish"
        )
        assert len(found) == 1
        assert "non-atomic publish" in found[0].message

    def test_tmp_then_replace_clean(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/deploy/pkg.py": GOOD_PUBLISH}, "atomic-publish"
        )

    def test_append_mode_exempt(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/observability/log.py": APPEND_LOG},
            "atomic-publish",
        )

    def test_savez_through_tmp_handle_clean(self, tmp_path):
        # np.savez(f) where f was opened on a tmp path must see through
        # the handle binding.
        assert not run_rule(
            tmp_path,
            {"dct_tpu/checkpoint/rot.py": SAVEZ_VIA_TMP_HANDLE},
            "atomic-publish",
        )

    def test_copy_dest_flagged(self, tmp_path):
        src = (
            "import shutil\n"
            "def pub(a, final):\n"
            "    shutil.copy2(a, final)\n"
        )
        found = run_rule(
            tmp_path, {"dct_tpu/tracking/store.py": src}, "atomic-publish"
        )
        assert len(found) == 1 and "shutil.copy2" in found[0].message

    def test_outside_publish_layers_exempt(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/train/foo.py": BAD_PUBLISH}, "atomic-publish"
        )

    def test_stream_layer_in_place_write_flagged(self, tmp_path):
        # The stream plane's durability story IS the atomic publish
        # (offset commits, watermark sidecars): an in-place write there
        # is a torn-commit bug, not a style nit.
        found = run_rule(
            tmp_path, {"dct_tpu/stream/offsets.py": BAD_PUBLISH},
            "atomic-publish",
        )
        assert len(found) == 1
        assert "non-atomic publish" in found[0].message

    def test_stream_layer_tmp_then_replace_clean(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/stream/offsets.py": GOOD_PUBLISH},
            "atomic-publish",
        )


# ----------------------------------------------------------------------
# lineage-publish


LINEAGE_BAD = """\
import json, os

def commit(d, obj):
    tmp = os.path.join(d, "etl.json.tmp")
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, os.path.join(d, "etl.json"))
"""

LINEAGE_GOOD = """\
import json, os

from dct_tpu.observability import lineage

def commit(d, obj):
    tmp = os.path.join(d, "etl.json.tmp")
    with open(tmp, "w") as f:
        json.dump(obj, f)
    final = os.path.join(d, "etl.json")
    os.replace(tmp, final)
    lineage.get_default().node("offset_commit", path=final, attrs=obj)
"""


class TestLineagePublish:
    def test_stream_publish_without_lineage_flagged(self, tmp_path):
        found = run_rule(
            tmp_path, {"dct_tpu/stream/offsets.py": LINEAGE_BAD},
            "lineage-publish",
        )
        assert len(found) == 1
        assert "never records lineage" in found[0].message

    def test_stream_publish_recording_lineage_clean(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/stream/offsets.py": LINEAGE_GOOD},
            "lineage-publish",
        )

    def test_etl_layer_covered_too(self, tmp_path):
        found = run_rule(
            tmp_path, {"dct_tpu/etl/state.py": LINEAGE_BAD},
            "lineage-publish",
        )
        assert len(found) == 1

    def test_outside_lineage_layers_exempt(self, tmp_path):
        # serving/ hot paths publish plenty of state files; the ledger
        # records them from the orchestrating layers instead.
        assert not run_rule(
            tmp_path, {"dct_tpu/serving/pool.py": LINEAGE_BAD},
            "lineage-publish",
        )

    def test_noqa_marks_deliberate_state_file(self, tmp_path):
        src = LINEAGE_BAD.replace(
            "    os.replace(tmp, os.path.join(d, \"etl.json\"))",
            "    os.replace(tmp, os.path.join(d, \"etl.json\"))"
            "  # dct: noqa[lineage-publish] -- scratch state, not an artifact",
        )
        assert not run_rule(
            tmp_path, {"dct_tpu/stream/offsets.py": src}, "lineage-publish"
        )


# ----------------------------------------------------------------------
# gather-on-publish


BAD_GATHER = """\
import numpy as np

def export(state):
    return {k: np.asarray(v) for k, v in state.params.items()}
"""

GOOD_GATHER = """\
from dct_tpu.parallel.sharding_rules import gather_tree

def export(state):
    return gather_tree(state.params)
"""

GOOD_GATHER_TO_HOST = """\
from dct_tpu.checkpoint.manager import to_host

def export(state):
    dense = to_host(state.params)
    return dense
"""

NOQA_GATHER = """\
def split(best):
    return dict(best.params)  # dct: noqa[gather-on-publish] — a tracking run's hyperparameter dict, not a TrainState
"""


class TestGatherOnPublish:
    def test_raw_params_read_flagged(self, tmp_path):
        found = run_rule(
            tmp_path, {"dct_tpu/deploy/pkg.py": BAD_GATHER},
            "gather-on-publish",
        )
        assert len(found) == 1
        assert "state.params" in found[0].message

    def test_serving_layer_also_checked(self, tmp_path):
        assert run_rule(
            tmp_path, {"dct_tpu/serving/exp.py": BAD_GATHER},
            "gather-on-publish",
        )

    @pytest.mark.parametrize(
        "src", [GOOD_GATHER, GOOD_GATHER_TO_HOST], ids=["gather", "to_host"]
    )
    def test_gather_fn_wrapped_clean(self, tmp_path, src):
        assert not run_rule(
            tmp_path, {"dct_tpu/deploy/pkg.py": src}, "gather-on-publish"
        )

    def test_justified_noqa_clean(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/deploy/rollup.py": NOQA_GATHER},
            "gather-on-publish",
        )

    def test_outside_publish_layers_exempt(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/train/foo.py": BAD_GATHER},
            "gather-on-publish",
        )


# ----------------------------------------------------------------------
# span-sync


BAD_SPAN = """\
import jax

def loop(step, state, x):
    # dct: begin-no-host-sync
    state, losses = step(state, x)
    last = float(losses[-1])
    # dct: end-no-host-sync
    return state, last
"""

GOOD_SPAN = """\
import jax

def loop(step, state, x):
    # dct: begin-no-host-sync
    state, losses = step(state, x)
    losses.copy_to_host_async()
    # dct: end-no-host-sync
    last = float(jax.device_get(losses)[-1])
    return state, last
"""


class TestSpanSync:
    def test_sync_in_region_flagged(self, tmp_path):
        found = run_rule(
            tmp_path, {"dct_tpu/train/loop.py": BAD_SPAN}, "span-sync"
        )
        assert len(found) == 1
        assert "float(...)" in found[0].message

    def test_sync_after_region_clean(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/train/loop.py": GOOD_SPAN}, "span-sync"
        )

    @pytest.mark.parametrize(
        "stmt,label",
        [
            ("jax.device_get(losses)", "jax.device_get"),
            ("losses.block_until_ready()", ".block_until_ready()"),
            ("losses[-1].item()", ".item()"),
            ("np.asarray(losses)", "np.asarray"),
        ],
    )
    def test_each_sync_construct(self, tmp_path, stmt, label):
        src = (
            "import jax\nimport numpy as np\n"
            "def loop(losses):\n"
            "    # dct: begin-no-host-sync\n"
            f"    {stmt}\n"
            "    # dct: end-no-host-sync\n"
        )
        found = run_rule(
            tmp_path, {"dct_tpu/train/loop.py": src}, "span-sync"
        )
        assert len(found) == 1 and label in found[0].message

    def test_duplicate_begin_keeps_wider_region(self, tmp_path):
        # A second begin before the end must not shrink the protected
        # window: the sync between the two begins is still a violation.
        src = (
            "import jax\n"
            "def loop(losses):\n"
            "    # dct: begin-no-host-sync\n"
            "    jax.device_get(losses)\n"
            "    # dct: begin-no-host-sync\n"
            "    losses.copy_to_host_async()\n"
            "    # dct: end-no-host-sync\n"
        )
        found = run_rule(
            tmp_path, {"dct_tpu/train/loop.py": src}, "span-sync"
        )
        assert len(found) == 1 and "jax.device_get" in found[0].message

    def test_trainer_region_markers_present(self):
        # The trainer's epoch loop carries the markers this rule enforces
        # — if a refactor drops them, the invariant silently lapses.
        root = core.default_root()
        src = open(os.path.join(root, "dct_tpu/train/epoch_loop.py")).read()
        assert core.REGION_BEGIN_RE.search(src)
        assert core.REGION_END_RE.search(src)


# ----------------------------------------------------------------------
# trace-purity


BAD_TRACE_DIRECT = """\
import time
import jax

@jax.jit
def step(state, x):
    t = time.time()
    return state, t
"""

BAD_TRACE_FACTORY = """\
import numpy as np
import jax

def make_step():
    def step(state, x):
        noise = np.random.normal(size=x.shape)
        return state, x + noise
    return jax.jit(step)
"""

BAD_TRACE_TRANSITIVE = """\
import os
import jax

def _body(x):
    if os.environ.get("DCT_DEBUG"):
        print(x)
    return x * 2

def make_step():
    def step(x):
        return _body(x)
    return jax.jit(step)
"""

GOOD_TRACE = """\
import time
import jax

def make_step():
    built_at = time.time()  # host side: factories may read the clock
    def step(state, x, rng):
        noise = jax.random.normal(rng, x.shape)
        return state, x + noise
    return jax.jit(step), built_at
"""


class TestTracePurity:
    def test_decorated_jit_flagged(self, tmp_path):
        found = run_rule(
            tmp_path, {"dct_tpu/train/s.py": BAD_TRACE_DIRECT}, "trace-purity"
        )
        assert len(found) == 1 and "time.time" in found[0].message

    def test_factory_inner_flagged(self, tmp_path):
        found = run_rule(
            tmp_path, {"dct_tpu/train/s.py": BAD_TRACE_FACTORY}, "trace-purity"
        )
        assert len(found) == 1 and "np.random" in found[0].message

    def test_transitive_helper_flagged(self, tmp_path):
        found = run_rule(
            tmp_path,
            {"dct_tpu/train/s.py": BAD_TRACE_TRANSITIVE},
            "trace-purity",
        )
        labels = {f.message for f in found}
        assert any("os.environ" in m for m in labels)
        assert any("print" in m for m in labels)

    def test_host_factory_clock_clean(self, tmp_path):
        assert not run_rule(
            tmp_path, {"dct_tpu/train/s.py": GOOD_TRACE}, "trace-purity"
        )

    def test_shard_map_body_flagged(self, tmp_path):
        src = (
            "import time\n"
            "from jax import shard_map\n"
            "def make(mesh):\n"
            "    def body(x):\n"
            "        time.sleep(0.1)\n"
            "        return x\n"
            "    return shard_map(body, mesh=mesh, in_specs=None,"
            " out_specs=None)\n"
        )
        found = run_rule(
            tmp_path, {"dct_tpu/parallel/k.py": src}, "trace-purity"
        )
        assert len(found) == 1 and "time.sleep" in found[0].message


# ----------------------------------------------------------------------
# env-registry


class TestEnvRegistry:
    def test_reconciled_mini_repo_clean(self, tmp_path):
        assert not run_rule(tmp_path, {}, "env-registry")

    def test_undeclared_use_flagged(self, tmp_path):
        files = {
            "dct_tpu/extra.py": (
                "import os\nX = os.environ.get('DCT_ROGUE')\n"
            )
        }
        found = run_rule(tmp_path, files, "env-registry")
        assert len(found) == 1
        assert "DCT_ROGUE" in found[0].message
        assert found[0].path == "dct_tpu/extra.py"

    def test_dead_registry_entry_flagged(self, tmp_path):
        files = {
            "dct_tpu/config.py": (
                "ENV_REGISTRY = {\n"
                '    "DCT_ALPHA": "used",\n'
                '    "DCT_GHOST": "never read anywhere",\n'
                "}\n"
            ),
            ".env.example": "# DCT_ALPHA=1\n# DCT_GHOST=1\n",
        }
        found = run_rule(tmp_path, files, "env-registry")
        assert len(found) == 1 and "dead entry" in found[0].message

    def test_missing_env_example_mention_flagged(self, tmp_path):
        files = {
            "dct_tpu/config.py": (
                "ENV_REGISTRY = {\n"
                '    "DCT_ALPHA": "used",\n'
                '    "DCT_BETA": "used but undocumented",\n'
                "}\n"
            ),
            "dct_tpu/user.py": (
                "import os\n"
                "A = os.environ.get('DCT_ALPHA')\n"
                "B = os.environ.get('DCT_BETA')\n"
            ),
        }
        found = run_rule(tmp_path, files, "env-registry")
        assert len(found) == 1
        assert "DCT_BETA" in found[0].message
        assert ".env.example" in found[0].message

    def test_stale_env_example_mention_flagged(self, tmp_path):
        files = {
            ".env.example": "# DCT_ALPHA=1\n# DCT_ZOMBIE=1\n",
        }
        found = run_rule(tmp_path, files, "env-registry")
        assert len(found) == 1
        assert found[0].path == ".env.example"
        assert "DCT_ZOMBIE" in found[0].message

    def test_wildcard_mentions_skipped(self, tmp_path):
        files = {
            ".env.example": (
                "# DCT_ALPHA=1\n"
                "# see DCT_BENCH_* for the bench knobs\n"
            ),
        }
        assert not run_rule(tmp_path, files, "env-registry")

    def test_kwarg_and_named_constant_uses_count(self, tmp_path):
        # The launchers export DCT_* via kwargs / named constants —
        # those are uses, so declared entries for them are not "dead".
        files = {
            "dct_tpu/config.py": (
                "ENV_REGISTRY = {\n"
                '    "DCT_ALPHA": "used",\n'
                '    "DCT_KWARG": "exported to children",\n'
                '    "DCT_NAMED": "named-key constant",\n'
                "}\n"
            ),
            ".env.example": (
                "# DCT_ALPHA=1\n# DCT_KWARG=1\n# DCT_NAMED=1\n"
            ),
            "dct_tpu/launchy.py": (
                "SPAN_ENV = 'DCT_NAMED'\n"
                "def child_env(build):\n"
                "    return build(DCT_KWARG='1')\n"
            ),
        }
        assert not run_rule(tmp_path, files, "env-registry")

    def test_missing_registry_is_one_loud_finding(self, tmp_path):
        files = {"dct_tpu/config.py": "# no registry here\n"}
        found = run_rule(tmp_path, files, "env-registry")
        assert len(found) == 1
        assert "ENV_REGISTRY" in found[0].message


# ----------------------------------------------------------------------
# event-names


class TestEventNames:
    def test_documented_emit_clean(self, tmp_path):
        src = "def f(log):\n    log.emit('trainer', 'epoch_end', epoch=1)\n"
        assert not run_rule(
            tmp_path, {"dct_tpu/t.py": src}, "event-names"
        )

    def test_undocumented_event_flagged(self, tmp_path):
        src = "def f(log):\n    log.emit('trainer', 'mystery_event')\n"
        found = run_rule(tmp_path, {"dct_tpu/t.py": src}, "event-names")
        assert len(found) == 1 and "mystery_event" in found[0].message

    def test_unknown_component_flagged(self, tmp_path):
        src = "def f(log):\n    log.emit('warp_drive', 'engaged')\n"
        found = run_rule(tmp_path, {"dct_tpu/t.py": src}, "event-names")
        assert len(found) == 1 and "warp_drive" in found[0].message

    def test_conditional_event_checks_both_arms(self, tmp_path):
        src = (
            "def f(log, improved):\n"
            "    log.emit('checkpoint',"
            " 'best_saved' if improved else 'torn_saved')\n"
        )
        found = run_rule(tmp_path, {"dct_tpu/t.py": src}, "event-names")
        assert len(found) == 1 and "torn_saved" in found[0].message

    def test_dynamic_event_skipped(self, tmp_path):
        src = (
            "def f(log, state):\n"
            "    log.emit('trainer', f'rank_{state}')\n"
        )
        assert not run_rule(tmp_path, {"dct_tpu/t.py": src}, "event-names")

    def test_real_docs_table_parses(self):
        from dct_tpu.analysis.rules.registry_rules import parse_event_table

        root = core.default_root()
        md = open(os.path.join(root, "docs/OBSERVABILITY.md")).read()
        table = parse_event_table(md)
        assert table is not None
        assert "epoch_end" in table["trainer"]
        assert "resume_state_saved" in table["checkpoint"]
        assert "supervise_end" in table["launcher"]


# ----------------------------------------------------------------------
# suppressions


class TestSuppressions:
    def test_line_noqa(self, tmp_path):
        src = BAD_RANK0.replace(
            'with open(path, "w") as f:',
            'with open(path, "w") as f:  '
            "# dct: noqa[rank0-io] — test fixture",
        )
        assert not run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")

    def test_def_level_noqa_covers_body(self, tmp_path):
        src = BAD_RANK0.replace(
            "def publish(path, data):",
            "def publish(path, data):  "
            "# dct: noqa[rank0-io] — per-process by design (fixture)",
        )
        assert not run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")

    def test_noqa_other_rule_does_not_suppress(self, tmp_path):
        src = BAD_RANK0.replace(
            'with open(path, "w") as f:',
            'with open(path, "w") as f:  # dct: noqa[atomic-publish]',
        )
        found = run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")
        assert len(found) == 1

    def test_bare_noqa_suppresses_all(self, tmp_path):
        src = BAD_RANK0.replace(
            'with open(path, "w") as f:',
            'with open(path, "w") as f:  # dct: noqa',
        )
        assert not run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")

    def test_marker_in_string_literal_does_not_arm_region(self, tmp_path):
        # Prose QUOTING the marker (docstrings, rule docs) must not arm
        # a no-host-sync region — only a real comment token does.
        src = (
            'DOC = "between `# dct: begin-no-host-sync` and the end"\n'
            "def f(x):\n"
            "    return float(x)\n"
        )
        assert not run_rule(
            tmp_path, {"dct_tpu/train/doc.py": src}, "span-sync"
        )

    def test_noqa_in_string_literal_does_not_suppress(self, tmp_path):
        src = BAD_RANK0.replace(
            'with open(path, "w") as f:',
            'note = "# dct: noqa[rank0-io]"\n    with open(path, "w") as f:',
        )
        found = run_rule(tmp_path, {"dct_tpu/spmd.py": src}, "rank0-io")
        assert len(found) == 1

    def test_linter_source_quotes_markers_without_arming_regions(self):
        # The rules' own docstrings quote the markers; tokenizer-based
        # comment extraction must keep the linter from linting itself
        # into a phantom EOF-length region.
        root = core.default_root()
        rel = "dct_tpu/analysis/rules/purity_rules.py"
        ctx = core.FileContext(
            os.path.join(root, rel), rel,
            open(os.path.join(root, rel)).read(),
        )
        assert ctx.regions() == []

    def test_noqa_binds_in_non_target_files(self, tmp_path):
        # Repo-wide rules anchor findings in files outside the lint
        # targets (chip_smoke.py); a noqa there must hold under the
        # default `lint dct_tpu/` invocation too, not only when
        # chip_smoke.py is itself a target.
        files = {
            "chip_smoke.py": (
                "import os\n"
                "K = os.environ.get('DCT_UNREGISTERED')  "
                "# dct: noqa[env-registry] — fixture: smoke-local knob\n"
            )
        }
        assert not run_rule(tmp_path, files, "env-registry")
        # And without the noqa the same setup does flag.
        files_bad = {
            "chip_smoke.py": (
                "import os\nK = os.environ.get('DCT_UNREGISTERED')\n"
            )
        }
        found = run_rule(tmp_path, files_bad, "env-registry")
        assert len(found) == 1 and found[0].path == "chip_smoke.py"


# ----------------------------------------------------------------------
# baseline


class TestBaseline:
    def _report(self, tmp_path, baseline=None):
        root = make_repo(tmp_path, {"dct_tpu/spmd.py": BAD_RANK0})
        return core.analyze(
            [os.path.join(root, "dct_tpu")],
            root=root,
            select={"rank0-io"},
            baseline=baseline,
        )

    def test_roundtrip_suppresses_with_justification(self, tmp_path):
        first = self._report(tmp_path)
        assert len(first.findings) == 1
        bl = core.Baseline.from_findings(first.findings)
        path = str(tmp_path / "baseline.json")
        bl.save(path)
        loaded = core.Baseline.load(path)
        # TODO justification: suppressed from findings but hygiene fails.
        second = self._report(tmp_path, baseline=loaded)
        assert second.baselined and not second.stale_baseline
        assert any(f.rule == "baseline-hygiene" for f in second.findings)
        # Justify -> fully clean.
        for e in loaded.entries:
            e.justification = "fixture: proven safe because reasons"
        loaded.save(path)
        third = self._report(tmp_path, baseline=core.Baseline.load(path))
        assert third.ok and len(third.baselined) == 1

    def test_fingerprint_survives_line_drift(self, tmp_path):
        first = self._report(tmp_path)
        fp = first.findings[0].fingerprint
        # Same offending line, shifted down by a new import block.
        root = make_repo(
            tmp_path, {"dct_tpu/spmd.py": "import sys\nimport io\n" + BAD_RANK0}
        )
        second = core.analyze(
            [os.path.join(root, "dct_tpu")], root=root, select={"rank0-io"}
        )
        assert second.findings[0].fingerprint == fp
        assert second.findings[0].line != first.findings[0].line

    def test_stale_entry_reported_not_failing(self, tmp_path):
        bl = core.Baseline(
            [
                core.BaselineEntry(
                    fingerprint="deadbeefdeadbeef",
                    rule="rank0-io",
                    path="dct_tpu/gone.py",
                    snippet="open('x', 'w')",
                    justification="was real once",
                )
            ]
        )
        report = self._report(tmp_path, baseline=bl)
        # The live finding is NOT matched by the stale entry.
        assert any(f.rule == "rank0-io" for f in report.findings)
        assert len(report.stale_baseline) == 1


# ----------------------------------------------------------------------
# CLI


class TestCli:
    def test_json_output_and_exit_code(self, tmp_path, capsys):
        root = make_repo(tmp_path, {"dct_tpu/spmd.py": BAD_RANK0})
        rc = lint_cli.main(
            [
                os.path.join(root, "dct_tpu"),
                "--root", root,
                "--select", "rank0-io",
                "--format", "json",
            ]
        )
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["ok"] is False
        assert out["findings"][0]["rule"] == "rank0-io"
        assert out["findings"][0]["fingerprint"]

    def test_clean_exit_zero(self, tmp_path, capsys):
        root = make_repo(tmp_path, {})
        rc = lint_cli.main([os.path.join(root, "dct_tpu"), "--root", root])
        assert rc == 0
        assert "clean" in capsys.readouterr().out

    def test_unknown_rule_id_exit_two(self, tmp_path, capsys):
        root = make_repo(tmp_path, {})
        rc = lint_cli.main(
            [os.path.join(root, "dct_tpu"), "--root", root,
             "--select", "no-such-rule"]
        )
        assert rc == 2

    def test_write_baseline_flow(self, tmp_path, capsys):
        root = make_repo(tmp_path, {"dct_tpu/spmd.py": BAD_RANK0})
        args = [
            os.path.join(root, "dct_tpu"),
            "--root", root, "--select", "rank0-io",
        ]
        assert lint_cli.main(args + ["--write-baseline"]) == 0
        baseline_path = os.path.join(root, ".dct-lint-baseline.json")
        assert os.path.exists(baseline_path)
        # Unjustified baseline: suppresses the finding but hygiene fails.
        rc = lint_cli.main(args)
        assert rc == 1
        assert "justification" in capsys.readouterr().out
        # Justify every entry -> clean.
        bl = core.Baseline.load(baseline_path)
        for e in bl.entries:
            e.justification = "reviewed: fixture"
        bl.save(baseline_path)
        assert lint_cli.main(args) == 0

    def test_write_baseline_preserves_justifications(self, tmp_path, capsys):
        # Regenerating the baseline must keep hand-written
        # justifications for findings that still exist.
        root = make_repo(tmp_path, {"dct_tpu/spmd.py": BAD_RANK0})
        args = [
            os.path.join(root, "dct_tpu"),
            "--root", root, "--select", "rank0-io",
        ]
        assert lint_cli.main(args + ["--write-baseline"]) == 0
        baseline_path = os.path.join(root, ".dct-lint-baseline.json")
        bl = core.Baseline.load(baseline_path)
        bl.entries[0].justification = "reviewed: the real reason"
        bl.save(baseline_path)
        # A second grandfathering run (e.g. after a new violation).
        assert lint_cli.main(args + ["--write-baseline"]) == 0
        again = core.Baseline.load(baseline_path)
        assert [e.justification for e in again.entries] == [
            "reviewed: the real reason"
        ]
        assert lint_cli.main(args) == 0

    def test_parse_error_is_a_finding(self, tmp_path, capsys):
        root = make_repo(tmp_path, {"dct_tpu/broken.py": "def f(:\n"})
        rc = lint_cli.main([os.path.join(root, "dct_tpu"), "--root", root])
        assert rc == 1
        assert "[parse]" in capsys.readouterr().out

    def test_list_rules(self, capsys):
        assert lint_cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in (
            "rank0-io", "atomic-publish", "span-sync",
            "trace-purity", "env-registry", "event-names",
        ):
            assert rid in out


# ----------------------------------------------------------------------
# Acceptance: the real tree


class TestRepoTree:
    def test_repo_lints_clean_with_six_rules(self):
        """ISSUE 6 acceptance: `python -m dct_tpu.analysis.lint dct_tpu/`
        exits 0 on the final tree with >= 6 active rules."""
        root = core.default_root()
        baseline_path = os.path.join(root, ".dct-lint-baseline.json")
        baseline = (
            core.Baseline.load(baseline_path)
            if os.path.exists(baseline_path)
            else None
        )
        report = core.analyze(
            [os.path.join(root, "dct_tpu")], root=root, baseline=baseline
        )
        assert len(report.active_rules) >= 6
        assert report.ok, "\n".join(
            f"{f.path}:{f.line}: [{f.rule}] {f.message}"
            for f in report.findings
        )

    def test_committed_baseline_entries_all_justified(self):
        root = core.default_root()
        path = os.path.join(root, ".dct-lint-baseline.json")
        bl = core.Baseline.load(path)
        assert not bl.hygiene_findings()
