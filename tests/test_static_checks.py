"""Tier-1 shim wiring the static observability checks into pytest.

Two tools guard the JSONL contract (docs/incidents.md,
docs/observability.md):

- ``tools/schema_check.py`` — every record kind written anywhere has a
  frozen schema, and any UNREGISTERED kind is an error (runtime half);
- ``tools/lint_emitters.py`` — every emit SITE in the source tree uses
  a registered record/event kind (static half).

Running both here means adding a new record kind without registering
its schema fails tier-1 instead of silently producing unvalidatable
JSONL in the next soak run.
"""

import json
import os
import sys

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, _ROOT)

from tools import lint_emitters, schema_check  # noqa: E402


# ---------------------------------------------------------------------------
# lint_emitters: the whole tree is clean
# ---------------------------------------------------------------------------


def test_tree_has_no_unregistered_emit_sites():
    errors = lint_emitters.lint(
        [
            os.path.join(_ROOT, "dpwa_tpu"),
            os.path.join(_ROOT, "tools"),
        ]
    )
    assert errors == [], "\n".join(
        f"{e['file']}:{e['line']}: {e['error']}" for e in errors
    )


def test_lint_catches_unregistered_record_kind(tmp_path):
    bad = tmp_path / "bad_emitter.py"
    bad.write_text(
        'def emit(log):\n'
        '    log.write({"record": "made_up_kind", "step": 1})\n'
        '    log.log_event(1, "made_up_event")\n'
    )
    errors = lint_emitters.lint([str(bad)])
    msgs = " ".join(e["error"] for e in errors)
    assert len(errors) == 2
    assert "made_up_kind" in msgs and "made_up_event" in msgs


def test_lint_skips_dynamic_sites(tmp_path):
    ok = tmp_path / "dynamic.py"
    ok.write_text(
        'def emit(log, fields):\n'
        '    kind = fields.pop("event")\n'
        '    log.log_event(1, kind, **fields)\n'
        '    log.write({"record": fields["record"]})\n'
    )
    assert lint_emitters.lint([str(ok)]) == []


def test_event_call_registry_matches_schema_check():
    # The lint resolves its registries from schema_check — a drift
    # between the two halves is impossible by construction; pin it.
    assert lint_emitters.RECORD_KINDS is schema_check.RECORD_KINDS
    assert lint_emitters.EVENT_KINDS is schema_check.EVENT_KINDS


# ---------------------------------------------------------------------------
# schema_check: every registered kind validates, anything else fails
# ---------------------------------------------------------------------------


def _valid_records():
    return [
        {"step": 1, "t": 0.1},
        {"step": 1, "t": 0.1, "record": "event", "event": "rollback"},
        {
            "step": 1, "t": 0.1, "record": "alert", "kind": "peer_failure",
            "severity": "critical", "plane": "health", "value": 2.0,
            "threshold": 2.0, "peer": 3,
        },
        {
            "step": 1, "t": 0.1, "record": "incident", "id": "0:1",
            "status": "open", "kind": "peer_down", "severity": "critical",
            "peers": [3], "alerts": 1, "opened_step": 1, "me": 0,
        },
        {
            "record": "flight", "kind": "meta", "me": 0, "step": 9,
            "t": 0.5, "reason": "incident", "rounds": 8, "dumps": 1,
        },
        {
            "record": "flight", "kind": "round", "me": 0, "step": 9,
            "t": 0.5, "partner": 1, "outcome": "refused",
            "alerts": ["peer_failure"],
        },
        {"record": "loss", "step": 3, "t": 1.0, "me": 0, "loss": 2.3},
        {
            "record": "island", "round": 4, "island": "island0",
            "term": 1, "live": 4, "rel_rms": 0.02, "leader": 3,
            "wide_frames": 16,
        },
        {
            "step": 1, "t": 0.1, "record": "event",
            "event": "leader_failover", "island": "island0",
            "old_leader": 3, "peer": 1, "term": 1,
        },
    ]


@pytest.mark.parametrize("rec", _valid_records())
def test_registered_kinds_validate(rec):
    assert schema_check.check_record(rec) == []


def test_unregistered_record_kind_fails():
    errs = schema_check.check_record(
        {"step": 1, "t": 0.1, "record": "surprise"}
    )
    assert errs and "unknown record kind" in errs[0]


def test_unregistered_event_kind_fails():
    errs = schema_check.check_record(
        {"step": 1, "t": 0.1, "record": "event", "event": "surprise"}
    )
    assert any("unregistered event kind" in e for e in errs)


def test_alert_and_incident_schemas_are_closed():
    alert = {
        "step": 1, "t": 0.1, "record": "alert", "kind": "trust_burst",
        "severity": "critical", "plane": "trust", "value": 2.0,
        "threshold": 2.0, "stray": 1,
    }
    errs = schema_check.check_record(alert)
    assert any("unknown field 'stray'" in e for e in errs)
    inc = {
        "step": 1, "t": 0.1, "record": "incident", "id": "0:1",
        "status": "open", "kind": "byzantine", "severity": "critical",
        "peers": [2], "alerts": 1, "opened_step": 1, "me": 0,
        "stray": True,
    }
    errs = schema_check.check_record(inc)
    assert any("unknown field 'stray'" in e for e in errs)


def test_flight_unknown_kind_fails():
    errs = schema_check.check_record(
        {"record": "flight", "kind": "mystery", "me": 0, "step": 1,
         "t": 0.1}
    )
    assert errs and "unknown flight kind" in errs[0]


def test_check_file_counts_errors(tmp_path):
    path = tmp_path / "mixed.jsonl"
    with open(path, "w") as fh:
        for rec in _valid_records():
            fh.write(json.dumps(rec) + "\n")
        fh.write(json.dumps({"step": 1, "t": 0.1, "record": "nope"}) + "\n")
    n, errors = schema_check.check_file(str(path))
    assert n == len(_valid_records()) + 1
    assert len(errors) == 1


def test_cli_entrypoints(tmp_path):
    path = tmp_path / "ok.jsonl"
    with open(path, "w") as fh:
        for rec in _valid_records():
            fh.write(json.dumps(rec) + "\n")
    assert schema_check.main([str(path)]) == 0
    assert lint_emitters.main([str(tmp_path)]) == 0


# ---------------------------------------------------------------------------
# dpwalint: the full static-analysis suite (dpwa_tpu/analysis/)
# ---------------------------------------------------------------------------

from dpwa_tpu import analysis  # noqa: E402
from dpwa_tpu.analysis.core import SourceFile, load_baseline  # noqa: E402
from dpwa_tpu.analysis.determinism import DeterminismChecker  # noqa: E402
from dpwa_tpu.analysis.lock_discipline import (  # noqa: E402
    LockDisciplineChecker,
)
from dpwa_tpu.analysis.wire_protocol import WireProtocolChecker  # noqa: E402
from dpwa_tpu.analysis.config_keys import ConfigKeysChecker  # noqa: E402
from dpwa_tpu.analysis.emit_kinds import EmitKindsChecker  # noqa: E402
from dpwa_tpu.analysis.device_roundtrip import (  # noqa: E402
    DeviceRoundtripChecker,
)
from dpwa_tpu.analysis.zerocopy import ZeroCopyChecker  # noqa: E402

_BASELINE = os.path.join(_ROOT, "tools", "dpwalint_baseline.json")


def _run_on_source(checkers, named_sources):
    """Run checkers over in-memory {path: source} fixtures."""
    files = [SourceFile(p, s) for p, s in named_sources.items()]
    return analysis.run_checkers(checkers, files, {})


def test_dpwalint_tree_is_clean():
    """The tier-1 gate: zero non-baselined findings on the whole tree,
    and no stale baseline entries (the ratchet only shrinks)."""
    targets = [
        os.path.join(_ROOT, "dpwa_tpu"),
        os.path.join(_ROOT, "tools"),
    ]
    files = analysis.load_files(analysis.iter_py_files(targets))
    result = analysis.run_checkers(
        analysis.all_checkers(), files, load_baseline(_BASELINE)
    )
    assert result.errors == [], "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in result.errors
    )
    assert result.stale_baseline == []


def test_rule_ids_are_frozen():
    # Adding a rule is fine (extend this set in the same commit);
    # renaming or deleting one orphans suppressions/baselines silently.
    assert analysis.RULE_IDS == frozenset({
        "lock-discipline",
        "det-random",
        "det-time",
        "det-dict-order",
        "det-tag-literal",
        "wire-magic",
        "wire-struct",
        "config-unknown-key",
        "config-undocumented-key",
        "config-unparsed-block",
        "emit-kind",
        "zerocopy-tobytes",
        "device-host-roundtrip",
        "dpwalint-annotation",
    })


# --- lock-discipline fixtures ---

_LOCK_BAD = '''
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0
        self._t = threading.Thread(target=self._run)

    def _run(self):
        self._count += 1  # spawned-thread store, no lock

    def poll(self):
        return self._count  # main-thread read, no lock
'''

_LOCK_GOOD = _LOCK_BAD.replace(
    "        self._count += 1  # spawned-thread store, no lock",
    "        with self._lock:\n            self._count += 1",
).replace(
    "        return self._count  # main-thread read, no lock",
    "        with self._lock:\n            return self._count",
)


def test_lock_discipline_flags_unguarded_cross_thread_state():
    result = _run_on_source(
        [LockDisciplineChecker()], {"fix/bad.py": _LOCK_BAD}
    )
    assert [f.symbol for f in result.errors] == ["Worker._count"]
    assert "thread domains" in result.errors[0].message


def test_lock_discipline_passes_guarded_state():
    result = _run_on_source(
        [LockDisciplineChecker()], {"fix/good.py": _LOCK_GOOD}
    )
    assert result.errors == []


def test_lock_discipline_honors_double_buffered_and_thread_root():
    src = '''
import threading

class Handoff:
    def __init__(self):
        # dpwalint: double_buffered(_box) -- join-ordered handoff
        self._box = None
        self._t = threading.Thread(target=self._fill)

    def _fill(self):
        self._box = 1

    def take(self):
        return self._box
'''
    result = _run_on_source([LockDisciplineChecker()], {"fix/h.py": src})
    assert result.errors == []
    # thread_root makes an invisible entry point visible: same class,
    # no spawn, but an annotated hook gives the second domain
    src2 = '''
class Hooked:
    def __init__(self):
        self._n = 0

    # dpwalint: thread_root(rx)
    def on_frame(self):
        self._n += 1

    def total(self):
        return self._n
'''
    result2 = _run_on_source([LockDisciplineChecker()], {"fix/h2.py": src2})
    assert [f.symbol for f in result2.errors] == ["Hooked._n"]


def test_deleting_a_guarded_by_annotation_fails_the_real_tree():
    """The annotations in shipped code are load-bearing: stripping the
    guarded_by on Scoreboard._clock must resurface the finding."""
    path = os.path.join(_ROOT, "dpwa_tpu", "health", "scoreboard.py")
    with open(path, "r", encoding="utf-8") as fh:
        text = fh.read()
    assert "# dpwalint: guarded_by(_lock)" in text
    stripped = text.replace("    # dpwalint: guarded_by(_lock)\n", "")
    result = _run_on_source(
        [LockDisciplineChecker()],
        {"dpwa_tpu/health/scoreboard.py": stripped},
    )
    assert any(f.symbol == "Scoreboard._round" for f in result.errors)


# --- determinism fixtures ---


def test_determinism_flags_ambient_randomness_and_dict_order():
    src = '''
import random
import time

def pick(peers, opts):
    if time.time() > 100:
        return 0
    for k, v in opts.items():
        pass
    return random.choice(peers)
'''
    result = _run_on_source(
        [DeterminismChecker()], {"dpwa_tpu/trust/pick.py": src}
    )
    rules = sorted(f.rule for f in result.errors)
    assert rules == ["det-dict-order", "det-random", "det-time"]


def test_determinism_allows_sorted_seeded_and_aggregates():
    src = '''
import numpy as np

def pick(peers, opts, seed):
    rng = np.random.default_rng(seed)
    total = sum(opts.values())
    for k, v in sorted(opts.items()):
        pass
    return rng, total
'''
    result = _run_on_source(
        [DeterminismChecker()], {"dpwa_tpu/trust/pick.py": src}
    )
    assert result.errors == []


def test_determinism_ignores_non_decision_modules():
    src = "import random\nx = random.random()\n"
    result = _run_on_source(
        [DeterminismChecker()], {"dpwa_tpu/parallel/tcp_helper.py": src}
    )
    assert result.errors == []


def test_determinism_covers_partial_view_as_decision_module():
    # The partial-view sampler draws peers and shuffles reservoirs; if it
    # ever regressed to ambient randomness, digests would diverge across
    # seeded reruns. Pin that dpwalint treats it as a decision path.
    src = "import random\nx = random.random()\n"
    result = _run_on_source(
        [DeterminismChecker()], {"dpwa_tpu/membership/partial_view.py": src}
    )
    assert [f.rule for f in result.errors] == ["det-random"]


def test_tag_literal_flagged_everywhere():
    src = '''
from dpwa_tpu.parallel.schedules import _pair_key
from dpwa_tpu.utils import tags

def draw(seed, step, pid):
    good = _pair_key(seed, step, pid, tags.TAG_FAULT)
    return _pair_key(seed, step, pid, 7)
'''
    result = _run_on_source(
        [DeterminismChecker()], {"dpwa_tpu/anywhere.py": src}
    )
    assert [f.rule for f in result.errors] == ["det-tag-literal"]
    assert result.errors[0].symbol == "_pair_key:7"


# --- wire-protocol fixtures ---


def test_wire_magic_flagged_outside_registry():
    src = 'MAGIC = b"DPWX"\nOTHER = b"not-a-magic"\n'
    result = _run_on_source(
        [WireProtocolChecker()], {"dpwa_tpu/parallel/rogue.py": src}
    )
    assert [f.rule for f in result.errors] == ["wire-magic"]


def test_wire_struct_flagged_on_wire_path_only():
    src = 'import struct\nHDR = struct.Struct("<4sB")\n'
    on_wire = _run_on_source(
        [WireProtocolChecker()], {"dpwa_tpu/parallel/tcp.py": src}
    )
    assert [f.rule for f in on_wire.errors] == ["wire-struct"]
    off_wire = _run_on_source(
        [WireProtocolChecker()], {"dpwa_tpu/utils/pack_helper.py": src}
    )
    assert off_wire.errors == []


def test_wire_registry_itself_is_exempt():
    with open(
        os.path.join(_ROOT, "dpwa_tpu", "parallel", "protocol_constants.py"),
        "r", encoding="utf-8",
    ) as fh:
        src = fh.read()
    result = _run_on_source(
        [WireProtocolChecker()],
        {"dpwa_tpu/parallel/protocol_constants.py": src},
    )
    assert result.errors == []


# --- zero-copy fixtures ---

_ZC_BAD = (
    "def decode(raw):\n"
    "    body = raw[4:].tobytes()\n"
    "    owned = bytes(raw[:4])\n"
    "    return body, owned\n"
)


def test_zerocopy_flags_copies_on_frame_path_only():
    on_path = _run_on_source(
        [ZeroCopyChecker()], {"dpwa_tpu/ops/quantize.py": _ZC_BAD}
    )
    assert [f.rule for f in on_path.errors] == [
        "zerocopy-tobytes", "zerocopy-tobytes"
    ]
    # The symbol carries the enclosing def and the copy's spelling.
    assert sorted(f.symbol for f in on_path.errors) == [
        "decode:.tobytes()", "decode:bytes(...)"
    ]
    off_path = _run_on_source(
        [ZeroCopyChecker()], {"dpwa_tpu/health/chaos.py": _ZC_BAD}
    )
    assert off_path.errors == []


def test_zerocopy_honors_standard_suppression_grammar():
    src = (
        "def snapshot(vec):\n"
        "    return vec.tobytes()  "
        "# dpwalint: ignore[zerocopy-tobytes] -- fixture proving the grammar\n"
    )
    result = _run_on_source(
        [ZeroCopyChecker()], {"dpwa_tpu/parallel/tcp.py": src}
    )
    assert result.errors == []
    assert len(result.suppressed) == 1


def test_zerocopy_passes_view_clean_decode():
    src = (
        "import numpy as np\n"
        "def decode(raw):\n"
        "    n = int(raw[:8].view('<u8')[0])\n"
        "    return raw[8:8 + 4 * n].view('<f4')\n"
    )
    result = _run_on_source(
        [ZeroCopyChecker()], {"dpwa_tpu/ops/shard.py": src}
    )
    assert result.errors == []


# --- device-host round-trip fixtures ---

_DRT_BAD = (
    "import numpy as np\n"
    "import jax.numpy as jnp\n"
    "def merge(dev, frame):\n"
    "    host = np.asarray(dev)\n"
    "    up = jnp.asarray(frame)\n"
    "    return host.tobytes(), up\n"
)


def test_device_roundtrip_flags_crossings_on_merge_path_only():
    on_path = _run_on_source(
        [DeviceRoundtripChecker()], {"dpwa_tpu/device/engine.py": _DRT_BAD}
    )
    assert [f.rule for f in on_path.errors] == [
        "device-host-roundtrip"
    ] * 3
    assert sorted(f.symbol for f in on_path.errors) == [
        "merge:.tobytes()", "merge:jnp.asarray(...)",
        "merge:np.asarray(...)",
    ]
    # The host exchange path in numpy-land is NOT merge path.
    off_path = _run_on_source(
        [DeviceRoundtripChecker()], {"dpwa_tpu/ops/quantize.py": _DRT_BAD}
    )
    assert off_path.errors == []


def test_device_roundtrip_scopes_tcp_to_device_exchange_methods():
    src = (
        "import numpy as np\n"
        "class T:\n"
        "    def exchange(self, vec):\n"
        "        return np.asarray(vec)\n"
        "    def exchange_on_device(self, dev):\n"
        "        return np.asarray(dev)\n"
    )
    result = _run_on_source(
        [DeviceRoundtripChecker()], {"dpwa_tpu/parallel/tcp.py": src}
    )
    assert [f.symbol for f in result.errors] == [
        "exchange_on_device:np.asarray(...)"
    ]


def test_device_roundtrip_honors_standard_suppression_grammar():
    # The handoff.to_host shape: a standalone ignore comment covering
    # the next code line — the one sanctioned readback boundary.
    src = (
        "import numpy as np\n"
        "def to_host(dev):\n"
        "    # dpwalint: ignore[device-host-roundtrip] -- fixture: the boundary itself\n"
        "    return np.asarray(dev)\n"
    )
    result = _run_on_source(
        [DeviceRoundtripChecker()], {"dpwa_tpu/device/handoff.py": src}
    )
    assert result.errors == []
    assert len(result.suppressed) == 1


def test_device_roundtrip_passes_handoff_routed_merge():
    src = (
        "from dpwa_tpu.device import handoff\n"
        "def merge(dev, frame, fn, t):\n"
        "    return fn(dev, handoff.to_device(frame), t)\n"
    )
    result = _run_on_source(
        [DeviceRoundtripChecker()], {"dpwa_tpu/device/engine.py": src}
    )
    assert result.errors == []


# --- config-keys fixtures ---

_CONFIG_FIXTURE = '''
"""Schema doc mentions alpha and beta."""
import dataclasses

@dataclasses.dataclass
class ProtoConfig:
    alpha: float = 0.5
    beta: int = 1

@dataclasses.dataclass
class DpwaConfig:
    proto: ProtoConfig = ProtoConfig()

def config_from_dict(raw):
    return DpwaConfig(proto=ProtoConfig(**dict(raw.get("proto") or {})))
'''


def test_config_unknown_key_flagged(tmp_path):
    reader = "def go(config):\n    return config.proto.gamma\n"
    files = {
        str(tmp_path / "dpwa_tpu/config.py"): _CONFIG_FIXTURE,
        str(tmp_path / "dpwa_tpu/reader.py"): reader,
    }
    result = _run_on_source([ConfigKeysChecker()], files)
    assert [f.rule for f in result.errors] == ["config-unknown-key"]
    assert result.errors[0].symbol == "proto.gamma"


def test_config_known_key_and_parsed_block_pass(tmp_path):
    reader = "def go(config):\n    return config.proto.alpha\n"
    files = {
        str(tmp_path / "dpwa_tpu/config.py"): _CONFIG_FIXTURE,
        str(tmp_path / "dpwa_tpu/reader.py"): reader,
    }
    result = _run_on_source([ConfigKeysChecker()], files)
    assert result.errors == []


def test_config_unparsed_block_flagged(tmp_path):
    broken = _CONFIG_FIXTURE.replace('raw.get("proto")', "raw.get(None)")
    files = {str(tmp_path / "dpwa_tpu/config.py"): broken}
    result = _run_on_source([ConfigKeysChecker()], files)
    assert any(f.rule == "config-unparsed-block" for f in result.errors)


def test_config_undocumented_key_flagged(tmp_path):
    undocumented = _CONFIG_FIXTURE.replace(
        '"""Schema doc mentions alpha and beta."""',
        '"""Schema doc mentions alpha only."""',
    )
    files = {str(tmp_path / "dpwa_tpu/config.py"): undocumented}
    result = _run_on_source([ConfigKeysChecker()], files)
    assert [f.symbol for f in result.errors if
            f.rule == "config-undocumented-key"] == ["proto.beta"]


# --- emit-kind fixture (framework port of the legacy pass) ---


def test_emit_kind_checker_matches_legacy_behaviour():
    bad = 'def emit(log):\n    log.write({"record": "made_up_kind"})\n'
    result = _run_on_source([EmitKindsChecker()], {"fix/e.py": bad})
    assert [f.rule for f in result.errors] == ["emit-kind"]
    ok = 'def emit(log):\n    log.write({"record": "health"})\n'
    result2 = _run_on_source([EmitKindsChecker()], {"fix/e2.py": ok})
    assert result2.errors == []


# --- suppression / baseline mechanics ---


def test_suppression_requires_a_reason():
    src = (
        "import struct\n"
        '# dpwalint: ignore[wire-struct]\n'
        'HDR = struct.Struct("<4sB")\n'
    )
    result = _run_on_source(
        [WireProtocolChecker()], {"dpwa_tpu/parallel/tcp.py": src}
    )
    rules = sorted(f.rule for f in result.errors)
    # the bare ignore is itself a finding AND does not suppress
    assert rules == ["dpwalint-annotation", "wire-struct"]


def test_suppression_with_reason_suppresses():
    src = (
        "import struct\n"
        "# dpwalint: ignore[wire-struct] -- fixture proving the grammar\n"
        'HDR = struct.Struct("<4sB")\n'
    )
    result = _run_on_source(
        [WireProtocolChecker()], {"dpwa_tpu/parallel/tcp.py": src}
    )
    assert result.errors == []
    assert [r for _, r in result.suppressed] == [
        "fixture proving the grammar"
    ]


def test_stale_baseline_entry_fails():
    files = [SourceFile("fix/clean.py", "x = 1\n")]
    result = analysis.run_checkers(
        [WireProtocolChecker()], files,
        {"wire-magic:fix/clean.py:b'DPWZ'": "long gone"},
    )
    assert result.errors == []
    assert result.stale_baseline == ["wire-magic:fix/clean.py:b'DPWZ'"]
    assert result.exit_code == 1


# --- registry pins: unregistering a magic or tag fails tier-1 ---


def test_wire_magics_are_pinned():
    from dpwa_tpu.parallel import protocol_constants as pc
    assert pc.registered_magics() == {
        b"DPWA?": "blob_request",
        b"DPWA@": "state_request",
        b"DPWA!": "relay_request",
        b"DPWA": "blob_frame",
        b"DPWS": "state_frame",
        b"DPWR": "relay_report",
        b"DPWB": "busy_nack",
        b"DPWM": "membership_digest",
        b"DPWT": "obs_section",
        b"DPST": "state_pack",
    }
    # layout contracts ride along: a format change is a wire break
    assert pc.BLOB_HDR_FMT == "<4sBBddQ"
    assert pc.STATE_HDR_FMT == "<4sBIQQII"
    assert sorted(pc.registered_payload_codes()) == [0, 1, 2, 3, 4, 5, 6]
    assert pc.registered_payload_codes()[5] == "topk_delta"
    assert pc.registered_payload_codes()[6] == "shard"
    assert pc.SHARD_HDR_FMT == "<IIQB"
    assert pc.RELAY_OUTCOME_NAMES == (
        "success", "timeout", "refused", "short_read", "corrupt", "busy",
    )


def test_threefry_tags_are_pinned():
    from dpwa_tpu.utils import tags
    assert tags.registered_tags() == {
        0: "participation_draw",
        1: "fault_draw",
        2: "pool_branch_draw",
        3: "fallback_draw",
        4: "backoff_jitter_draw",
        5: "bootstrap_donor_draw",
        6: "relay_probe_draw",
        7: "heal_donor_draw",
        8: "degrade_shed_draw",
        9: "replica_sketch_draw",
        10: "churn_leave_draw",
        11: "churn_join_draw",
        12: "churn_cohort_draw",
        13: "churn_restart_draw",
        14: "leader_draw",
        15: "island_churn_draw",
        16: "chaos:drop",
        17: "chaos:delay",
        18: "chaos:throttle",
        19: "chaos:truncate",
        20: "chaos:corrupt",
        21: "chaos:partition",
        22: "chaos:partition_side",
        23: "chaos:byz_sign",
        24: "chaos:byz_scale",
        25: "chaos:byz_replay",
        26: "chaos:byz_zero",
        27: "chaos:stall",
        28: "chaos:stall_len",
        29: "chaos:bandwidth_flap",
        30: "chaos:bandwidth_rate",
        32: "shard_draw",
        33: "async_drain_draw",
        34: "view_sample_draw",
        35: "passive_shuffle_draw",
        36: "data_shuffle_draw",
        37: "tune_jitter_draw",
    }
    assert tags.CHAOS_TAG_BASE == 16
    # Second control-plane block: 0..15 is full, 16..31 belongs to the
    # chaos fault-kind streams, so new control draws allocate from 32 up.
    assert tags.CONTROL_TAG_BASE_2 == 32
    assert tags.TAG_SHARD == 32
    assert tags.TAG_ASYNC_DRAIN == 33
    assert tags.TAG_VIEW_SAMPLE == 34
    assert tags.TAG_PASSIVE_SHUFFLE == 35
    assert tags.TAG_DATA_SHUFFLE == 36
    assert tags.TAG_TUNE_JITTER == 37
    assert tags.CHAOS_KIND_BANDWIDTH_FLAP == 13
    assert tags.CHAOS_KIND_BANDWIDTH_RATE == 14


def test_tag_collision_raises():
    from dpwa_tpu.utils import tags
    with pytest.raises(ValueError, match="collision"):
        tags._register("imposter", tags.TAG_FAULT)
    with pytest.raises(ValueError, match="collision"):
        tags._register_chaos_kind("imposter", 0)


def test_magic_collision_raises():
    from dpwa_tpu.parallel import protocol_constants as pc
    with pytest.raises(ValueError, match="collision"):
        pc._magic("imposter", pc.BLOB_MAGIC)
