"""End-to-end acceptance legs of the chaos-certified harness (ISSUE 19).

Each leg drives the REAL stack — TcpTransport, trust, health, obs,
recovery — through real optimizer steps and judges the outcome in
time-to-quality terms.  The legs are seconds-to-a-
minute soaks, so they ride under ``@pytest.mark.slow``; tier-1 covers
the same machinery through the fast mini-train in
tests/test_run_harness.py."""

import pytest

from dpwa_tpu.run.legs import (
    LegResult,
    byzantine_leg,
    clean_leg,
    crash_leg,
    lora_leg,
    straggler_leg,
)


def test_leg_result_record_shape():
    res = LegResult(
        leg="clean", ok=True, verdict={"converged_ok": True},
        summary={}, report={}, workdir="/tmp/x",
    )
    rec = res.to_record()
    assert rec == {
        "leg": "clean", "ok": True, "verdict": {"converged_ok": True}
    }


@pytest.mark.slow
def test_clean_leg_time_to_quality(tmp_path):
    res = clean_leg(str(tmp_path), n_peers=4, base_port=48100)
    assert res.ok, res.verdict
    v = res.verdict
    assert v["gossip_steps_to_target"] is not None
    assert v["single_steps_to_target"] is not None
    assert v["incident_clusters"] == 0


@pytest.mark.slow
def test_byzantine_leg_quarantine_and_bracket(tmp_path):
    res = byzantine_leg(str(tmp_path), base_port=48200)
    assert res.ok, res.verdict
    v = res.verdict
    # trust fired within K rounds of the attack window opening
    assert v["quarantine_time_ok"], v
    # exactly one incident cluster, and it brackets the dent
    assert v["single_cluster_ok"] and v["incident_bracket_ok"], v
    assert v["reconverged_ok"], v


@pytest.mark.slow
def test_crash_leg_checkpoint_rejoin(tmp_path):
    res = crash_leg(str(tmp_path), base_port=48300)
    assert res.ok, res.verdict
    v = res.verdict
    assert v["crashed_ok"] and v["restarted_ok"], v
    # restart resumed from a periodic checkpoint, not step 0
    assert v["checkpoint_restored_ok"], v
    assert v["rejoined_ok"], v


@pytest.mark.slow
def test_straggler_leg_unthrottled(tmp_path):
    res = straggler_leg(str(tmp_path), base_port=48400)
    assert res.ok, res.verdict
    assert res.verdict["unthrottled_wall_ok"], res.verdict


@pytest.mark.slow
def test_lora_leg_small_frames(tmp_path):
    res = lora_leg(str(tmp_path), base_port=48500)
    assert res.ok, res.verdict
    v = res.verdict
    assert v["adapter_only_ok"] and v["exchanged_ok"], v
