"""Subprocess smoke tests for ``launch/serve.py`` — flow (anytime artifact,
budget routing, --strict-nfe) and decode modes on the smoke config."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.integration

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *argv],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


@pytest.fixture(scope="module")
def anytime_artifact(tmp_path_factory):
    """An (untrained) anytime artifact on disk — serving must not retrain."""
    from repro.core.anytime import init_anytime
    from repro.solvers import SolverArtifact, SolverSpec

    path = str(tmp_path_factory.mktemp("zoo") / "anytime.msgpack")
    budgets = (2, 4)
    SolverArtifact(
        spec=SolverSpec("midpoint", mode="anytime", budgets=budgets),
        params=init_anytime(None, budgets),
        val_psnr=0.0,
        provenance={"arch": "yi-6b", "scheduler": "fm_ot"},
    ).save(path)
    return path


def test_flow_mode_serves_mixed_budgets_from_one_artifact(anytime_artifact):
    res = _run("--arch", "yi-6b", "--mode", "flow",
               "--solver-artifact", anytime_artifact,
               "--request-budgets", "2,4,8", "--requests", "3",
               "--batch", "2", "--seq", "4")
    assert res.returncode == 0, res.stderr
    out = res.stdout
    assert "no retraining" in out
    assert "distilling" not in out               # zero re-distillation
    assert "(2 NFE)" in out and "(4 NFE)" in out
    # the unserved budget 8 is routed to the nearest one, loudly
    assert "WARNING: requested NFE 8" in out
    assert "using nearest budget 4" in out


def test_flow_mode_explicit_nfe_is_routed_not_ignored(anytime_artifact):
    """Regression: --nfe used to be silently ignored when an artifact was
    loaded; it must route through nearest-budget selection with a WARNING."""
    res = _run("--arch", "yi-6b", "--mode", "flow",
               "--solver-artifact", anytime_artifact, "--nfe", "16",
               "--requests", "1", "--batch", "2", "--seq", "4")
    assert res.returncode == 0, res.stderr
    assert "WARNING: requested NFE 16" in res.stdout
    assert "using nearest budget 4" in res.stdout
    assert "(4 NFE)" in res.stdout


def test_flow_mode_strict_nfe_rejects_unserved_budget(anytime_artifact):
    res = _run("--arch", "yi-6b", "--mode", "flow",
               "--solver-artifact", anytime_artifact, "--strict-nfe",
               "--request-budgets", "8", "--requests", "1",
               "--batch", "2", "--seq", "4")
    assert res.returncode != 0
    assert "--strict-nfe" in res.stderr + res.stdout


def test_flow_mode_gateway_coalesces_requests(anytime_artifact):
    """--gateway serves the request stream through the batching gateway:
    same-budget requests coalesce (4 requests -> 2 batches here), and the
    summary line reports batch/occupancy/NFE metrics."""
    res = _run("--arch", "yi-6b", "--mode", "flow",
               "--solver-artifact", anytime_artifact, "--gateway",
               "--max-batch", "2", "--max-wait-ms", "200",
               "--request-budgets", "2,4,2,4", "--requests", "4",
               "--batch", "2", "--seq", "4")
    assert res.returncode == 0, res.stderr
    out = res.stdout
    assert "gateway stats: done=4/4" in out
    assert "batches=2" in out
    assert "request 0: served 2 NFE" in out
    assert "request 1: served 4 NFE" in out
    assert "batch 2/2" in out                    # full bucket, no padding


def test_flow_mode_gateway_records_budget_drift(anytime_artifact):
    """An unserved budget is routed AND the (requested, served) pair is in
    the response metadata — printed per request, not only a warning."""
    res = _run("--arch", "yi-6b", "--mode", "flow",
               "--solver-artifact", anytime_artifact, "--gateway",
               "--max-batch", "2", "--max-wait-ms", "50",
               "--request-budgets", "8", "--requests", "2",
               "--batch", "2", "--seq", "4")
    assert res.returncode == 0, res.stderr
    assert "served 4 NFE (requested 8)" in res.stdout


def test_flow_mode_gateway_mesh_host(anytime_artifact):
    """--mesh host runs gateway batches through the sharded execution path
    (1x1 mesh on CPU) end-to-end."""
    res = _run("--arch", "yi-6b", "--mode", "flow",
               "--solver-artifact", anytime_artifact, "--gateway",
               "--mesh", "host", "--max-batch", "2", "--max-wait-ms", "50",
               "--request-budgets", "2", "--requests", "2",
               "--batch", "2", "--seq", "4")
    assert res.returncode == 0, res.stderr
    assert "gateway stats: done=2/2" in res.stdout


def test_flow_mode_fleet_gateway(anytime_artifact):
    """--fleet 2 serves the stream through a two-host FleetGateway: all
    requests complete and the summary reports the fleet routing stats."""
    res = _run("--arch", "yi-6b", "--mode", "flow",
               "--solver-artifact", anytime_artifact, "--gateway",
               "--fleet", "2", "--max-batch", "2", "--max-wait-ms", "50",
               "--request-budgets", "2,4", "--requests", "4",
               "--batch", "2", "--seq", "4")
    assert res.returncode == 0, res.stderr
    out = res.stdout
    assert "gateway stats: done=4/4" in out
    assert "fleet hosts=2" in out
    assert "routed:" in out


def test_flow_mode_continuous_gateway(anytime_artifact):
    """--continuous serves the stream through the continuous-batching
    gateway: requests ride shared trajectories and the summary reports
    trajectory/join/slot-occupancy metrics."""
    res = _run("--arch", "yi-6b", "--mode", "flow",
               "--solver-artifact", anytime_artifact, "--gateway",
               "--continuous", "--max-slots", "2", "--max-wait-ms", "50",
               "--request-budgets", "2,4", "--requests", "4",
               "--batch", "2", "--seq", "4")
    assert res.returncode == 0, res.stderr
    out = res.stdout
    assert "gateway stats: done=4/4" in out
    assert "traj=" in out and "slot_occ=" in out


def test_decode_mode_smoke():
    res = _run("--arch", "yi-6b", "--mode", "decode", "--batch", "2",
               "--steps", "3", "--slots", "16")
    assert res.returncode == 0, res.stderr
    assert "decoded 3 tokens x 2 seqs" in res.stdout


def test_decode_mode_gateway_continuous_batching():
    """--mode decode --gateway serves concurrent prompts through the
    continuous-batching decode gateway: mixed lengths on a small slot pool
    force mid-flight admission (joins) and the stats line reports
    tokens/occupancy."""
    res = _run("--arch", "yi-6b", "--mode", "decode", "--gateway",
               "--max-slots", "2", "--requests", "5",
               "--decode-lengths", "6,2,4", "--slots", "16")
    assert res.returncode == 0, res.stderr
    out = res.stdout
    assert out.count("request ") == 5
    assert "decode gateway stats: done=5/5" in out
    assert "slot_occ=" in out and "tok/s=" in out
    # a freed slot was refilled mid-flight at least once
    assert "joins=0" not in out


def test_profile_tuned_reexecs_and_serves():
    """--profile tuned re-execs under its XLA flags and the child runs: the
    installed XLA aborts on any flag it does not know."""
    r = _run("--arch", "rwkv6-7b", "--mode", "decode", "--batch", "1",
             "--steps", "2", "--profile", "tuned")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "re-exec under 'tuned' profile" in r.stdout
    assert "decoded 2 tokens" in r.stdout
