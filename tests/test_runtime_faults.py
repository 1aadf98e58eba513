"""Fault-tolerance layer (utils/runtime.py) under injected faults — all
CPU-only, subprocess-based where the failure mode is a hang or a death.

Scenarios: probe timeout on a hung backend; dryrun_multichip failing on an
unprobeable backend or a failed child and drilling on the CPU only when
the backend has too few devices; bootstrap retry-then-succeed,
retry-then-raise (cluster expected) and no join attempt on a single host;
crash-surviving JSONL section records.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from distributed_embeddings_tpu.parallel import bootstrap
from distributed_embeddings_tpu.utils import runtime

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv(runtime.FAULT_ENV, raising=False)
    runtime.reset_fault_counts()
    yield
    runtime.reset_fault_counts()


# ------------------------------------------------------- fault_point/retry


def test_fault_point_modes(monkeypatch):
    runtime.fault_point("nothing_set")  # no env: no-op

    monkeypatch.setenv(runtime.FAULT_ENV, "raise:ckpt")
    with pytest.raises(runtime.FaultInjected):
        runtime.fault_point("ckpt")
    runtime.fault_point("other_point")  # non-matching point passes

    # budgeted raise: first 2 calls fail, third passes
    runtime.reset_fault_counts()
    monkeypatch.setenv(runtime.FAULT_ENV, "raise:join:2")
    for _ in range(2):
        with pytest.raises(runtime.FaultInjected):
            runtime.fault_point("join")
    runtime.fault_point("join")

    monkeypatch.setenv(runtime.FAULT_ENV, "slow:io:0.05")
    t0 = time.monotonic()
    runtime.fault_point("io")
    assert time.monotonic() - t0 >= 0.05


def test_die_and_hang_at_parsing(monkeypatch):
    """die@/hang@ positional drills parse like the other @-style specs,
    combine with them, and never leak into the mode:point spec list (a
    die@5 must not warn as a malformed die:<point> entry)."""
    assert runtime.die_steps() == ()
    assert runtime.hang_steps() == ()
    monkeypatch.setenv(runtime.FAULT_ENV, "die@5")
    assert runtime.die_steps() == (5,)
    monkeypatch.setenv(runtime.FAULT_ENV, "hang@3,hang@9")
    assert runtime.hang_steps() == (3, 9)
    monkeypatch.setenv(runtime.FAULT_ENV,
                       "oovflood@2,die@4,burst@1,hang@7,corrupt@ckpt")
    assert runtime.die_steps() == (4,)
    assert runtime.hang_steps() == (7,)
    assert runtime.oovflood_steps() == (2,)
    assert runtime.burst_steps() == (1,)
    assert runtime._fault_specs() == []  # all skipped, none malformed
    # malformed positions warn and drop (like nan@/burst@)
    monkeypatch.setenv(runtime.FAULT_ENV, "die@notanint,die@2")
    assert runtime.die_steps() == (2,)
    # the mode:point grammar is untouched: hang:point still parses as a
    # fault_point spec, not a positional drill
    monkeypatch.setenv(runtime.FAULT_ENV, "hang:backend:60,hang@4")
    assert runtime._fault_specs() == [("hang", "backend", "60")]
    assert runtime.hang_steps() == (4,)


def test_retry_succeeds_after_transient_failures():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert runtime.retry(flaky, max_attempts=5, base_delay_s=0.01) == "ok"
    assert calls["n"] == 3


def test_retry_attempt_budget_reraises():
    def always():
        raise ValueError("permanent")

    with pytest.raises(ValueError):
        runtime.retry(always, max_attempts=2, base_delay_s=0.01)


def test_retry_deadline_raises_deadline_exceeded():
    def always():
        raise ValueError("permanent")

    with pytest.raises(runtime.DeadlineExceeded):
        runtime.retry(always, deadline_s=0.05, base_delay_s=0.05)


def test_deadline_interrupts_sleep():
    t0 = time.monotonic()
    with pytest.raises(runtime.DeadlineExceeded):
        with runtime.deadline(0.2, label="nap"):
            time.sleep(30)
    assert time.monotonic() - t0 < 5


# ------------------------------------------------------------------- probe


def test_probe_backend_cpu_reports_devices():
    probe = runtime.probe_backend(timeout_s=120, platform="cpu")
    assert probe.ok, probe
    assert probe.platform == "cpu"
    assert probe.device_count >= 1


def test_probe_backend_hang_times_out(monkeypatch):
    monkeypatch.setenv(runtime.FAULT_ENV, "hang:backend:60")
    probe = runtime.probe_backend(timeout_s=2)
    assert not probe.ok
    assert "timed out" in probe.error
    assert probe.elapsed_s < 30


def test_dryrun_multichip_unprobeable_backend_fails(monkeypatch):
    """A backend that cannot be probed fails the dryrun — fast, and without
    a CPU pass standing in for it."""
    monkeypatch.setenv(runtime.FAULT_ENV, "hang:backend:120")
    monkeypatch.syspath_prepend(_REPO)
    import __graft_entry__ as g

    t0 = time.monotonic()
    with pytest.raises(runtime.BackendUnavailable):
        g.dryrun_multichip(2, probe_timeout_s=3, child_timeout_s=420)
    assert time.monotonic() - t0 < 60


def test_dryrun_multichip_too_few_devices_is_a_cpu_drill(monkeypatch):
    """Fewer devices than asked for: the step runs as a CPU drill on
    virtual devices, and the return value says it was not the backend."""
    monkeypatch.syspath_prepend(_REPO)
    import __graft_entry__ as g

    one = runtime.BackendProbe(ok=True, platform="cpu", device_count=1,
                               elapsed_s=0.0)
    assert g.dryrun_multichip(2, probe=one, child_timeout_s=420) is False


def test_dryrun_multichip_child_failure_on_the_backend_fails(monkeypatch):
    """The probe promised devices the child does not find: the child's
    failure is the dryrun's failure — no retry on a CPU mesh."""
    monkeypatch.syspath_prepend(_REPO)
    import __graft_entry__ as g

    liar = runtime.BackendProbe(ok=True, platform="cpu", device_count=64,
                                elapsed_s=0.0)
    with pytest.raises(subprocess.CalledProcessError):
        g.dryrun_multichip(64, probe=liar, child_timeout_s=420)


# --------------------------------------------------------------- bootstrap


def test_bootstrap_single_host_never_joins(monkeypatch):
    """No cluster announced: initialize() returns False without a join
    attempt (on a sealed single-host TPU machine jax.distributed's
    auto-detection may wait on a metadata service)."""
    for var in ("SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE", "PMI_SIZE",
                "JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                "MEGASCALE_COORDINATOR_ADDRESS", "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(var, raising=False)
    calls = []
    monkeypatch.setattr(bootstrap, "_join_runtime",
                        lambda *a: calls.append(a))
    assert bootstrap.initialize() is False
    assert not calls


def test_bootstrap_retries_then_succeeds(monkeypatch):
    monkeypatch.setenv("SLURM_NTASKS", "2")  # cluster expected
    calls = {"n": 0}

    def flaky(*a):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("coordinator warming up")

    monkeypatch.setattr(bootstrap, "_join_runtime", flaky)
    assert bootstrap.initialize(retries=3) is True
    assert calls["n"] == 3


def test_bootstrap_cluster_expected_raises_after_retries(monkeypatch):
    monkeypatch.setenv("SLURM_NTASKS", "2")
    calls = {"n": 0}

    def dead(*a):
        calls["n"] += 1
        raise RuntimeError("connection refused")

    monkeypatch.setattr(bootstrap, "_join_runtime", dead)
    with pytest.raises(runtime.CoordinatorUnreachable):
        bootstrap.initialize(retries=1)
    assert calls["n"] == 2


def test_bootstrap_slow_coordinator_hits_deadline(monkeypatch):
    """DETPU_FAULT=slow:coordinator + a short per-attempt timeout_s: every
    attempt times out inside fault_point (before any real jax.distributed
    call) and a cluster-expected job raises CoordinatorUnreachable."""
    monkeypatch.setenv("SLURM_NTASKS", "2")
    monkeypatch.setenv(runtime.FAULT_ENV, "slow:coordinator:30")
    t0 = time.monotonic()
    with pytest.raises(runtime.CoordinatorUnreachable):
        bootstrap.initialize(timeout_s=0.3, retries=1)
    assert time.monotonic() - t0 < 20


# ------------------------------------------- crash-surviving section records


def test_section_recorder_survives_process_death(tmp_path):
    side = str(tmp_path / "sections.jsonl")
    code = (
        f"import os, sys; sys.path.insert(0, {_REPO!r})\n"
        "from distributed_embeddings_tpu.utils import runtime\n"
        f"rec = runtime.SectionRecorder({side!r})\n"
        "runtime.fault_point('alpha')\n"
        "rec.record('alpha', ok=True, value=1.5)\n"
        "os.environ[runtime.FAULT_ENV] = 'die:beta'\n"
        "runtime.fault_point('beta')\n"
        "rec.record('never_reached', ok=True)\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 17, proc.stderr[-2000:]  # die:* exit code
    recs = runtime.SectionRecorder.load(side)
    assert [r["section"] for r in recs] == ["alpha"]
    assert recs[0]["ok"] and recs[0]["value"] == 1.5
    # a torn trailing line (killed mid-write) must not break parsing
    with open(side, "a", encoding="utf-8") as f:
        f.write('{"section": "torn", "ok"')
    recs = runtime.SectionRecorder.load(side)
    assert [r["section"] for r in recs] == ["alpha"]
