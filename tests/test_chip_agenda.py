"""Deadline mechanics of scripts/chip_agenda.py (ROADMAP C1 removes
the script and this file together).

A phase can hang inside native code where no in-process watchdog —
SIGALRM included — can fire, and a grandchild process (bench.py) is the
one holding the chip. The agenda therefore runs every phase in its own
process GROUP with a parent-enforced deadline and SIGTERM-first group
kill. These tests drive that parent machinery end to end with a sleep
standing in for the hang, via the env-gated ``selftest`` phase.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENDA = os.path.join(REPO, "scripts", "chip_agenda.py")


def _run_agenda(tmp_path, mode, timeout_s="3"):
    out = tmp_path / "agenda.jsonl"
    env = {
        **os.environ,
        "NANODILOCO_AGENDA_SELFTEST": mode,
        "NANODILOCO_AGENDA_SKIP_PROBE": "1",
        "NANODILOCO_AGENDA_OUT": str(out),
        "NANODILOCO_AGENDA_TIMEOUT_SELFTEST": timeout_s,
    }
    proc = subprocess.run(
        [sys.executable, AGENDA, "selftest"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    records = []
    if out.exists():
        records = [json.loads(l) for l in out.read_text().splitlines()]
    return proc, records


def _pid_alive(pid):
    """True only for a RUNNING process: the killed grandchild reparents
    to init when its parent dies first, and an unreaped zombie still
    answers ``os.kill(pid, 0)`` — read the state instead."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            # field 3 is the state; comm (field 2) can contain spaces but
            # is parenthesized, so split after the closing paren
            state = f.read().rsplit(")", 1)[1].split()[0]
        return state not in ("Z", "X")
    except (FileNotFoundError, ProcessLookupError, IndexError):
        return False


def test_wedged_phase_is_terminated_with_its_process_group(tmp_path):
    """A phase that outlives its deadline is SIGTERMed as a GROUP: the
    grandchild (bench.py's analog — the process actually holding the
    chip claim) must die with the phase child, and the parent must
    record the wedge and exit nonzero."""
    # deadline long enough for interpreter startup on a loaded machine
    # (measured ~3 s under a concurrent suite run) plus the grandchild
    # spawn, short enough to keep the test quick
    proc, records = _run_agenda(tmp_path, "wedge", timeout_s="10")
    assert proc.returncode != 0
    wedged = [r for r in records if r.get("status") == "wedged"]
    assert wedged and wedged[0]["phase"] == "selftest"
    assert wedged[0]["timeout_s"] == 10.0
    gc_pids = [r["grandchild_pid"] for r in records if "grandchild_pid" in r]
    assert gc_pids, "selftest child never recorded its grandchild"
    assert not _pid_alive(gc_pids[0]), (
        "grandchild survived the group SIGTERM — a wedged bench.py would "
        "keep holding the chip claim and wedge every later phase"
    )


def test_crashed_phase_records_traceback_in_child(tmp_path):
    """A phase that raises records its own traceback from the child (the
    JSONL is the only diagnostic in an unattended recovery window) and
    the parent reports failure without duplicating the record."""
    proc, records = _run_agenda(tmp_path, "crash", timeout_s="60")
    assert proc.returncode != 0
    crashed = [r for r in records if r.get("status") == "crashed"]
    assert len(crashed) == 1
    assert "selftest crash" in crashed[0]["error"]
    assert "RuntimeError" in crashed[0]["traceback"]


def test_healthy_phase_completes_and_exits_zero(tmp_path):
    proc, records = _run_agenda(tmp_path, "ok", timeout_s="60")
    assert proc.returncode == 0, proc.stderr[-500:]
    assert any(r.get("status") == "ran" for r in records)
    assert not any(r.get("status") in ("wedged", "crashed") for r in records)


def test_resume_skips_succeeded_phases(tmp_path):
    """A retry with --resume: a phase whose latest record is 'done'
    must be skipped (a retry must not re-run succeeded phases),
    recorded via a 'skipping_done' line."""
    out = tmp_path / "agenda.jsonl"
    env = {
        **os.environ,
        "NANODILOCO_AGENDA_SELFTEST": "ok",
        "NANODILOCO_AGENDA_SKIP_PROBE": "1",
        "NANODILOCO_AGENDA_OUT": str(out),
        "NANODILOCO_AGENDA_TIMEOUT_SELFTEST": "60",
    }
    first = subprocess.run(
        [sys.executable, AGENDA, "selftest"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert first.returncode == 0, first.stderr[-500:]
    second = subprocess.run(
        [sys.executable, AGENDA, "--resume", "selftest"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert second.returncode == 0, second.stderr[-500:]
    records = [json.loads(l) for l in out.read_text().splitlines()]
    assert any(r.get("skipping_done") == ["selftest"] for r in records)
    # exactly one actual execution: the resume run added no start record
    assert len([r for r in records if r.get("status") == "start"]) == 1


def test_resume_done_from_previous_session_is_not_skipped(tmp_path):
    """The JSONL is a permanent append-only ledger: a 'done' recorded in
    an EARLIER watch session (before the latest session marker) must not
    satisfy this session's --resume — otherwise a week-old success
    silently replaces this week's evidence."""
    out = tmp_path / "agenda.jsonl"
    out.write_text(
        json.dumps({"phase": "agenda", "status": "session"}) + "\n"
        + json.dumps({"phase": "selftest", "status": "done"}) + "\n"
        + json.dumps({"phase": "agenda", "status": "session"}) + "\n"
    )
    env = {
        **os.environ,
        "NANODILOCO_AGENDA_SELFTEST": "ok",
        "NANODILOCO_AGENDA_SKIP_PROBE": "1",
        "NANODILOCO_AGENDA_OUT": str(out),
        "NANODILOCO_AGENDA_TIMEOUT_SELFTEST": "60",
    }
    proc = subprocess.run(
        [sys.executable, AGENDA, "--resume", "selftest"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    records = [json.loads(l) for l in out.read_text().splitlines()]
    assert any(r.get("status") == "start" for r in records), (
        "phase was skipped on the strength of a previous session's 'done'"
    )
    assert not any(r.get("skipping_done") for r in records)


@pytest.mark.parametrize("mode", ["wedge"])
def test_wedge_with_skip_probe_continues_not_aborts(tmp_path, mode):
    """With the probe skipped (test hook), a wedge must NOT emit the
    claim-dead abort record — that path is reserved for a real failed
    re-probe after a wedge."""
    _, records = _run_agenda(tmp_path, mode, timeout_s="10")
    assert not any(r.get("phase") == "abort" for r in records)
