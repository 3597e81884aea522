"""The trainer-fleet launcher (paddle_tpu.distributed.launch): rank
env/argv templating, per-rank log tee, first-failure propagation, pod
command emission — the SSH cluster launcher of the reference
(``paddle/scripts/cluster_train/paddle.py``) rebuilt for SPMD."""

from __future__ import annotations

import os
import sys

from paddle_tpu.distributed.launch import (
    emit_pod_commands,
    launch_local,
    main,
    rank_env,
)

_PY = sys.executable


def test_all_ranks_succeed_and_logs_teed(tmp_path):
    rc = launch_local(
        [_PY, "-c",
         "import os, sys; print('rank', os.environ['PADDLE_TPU_TRAINER_ID'],"
         " 'of', os.environ['PADDLE_TPU_NPROC'], 'arg {rank}')"],
        nproc=3, log_dir=str(tmp_path), echo_rank0=False, timeout=60)
    assert rc == 0
    for i in range(3):
        text = (tmp_path / f"rank{i}.log").read_text()
        # env AND {rank} substitution agree
        assert f"rank {i} of 3 arg {i}" in text


def test_first_failure_propagates_and_kills_stragglers(tmp_path):
    import time

    t0 = time.monotonic()
    rc = launch_local(
        [_PY, "-c",
         "import os, sys, time\n"
         "r = int(os.environ['PADDLE_TPU_TRAINER_ID'])\n"
         "sys.exit(7) if r == 1 else time.sleep(120)"],
        nproc=3, log_dir=str(tmp_path), echo_rank0=False, timeout=90)
    # rank 1's code comes back, and the 120 s sleepers were reaped
    assert rc == 7
    assert time.monotonic() - t0 < 60


def test_coordinator_env_is_shared(tmp_path):
    rc = launch_local(
        [_PY, "-c",
         "import os; print('coord', os.environ['PADDLE_TPU_COORDINATOR'],"
         " 'port {port}')"],
        nproc=2, log_dir=str(tmp_path), echo_rank0=False, timeout=60)
    assert rc == 0
    texts = [(tmp_path / f"rank{i}.log").read_text() for i in range(2)]
    coord0 = texts[0].split("coord ")[1].split()[0]
    coord1 = texts[1].split("coord ")[1].split()[0]
    assert coord0 == coord1  # every rank sees the same rendezvous point
    assert coord0.split(":")[1] in texts[0]  # {port} matches the env


def test_timeout_kills_fleet(tmp_path):
    rc = launch_local([_PY, "-c", "import time; time.sleep(60)"],
                      nproc=2, log_dir=str(tmp_path), echo_rank0=False,
                      timeout=1.0, poll_s=0.05)
    assert rc == 124  # the timeout(1) convention


def test_emit_pod_commands():
    lines = emit_pod_commands(["h0", "h1"], ["python", "train.py",
                                             "--trainer_id", "{rank}"])
    assert len(lines) == 2
    assert "PADDLE_TPU_TRAINER_ID=0" in lines[0]
    assert "PADDLE_TPU_COORDINATOR=h0:8476" in lines[1]  # host 0 leads
    assert "--trainer_id 1" in lines[1]


def test_cli_emit_mode(capsys):
    rc = main(["--emit_hosts", "a,b", "--", "python", "w.py"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "# on a:" in out and "# on b:" in out


def test_rank_env_isolated_base():
    env = rank_env(2, 4, 1234, base_env={"KEEP": "1"})
    assert env["PADDLE_TPU_TRAINER_ID"] == "2"
    assert env["PADDLE_TPU_NPROC"] == "4"
    assert env["KEEP"] == "1"
    assert env["PADDLE_TPU_RENDEZVOUS_EPOCH"] == "0"
    assert "PATH" not in env or os.environ.get("PATH") != env  # no leak


# -- operator signals / drain / elastic membership ---------------------------

_TRAP_CHILD = (
    "import os, signal, sys, time\n"
    "def bye(sig, frame):\n"
    "    print('rank', os.environ['PADDLE_TPU_TRAINER_ID'],\n"
    "          'draining', flush=True)\n"
    "    sys.exit(0)\n"
    "signal.signal(signal.SIGTERM, bye)\n"
    "print('ready', flush=True)\n"
    "time.sleep(120)\n"
)


def _spawn_launcher(tmp_path, extra_args, child_src, nproc=2):
    import subprocess

    return subprocess.Popen(
        [_PY, "-m", "paddle_tpu.distributed.launch",
         "--nproc", str(nproc), "--log_dir", str(tmp_path),
         "--grace", "10", *extra_args, "--", _PY, "-c", child_src],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _wait_logs(tmp_path, nproc, marker, timeout=30.0):
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        texts = []
        for i in range(nproc):
            p = tmp_path / f"rank{i}.log"
            texts.append(p.read_text() if p.exists() else "")
        if all(marker in t for t in texts):
            return texts
        time.sleep(0.1)
    raise AssertionError(f"marker {marker!r} never appeared in all "
                         f"rank logs: {texts}")


def test_sigterm_forwarded_to_ranks_and_reaped(tmp_path):
    """An operator SIGTERM to the launcher must reach every rank (their
    graceful-shutdown handlers run) and reap them — not orphan sleepers
    behind a dead launcher."""
    import signal as sig

    p = _spawn_launcher(tmp_path, [], _TRAP_CHILD)
    try:
        _wait_logs(tmp_path, 2, "ready")
        p.send_signal(sig.SIGTERM)
        rc = p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    assert rc == 128 + sig.SIGTERM  # 143: terminated, after forwarding
    for i in range(2):
        assert f"rank {i} draining" in (tmp_path / f"rank{i}.log"
                                        ).read_text()


def test_drain_signal_delivers_sigterm_and_waits(tmp_path):
    """--drain: SIGUSR1 to the launcher SIGTERMs the ranks (the trainer
    checkpoint-and-exit path) and WAITS for their graceful exit —
    rc 0, nobody killed."""
    import signal as sig

    p = _spawn_launcher(tmp_path, ["--drain"], _TRAP_CHILD)
    try:
        _wait_logs(tmp_path, 2, "ready")
        p.send_signal(sig.SIGUSR1)
        rc = p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    assert rc == 0
    for i in range(2):
        assert f"rank {i} draining" in (tmp_path / f"rank{i}.log"
                                        ).read_text()


def test_elastic_rank_death_updates_membership_and_notifies(tmp_path):
    """--elastic: a dying rank is a membership event, not fleet death —
    the membership file is rewritten (epoch bump, rank removed) and the
    survivors get SIGUSR1; the launcher returns the SURVIVORS' verdict."""
    import json

    child = (
        "import json, os, signal, sys, time\n"
        "r = int(os.environ['PADDLE_TPU_TRAINER_ID'])\n"
        "path = os.environ['PADDLE_TPU_MEMBERSHIP']\n"
        "assert os.environ['PADDLE_TPU_RENDEZVOUS_EPOCH'] == '0'\n"
        "deadline = time.monotonic() + 50\n"
        "if r == 1:\n"
        # die once the survivor has ARMED its handler (children start with
        # SIGUSR1 ignored: a notice that beats the handler is dropped, and
        # the survivor then waited out its whole deadline)
        "    log0 = os.path.join(os.path.dirname(path), 'rank0.log')\n"
        "    while time.monotonic() < deadline and not (\n"
        "            os.path.exists(log0) and 'ready' in open(log0).read()):\n"
        "        time.sleep(0.02)\n"
        "    sys.exit(5)\n"
        "hit = []\n"
        "signal.signal(signal.SIGUSR1, lambda s, f: hit.append(s))\n"
        "print('ready', flush=True)\n"
        "while not hit and time.monotonic() < deadline:\n"
        "    time.sleep(0.05)\n"
        "assert hit, 'no SIGUSR1 inside the deadline'\n"
        "m = json.load(open(path))\n"
        "print('notified epoch', m['epoch'], 'ranks', m['ranks'],\n"
        "      flush=True)\n"
        "sys.exit(0)\n"
    )
    p = _spawn_launcher(tmp_path, ["--elastic"], child)
    try:
        rc = p.wait(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    assert rc == 0  # survivor exited clean; the lost rank is the event
    m = json.loads((tmp_path / "membership.json").read_text())
    assert m["epoch"] == 1 and m["ranks"] == [0]
    log0 = (tmp_path / "rank0.log").read_text()
    assert "notified epoch 1 ranks [0]" in log0


def test_elastic_all_ranks_dead_is_a_failure(tmp_path):
    """--elastic must not launder a fully-failed fleet into rc 0: when
    every rank dies, the first failure's code comes back."""
    rc = launch_local(
        [_PY, "-c", "import sys; sys.exit(9)"], nproc=2,
        log_dir=str(tmp_path), echo_rank0=False, timeout=60,
        elastic=True)
    assert rc == 9


def test_elastic_sigusr1_ignored_until_armed(tmp_path):
    """Elastic children start with SIGUSR1 ignored (exec keeps ignored
    dispositions), so the membership notice fired by a sibling's death
    cannot kill a survivor that has not armed its handler yet."""
    child = (
        "import os, signal, sys, time\n"
        "r = int(os.environ['PADDLE_TPU_TRAINER_ID'])\n"
        "assert signal.getsignal(signal.SIGUSR1) is signal.SIG_IGN\n"
        "if r == 1:\n"
        "    sys.exit(5)\n"  # dies while rank 0 is still 'importing'
        "time.sleep(1.0)\n"  # absorb the SIGUSR1 notice unarmed
        "print('survived unarmed', flush=True)\n"
    )
    rc = launch_local([_PY, "-c", child], nproc=2,
                      log_dir=str(tmp_path), echo_rank0=False,
                      timeout=60, elastic=True)
    assert rc == 0
    assert "survived unarmed" in (tmp_path / "rank0.log").read_text()


def test_serving_env_has_replica_id_and_no_rendezvous():
    from paddle_tpu.distributed.launch import serving_env

    base = {"PATH": "/bin", "PADDLE_TPU_COORDINATOR": "stale:1"}
    env = serving_env(2, 3, base_env=base)
    assert env["PADDLE_TPU_REPLICA_ID"] == "2"
    assert env["PADDLE_TPU_NREPLICAS"] == "3"
    # replicas are independent processes: no trainer rendezvous vars,
    # and a stale inherited coordinator is scrubbed (a replica that
    # kept it would try to join a collective fleet that does not exist)
    assert "PADDLE_TPU_COORDINATOR" not in env
    assert "PADDLE_TPU_NPROC" not in env


def test_serving_replica_death_is_membership_event_not_fleet_death(
        tmp_path):
    """--serving: one replica dying removes it from the membership file
    (the fleet health monitor's failover signal) while the survivors
    keep serving and decide the verdict."""
    child = (
        "import os, sys, time\n"
        "r = int(os.environ['PADDLE_TPU_REPLICA_ID'])\n"
        "assert os.environ['PADDLE_TPU_NREPLICAS'] == '3'\n"
        "assert 'PADDLE_TPU_COORDINATOR' not in os.environ\n"
        "if r == 1:\n"
        "    sys.exit(3)\n"
        "time.sleep(0.5)\n"
        "print('replica', r, 'served', flush=True)\n"
    )
    rc = launch_local([_PY, "-c", child], nproc=3,
                      log_dir=str(tmp_path), echo_rank0=False,
                      timeout=60, serving=True)
    assert rc == 0  # survivors' verdict; the lost replica is the event
    from paddle_tpu.distributed.multihost import Membership

    m = Membership.read(str(tmp_path / "membership.json"))
    assert m.ranks == [0, 2] and m.epoch == 1
    assert m.missing(range(3)) == [1]
    for r in (0, 2):
        assert f"replica {r} served" in \
            (tmp_path / f"rank{r}.log").read_text()
