"""Elastic heartbeat state machine (VERDICT r4 "do this" #8; reference:
fleet/elastic/manager.py — etcd lease :257, scale decisions :487/:510,
fault-tolerance levels :126): lease/TTL heartbeats against the TCP store,
registry diff -> scale-in/out decisions, 2->3 scale-out relaunch and
rank-kill restart under the launcher."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_store_lease_scale_out_and_in():
    from paddle_tpu.distributed.fleet.elastic import (
        ElasticManager, ElasticStatus, StoreHeartbeatAgent, store_listener)
    from paddle_tpu.distributed.store import TCPStore

    port = _free_port()
    master = TCPStore("127.0.0.1", port, is_master=True)
    try:
        ttl = 1.5
        a = StoreHeartbeatAgent(
            TCPStore("127.0.0.1", port, False), "host-a", ttl).start()
        b = StoreHeartbeatAgent(
            TCPStore("127.0.0.1", port, False), "host-b", ttl).start()
        listener = store_listener(TCPStore("127.0.0.1", port, False), ttl)
        time.sleep(0.2)
        mgr = ElasticManager(listener=listener, min_hosts=1,
                             max_hosts=8, scale=1)
        assert sorted(mgr.hosts) == ["host-a", "host-b"]
        assert mgr.watch() == ElasticStatus.HOLD

        # 2 -> 3 scale-OUT: a third pod registers and beats
        c = StoreHeartbeatAgent(
            TCPStore("127.0.0.1", port, False), "host-c", ttl).start()
        time.sleep(0.2)
        assert mgr.watch() == ElasticStatus.RESTART
        assert mgr.last_event[0] == "scale_out"
        assert mgr.last_event[1] == ["host-c"]
        assert mgr.np == 3

        # rank kill: host-b's lease expires after its agent dies
        b.stop()
        deadline = time.time() + 3 * ttl
        status = ElasticStatus.HOLD
        while time.time() < deadline:
            status = mgr.watch()
            if status == ElasticStatus.RESTART:
                break
            time.sleep(0.3)
        assert status == ElasticStatus.RESTART
        assert mgr.last_event[0] == "scale_in"
        assert mgr.last_event[2] == ["host-b"]
        assert mgr.np == 2
        a.stop()
        c.stop()
    finally:
        master.shutdown()


def test_fault_tolerance_level_replacement():
    """Same host count, different member: level 1 holds, level 2
    restarts (reference fault-tolerance levels)."""
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      ElasticStatus)
    live = {"hosts": ["a", "b"]}
    mk = lambda lvl: ElasticManager(  # noqa: E731
        hosts=["a", "b"], listener=lambda: list(live["hosts"]),
        min_hosts=1, max_hosts=4, elastic_level=lvl)
    m1, m2 = mk(1), mk(2)
    live["hosts"] = ["a", "c"]          # b replaced by c
    assert m1.watch() == ElasticStatus.HOLD
    assert m2.watch() == ElasticStatus.RESTART
    assert m2.last_event[0] == "replace"


@pytest.mark.parametrize("mode", ["store"])
def test_launcher_store_elastic_scale_out(tmp_path, mode):
    """2 -> 3 pod scale-out through the launcher's --elastic_store path:
    a new pod's heartbeat triggers a full relaunch (generation bump)."""
    from paddle_tpu.distributed.fleet.elastic import StoreHeartbeatAgent
    from paddle_tpu.distributed.store import TCPStore

    port = _free_port()
    master = TCPStore("127.0.0.1", port, is_master=True)
    marker = tmp_path / "gen.log"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import os, time
        gen = os.environ.get("PADDLE_RESTART_COUNT", "0")
        with open(%r, "a") as f:
            f.write("gen=%%s rank=%%s\\n"
                    %% (gen, os.environ.get("PADDLE_TRAINER_ID")))
        if gen == "0":
            time.sleep(120)
    """ % str(marker)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    try:
        # a peer pod already beating
        peer = StoreHeartbeatAgent(
            TCPStore("127.0.0.1", port, False), "pod-1", 4.0).start()
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2",
             "--elastic_store", f"127.0.0.1:{port}",
             "--elastic_endpoint", "pod-0",
             "--elastic_ttl", "4.0",
             "--elastic_poll_interval", "0.2", str(script)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        try:
            deadline = time.time() + 30
            while time.time() < deadline and (
                    not marker.exists()
                    or marker.read_text().count("gen=0") < 2):
                time.sleep(0.2)
            # third pod joins -> scale-out
            extra = StoreHeartbeatAgent(
                TCPStore("127.0.0.1", port, False), "pod-2", 4.0).start()
            out, err = proc.communicate(timeout=90)
            extra.stop()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
        peer.stop()
        text = marker.read_text()
        assert proc.returncode == 0, (out, err, text)
        assert "relaunch #1" in err, err
        assert text.count("gen=0") == 2, text
        assert text.count("gen=1") == 2, text
    finally:
        master.shutdown()


def test_launcher_rank_kill_restart(tmp_path):
    """Kill-one-rank recovery: a worker that dies with rc!=0 is restarted
    by the launcher (max_restart) and the job completes."""
    marker = tmp_path / "runs.log"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        rank = os.environ.get("PADDLE_TRAINER_ID")
        path = %r
        with open(path, "a") as f:
            f.write("run rank=%%s\\n" %% rank)
        # rank 1 kills itself ONCE (simulated fault), then recovers
        if rank == "1":
            died = path + ".died"
            if not os.path.exists(died):
                open(died, "w").write("x")
                os._exit(17)
    """ % str(marker)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--max_restart", "2", str(script)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    text = marker.read_text()
    assert out.returncode == 0, (out.stdout, out.stderr, text)
    assert "restart 1/2" in out.stderr, out.stderr
    assert text.count("run rank=1") == 2, text   # died once, reran
    assert text.count("run rank=0") == 1, text
