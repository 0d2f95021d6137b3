"""Elastic membership: heartbeat registry, lost-peer detection, launcher
relaunch-on-membership-change (reference: fleet/elastic/manager.py etcd
registration/heartbeats — SURVEY.md §5-failure)."""

import os
import subprocess
import sys
import time

from paddle_tpu.parallel.elastic import ElasticManager, FileHeartbeatStore


def test_heartbeat_membership(tmp_path):
    store = FileHeartbeatStore(str(tmp_path))
    a = ElasticManager(store, rank=0, world_size=2,
                       heartbeat_interval=0.05).start()
    b = ElasticManager(store, rank=1, world_size=2,
                       heartbeat_interval=0.05).start()
    try:
        assert a.wait_for_world(timeout=5.0)
        assert a.alive() == {0, 1}
        assert a.dead() == set()

        # peer 1 dies (stops heartbeating, no deregister — a crash)
        b.stop(deregister=False)
        deadline = time.time() + 5.0
        while time.time() < deadline and 1 in a.alive():
            time.sleep(0.05)
        assert a.alive() == {0}
        assert a.dead() == {1}

        # peer 1 rejoins
        b = ElasticManager(store, rank=1, world_size=2,
                           heartbeat_interval=0.05).start()
        assert a.wait_for_world(timeout=5.0)
    finally:
        a.stop()
        b.stop()


def test_watch_fires_on_loss(tmp_path):
    store = FileHeartbeatStore(str(tmp_path))
    a = ElasticManager(store, rank=0, world_size=2,
                       heartbeat_interval=0.05).start()
    b = ElasticManager(store, rank=1, world_size=2,
                       heartbeat_interval=0.05).start()
    events = []
    try:
        assert a.wait_for_world(timeout=5.0)
        a.watch(lambda alive, dead: events.append((alive, dead)),
                poll_interval=0.05)
        b.stop(deregister=False)
        deadline = time.time() + 5.0
        while time.time() < deadline and not events:
            time.sleep(0.05)
        assert events, "watch never fired after peer loss"
        alive, dead = events[0]
        assert 1 in dead
    finally:
        a.stop()
        b.stop()


def test_deregister_is_immediate(tmp_path):
    store = FileHeartbeatStore(str(tmp_path))
    a = ElasticManager(store, rank=0, world_size=2, heartbeat_interval=0.05)
    a.register()
    assert 0 in a.alive()
    a.stop(deregister=True)
    assert 0 not in a.alive()


def test_launcher_kills_child_on_peer_loss(tmp_path):
    """launch() with elastic_dir must terminate the child when a peer's
    heartbeat lapses (without consuming the restart budget), wait for the
    world to re-form, and — when the peer never returns — give up with the
    child's exit code."""
    from paddle_tpu.parallel.launch import launch

    hb_dir = str(tmp_path / "hb")
    script = tmp_path / "sleeper.py"
    script.write_text("import time\ntime.sleep(120)\n")

    # fake peer (rank 1) that dies quickly
    store = FileHeartbeatStore(hb_dir)
    peer = ElasticManager(store, rank=1, world_size=2,
                          heartbeat_interval=0.05).start()

    import threading
    rc_box = {}

    def run():
        rc_box["rc"] = launch([str(script)], nnodes=2, node_rank=0,
                              max_restarts=0, elastic_dir=hb_dir,
                              heartbeat_interval=0.05,
                              elastic_world_timeout=2.0)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    time.sleep(1.0)           # child starts, both heartbeats alive
    peer.stop(deregister=False)  # peer crashes
    t.join(timeout=30)
    assert not t.is_alive(), "launch did not react to peer loss"
    assert rc_box["rc"] != 0  # child was terminated, not graceful exit


def test_launcher_relaunches_when_peer_returns(tmp_path):
    """Elastic kill → peer rejoins → child relaunched WITHOUT consuming
    max_restarts; second run completes normally."""
    from paddle_tpu.parallel.launch import launch

    hb_dir = str(tmp_path / "hb")
    marker = tmp_path / "runs.txt"
    script = tmp_path / "worker.py"
    # first run sleeps (will be killed); later runs exit 0 quickly
    script.write_text(
        "import os, sys, time\n"
        f"p = {str(marker)!r}\n"
        "n = len(open(p).readlines()) if os.path.exists(p) else 0\n"
        "open(p, 'a').write('run\\n')\n"
        "time.sleep(60 if n == 0 else 0)\n")

    store = FileHeartbeatStore(hb_dir)
    peer = ElasticManager(store, rank=1, world_size=2,
                          heartbeat_interval=0.05).start()

    import threading
    rc_box = {}

    def run():
        rc_box["rc"] = launch([str(script)], nnodes=2, node_rank=0,
                              max_restarts=0, elastic_dir=hb_dir,
                              heartbeat_interval=0.05,
                              elastic_world_timeout=20.0)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # wait until child 1 has actually booted and written its marker line
    deadline = time.time() + 60
    while time.time() < deadline and not marker.exists():
        time.sleep(0.1)
    assert marker.exists(), "first child never started"
    peer.stop(deregister=False)  # crash → child killed
    time.sleep(1.0)
    peer = ElasticManager(store, rank=1, world_size=2,
                          heartbeat_interval=0.05).start()  # peer rejoins
    t.join(timeout=60)
    peer.stop()
    assert not t.is_alive(), "launch never finished after peer rejoin"
    assert rc_box["rc"] == 0, rc_box
    assert len(marker.read_text().splitlines()) >= 2  # really relaunched
