"""Process-native cluster acceptance (ISSUE 14 tentpole).

Real OS shard processes (``yjs_tpu.cluster.shard``) under the
:class:`Supervisor`, fronted by the y-websocket gateway, with live
session peers attached over real sockets.  The headline contract:
``kill -9`` of the owner shard mid-flush → the supervisor restarts it
through ``recover()`` (or fails over past the restart budget), every
surviving peer reconverges byte-identically with at most one full
resync, and no acked update is lost — the BUSY refusal keeps unacked
frames in the session outbox until the shard is back."""

import importlib.util
import io
import json
import os
import signal
import socket
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "examples"))
from socket_connector import SocketConnector  # noqa: E402

import yjs_tpu as Y  # noqa: E402
from yjs_tpu.cluster import (  # noqa: E402
    ClusterConfig,
    Gateway,
    GatewayConfig,
    RpcBusy,
    RpcError,
    Supervisor,
)

pytestmark = pytest.mark.cluster

# tight supervision so one kill costs ~a second of test wall time, not
# the production defaults' five
FAST = dict(heartbeat_s=0.15, restart_backoff_s=0.05, busy_retry_ticks=4)


def _connect(gw_port: int, room: str, client_id: int):
    doc = Y.Doc(gc=False)
    doc.client_id = client_id
    sock = socket.create_connection(("127.0.0.1", gw_port), timeout=30)
    conn = SocketConnector(doc, sock, room=room, peer=f"peer-{client_id}")
    conn.connect()
    return doc, conn


def _texts(pairs):
    out = []
    for doc, conn in pairs:
        with conn.lock:
            out.append(doc.get_text("text").to_string())
    return out


def _wait_equal(pairs, deadline_s: float = 60.0, require=()):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        texts = _texts(pairs)
        if (
            len(set(texts)) == 1
            and texts[0] != ""
            and all(tok in texts[0] for tok in require)
        ):
            return texts[0]
        time.sleep(0.05)
    raise AssertionError(f"no convergence: {_texts(pairs)!r}")


def _wait_outcome(sup, outcome: str, deadline_s: float = 90.0) -> dict:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        report = sup.recovery_report()
        if report["outcomes"].get(outcome, 0) >= 1:
            return report
        time.sleep(0.1)
    raise AssertionError(
        f"supervision never reported {outcome!r}: {sup.recovery_report()}"
    )


def test_kill9_owner_mid_flush_reconverges_with_zero_acked_loss(tmp_path):
    """The ISSUE 14 acceptance scenario end to end."""
    snap_dir = str(tmp_path / "snap")
    sup = Supervisor(
        3, str(tmp_path / "wal"), docs_per_shard=8,
        config=ClusterConfig(
            restart_max=2, snapshot_dir=snap_dir, snapshot_s=0.5, **FAST
        ),
    ).start()
    gw = Gateway(sup, config=GatewayConfig(port=0)).start()
    pairs = []
    try:
        room = "accept-room"
        a = _connect(gw.port, room, 1)
        b = _connect(gw.port, room, 2)
        pairs = [a, b]
        with a[1].lock:
            a[0].get_text("text").insert(0, "[A0]")
        with b[1].lock:
            b[0].get_text("text").insert(0, "[B0]")
        _wait_equal(pairs, require=("[A0]", "[B0]"))

        owner = sup.owner_of(room)
        pid = sup._shards[owner].pid
        assert pid is not None

        # an edit right before the kill: its frame is acked only once
        # the shard durably holds it, so either it lands in the WAL and
        # survives the replay, or it stays unacked in the session
        # outbox and retransmits after the restart — never lost
        with a[1].lock:
            a[0].get_text("text").insert(0, "[A-preckill]")
        os.kill(pid, signal.SIGKILL)

        # edits DURING the outage from both sides: the gateway answers
        # BUSY (shard mid-restart) and the sessions hold + retransmit
        with a[1].lock:
            a[0].get_text("text").insert(0, "[A-outage]")
        with b[1].lock:
            b[0].get_text("text").insert(0, "[B-outage]")

        report = _wait_outcome(sup, "recovered")
        ev = report["events"][0]
        assert ev["shard"] == owner
        assert ev["outcome"] == "recovered"
        assert ev["unavailable_s"] > 0
        assert report["epoch"] >= 1
        # the restarted child replayed its WAL (the pre-kill edits were
        # flushed durably before their frames were acked)
        assert "records_applied" in (ev.get("recovery") or {})

        final = _wait_equal(
            pairs,
            require=("[A0]", "[B0]", "[A-preckill]",
                     "[A-outage]", "[B-outage]"),
        )
        # identical CRDT state on both peers, not just equal text (the
        # sv map is key-order-agnostic on the wire, so compare decoded)
        with a[1].lock:
            sv_a = Y.decode_state_vector(Y.encode_state_vector(a[0]))
        with b[1].lock:
            sv_b = Y.decode_state_vector(Y.encode_state_vector(b[0]))
        assert sv_a == sv_b

        # the cluster's own copy agrees with the peers (retry while the
        # routed shard finishes settling)
        deadline = time.time() + 30
        cluster_text = None
        while time.time() < deadline:
            try:
                cluster_text = sup.text(room)
                if cluster_text == final:
                    break
            except (RpcBusy, RpcError):
                pass
            time.sleep(0.1)
        assert cluster_text == final

        # ≤ 1 full resync per surviving session, and nothing acked was
        # dropped: outboxes drain to empty once the shard is back
        for doc, conn in pairs:
            with conn.lock:
                snap = conn.session.snapshot()
            assert snap["full_resyncs"] <= 1, snap
        deadline = time.time() + 30
        while time.time() < deadline:
            depths = []
            for doc, conn in pairs:
                with conn.lock:
                    depths.append(conn.session.snapshot()["outbox_depth"])
            if depths == [0, 0]:
                break
            time.sleep(0.1)
        assert depths == [0, 0], f"undrained outboxes: {depths}"

        # the monitor's periodic file drop federated through the kill:
        # per-shard snapshots + the cluster report ytpu_top tails
        deadline = time.time() + 15
        while time.time() < deadline:
            if os.path.exists(os.path.join(snap_dir, "cluster.json")):
                break
            time.sleep(0.1)
        assert os.path.exists(os.path.join(snap_dir, "cluster.json"))
        assert any(
            name.startswith("shard-") and name.endswith(".json")
            for name in os.listdir(snap_dir)
        )
    finally:
        for doc, conn in pairs:
            conn.close()
        gw.close()
        sup.close()


def test_failover_promotes_replica_past_restart_budget(tmp_path):
    """With a zero restart budget a SIGKILL is a permanent loss: the
    ring successor's journal-only replica records materialize via a
    recover-restart and the room rehomes — text survives the shard."""
    sup = Supervisor(
        3, str(tmp_path / "wal"), docs_per_shard=8,
        config=ClusterConfig(restart_max=0, **FAST),
    ).start()
    try:
        room = "failover-room"
        doc = Y.Doc(gc=False)
        doc.client_id = 9
        doc.get_text("text").insert(0, "survives the shard")
        assert sup.receive_update(room, Y.encode_state_as_update(doc))
        sup.flush(room)
        assert sup.text(room) == "survives the shard"

        owner = sup.owner_of(room)
        replica = sup.replica_of(room)
        assert replica is not None and replica != owner
        os.kill(sup._shards[owner].pid, signal.SIGKILL)

        report = _wait_outcome(sup, "failover")
        ev = report["events"][0]
        assert ev["outcome"] == "failover"
        assert ev["shard"] == owner
        assert ev["promoted"] >= 1
        assert report["shards"][owner]["state"] == "lost"
        assert report["epoch"] >= 1

        new_owner = sup.owner_of(room)
        assert new_owner != owner
        deadline = time.time() + 30
        text = None
        while time.time() < deadline:
            try:
                text = sup.text(room)
                break
            except (RpcBusy, RpcError):
                time.sleep(0.1)
        assert text == "survives the shard"

        # post-failover writes land on the promoted owner
        doc.get_text("text").insert(0, "and keeps going: ")
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                assert sup.receive_update(
                    room, Y.encode_state_as_update(doc)
                )
                break
            except (RpcBusy, RpcError):
                time.sleep(0.1)
        assert sup.text(room) == "and keeps going: survives the shard"
    finally:
        sup.close()


def test_hung_shard_convicted_by_heartbeat_probe(tmp_path):
    """A shard whose process is alive and socket open but which stopped
    serving (here: SIGSTOP) is invisible to ``proc.poll()`` and
    ``client.alive`` — only the monitor's heartbeat probe can convict
    it.  Two unanswered probes must force a restart-through-recover."""
    sup = Supervisor(
        2, str(tmp_path / "wal"), docs_per_shard=8,
        config=ClusterConfig(probe_timeout_s=0.5, **FAST),
    ).start()
    hung_pid = None
    try:
        room = "hang-room"
        doc = Y.Doc(gc=False)
        doc.client_id = 11
        doc.get_text("text").insert(0, "before the hang")
        assert sup.receive_update(room, Y.encode_state_as_update(doc))
        sup.flush(room)

        owner = sup.owner_of(room)
        hung_pid = sup._shards[owner].pid
        os.kill(hung_pid, signal.SIGSTOP)

        report = _wait_outcome(sup, "recovered")
        ev = report["events"][0]
        assert ev["shard"] == owner
        assert ev["outcome"] == "recovered"
        # the replacement serves the room again, WAL replayed
        deadline = time.time() + 30
        text = None
        while time.time() < deadline:
            try:
                text = sup.text(room)
                break
            except (RpcBusy, RpcError):
                time.sleep(0.1)
        assert text == "before the hang"
        assert sup._shards[owner].pid != hung_pid
    finally:
        if hung_pid is not None:
            try:
                os.kill(hung_pid, signal.SIGKILL)
            except OSError:
                pass
        sup.close()


def test_busy_provider_is_not_a_hung_shard(tmp_path):
    """A provider busy under its lock (a cold compile or a bulk flush on
    a device-backed shard: seconds) is not a hung shard.  The probe
    rides a connection of its own and the shard answers it outside the
    provider lock, so neither the lock nor a data call stuck behind it
    on the supervisor's data connection can fail a heartbeat."""
    import threading

    from yjs_tpu.cluster.rpc import RpcClient
    from yjs_tpu.cluster.shard import ShardServer

    shard = ShardServer(0, str(tmp_path / "wal"), n_docs=4)
    sup = Supervisor(
        1, str(tmp_path / "sup"),
        config=ClusterConfig(probe_timeout_s=0.5, **FAST),
    )
    sp = sup._shards[0]
    sp.port = shard.port
    sp.client = RpcClient("127.0.0.1", shard.port)
    release = threading.Event()
    held = threading.Event()

    def hold():
        with shard._plock:
            held.set()
            release.wait(30)

    holder = threading.Thread(target=hold)
    holder.start()
    stuck = threading.Thread(
        target=lambda: sp.client.call("text", {"guid": "room"})
    )
    try:
        assert held.wait(5)
        stuck.start()  # head of the data connection's line, behind _plock
        time.sleep(0.2)
        for _ in range(3):
            assert sup._probe(sp) and sp.probe_fails == 0
        assert stuck.is_alive()
    finally:
        release.set()
        holder.join()
        stuck.join(10)
        sp.client.close()
        shard.close(checkpoint=False)


def test_slow_shard_never_costs_a_session_its_liveness(tmp_path):
    """Session liveness is the transport's: while every call into the
    cluster blocks for longer than the peers' whole liveness window (the
    facade lock held here; a shard compiling there), the gateway's
    keepalive thread answers for the sessions that cannot tick, no peer
    declares the connection dead, and the edit made meanwhile lands.
    Every timeout is the default."""
    from yjs_tpu.cluster import LocalCluster
    from yjs_tpu.fleet import FleetRouter

    fleet = FleetRouter(
        n_shards=1, docs_per_shard=8, backend="cpu",
        wal_dir=str(tmp_path / "wal"),
    )
    cluster = LocalCluster(fleet)
    gw = Gateway(cluster, config=GatewayConfig(port=0)).start()
    pairs = []
    try:
        pairs = [_connect(gw.port, "slow-room", cid) for cid in (21, 22)]
        with pairs[0][1].lock:
            pairs[0][0].get_text("text").insert(0, "before. ")
        _wait_equal(pairs)
        window_s = pairs[0][1].session.config.liveness * 0.05
        with cluster._lock:  # every facade call now waits, the tick's too
            with pairs[1][1].lock:
                pairs[1][0].get_text("text").insert(0, "[meanwhile]")
            time.sleep(2 * window_s)
        _wait_equal(pairs, require=("[meanwhile]",))
        for _doc, conn in pairs:
            with conn.lock:
                snap = conn.session.snapshot()
            assert snap["liveness_timeouts"] == 0, snap
            assert snap["state"] == "live", snap
            assert snap["full_resyncs"] == 1, snap
    finally:
        for _doc, conn in pairs:
            conn.close()
        gw.close()
        cluster.close()


def test_spawn_ready_timeout_kills_silent_child(tmp_path):
    """A child that starts but never prints its ready line must fail
    the spawn at ``spawn_timeout_s`` — not block the caller forever
    (during a restart the caller is the monitor thread, i.e. all
    supervision) — and must not leak the process."""
    import subprocess

    sup = Supervisor(
        1, str(tmp_path / "wal"),
        config=ClusterConfig(spawn_timeout_s=0.5, **FAST),
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(60)"],
        stdout=subprocess.PIPE, text=True,
    )
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="timed out"):
        sup._read_ready(proc)
    assert time.monotonic() - t0 < 10.0
    assert proc.poll() is not None  # killed, not leaked

    # and a child that dies before ready reports its exit code
    proc = subprocess.Popen(
        [sys.executable, "-c", "raise SystemExit(3)"],
        stdout=subprocess.PIPE, text=True,
    )
    with pytest.raises(RuntimeError, match="exited before ready"):
        sup._read_ready(proc)


def test_spawn_failure_carries_the_childs_stderr(tmp_path):
    """A child that dies before its ready line takes its reason with it
    unless the supervisor keeps its stderr: the error names it."""
    sup = Supervisor(
        1, str(tmp_path / "wal"), backend="no-such-backend",
        config=ClusterConfig(**FAST),
    )
    try:
        with pytest.raises(RuntimeError, match="unknown policy 'no-such-backend'"):
            sup.start()
    finally:
        sup.close()


def test_supervisor_facade_and_federated_metrics(tmp_path):
    """The FleetRouter-shaped facade over RPC: sv/diff/text round-trip,
    and the federated snapshot carries every shard's families plus the
    supervisor's own cluster gauges."""
    sup = Supervisor(
        2, str(tmp_path / "wal"), docs_per_shard=8,
        config=ClusterConfig(**FAST),
    ).start()
    try:
        doc = Y.Doc(gc=False)
        doc.client_id = 5
        doc.get_text("text").insert(0, "facade")
        assert sup.receive_update("room-f", Y.encode_state_as_update(doc))
        assert sup.text("room-f") == "facade"

        sv = sup.state_vector_bytes("room-f")
        assert sv and sv != b"\x00"
        diff = sup.diff_update("room-f", b"\x00")
        probe = Y.Doc()
        Y.apply_update(probe, diff)
        assert probe.get_text("text").to_string() == "facade"
        # a caught-up peer gets an empty-ish diff, not the full doc
        assert len(sup.diff_update("room-f", sv)) < len(diff)

        snap = sup.metrics_snapshot()
        assert snap["federation"]["sources"], snap["federation"]
        names = set(snap["counters"]) | set(snap["gauges"])
        # every shard's engine families federate, and the supervisor's
        # own process-global cluster families layer in
        assert any(n.startswith("ytpu_cluster_") for n in names), names
        assert any(n.startswith("ytpu_") and "cluster" not in n
                   for n in names), names
    finally:
        sup.close()


# -- satellite 2: FleetRouter.recovery_report + ytpu_top --cluster ------------


def test_fleet_recovery_report_matches_supervisor_shape(tmp_path):
    """The in-process fleet reports recovery outcomes in the SAME
    structured shape the supervisor emits, so one renderer serves
    both (``ytpu_top --cluster``)."""
    from yjs_tpu.fleet import FleetRouter

    wal = str(tmp_path / "fleet")
    fleet = FleetRouter(
        n_shards=2, docs_per_shard=8, backend="cpu", wal_dir=wal
    )
    doc = Y.Doc(gc=False)
    doc.client_id = 3
    doc.get_text("text").insert(0, "fleet doc")
    fleet.receive_update("room-r", Y.encode_state_as_update(doc))
    fleet.flush()
    fresh = fleet.recovery_report()
    assert fresh["kind"] == "fleet"
    assert fresh["outcomes"] == {"recovered": 0, "failover": 0}
    assert all(r["outcome"] == "fresh" for r in fresh["shards"])
    fleet.close()

    recovered = FleetRouter.recover(wal, docs_per_shard=8, backend="cpu")
    report = recovered.recovery_report()
    try:
        assert report["kind"] == "fleet"
        assert report["outcomes"]["recovered"] >= 1
        for key in ("epoch", "shards", "events", "outcomes", "resolution"):
            assert key in report
        for kind in ("completed", "aborted", "fenced"):
            assert kind in report["resolution"]
        row = report["shards"][0]
        for key in ("shard", "state", "pid", "port", "restarts",
                    "outcome", "records_applied"):
            assert key in row
        assert any(
            r["records_applied"] >= 1 for r in report["shards"]
        ), report["shards"]
    finally:
        recovered.close()


def _load_script(name):
    root = Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        name, root / "scripts" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ytpu_top_cluster_mode_renders_supervision_panel(tmp_path):
    top = _load_script("ytpu_top")
    report = {
        "kind": "cluster",
        "epoch": 2,
        "shards": [
            {"shard": 0, "state": "live", "pid": 41, "port": 9001,
             "restarts": 0, "outcome": "fresh", "records_applied": 0},
            {"shard": 1, "state": "lost", "pid": 42, "port": 9002,
             "restarts": 3, "outcome": "recovered",
             "records_applied": 17},
        ],
        "events": [{"shard": 1, "outcome": "failover", "epoch": 2,
                    "unavailable_s": 1.25,
                    "resolution": {"completed": 0, "aborted": 0,
                                   "fenced": 1}}],
        "outcomes": {"recovered": 0, "failover": 1},
        "resolution": {"completed": 0, "aborted": 0, "fenced": 1},
    }
    (tmp_path / "cluster.json").write_text(json.dumps(report))
    (tmp_path / "shard-000.json").write_text(
        json.dumps({"counters": {}, "gauges": {}, "histograms": {}})
    )
    out = io.StringIO()
    top.run_plain(
        top.ClusterDirSource(str(tmp_path)),
        interval=0.01, iterations=1, out=out,
    )
    frame = out.getvalue()
    assert "cluster epoch 2" in frame
    assert "failover" in frame and "recovered" in frame
    assert "unavailable=1.25s" in frame
    # cluster.json is the panel, NOT a shard row; shard-000 federates
    assert "CLUSTER" in frame and "shard-000" in frame
    lines = [ln for ln in frame.splitlines() if ln.startswith("cluster")]
    assert lines, frame
    # an empty dir (report not dumped yet) renders a placeholder panel
    empty = tmp_path / "empty"
    empty.mkdir()
    src = top.ClusterDirSource(str(empty))
    assert "no cluster.json" in src.header()


def test_cluster_launcher_parses_compose_shaped_config():
    """`scripts/ytpu_cluster.py --config` speaks the docker-compose
    shape: replicas -> shard count, published port -> gateway port,
    environment in both map and KEY=VALUE-list form."""
    launcher = _load_script("ytpu_cluster")
    got = launcher.parse_compose({
        "services": {
            "shard": {
                "deploy": {"replicas": 5},
                "environment": {"YTPU_CLUSTER_HEARTBEAT_S": "0.15"},
            },
            "gateway": {
                "ports": ["8765:8765"],
                "environment": ["YTPU_GATEWAY_TICK_S=0.01"],
            },
        }
    })
    assert got["shards"] == 5
    assert got["gateway_port"] == 8765
    assert got["env"] == {
        "YTPU_CLUSTER_HEARTBEAT_S": "0.15",
        "YTPU_GATEWAY_TICK_S": "0.01",
    }
    # irrelevant compose content (volumes, extra services) is ignored
    assert launcher.parse_compose({"services": {"redis": {}}}) == {
        "shards": None, "gateway_port": None, "env": {},
    }


def test_cluster_launcher_refuses_shards_that_would_share_a_chip(
    tmp_path, monkeypatch, capsys
):
    """With JAX_PLATFORMS naming an accelerator every shard child claims
    it, and a chip belongs to one process: more than one such shard is
    refused before anything is spawned, with the way out in the
    message."""
    launcher = _load_script("ytpu_cluster")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    rc = launcher.main(["--shards", "3", "--wal-root", str(tmp_path / "wal")])
    assert rc == 2
    err = capsys.readouterr().err
    assert "refusing 3 shards" in err and "--shards 1" in err
    assert not (tmp_path / "wal").exists()


def test_cluster_launcher_smoke_round_trips_an_edit(tmp_path):
    """The CI probe: launch 1 shard + gateway from a compose-shaped
    config file, push one edit through the session dialect, verify it
    server-side, exit 0."""
    import subprocess

    cfg = tmp_path / "cluster.json"
    cfg.write_text(json.dumps({
        "services": {
            "shard": {
                "deploy": {"replicas": 1},
                "environment": {"YTPU_CLUSTER_HEARTBEAT_S": "0.15"},
            },
            "gateway": {"ports": ["0:0"]},
        }
    }))
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(root / "scripts" / "ytpu_cluster.py"),
         "--config", str(cfg), "--smoke",
         "--wal-root", str(tmp_path / "wal")],
        capture_output=True, text=True, timeout=120, env=env, cwd=str(root),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "smoke: OK" in proc.stdout
    assert "1 shard(s) up" in proc.stdout
