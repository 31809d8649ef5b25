#!/usr/bin/env python
"""Launch a process-native y-tpu cluster from the CLI (ISSUE 14).

Spawns N shard processes under a :class:`~yjs_tpu.cluster.Supervisor`
and fronts them with the y-websocket-compatible
:class:`~yjs_tpu.cluster.Gateway`, then runs until SIGINT/SIGTERM —
the operator-facing equivalent of the acceptance suite's topology.

Shape of a run::

    python scripts/ytpu_cluster.py --shards 3 --gateway 8765
    python scripts/ytpu_cluster.py --config cluster.json
    python scripts/ytpu_cluster.py --shards 1 --smoke   # CI round-trip

``--config`` takes a **docker-compose-shaped** JSON file, so the same
topology description moves between this launcher and a real compose
deployment without translation::

    {
      "services": {
        "shard": {
          "deploy": {"replicas": 3},
          "environment": {"YTPU_CLUSTER_HEARTBEAT_S": "0.25"}
        },
        "gateway": {
          "ports": ["8765:8765"],
          "environment": {"YTPU_GATEWAY_TICK_S": "0.05"}
        }
      }
    }

``services.shard.deploy.replicas`` is the shard count,
``services.gateway.ports[0]`` ("HOST:CONTAINER" or a bare port) is the
gateway port, and each service's ``environment`` map is applied to
``os.environ`` before the ``YTPU_CLUSTER_*`` / ``YTPU_GATEWAY_*``
configs are constructed (shard children inherit it).  CLI flags win
over the config file.

The shards' backend follows ``JAX_PLATFORMS`` (the launcher prints its
choice): unset or ``cpu``, they serve from the CPU reference core and
any number may run; anything else names an accelerator, the shard
process claims it, and since a chip belongs to one process at a time
only ``--shards 1`` is accepted there.

``--smoke`` connects one raw-session client through the gateway, makes
an edit, waits for the acked round-trip, verifies the text server-side,
then curls every spawned process's admin plane (``/healthz``,
``/statusz``, and a well-formed ``/metrics`` exposition on the
supervisor, each shard child, and the gateway — ISSUE 16), and exits
0/1 — the one-shot health probe `scripts/ci_check.sh` runs.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_compose(cfg: dict) -> dict:
    """Flatten a docker-compose-shaped dict into launcher settings:
    ``{"shards": int | None, "gateway_port": int | None, "env": dict}``.
    Unknown services/keys are ignored (the file may drive a real
    compose deployment with more in it)."""
    out = {"shards": None, "gateway_port": None, "env": {}}
    services = cfg.get("services") or {}
    shard = services.get("shard") or {}
    deploy = shard.get("deploy") or {}
    if "replicas" in deploy:
        out["shards"] = int(deploy["replicas"])
    gateway = services.get("gateway") or {}
    ports = gateway.get("ports") or []
    if ports:
        # compose publishes "HOST:CONTAINER"; the host side is ours
        host_port = str(ports[0]).split(":", 1)[0]
        out["gateway_port"] = int(host_port)
    for svc in (shard, gateway):
        env = svc.get("environment") or {}
        if isinstance(env, list):  # compose's KEY=VALUE list form
            env = dict(e.split("=", 1) for e in env if "=" in e)
        out["env"].update({str(k): str(v) for k, v in env.items()})
    return out


import re

# a Prometheus exposition sample line: name{labels} value [timestamp]
_EXPO_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})?"
    r" [-+]?([0-9.eE+-]+|NaN|Inf)( [0-9]+)?$"
)


def _check_admin(name: str, base: str) -> list[str]:
    """Curl one process's admin plane: /healthz, /statusz, and a
    well-formed /metrics exposition.  Returns failure strings."""
    import urllib.request

    fails = []
    for ep in ("/healthz", "/statusz", "/metrics"):
        try:
            with urllib.request.urlopen(base + ep, timeout=10) as r:
                body = r.read().decode("utf-8", "replace")
                if r.status != 200:
                    fails.append(f"{name}{ep}: HTTP {r.status}")
                    continue
        except OSError as e:
            fails.append(f"{name}{ep}: {e}")
            continue
        if ep == "/statusz":
            try:
                json.loads(body)
            except ValueError:
                fails.append(f"{name}{ep}: malformed JSON")
        elif ep == "/metrics":
            bad = [
                ln for ln in body.splitlines()
                if ln and not ln.startswith("#")
                and not _EXPO_LINE.match(ln)
            ]
            if bad:
                fails.append(
                    f"{name}{ep}: malformed exposition: {bad[0]!r}"
                )
            if "ytpu_" not in body:
                fails.append(f"{name}{ep}: no ytpu_ families")
    return fails


def _smoke_admin(gw, sup) -> list[str]:
    """Hit every spawned process's admin endpoints (ISSUE 16): the
    supervisor, each shard child, and the gateway."""
    fails = []
    urls = dict(sup.admin_urls())
    if "supervisor" not in urls:
        fails.append("supervisor: admin plane not serving")
    want_shards = {f"shard-{r['shard']:03d}"
                   for r in sup.recovery_report()["shards"]}
    missing = want_shards - set(urls)
    fails.extend(f"{m}: admin plane not serving" for m in sorted(missing))
    if gw.admin is not None and gw.admin.port:
        urls["gateway"] = gw.admin.url
    else:
        fails.append("gateway: admin plane not serving")
    for name, base in sorted(urls.items()):
        fails.extend(_check_admin(name, base))
    return fails


def _smoke(gw, sup) -> int:
    """One edit through the gateway's session dialect, verified
    server-side, plus an admin-plane probe of every process — exits
    nonzero unless both land."""
    import socket as socketlib

    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "examples",
        ),
    )
    from socket_connector import SocketConnector

    import yjs_tpu as Y

    room, text = "smoke-room", "cluster smoke ok"
    doc = Y.Doc()
    sock = socketlib.create_connection(("127.0.0.1", gw.port), timeout=30)
    conn = SocketConnector(doc, sock, room=room, peer="smoke-client")
    try:
        conn.connect()
        with conn.lock:
            doc.get_text("text").insert(0, text)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                if sup.text(room) == text:
                    break
            except Exception:
                pass
            time.sleep(0.1)
        else:
            print("smoke: FAILED (edit never landed)", file=sys.stderr)
            return 1
        with conn.lock:
            snap = conn.session.snapshot()
        if snap.get("outbox_depth"):
            time.sleep(0.5)  # let the ack drain before judging
            with conn.lock:
                snap = conn.session.snapshot()
        admin_fails = _smoke_admin(gw, sup)
        if admin_fails:
            for f in admin_fails:
                print(f"smoke: admin FAILED {f}", file=sys.stderr)
            return 1
        print(
            "smoke: OK room=%r text=%r outbox=%s admin=ok"
            % (room, text, snap.get("outbox_depth"))
        )
        return 0
    finally:
        conn.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--shards", type=int, default=None,
                    help="shard process count (default 3)")
    ap.add_argument("--gateway", type=int, default=None, metavar="PORT",
                    help="gateway TCP port (default 0 = ephemeral)")
    ap.add_argument("--config", default=None, metavar="FILE",
                    help="docker-compose-shaped JSON topology file")
    ap.add_argument("--wal-root", default=None, metavar="DIR",
                    help="per-shard WAL root (default: a temp dir)")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="federated snapshot dir for ytpu_top --cluster")
    ap.add_argument("--docs-per-shard", type=int, default=64)
    ap.add_argument("--smoke", action="store_true",
                    help="one edit round-trip through the gateway, "
                         "then exit 0/1")
    args = ap.parse_args(argv)

    shards, gw_port = args.shards, args.gateway
    if args.config:
        with open(args.config) as f:
            compose = parse_compose(json.load(f))
        os.environ.update(compose["env"])
        if shards is None:
            shards = compose["shards"]
        if gw_port is None:
            gw_port = compose["gateway_port"]
    shards = 3 if shards is None else shards
    if shards < 1:
        ap.error("--shards must be >= 1")

    # env must be settled before the configs read it
    from yjs_tpu.cluster import (
        ClusterConfig, Gateway, GatewayConfig, Supervisor,
    )

    wal_root = args.wal_root or tempfile.mkdtemp(prefix="ytpu-cluster-")
    cconfig = ClusterConfig(
        snapshot_dir=args.snapshot_dir
        if args.snapshot_dir is not None else None,
    )
    gconfig = GatewayConfig(port=gw_port)

    # the shards' backend follows JAX_PLATFORMS: unset or pinned to the
    # CPU, they serve from the CPU reference core; anything else names an
    # accelerator, and the shard process claims it
    platforms = os.environ.get("JAX_PLATFORMS", "")
    backend = "cpu" if platforms in ("", "cpu") else "auto"
    if backend != "cpu" and shards > 1:
        # every child would claim the same chip, and a chip belongs to
        # one process: the second child fails or hangs at start-up.
        # This process cannot count the chips either (touching JAX here
        # would take one).
        print(
            "ytpu-cluster: refusing %d shards with JAX_PLATFORMS=%s: each "
            "shard process claims the accelerator, a chip belongs to one "
            "process at a time, and nothing maps a shard to a chip of its "
            "own yet.  Run --shards 1, or JAX_PLATFORMS=cpu for CPU-core "
            "shards." % (shards, platforms),
            file=sys.stderr,
        )
        return 2
    sup = Supervisor(
        shards, wal_root, docs_per_shard=args.docs_per_shard,
        config=cconfig, backend=backend,
    ).start()
    gw = Gateway(sup, config=gconfig).start()
    print(
        "ytpu-cluster: %d shard(s) up (backend=%s, JAX_PLATFORMS=%s), "
        "gateway on %s:%d, wal-root %s"
        % (shards, backend, platforms or "unset", gw.config.host, gw.port,
           wal_root)
    )
    for row in sup.recovery_report()["shards"]:
        print(
            "  shard %(shard)d: %(state)s pid=%(pid)s port=%(port)s" % row
        )

    if args.smoke:
        try:
            return _smoke(gw, sup)
        finally:
            gw.close()
            sup.close()

    stop = []
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.append(1))
    try:
        while not stop:
            time.sleep(0.25)
    finally:
        print("ytpu-cluster: shutting down")
        gw.close()
        sup.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
