#!/usr/bin/env python
"""Cluster launcher (reference `tools/launch.py` + dmlc-core tracker).

Starts a parameter server + N worker processes with the `DMLC_*` env
contract (`include/mxnet/kvstore.h:157-206`) and runs the user command in
each worker.  Localhost multi-process is the primary mode (the reference's
nightly distributed tests ran exactly this way,
`tests/nightly/test_all.sh:34-37`); `--hostfile` runs workers over ssh.

Usage:
    python tools/launch.py -n 4 [-s 1] [--sync-dst-dir DIR] CMD...

Each worker gets DMLC_ROLE=worker, DMLC_RANK, DMLC_NUM_WORKER,
DMLC_PS_ROOT_URI/PORT; the server process runs the kvstore server loop and
exits on kStopServer (sent by rank 0 teardown).

Devices: a chip belongs to one process.  This launcher never imports jax,
so it holds none; the server processes are pinned to the CPU backend
(`JAX_PLATFORMS=cpu` — they only sum host arrays) so they cannot take a chip
from a worker.  Every local worker opens the default backend, so on one
host the worker count is the chip count: `-n 1` on a one-chip machine, and
on a multi-chip host the command itself must bind each worker to its own
chip (it can key on DMLC_RANK).  More workers than chips fail or hang at
backend start-up.  On the CPU test mesh (`JAX_PLATFORMS=cpu` in the
environment) any worker count runs.
"""
from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys


def _free_ports(n):
    """A contiguous run of n free ports starting at the returned base
    (server i binds base+i; probing only the base would crash server i>0
    at bind on a collision)."""
    for _ in range(64):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        base = probe.getsockname()[1]
        held = [probe]
        try:
            for i in range(1, n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                held.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in held:
                s.close()
    raise RuntimeError("could not reserve %d contiguous ports" % n)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=1,
                    help="parameter servers; server i binds PORT+i and keys "
                         "shard over them (hash small, range big arrays)")
    ap.add_argument("--restart-servers", type=int, default=0, metavar="N",
                    help="supervise the parameter servers: respawn one that "
                         "exits while workers are still running, up to N "
                         "respawns total.  Pair with MXNET_PS_SNAPSHOT_DIR "
                         "so the respawned server rehydrates its state and "
                         "in-flight workers retry instead of aborting "
                         "(docs/fault_tolerance.md)")
    ap.add_argument("--host", default=None,
                    help="address workers use to reach the parameter server "
                         "(default 127.0.0.1; required with --hostfile)")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--hostfile", default=None,
                    help="file with one host per line; workers run via ssh")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE for all processes")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")

    hosts = None
    if args.hostfile:
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f if h.strip()]
        if args.host is None:
            ap.error("--hostfile requires an explicit --host (the address "
                     "remote workers use to reach the parameter server)")
    if args.host is None:
        args.host = "127.0.0.1"

    port = args.port or _free_ports(max(1, args.num_servers))
    base_env = dict(os.environ)
    for kv in args.env:
        k, _, v = kv.partition("=")
        base_env[k] = v
    base_env.update({
        "DMLC_PS_ROOT_URI": args.host,
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": str(max(1, args.num_servers)),
    })

    procs = []

    # server processes (kvstore_dist_server analogue): server i binds PORT+i
    num_servers = max(1, args.num_servers)
    server_cmd = [sys.executable, "-c",
                  "from mxnet_tpu.parallel.dist import run_server; run_server()"]
    def server_env(sid):
        senv = dict(base_env)
        senv["DMLC_ROLE"] = "server"
        senv["DMLC_SERVER_ID"] = str(sid)
        senv["JAX_PLATFORMS"] = "cpu"  # a server must never hold a chip
        return senv

    for sid in range(num_servers):
        procs.append(subprocess.Popen(server_cmd, env=server_env(sid)))

    extra_keys = {kv.partition("=")[0] for kv in args.env}
    for rank in range(args.num_workers):
        wenv = dict(base_env)
        wenv["DMLC_ROLE"] = "worker"
        wenv["DMLC_RANK"] = str(rank)
        if hosts:
            host = hosts[rank % len(hosts)]
            envs = " ".join("%s=%s" % (k, shlex.quote(v))
                            for k, v in wenv.items()
                            if k.startswith("DMLC_") or k in extra_keys)
            cmd = ["ssh", host, "cd %s && env %s %s"
                   % (shlex.quote(os.getcwd()), envs,
                      " ".join(shlex.quote(c) for c in args.command))]
            procs.append(subprocess.Popen(cmd))
        else:
            procs.append(subprocess.Popen(args.command, env=wenv))

    def _terminate(*_):
        for p in procs:
            p.terminate()
        sys.exit(1)

    signal.signal(signal.SIGINT, _terminate)
    signal.signal(signal.SIGTERM, _terminate)

    workers = procs[num_servers:]
    if args.restart_servers:
        # supervised mode: a server that dies mid-job (crash, chaos
        # injection) is respawned with the same env; with snapshots on it
        # rehydrates and the workers' RPC retries reconnect transparently
        import time

        restarts_left = args.restart_servers
        while any(w.poll() is None for w in workers):
            for sid in range(num_servers):
                s = procs[sid]
                if s.poll() is not None and restarts_left > 0:
                    print("launch: server %d exited rc=%s; respawning "
                          "(%d restart(s) left)"
                          % (sid, s.returncode, restarts_left - 1),
                          file=sys.stderr, flush=True)
                    procs[sid] = subprocess.Popen(server_cmd,
                                                  env=server_env(sid))
                    restarts_left -= 1
            time.sleep(0.2)

    rc = 0
    # wait for workers (skip the servers: they exit on kStopServer)
    for p in workers:
        p.wait()
        rc = rc or p.returncode
    # workers that never created a dist kvstore never send kStopServer;
    # don't hang on the servers in that case
    for p in procs[:num_servers]:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.terminate()
    sys.exit(rc)


if __name__ == "__main__":
    main()
