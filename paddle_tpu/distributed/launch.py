"""Cluster launcher CLI: `python -m paddle_tpu.distributed.launch train.py`.

Reference: fleet/launch.py:396 (CollectiveLauncher spawning one process per GPU
with PADDLE_TRAINER_* env) + launch_utils.py (Cluster/Pod model, log redirection,
watch_local_trainers restart/abort) + elastic.py:90 (etcd membership watch).

TPU-native: the unit is one process per HOST (jax owns all local chips), so on a
single host the launcher mostly execs the script directly; multi-host mode wires
PADDLE_TRAINER_ENDPOINTS → jax.distributed coordinator. A chip belongs to one
process at a time, so:

- this launcher never touches a JAX backend (importing paddle_tpu initialises
  none; tests/test_chip_bringup.py pins that) — a parent that held the chips
  would leave its workers to fail or hang;
- `--nproc_per_node N>1` is for CPU-mesh testing (`JAX_PLATFORMS=cpu`, the
  reference TestDistBase pattern). On a host that exposes TPU chips it is
  refused: every worker would claim all local chips. One process drives them
  all; the mesh inside it is the unit of parallelism;
- workers inherit the compile-cache placement (utils/compile_cache.py).

A watch loop restarts failed ranks up to --max_restarts (elastic.py behavior
without the etcd dependency; state comes back via checkpoint auto-resume).
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import time

from ..utils import compile_cache


def local_tpu_chips() -> int:
    """TPU chips this host exposes, counted from the device nodes libtpu
    opens — without JAX, which would claim them."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def require_one_process_per_chip_host(nprocs: int, env=None):
    """Refuse `nprocs > 1` local workers where each would come up on the
    TPU and claim every local chip. CPU-pinned workers are fine."""
    if nprocs <= 1:
        return
    env = os.environ if env is None else env
    if env.get("JAX_PLATFORMS", "").split(",")[0].strip().lower() == "cpu":
        return
    chips = local_tpu_chips()
    if chips:
        raise SystemExit(
            f"[launch] refusing {nprocs} worker processes on a host with "
            f"{chips} TPU chip(s): a chip belongs to one process and each "
            "worker would claim all of them. One process drives all local "
            "chips (build the mesh over jax.devices()); use "
            "--nproc_per_node 1, or JAX_PLATFORMS=cpu for a CPU-mesh test")


class Pod:
    def __init__(self, rank, endpoints, script, script_args, log_dir, env):
        self.rank = rank
        self.endpoints = endpoints
        self.script = script
        self.script_args = script_args
        self.log_dir = log_dir
        self.env = env
        self.proc = None
        self.log_fh = None

    def start(self):
        env = dict(os.environ)
        env.update(compile_cache.child_env())
        env.update(self.env)
        env["PADDLE_TRAINER_ID"] = str(self.rank)
        env["PADDLE_TRAINERS_NUM"] = str(len(self.endpoints))
        env["PADDLE_TRAINER_ENDPOINTS"] = ",".join(self.endpoints)
        env["PADDLE_CURRENT_ENDPOINT"] = self.endpoints[self.rank]
        cmd = [sys.executable, self.script] + list(self.script_args)
        if self.log_dir:
            os.makedirs(self.log_dir, exist_ok=True)
            self.log_fh = open(
                os.path.join(self.log_dir, f"worker.{self.rank}.log"), "a")
            self.proc = subprocess.Popen(cmd, env=env, stdout=self.log_fh,
                                         stderr=subprocess.STDOUT)
        else:
            self.proc = subprocess.Popen(cmd, env=env)
        return self.proc

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    def returncode(self):
        return self.proc.poll() if self.proc else None

    def terminate(self):
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        if self.log_fh:
            self.log_fh.close()


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch distributed training (one process per host)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes on this host (CPU-mesh testing with "
                        "JAX_PLATFORMS=cpu; refused above 1 on a host with "
                        "TPU chips — one process drives all local chips)")
    p.add_argument("--hosts", type=str, default=None,
                   help="comma list host:port of all nodes; this host first "
                        "env-detected via PADDLE_TRAINER_ID")
    p.add_argument("--started_port", type=int, default=36001)
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic: restart failed workers this many times")
    p.add_argument("--elastic", action="store_true",
                   help="multi-host membership watch: rewrite endpoints and "
                        "relaunch on node join/leave (elastic.py analog, "
                        "KV-server-backed instead of etcd)")
    p.add_argument("--np", type=str, default=None,
                   help="elastic min[:max] node count, e.g. 2 or 2:4")
    p.add_argument("--elastic_timeout", type=float, default=10.0,
                   help="heartbeat expiry (seconds) for membership")
    p.add_argument("--devices", type=str, default=None,
                   help="reference-CLI flag; refused — one process drives "
                        "all local chips, so there is nothing to assign "
                        "(restrict the chips a host process sees with "
                        "libtpu's TPU_VISIBLE_CHIPS in its environment)")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def get_cluster(args):
    if args.hosts:
        endpoints = args.hosts.split(",")
    else:
        endpoints = [f"127.0.0.1:{args.started_port + i}"
                     for i in range(args.nproc_per_node)]
    return endpoints


def watch_local_trainers(pods, max_restarts):
    """launch_utils.watch_local_trainers + elastic restart semantics."""
    restarts = 0
    while True:
        time.sleep(0.5)
        statuses = [(p, p.returncode()) for p in pods]
        failed = [p for p, rc in statuses if rc not in (None, 0)]
        done = all(rc == 0 for _, rc in statuses)
        if done:
            return 0
        if failed:
            if restarts < max_restarts:
                restarts += 1
                print(f"[launch] {len(failed)} worker(s) failed; "
                      f"restart {restarts}/{max_restarts}", file=sys.stderr)
                for p in pods:
                    p.terminate()
                for p in pods:
                    p.start()
            else:
                for p in pods:
                    p.terminate()
                return failed[0].returncode() or 1


def _parse_np(spec, default_n):
    if not spec:
        return (1, default_n)
    parts = spec.split(":")
    lo = int(parts[0])
    hi = int(parts[1]) if len(parts) > 1 else None
    return (lo, hi)


def _elastic_host_loop(args, endpoints, rank, script_args):
    """Membership-watched per-host worker (elastic.py:294-327 analog):
    node 0 hosts the KV, every node heartbeats, a membership change kills
    the local trainer and respawns it with rewritten endpoints; training
    state returns via checkpoint auto-resume."""
    from .elastic import ElasticManager, ElasticStatus
    from .fleet.utils.http_server import KVClient, KVServer

    me = endpoints[rank]
    host0, port0 = endpoints[0].rsplit(":", 1)
    kv_port = int(port0) + 1000
    server = KVServer(kv_port) if rank == 0 else None
    if server is not None:
        server.start()
    kv = KVClient(f"{host0}:{kv_port}")
    mgr = ElasticManager(me, kv=kv,
                         np_range=_parse_np(args.np, len(endpoints)),
                         timeout=args.elastic_timeout)
    mgr.register()
    # settle initial membership: give slow-starting peers (python import
    # time) a generous window before proceeding with whoever showed up
    deadline = time.time() + max(args.elastic_timeout * 4, 15.0)
    while time.time() < deadline and len(mgr.alive_hosts()) < len(endpoints):
        time.sleep(0.2)
    # never start a pod below min_np: HOLD until membership forms (a pod
    # started in a too-small world would not be relaunched on first join,
    # since the first hosts assignment is COMPLETED, not RESTART)
    while mgr.watch_once() == ElasticStatus.HOLD:
        time.sleep(0.5)
    hosts = mgr.hosts
    if me not in hosts:
        print("[elastic] this node was truncated out by --np max; exiting",
              file=sys.stderr)
        mgr.deregister()
        return 0

    restarts = 0
    pod = Pod(hosts.index(me), hosts, args.training_script, script_args,
              args.log_dir, {})
    pod.start()
    try:
        while True:
            time.sleep(0.5)
            rc = pod.returncode()
            if rc == 0:
                return 0
            if rc not in (None, 0):
                # a peer death usually surfaces here FIRST (collective error
                # kills the trainer before the peer's heartbeat expires):
                # wait out one heartbeat window so the membership watch can
                # rewrite the world, and only charge max_restarts when the
                # membership did NOT change (a genuine local crash)
                deadline = time.time() + args.elastic_timeout + 1.0
                changed = False
                while time.time() < deadline:
                    if mgr.watch_once() == ElasticStatus.RESTART:
                        changed = True
                        break
                    time.sleep(0.5)
                if not changed:
                    if restarts >= args.max_restarts:
                        return rc
                    restarts += 1
                    print(f"[elastic] worker failed rc={rc}; restart "
                          f"{restarts}/{args.max_restarts}", file=sys.stderr)
                hosts = mgr.hosts
                if me not in hosts:
                    return 0
                pod = Pod(hosts.index(me), hosts, args.training_script,
                          script_args, args.log_dir, {})
                pod.start()
                continue
            if mgr.watch_once() == ElasticStatus.RESTART:
                pod.terminate()
                hosts = mgr.hosts
                if me not in hosts:
                    return 0  # this node was scaled out
                pod = Pod(hosts.index(me), hosts, args.training_script,
                          script_args, args.log_dir, {})
                pod.start()
    finally:
        mgr.deregister()
        if server is not None:
            server.stop()


def launch(argv=None):
    args = parse_args(argv)
    if args.devices is not None:
        raise SystemExit(
            "[launch] --devices is not supported: one process drives all "
            "local chips. To hide chips from a host process set libtpu's "
            "TPU_VISIBLE_CHIPS in its environment")
    require_one_process_per_chip_host(
        1 if args.hosts else args.nproc_per_node)
    endpoints = get_cluster(args)
    script_args = list(args.training_script_args)
    if script_args and script_args[0] == "--":
        script_args = script_args[1:]

    if args.hosts:
        # multi-host: this process IS the single per-host worker
        rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        if args.elastic:
            sys.exit(_elastic_host_loop(args, endpoints, rank, script_args))
        pod = Pod(rank, endpoints, args.training_script, script_args,
                  args.log_dir, {})
        pod.start()
        rc = pod.proc.wait()
        sys.exit(rc)

    pods = [Pod(i, endpoints, args.training_script, script_args,
                args.log_dir, {}) for i in range(len(endpoints))]
    for pod in pods:
        pod.start()

    def _sig(_s, _f):
        for p in pods:
            p.terminate()
        sys.exit(1)

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    rc = watch_local_trainers(pods, args.max_restarts)
    sys.exit(rc)


if __name__ == "__main__":
    launch()
