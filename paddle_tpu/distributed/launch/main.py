"""`python -m paddle_tpu.distributed.launch` — multi-process job launcher.

Reference: python/paddle/distributed/launch/main.py:18 + controllers/
collective.py (build_pod): the launcher materializes the env contract that
`distributed/env.py` reads (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
PADDLE_MASTER / PADDLE_TRAINER_ENDPOINTS), spawns one worker per local
process, tails logs into --log_dir, and restarts failed workers up to
--max_restart times (the controller watch loop, controller.py:79).

TPU-native: the normal deployment is ONE process per host (jax.distributed
over DCN; all local chips visible to that process), so --nproc_per_node
defaults to 1; multi-proc-per-node remains available for CPU workers
(JAX_PLATFORMS=cpu) — the reference's Gloo-style pattern (SURVEY.md §4.2) —
and is refused on a host with TPU chips, where the children would contend
for chips they are not bound to.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch a distributed training job")
    p.add_argument("--master", default=None,
                   help="coordinator ip:port (default: 127.0.0.1:<free>)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", "--rank", type=int, dest="node_rank",
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--log_dir", default=None)
    p.add_argument("--job_id", default="default")
    p.add_argument("--devices", default=None,
                   help="comma-separated local device ids")
    p.add_argument("--max_restart", type=int, default=0)
    p.add_argument("--elastic_membership_file", default=None,
                   help="elastic mode: path whose comma/newline-separated "
                        "host list is watched; a membership change tears "
                        "down and relaunches the pod (reference "
                        "fleet/elastic/manager.py scale events)")
    p.add_argument("--elastic_poll_interval", type=float, default=0.5)
    p.add_argument("--elastic_store", default=None,
                   help="elastic mode over the TCP store (host:port): pod "
                        "membership comes from lease/TTL heartbeats "
                        "(fleet.elastic.StoreHeartbeatAgent) instead of a "
                        "file — the reference's etcd-backed manager")
    p.add_argument("--elastic_ttl", type=float, default=6.0)
    p.add_argument("--elastic_endpoint", default=None,
                   help="this pod's endpoint name to register+heartbeat in "
                        "the elastic store (default ip:node_rank)")
    p.add_argument("--run_mode", default="collective",
                   choices=["collective", "ps", "rpc"],
                   help="collective (default), parameter-server, or rpc pods")
    p.add_argument("--server_num", type=int, default=1,
                   help="ps mode: number of parameter servers")
    p.add_argument("--trainer_num", type=int, default=None,
                   help="ps mode: number of trainers "
                        "(default: nproc_per_node)")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(args, local_rank, master, endpoint=None, store_port=None):
    world = args.nnodes * args.nproc_per_node
    rank = args.node_rank * args.nproc_per_node + local_rank
    env = dict(os.environ)
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_MASTER": master,
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_JOB_ID": args.job_id,
        "PADDLE_CURRENT_ENDPOINT": endpoint or f"127.0.0.1:{_free_port()}",
        "RANK": str(rank),
        "WORLD_SIZE": str(world),
        "MASTER_ADDR_PORT": master,
    })
    if store_port is not None:
        # dedicated object-store port, allocated by the launcher so it
        # cannot collide with another job's coordinator (derived master+7
        # offsets are only the launcher-less fallback)
        host = master.rpartition(":")[0] or "127.0.0.1"
        env["PADDLE_STORE_ENDPOINT"] = f"{host}:{store_port}"
    if args.devices is not None:
        devs = args.devices.split(",")
        env["FLAGS_selected_tpus"] = devs[local_rank % len(devs)]
    return env


def _ps_env(args, role, index, server_eps, trainer_eps, master):
    """PS-mode env contract (reference launch/controllers/ps.py build_pod:
    PADDLE_PSERVERS_IP_PORT_LIST / PADDLE_TRAINING_ROLE / PADDLE_PORT)."""
    env = dict(os.environ)
    env.update({
        "PADDLE_MASTER": master,
        "PADDLE_JOB_ID": args.job_id,
        "PADDLE_PSERVERS_IP_PORT_LIST": ",".join(server_eps),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(trainer_eps),
        "PADDLE_TRAINERS_NUM": str(len(trainer_eps)),
        "PADDLE_TRAINING_ROLE": role,
    })
    if role == "PSERVER":
        ip, port = server_eps[index].rsplit(":", 1)
        env.update({"PADDLE_PORT": port, "POD_IP": ip,
                    "PADDLE_CURRENT_ENDPOINT": server_eps[index]})
    else:
        env.update({"PADDLE_TRAINER_ID": str(index),
                    "PADDLE_CURRENT_ENDPOINT": trainer_eps[index]})
    return env


def _local_tpu_chips() -> int:
    """TPU chips on this host, counted from their device files so that the
    launcher itself never initialises jax (a parent that has touched jax
    holds the chip its children need)."""
    import glob
    return len(glob.glob("/dev/accel*")) or len(glob.glob("/dev/vfio/[0-9]*"))


def launch(argv=None):
    args = _parse(argv if argv is not None else sys.argv[1:])
    if args.nproc_per_node > 1 and _local_tpu_chips() and \
            os.environ.get("JAX_PLATFORMS", "") != "cpu":
        raise SystemExit(
            f"--nproc_per_node {args.nproc_per_node} on a TPU host: a chip "
            "belongs to one process, and the children are not bound to "
            "chips of their own. Run one process per host (it drives every "
            "local chip), or set JAX_PLATFORMS=cpu for CPU workers.")
    master = args.master or f"127.0.0.1:{_free_port()}"
    log_dir = args.log_dir
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)

    if args.run_mode == "ps":
        if args.nnodes != 1:
            raise SystemExit(
                "--run_mode ps supports a single node in this build; "
                "multi-node PS pods need externally assigned endpoints "
                "(set PADDLE_PSERVERS_IP_PORT_LIST yourself)")
        n_tr = (args.trainer_num if args.trainer_num is not None
                else args.nproc_per_node)
        server_eps = [f"127.0.0.1:{_free_port()}"
                      for _ in range(args.server_num)]
        trainer_eps = [f"127.0.0.1:{_free_port()}" for _ in range(n_tr)]
        jobs = ([("PSERVER", i) for i in range(args.server_num)]
                + [("TRAINER", i) for i in range(n_tr)])
    else:
        jobs = None

    rpc_eps = None
    if args.run_mode == "rpc":
        if args.nnodes != 1:
            raise SystemExit(
                "--run_mode rpc supports a single node in this build; "
                "multi-node rpc pods need externally assigned endpoints "
                "(set PADDLE_WORKER_ENDPOINTS yourself)")
        # rpc mode (reference launch/controllers/rpc.py): collective-style
        # ranks plus a pre-assigned endpoint list every worker can dial
        rpc_eps = [f"127.0.0.1:{_free_port()}"
                   for _ in range(args.nproc_per_node)]

    store_port = _free_port()  # dedicated object-store port for this job

    def spawn(local_rank):
        if jobs is not None:
            role, idx = jobs[local_rank]
            env = _ps_env(args, role, idx, server_eps, trainer_eps, master)
        else:
            env = _worker_env(
                args, local_rank, master,
                endpoint=rpc_eps[local_rank] if rpc_eps else None,
                store_port=store_port)
            if rpc_eps is not None:
                env["PADDLE_WORKER_ENDPOINTS"] = ",".join(rpc_eps)
        cmd = [sys.executable, args.training_script] + \
            args.training_script_args
        if log_dir:
            if jobs is not None:
                role, idx = jobs[local_rank]
                tag = f"{role.lower()}log.{idx}"
            else:
                tag = f"workerlog.{env['PADDLE_TRAINER_ID']}"
            logf = open(os.path.join(log_dir, tag), "ab")
            return subprocess.Popen(cmd, env=env, stdout=logf,
                                    stderr=subprocess.STDOUT), logf
        return subprocess.Popen(cmd, env=env), None

    n_procs = len(jobs) if jobs is not None else args.nproc_per_node
    relaunch_count = 0
    procs = [spawn(i) for i in range(n_procs)]
    restarts = [0] * len(procs)

    elastic = None
    if args.elastic_store:
        from ..fleet.elastic import (ElasticManager, ElasticStatus,
                                     StoreHeartbeatAgent, store_listener)
        from ..store import TCPStore
        host, port = args.elastic_store.rsplit(":", 1)
        store = TCPStore(host, int(port), is_master=False)
        endpoint = args.elastic_endpoint or \
            f"{host}:{args.node_rank}"
        agent = StoreHeartbeatAgent(store, endpoint,
                                    ttl=args.elastic_ttl).start()
        elastic = ElasticManager(listener=store_listener(
            store, ttl=args.elastic_ttl), min_hosts=1, max_hosts=1 << 30,
            scale=1)
    elif args.elastic_membership_file:
        from ..fleet.elastic import ElasticManager, ElasticStatus

        def file_listener(path=args.elastic_membership_file):
            try:
                with open(path) as f:
                    raw = f.read().replace("\n", ",")
                return [h for h in raw.split(",") if h.strip()]
            except OSError:
                return []

        elastic = ElasticManager(listener=file_listener, min_hosts=1,
                                 max_hosts=1 << 30, scale=1)
    last_elastic_poll = time.monotonic()
    rc = 0
    try:
        while True:
            if elastic is not None and \
                    time.monotonic() - last_elastic_poll >= \
                    args.elastic_poll_interval:
                last_elastic_poll = time.monotonic()
                if elastic.watch() == ElasticStatus.RESTART:
                    # scale event: tear the pod down and relaunch every
                    # worker (reference manager.py:487,510 re-exec path);
                    # workers see the generation via PADDLE_RESTART_COUNT
                    relaunch_count += 1
                    print(f"[launch] elastic membership changed -> "
                          f"relaunch #{relaunch_count} "
                          f"({elastic.np} hosts)", file=sys.stderr)
                    for proc, logf in procs:
                        if proc.poll() is None:
                            proc.send_signal(signal.SIGTERM)
                    for proc, logf in procs:
                        try:
                            proc.wait(timeout=10)
                        except subprocess.TimeoutExpired:
                            proc.kill()
                        if logf:
                            logf.close()
                    os.environ["PADDLE_RESTART_COUNT"] = \
                        str(relaunch_count)
                    procs = [spawn(i) for i in range(n_procs)]
                    restarts = [0] * len(procs)
            alive = False
            for i, (proc, logf) in enumerate(procs):
                ret = proc.poll()
                if ret is None:
                    alive = True
                elif ret != 0:
                    if restarts[i] < args.max_restart:
                        restarts[i] += 1
                        print(f"[launch] worker {i} exited rc={ret}; "
                              f"restart {restarts[i]}/{args.max_restart}",
                              file=sys.stderr)
                        if logf:  # don't leak the dead worker's log fd
                            logf.close()
                        procs[i] = spawn(i)
                        alive = True
                    else:
                        rc = ret
                        raise KeyboardInterrupt  # tear the pod down
            if not alive:
                break
            time.sleep(0.1 if elastic is not None else 0.3)
    except KeyboardInterrupt:
        for proc, _ in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc, _ in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
    finally:
        for _, logf in procs:
            if logf:
                logf.close()
    return rc


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
